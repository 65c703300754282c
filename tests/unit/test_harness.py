"""The harness itself (``tests/conftest.py``): what it asks of XLA for the
test process, and that the benchmark's command-line runs do not inherit it."""

import os

import pytest

from tests.benchmark import helpers
from tests.conftest import harness_xla_flags

DEVICES = "--xla_force_host_platform_device_count"
LEVEL = "--xla_backend_optimization_level"


def test_the_test_process_has_eight_devices_and_names_level_0_once(eight_devices):
    flags = os.environ["XLA_FLAGS"].split()
    assert [f for f in flags if f.startswith(LEVEL)] == [LEVEL + "=0"]
    assert [f for f in flags if f.startswith(DEVICES)] == [DEVICES + "=8"]


@pytest.mark.parametrize("incoming, composed", [
    ("", f"{DEVICES}=8 {LEVEL}=0"),
    (f"{DEVICES}=2", f"{DEVICES}=2 {LEVEL}=0"),
    (f"{LEVEL}=3", f"{LEVEL}=3 {DEVICES}=8"),
    (f"--xla_dump_to=/x {LEVEL}=3 {DEVICES}=4", f"--xla_dump_to=/x {LEVEL}=3 {DEVICES}=4"),
], ids=["nothing", "a device count alone", "the caller's level 3", "both and another"])
def test_a_flag_the_caller_names_is_left_alone(incoming, composed):
    assert harness_xla_flags(incoming) == composed
    assert harness_xla_flags(composed) == composed


@pytest.mark.parametrize("devices", [1, 8])
def test_the_benchmarks_command_line_runs_do_not_carry_the_level(monkeypatch, devices):
    """``helpers.run_cli`` REPLACES ``XLA_FLAGS``: a cell's program under
    ``tests/benchmark/`` is compiled as a user's run compiles it."""
    assert LEVEL in os.environ["XLA_FLAGS"]
    seen = {}
    monkeypatch.setattr(helpers.subprocess, "run",
                        lambda cmd, env, **kw: seen.update(env=env, cmd=cmd))
    helpers.run_cli("run.py", devices=devices)
    assert seen["cmd"][1].endswith(os.path.join("benchmark", "run.py"))
    assert LEVEL not in seen["env"].get("XLA_FLAGS", "")
    assert (devices == 1) == ("XLA_FLAGS" not in seen["env"])
