"""Test harness: a virtual 8-device CPU mesh.

Counterpart of the reference's ``tests/unit/common.py`` DistributedTest
harness (common.py:105): the reference forks N processes with real NCCL over
localhost; here the same multi-device semantics come from XLA's host-platform
device partitioning — one process, 8 virtual CPU devices, real collectives,
real shardings. Must run before jax initializes its backends.
"""

import os

#: What the harness asks of XLA, each flag unless the caller's ``XLA_FLAGS``
#: names it. Level 0: the suite's time is COMPILING (PR 58's profile; six
#: workers on eight cores), and LLVM's optimiser for the host is no part of what
#: a test holds: HLO passes, buffers and schedules come before it, the chip's
#: programs never pass through it. The whole run's seconds: CHANGES.md, PR 66.
HARNESS_XLA_FLAGS = ("--xla_force_host_platform_device_count=8",
                     "--xla_backend_optimization_level=0")


def harness_xla_flags(incoming):
    """``XLA_FLAGS`` for the test process, from the caller's: ``XLA_FLAGS=
    --xla_backend_optimization_level=3 pytest <file>`` keeps the optimiser."""
    flags = incoming.split()
    for flag in HARNESS_XLA_FLAGS:
        if flag.split("=")[0].lstrip("-") not in incoming:
            flags.append(flag)
    return " ".join(flags)


os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = harness_xla_flags(os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("DSTPU_ACCELERATOR", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
# No persistent compile cache under test: the suite must not write into the
# checkout's .jax_cache (initialize() places it there), and a program
# compiled for a DESCRIBED TPU (test_chip_compile.py) is written to the
# cache but cannot be read back without a chip.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

from deepspeed_tpu.runtime import topology as topo_mod  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (-m 'not slow')")
    if hasattr(config.option, "loadscopereorder"):
        # pytest-xdist's ``--dist loadfile`` hands the files out by their NUMBER
        # OF CASES, most first; in path order instead tests/benchmark/
        # test_reference.py (one worker's 842 s of the driver's 1,365: 19 cases)
        # starts in the run's first second, so a new reference costs the wall
        # a sixth of its seconds and not all of them. Without xdist: no option.
        config.option.loadscopereorder = False


# ---------------------------------------------------------------------------
# capability probe: cross-process CPU collectives
#
# tests/unit/runtime/test_multiprocess.py launches REAL two-process runs
# whose collectives must cross the process boundary. Some jaxlib builds
# (including the current pin) refuse this outright — the CPU backend
# raises "Multiprocess computations aren't implemented" on the first
# cross-process program. That is a toolchain capability gap, not a repo
# regression, so those tests SKIP (with the probe's evidence) instead of
# failing. The probe runs at most once per session, and only when a
# multiprocess test was actually collected.
# ---------------------------------------------------------------------------

_MP_PROBE_SRC = """
import os, sys
port, pid = sys.argv[1], int(sys.argv[2])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ.pop("JAX_PLATFORMS", None)
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=pid)
import jax.numpy as jnp
from jax.experimental import multihost_utils
x = multihost_utils.process_allgather(jnp.ones((1,)))
assert x.shape == (2, 1), x.shape
"""

_mp_capability = None  # None = not probed yet; (bool, reason)


def _cross_process_cpu_collectives_work():
    global _mp_capability
    if _mp_capability is not None:
        return _mp_capability
    import socket
    import subprocess
    import sys
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    procs = [subprocess.Popen(
        [sys.executable, "-c", _MP_PROBE_SRC, str(port), str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for i in range(2)]
    outs, ok = [], True
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            out, ok = "probe timeout", False
        outs.append(out or "")
        ok = ok and p.returncode == 0
    if ok:
        _mp_capability = (True, "")
    else:
        tail = next((l for o in outs for l in reversed(o.splitlines())
                     if "Error" in l or "error" in l), "see probe output")
        _mp_capability = (False, tail.strip()[:200])
    return _mp_capability


def pytest_collection_modifyitems(config, items):
    mp_items = [i for i in items
                if "test_multiprocess" in os.path.basename(str(i.fspath))]
    if not mp_items:
        return
    capable, reason = _cross_process_cpu_collectives_work()
    if capable:
        return
    marker = pytest.mark.skip(
        reason="cross-process CPU collectives unavailable in this "
               f"jaxlib (capability probe: {reason})")
    for item in mp_items:
        item.add_marker(marker)


@pytest.fixture(autouse=True)
def _reset_topology():
    topo_mod.reset()
    yield
    topo_mod.reset()
    # a test that enabled telemetry must not leak its recorder (or its
    # watchdog thread / close-time export) into the next test
    from deepspeed_tpu.telemetry import reset_telemetry
    reset_telemetry()
    # nor may a test's comm_transport policy (engine config block or
    # direct configure_transport call) leak into the next test
    from deepspeed_tpu import comm as dist
    dist.reset_transport()
    # nor an engine-installed overlap_plan flag (the plan/map caches are
    # static committed files; only the config flag is test-varying)
    from deepspeed_tpu.runtime.overlap_planner import configure_planner
    configure_planner(None)


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def host_lock_graph():
    """Layer F's static lock-acquisition graph over the package, built
    once per session — the reference the lockdep-lite cross-check
    (chaos/durability/autotuning suite conftests) compares observed
    acquisition order against."""
    from deepspeed_tpu.analysis.host_audit import build_host_graph
    return build_host_graph(None)
