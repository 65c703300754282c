"""Set-up seen from inside (ISSUE 36): ``engine.setup_totals`` after
``initialize`` and two steps, the one first-call door (training and
serving), what compiles after set-up, the recorder's tree with telemetry on,
nothing written and nothing started with it off, the step's program
untouched, and the counters as the flat dict that rides in a profiler
trace."""

import glob
import json
import sys
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2_model
from deepspeed_tpu.telemetry import (NULL_TELEMETRY, get_telemetry, memory,
                                     reset_telemetry, setup_spans)

#: every key of ``setup_totals`` (ISSUE 36, C; docs/OBSERVABILITY.md)
KEYS = {"import_s", "initialize_s", "config_topology_s", "zero_plan_s",
        "init_state_s", "programs", "trace_s", "lower_s", "compile_s", "run_s",
        "remat_plan_s", "first_calls_s", "program_s", "programs_compiled",
        "cache_hits", "cache_misses", "compiled_after_setup",
        "traced_functions"}
PARTS = ("trace_s", "lower_s", "compile_s", "run_s")


def _engine(telemetry_dir=None, **config):
    model = gpt2_model("gpt2-tiny", max_seq_len=32, vocab_size=256, remat=True)
    if telemetry_dir is not None:
        config["telemetry"] = {"enabled": True,
                               "trace": {"output_path": str(telemetry_dir)}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1}, "steps_per_print": 100, **config})
    return engine


def _batch(seq=16):
    return {"input_ids": np.arange(8 * seq, dtype=np.int32).reshape(8, seq) % 256}


@pytest.fixture(scope="module")
def stepped():
    """One engine through ``initialize`` and two steps, telemetry off."""
    reset_telemetry()
    engine = _engine()
    engine.memory_at_construction = dict(engine.memory_totals)
    for _ in range(2):
        engine.train_batch(_batch())
    return engine


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------

def test_every_key_is_there_from_the_start_and_after():
    assert set(setup_spans.SetupTotals().totals) == KEYS


def test_the_record_after_two_steps(stepped):
    t = stepped.setup_totals
    assert set(t) == KEYS
    assert t["import_s"] == setup_spans.import_s > 0
    assert t["initialize_s"] >= sum(
        t[f"{part}_s"] for part in setup_spans.INITIALIZE_PARTS) > 0
    assert not stepped._setup.open        # the first optimizer step returned
    assert t["compiled_after_setup"] == 0


@pytest.mark.parametrize("program", ["init_state", "train_step"])
def test_a_program_is_in_the_table_once(stepped, program):
    t = stepped.setup_totals
    entry = t["programs"][program]
    assert entry["trace_s"] > 0 and entry["lower_s"] > 0 and entry["compile_s"] > 0
    assert entry["cache"] in ("hit", "miss", "mixed")
    assert entry["in_initialize"] == (program == "init_state")
    assert not entry["after_setup"]
    # the four parts of a first call ARE its wall: run_s is the rest
    assert sum(entry[k] for k in PARTS) == pytest.approx(entry["wall_s"], rel=1e-9)
    assert f"{program}#2" not in t["programs"]      # the second step: no entry


def test_the_sums_are_the_first_calls(stepped):
    t = stepped.setup_totals
    calls = [p for name, p in t["programs"].items() if name != setup_spans.EAGER]
    assert t["programs_compiled"] == len(calls) == 2
    assert t["cache_hits"] + t["cache_misses"] >= 2
    walls = sum(p["wall_s"] for p in calls)
    assert t["first_calls_s"] == pytest.approx(walls, rel=1e-9)
    assert sum(t[k] for k in PARTS) == pytest.approx(walls, rel=0.01)
    for k in PARTS + ("remat_plan_s",):
        assert t[k] == pytest.approx(sum(p[k] for p in calls), rel=1e-9)
    # the remat budget's reading of the block lies inside the step's trace
    assert 0 < t["remat_plan_s"] <= t["trace_s"]
    # the program's part of set-up: import, initialize, and the first calls
    # outside it (init_state's lies inside initialize)
    assert t["program_s"] == pytest.approx(
        t["import_s"] + t["initialize_s"] + t["programs"]["train_step"]["wall_s"])


def test_what_a_phase_dispatches_one_by_one_is_counted():
    """Operations dispatched one at a time while a set-up span is open and
    no first call (shapes this process has not met, so each is a little
    program of its own) go to ``programs["(eager)"]``, which has no wall:
    it lies inside the span's seconds."""
    record = setup_spans.SetupTotals()
    with record.phase("init_state"):
        x = jnp.full((3, 7, 11), 2.0)
        (x * 3 + 1).block_until_ready()
    record.finish()
    eager = record.totals["programs"][setup_spans.EAGER]
    assert eager["compiles"] >= 2 and eager["traces"] >= 2
    assert eager["trace_s"] > 0 and eager["compile_s"] > 0
    assert "wall_s" not in eager
    assert record.totals["trace_s"] == 0        # the sums are the first calls'
    assert record.totals["init_state_s"] >= eager["trace_s"] + eager["compile_s"]


def test_a_jit_inside_a_jit_is_counted_once():
    @jax.jit
    def inner_fn(x):
        return x * 2 + 1

    @jax.jit
    def outer_fn(x):
        return inner_fn(x).sum()

    x = jnp.arange(7.0)     # made outside: an eager op is a program too
    with setup_spans.FirstCall("nested", NULL_TELEMETRY) as call:
        outer_fn(x).block_until_ready()
    fns = call.account.functions
    assert fns["outer_fn"][0] == 1 and fns["inner_fn"][0] == 1
    # the inner trace lies inside the outer one: trace_s is the outer's
    assert call.numbers["trace_s"] == pytest.approx(fns["outer_fn"][1])
    assert fns["inner_fn"][1] < fns["outer_fn"][1]
    assert call.account.traces == 1
    n = call.numbers
    assert n["trace_s"] + n["lower_s"] + n["compile_s"] <= n["wall_s"]
    assert n["run_s"] >= 0
    # and through a record both appear among the dearest traced functions
    record = setup_spans.SetupTotals()
    x = jnp.arange(9.0)
    with record.first_call("nested", x.shape, NULL_TELEMETRY):
        outer_fn(x).block_until_ready()
    assert {"outer_fn", "inner_fn"} <= set(record.totals["traced_functions"])
    assert record.first_call("nested", x.shape, NULL_TELEMETRY) \
        is setup_spans.NULL_SPAN


def test_events_outside_any_span_go_nowhere(stepped):
    before = json.dumps(stepped.setup_totals)
    jax.jit(lambda x: x - 3)(jnp.ones(5)).block_until_ready()
    assert json.dumps(stepped.setup_totals) == before


# ---------------------------------------------------------------------------
# a program that compiles after set-up
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("telemetry", ["off", "on"])
def test_another_batch_shape_compiles_through_the_same_door(telemetry, tmp_path):
    reset_telemetry()
    threads = set(threading.enumerate())
    engine = _engine(tmp_path if telemetry == "on" else None)
    for _ in range(2):
        engine.train_batch(_batch())
    t = engine.setup_totals
    compiled, sums = t["programs_compiled"], {k: t[k] for k in PARTS}
    engine.train_batch(_batch(seq=32))
    assert t["programs_compiled"] == compiled + 1
    assert t["compiled_after_setup"] == 1
    late = t["programs"]["train_step#2"]
    assert late["after_setup"] and late["trace_s"] > 0
    assert {k: t[k] for k in PARTS} == sums    # set-up's sums stay set-up's
    engine.train_batch(_batch(seq=32))         # met before: nothing new
    assert t["programs_compiled"] == compiled + 1
    if telemetry == "off":
        assert engine.telemetry is NULL_TELEMETRY
        assert get_telemetry() is NULL_TELEMETRY
        # none started; another file's may END meanwhile (3 -> 1 in a whole run)
        assert set(threading.enumerate()) <= threads
        assert not list(tmp_path.iterdir())              # no file written
        return
    events = engine.telemetry.trace.events()
    spans = {e["id"]: e for e in events if e["kind"] == "span"}
    compiles = [e for e in events if e["kind"] == "instant"
                and e["name"] == "compile:train_step"]
    assert len(compiles) == 2
    # the late one hangs under the dispatch it delayed, in that step's span
    parent = spans[compiles[1]["parent"]]
    assert parent["name"] == "fused_dispatch" and parent["step"] == 2
    assert spans[parent["parent"]]["name"] == "train_step"
    assert compiles[1]["args"]["trace_s"] > 0
    assert compiles[1]["args"]["cache"] in ("hit", "miss", "mixed")
    # set-up's spans are in the recorder: those that closed before it
    # existed were written into it where they were
    by_name = {}
    for e in spans.values():
        by_name.setdefault(e["name"], []).append(e)
    for name in ("initialize",) + setup_spans.INITIALIZE_PARTS:
        assert len(by_name[name]) == 1 and by_name[name][0]["phase"] == "setup"
    first_calls = sorted(by_name["first_call"], key=lambda e: e["ts"])
    assert [e["args"]["program"] for e in first_calls] == [
        "init_state", "train_step", "train_step"]
    init, state = by_name["initialize"][0], by_name["init_state"][0]
    assert init["ts"] <= state["ts"]
    assert state["ts"] + state["dur"] <= init["ts"] + init["dur"] + 1e-6
    assert by_name["remat_plan"]
    reset_telemetry()


# ---------------------------------------------------------------------------
# the step's program is untouched
# ---------------------------------------------------------------------------

def _lowered(engine):
    engine._build_fused_jit()
    batch = engine._device_batch(_batch())
    with engine.mesh:
        return engine._jit_train_step.lower(
            engine.state, batch, jnp.asarray(1e-3, jnp.float32))


def _step_jaxpr(engine):
    batch = engine._device_batch(_batch())
    with engine.mesh:
        jaxpr = str(jax.make_jaxpr(engine._train_step_fn)(
            engine.state, batch, jnp.asarray(1e-3, jnp.float32)))
    return re.sub(r" at 0x[0-9a-f]+", "", jaxpr)    # objects' addresses


def test_the_fused_step_is_the_same_program_without_the_listeners(monkeypatch):
    """Jaxpr and lowered text with the listeners registered and the spans
    open equal the ones with JAX's monitoring emptied of every listener:
    nothing of this PR is in traced code."""
    import jax._src.monitoring as mon
    reset_telemetry()
    engine = _engine()
    with engine._first_call("train_step", _batch()):   # as the first step would
        with_text = _lowered(engine).as_text()
        with_jaxpr = _step_jaxpr(engine)
    jax.clear_caches()
    for listeners in ("_event_listeners", "_event_duration_secs_listeners",
                      "_event_time_span_listeners", "_scalar_listeners"):
        monkeypatch.setattr(mon, listeners, [])
    assert _lowered(engine).as_text() == with_text
    assert _step_jaxpr(engine) == with_jaxpr


# ---------------------------------------------------------------------------
# the counters in a profiler trace
# ---------------------------------------------------------------------------

def test_flat_totals_encoding():
    flat = setup_spans.flat_totals(
        setup={"import_s": 2.125, "initialize_s": None,
               "programs": {"train_step": {"trace_s": 0.5, "cache": "hit",
                                           "after_setup": False}},
               "traced_functions": {"_fwd,x#y": [3, 0.25]}},
        moe={"products_kernel": {"forward": 6}, "grouped_matmul_route": "kernel"},
        remat={"saved": ("attn_lse", "attn_o"), "saved_bytes": np.int64(7)},
        attn={})
    assert flat == {
        "setup.import_s": 2.125,
        "setup.programs.train_step.trace_s": 0.5,
        "setup.programs.train_step.cache": "hit",
        "setup.programs.train_step.after_setup": 0,
        "setup.traced_functions._fwd_x_y": "3+0.25",
        "moe.products_kernel.forward": 6,
        "moe.grouped_matmul_route": "kernel",
        "remat.saved": "attn_lse+attn_o", "remat.saved_bytes": 7}
    assert all(not set("#,=") & set(f"{k}{v}") for k, v in flat.items())


def test_engine_totals_rides_in_the_profiler_trace(stepped, tmp_path, monkeypatch):
    """With a profiler session running a step writes ONE ``engine_totals``
    annotation whose stats are the flat counters; with none it writes
    nothing (one flag test). The allocator is read again ONCE a session, at
    its first step, and never on an untraced step: a trace's
    ``memory.resident_bytes`` and what is made of it are of the steps it
    holds."""
    from jax.profiler import ProfileData
    readings = []

    def allocator(devices):     # what a device with an allocator would report
        readings.append(len(list(devices)))
        return {"bytes_limit": 16_000, "bytes_in_use": 1_000 * len(readings),
                "bytes_reserved": 500}

    monkeypatch.setattr(memory, "device_memory", allocator)
    # as if the first step had found its programs' reservation (the CPU has none)
    monkeypatch.setitem(stepped.memory_totals, "step_extra_bytes", 500)
    stepped.train_batch(_batch())        # no session: nothing to find later
    assert readings == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            stepped.train_batch(_batch())
        jax.block_until_ready(stepped.state)
    finally:
        jax.profiler.stop_trace()
    stepped.train_batch(_batch())        # untraced again: no reading
    assert len(readings) == 1
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "engine_totals"]
    assert len(found) == 2
    t = stepped.setup_totals
    for stats in found:
        assert stats["setup.import_s"] == pytest.approx(t["import_s"])
        assert stats["setup.trace_s"] == pytest.approx(t["trace_s"])
        assert stats["setup.programs_compiled"] == t["programs_compiled"]
        assert stats["setup.programs.train_step.cache"] == \
            t["programs"]["train_step"]["cache"]
        assert stats["remat.policy"] == "matmul_and_kernel_outputs"
        assert stats["remat.saved"] == "+".join(stepped.remat_totals["saved"])
        assert stats["opt_kernel.path"] == stepped.opt_kernel_totals["path"]
        assert stats["attn.layers_full"] == 2
        assert {k: v for k, v in stats.items() if k.startswith("memory.")} == {
            "memory.limit_bytes": 16_000, "memory.resident_bytes": 1_000,
            "memory.step_extra_bytes": 500, "memory.step_peak_bytes": 1_500,
            "memory.headroom_bytes": 14_500,
            "memory.account_s": pytest.approx(stepped.memory_totals["account_s"])}
    # as the CPU leaves it, for the tests that follow
    memory.with_residents(stepped.memory_totals, None)


# ---------------------------------------------------------------------------
# a step's memory (ISSUE 53): ``engine.memory_totals``, written once when the
# first optimizer step has returned, from the allocator's own reading
# ---------------------------------------------------------------------------

def test_memory_totals_from_construction_and_after_two_steps(stepped):
    assert stepped.memory_at_construction == memory.empty_totals()
    m = stepped.memory_totals
    assert set(m) == set(memory.empty_totals())
    assert 0 < m["account_s"] < 1.0
    # the CPU's allocator reports nothing: every byte stays None
    assert {v for k, v in m.items() if k != "account_s"} == {None}
    # it cost set-up no program
    t = stepped.setup_totals
    assert set(t["programs"]) == {"init_state", "train_step", setup_spans.EAGER}
    assert t["programs_compiled"] == 2 and t["compiled_after_setup"] == 0
    # written once: a later step leaves the very dict as it was
    before = json.dumps(m)
    stepped.train_batch(_batch())
    assert json.dumps(stepped.memory_totals) == before


def test_flat_totals_carries_the_memory_counter(stepped):
    flat = setup_spans.flat_totals(memory=stepped.memory_totals)
    assert flat == {"memory.account_s": stepped.memory_totals["account_s"]}   # None is left out
    full = memory.step_totals({"bytes_limit": 16, "bytes_in_use": 5, "bytes_reserved": 0},
                              {"bytes_limit": 16, "bytes_in_use": 4, "bytes_reserved": 1})
    assert setup_spans.flat_totals(memory=full) == {
        "memory.limit_bytes": 16, "memory.resident_bytes": 4,
        "memory.reserved_before_bytes": 0, "memory.step_extra_bytes": 1,
        "memory.step_peak_bytes": 5, "memory.headroom_bytes": 11}


# ---------------------------------------------------------------------------
# the other users of the door
# ---------------------------------------------------------------------------

def test_the_split_path_and_the_flops_probe(monkeypatch, tmp_path):
    """``forward`` / ``step`` go through the door as ``micro_step`` and
    ``apply_step``; the MFU's FLOPs come from the lowered module, so the
    first flush compiles nothing (no ``flops_probe`` entry)."""
    monkeypatch.setenv("DSTPU_FUSED_STEP", "0")
    readings = []

    def allocator(devices):     # a device whose step programs reserve 500 bytes
        readings.append(sys._getframe(1).f_code.co_name)
        return {"bytes_limit": 16 * 10 ** 9, "bytes_in_use": 1_000,
                "bytes_reserved": 500 if len(readings) > 1 else 0}

    monkeypatch.setattr(memory, "device_memory", allocator)
    reset_telemetry()
    engine = _engine(tmp_path)
    for _ in range(2):
        engine.train_batch(_batch())
    t = engine.setup_totals
    assert {"init_state", "micro_step", "apply_step"} <= set(t["programs"])
    assert t["programs_compiled"] == 3 and not engine._setup.open
    compiled = t["programs_compiled"]
    assert engine._telemetry_flops() > 0
    assert "flops_probe" not in t["programs"]
    assert t["programs_compiled"] == compiled
    # the allocator was read before the step's FIRST program (not again
    # before its second) and when the first optimizer step had returned
    m = engine.memory_totals
    assert readings[0] == "_first_call" and readings.count("_first_call") == 1
    assert readings[-1] == "_account_memory" and readings.count("_account_memory") == 1
    assert m["reserved_before_bytes"] == 0 and m["step_extra_bytes"] == 500
    assert m["resident_bytes"] == 1_000 and m["step_peak_bytes"] == 1_500
    assert m["headroom_bytes"] == 16 * 10 ** 9 - 1_500 and 0 < m["account_s"] < 1.0
    reset_telemetry()


def test_serving_buckets_go_through_the_same_door():
    from tests.unit.telemetry.test_named_work import _serve_engine
    reset_telemetry()
    engine = _serve_engine()
    engine.put([7], [np.arange(5, dtype=np.int32)])
    [(key, first)] = engine.seen_buckets().items()
    assert key == ("wave", (16, 8, 4, 8))
    assert first["wall_s"] > 0
    assert first["trace_s"] > 0 and first["compile_s"] > 0
    assert first["cache"] in ("hit", "miss", "mixed")
    assert sum(first[k] for k in PARTS) == pytest.approx(first["wall_s"])
    engine.put([8], [np.arange(5, dtype=np.int32)])    # the same bucket
    assert list(engine.seen_buckets()) == [key]
