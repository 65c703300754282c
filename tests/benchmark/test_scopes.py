"""benchmark/trace/scopes.py on its recorded fixture: the sums worked out by
hand in benchmark/trace/scopes_fixture.md, and the readers' behaviour where
the program names nothing."""

import os
import types

import pytest

from benchmark.trace import reduce, scopes
from tests.benchmark.helpers import REPO

FIXTURE = os.path.join(REPO, "benchmark", "trace", "scopes_fixture.json")


@pytest.fixture(scope="module")
def fx():
    return reduce.load(FIXTURE), scopes.op_names(FIXTURE)


def _ctx(trace, trace_steps=1):
    cell = types.SimpleNamespace(traffic={"trace_steps": trace_steps})
    return {"trace": trace, "cell": cell, "trace_out": {"trace_file": FIXTURE}}


def test_sums_by_scope_are_the_hand_worked_ones(fx):
    trace, names = fx
    s = scopes.seconds_by_scope(trace, names)
    ns = {k: round(v * 1e9) for k, v in s.items()}
    assert ns == {"attn": 2193396, "mlp": 606914, "head": 3416770,
                  "optimizer": 10300344, "unscoped": 233372,
                  "remat": 823335, "total": 16750796}


def test_classes_add_up_to_busy_time_over_steps(fx):
    trace, names = fx
    s = scopes.seconds_by_scope(trace, names)
    assert scopes.steps(trace) == 1
    assert sum(s[c] for c in scopes.CLASSES) == pytest.approx(
        reduce.busy_s(trace), rel=1e-12)
    per_step = sum(scopes.ms_per_step(_ctx(trace), c) for c in scopes.CLASSES)
    assert per_step == pytest.approx(16.750796)


@pytest.mark.parametrize("op_name,want", [
    ("jit(_train_step_fn)/jvp(head)/dot_general:", ("head", False)),
    ("jit(_train_step_fn)/transpose(jvp(embed))/jit(_take)/scatter-add:",
     ("head", False)),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/block/attn/core/sub:", ("attn", True)),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/block/"
     "reduce_sum:", ("unscoped", False)),
    ("jit(f)/optimizer/cond/branch_0_fun/adam_bucket/pallas_call:",
     ("optimizer", False)),
    ("jit(wave_forward)/while/body/kv_write/scatter:", ("unscoped", False)),
    ("", ("unscoped", False)), (None, ("unscoped", False)),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


def test_the_adam_kernel_is_found_by_its_name(fx):
    trace, names = fx
    (adam,) = [e for e in reduce.leaf_events(trace["devices"]["/device:TPU:0"])
               if "adam_bucket" in e[0]]
    assert "adam_bucket" in names[adam[0]]
    assert reduce.top_ops(trace, 1)[0][0] == adam[0]


def test_program_spans_are_read_from_the_host_plane(fx):
    trace, _ = fx
    ctx = _ctx(trace)
    assert scopes.span_median_ms(ctx, "prepare_batch") == pytest.approx(0.737871)
    assert scopes.span_median_ms(ctx, "fused_dispatch") == pytest.approx(1.377111)
    assert scopes.span_median_ms(ctx, "no_such_span") is None


def test_step_count_must_match_the_traffic_file(fx):
    trace, _ = fx
    with pytest.raises(ValueError, match="train_step"):
        scopes.of_run(_ctx(trace, trace_steps=5))


def test_a_program_without_names_gives_nothing(fx):
    """The parent of PR 24: no train_step annotation, no program span."""
    trace, _ = fx
    bare = {"devices": trace["devices"],
            "host": [e for e in trace["host"] if e[0] == "train_batch"]}
    ctx = _ctx(bare)
    assert scopes.of_run(ctx) is None
    assert scopes.ms_per_step(ctx, "attn") is None
    assert scopes.span_median_ms(ctx, "prepare_batch") is None
    assert scopes.ms_per_step({"trace": None}, "attn") is None


def test_wire_decoder_reads_a_hand_built_xplane(tmp_path):
    """The protobuf reader on bytes built by hand from xplane.proto's field
    numbers: one chip plane, stat metadata 7 = tf_op, two operations (one by
    str_value, one by ref_value into the stat names) and one without."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(num, wire, payload):
        head = varint(num << 3 | wire)
        return head + (varint(len(payload)) + payload if wire == 2 else payload)

    def msg(num, payload):
        return field(num, 2, payload)

    def entry(key, value):                     # a map<int64, Message> entry
        return field(1, 0, varint(key)) + msg(2, value)

    stat_md = lambda i, name: field(1, 0, varint(i)) + msg(2, name.encode())
    plane = (msg(2, b"/device:TPU:0")
             + msg(5, entry(7, stat_md(7, "tf_op")))
             + msg(5, entry(9, stat_md(9, "jit(f)/mlp/dot_general:")))
             + msg(4, entry(1, field(1, 0, varint(1))
                            + msg(2, b"%fusion.1 = f32[8] fusion(f32[8] %p)")
                            + msg(5, field(1, 0, varint(7))
                                  + msg(5, b"jit(f)/attn/core/mul:"))))
             + msg(4, entry(2, field(1, 0, varint(2))
                            + msg(2, b"%fusion.2 = f32[8] fusion(f32[8] %q)")
                            + msg(5, field(1, 0, varint(7))
                                  + field(7, 0, varint(9)))))
             + msg(4, entry(3, field(1, 0, varint(3))
                            + msg(2, b"%copy.3 = f32[8] copy(f32[8] %r)"))))
    host = msg(2, b"/host:CPU")
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(msg(1, host) + msg(1, plane))
    assert scopes.op_names(str(path)) == {
        "fusion.1": "jit(f)/attn/core/mul:",
        "fusion.2": "jit(f)/mlp/dot_general:"}
