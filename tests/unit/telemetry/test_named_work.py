"""Named work on one clock (ISSUE 24): scopes and kernel names are metadata
only, the program's spans reach the profiler's trace with telemetry off,
recorder spans carry id / parent / req, and the serving engine's per-wave
counters and bucket keys equal sums worked out by hand."""

import ast
import contextlib
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2.config_v2 import (
    DeepSpeedTPStateManagerConfig, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.engine_v2 import build_engine
from deepspeed_tpu.inference.v2.ragged.wave import WaveEntry, build_wave
from deepspeed_tpu.inference.v2.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import gpt2_model
from deepspeed_tpu.telemetry import (NULL_TELEMETRY, TelemetryConfig,
                                     build_telemetry, get_telemetry,
                                     reset_telemetry)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


# ---------------------------------------------------------------------------
# the two programs, tiny
# ---------------------------------------------------------------------------

def _train_engine():
    model = gpt2_model("gpt2-tiny", max_seq_len=32, vocab_size=256, remat=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1}, "steps_per_print": 100})
    return engine


def _batch():
    return {"input_ids": np.arange(8 * 16, dtype=np.int32).reshape(8, 16) % 256}


def _serve_engine(decode_burst=4):
    model = gpt2_model("gpt2-tiny", max_seq_len=128, vocab_size=256, remat=False)
    cfg = RaggedInferenceEngineConfig(
        state_manager=DeepSpeedTPStateManagerConfig(
            max_ragged_batch_size=64, max_ragged_sequence_count=8,
            max_context=96),
        kv_block_size=8, num_kv_blocks=64, max_prefill_chunk=32,
        decode_burst=decode_burst)
    return build_engine(model, config=cfg, seed=0)


def _lower_train(engine):
    engine._build_fused_jit()
    batch = engine._device_batch(_batch())
    with engine.mesh:
        return engine._jit_train_step.lower(
            engine.state, batch, jnp.asarray(1e-3, jnp.float32))


def _lower_wave(engine):
    entries = [WaveEntry(1, np.arange(5, dtype=np.int32), 0, [1]),
               WaveEntry(2, np.arange(11, dtype=np.int32), 0, [2, 3])]
    d = build_wave(entries, block_q=engine.config.ragged_block_q, block_size=8)
    with engine.mesh:
        return engine._wave_fn.lower(
            engine.params, engine.kv_cache.k_pages, engine.kv_cache.v_pages,
            *(jnp.asarray(a) for a in (d.tokens, d.positions, d.write_idx,
                                       d.cu_q_lens, d.kv_lens, d.page_indices,
                                       d.last_rows)))


@pytest.mark.parametrize("program", ["fused_train_step", "ragged_wave"])
def test_scopes_are_metadata_only(program, monkeypatch):
    """The lowered program is the same text with ``jax.named_scope`` a
    no-op; the scopes show only in the debug locations."""
    engine, lower = ((_train_engine(), _lower_train)
                     if program == "fused_train_step"
                     else (_serve_engine(), _lower_wave))
    named = lower(engine)
    assert "attn" in named.as_text(debug_info=True)
    jax.clear_caches()            # or the second lowering re-uses the trace
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower(engine)
    monkeypatch.undo()
    assert "/attn/" not in bare.as_text(debug_info=True)
    assert named.as_text() == bare.as_text()
    if program == "fused_train_step":
        with_names = named.as_text(debug_info=True)
        for scope in ("embed", "block/attn/qkv", "block/attn/core",
                      "block/attn/out", "block/mlp", "head", "loss",
                      "optimizer", "rematted_computation/block/attn"):
            assert scope in with_names, scope
    else:
        with_names = named.as_text(debug_info=True)
        for scope in ("embed", "attn/qkv", "kv_write", "attn/core",
                      "attn/out", "mlp", "head"):
            assert scope in with_names, scope


# ---------------------------------------------------------------------------
# a name on every kernel
# ---------------------------------------------------------------------------

def _pallas_call_sites():
    sites = []
    root = os.path.join(REPO, "deepspeed_tpu")
    for path in sorted(glob.glob(root + "/**/*.py", recursive=True)):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                name = next((k.value for k in node.keywords
                             if k.arg == "name"), None)
                sites.append((os.path.relpath(path, REPO), node.lineno, name))
    return sites


PALLAS_SITES = _pallas_call_sites()
KERNEL_NAMES = {
    "adam_bucket", "lion_bucket", "flash_fwd", "flash_bwd",
    "ragged_paged_attention", "paged_decode", "woq_matmul",
    "quantize_rows_int8", "moe_route", "moe_dispatch_gather",
    "moe_dispatch_gather_int8", "moe_ffn_combine", "moe_ffn", "moe_combine",
    # the grouped matmul's three (PR 33): under XLA's own instruction name
    # for the product they take the place of, so the trace's reader finds them
    "ragged-dot-gmm-fwd", "ragged-dot-gmm-dlhs", "ragged-dot-gmm-dw",
    # the flash pair with its grids cut to a static window (PR 34): a name of
    # their own, so that a reader tells a sliding layer's launch from a full one's
    "flash_fwd_window", "flash_bwd_window",
    # the flash pair under the block-diffusion mask (PR 39): one launch over
    # the clean keys for a clean and a noised copy's queries
    "flash_fwd_blockdiff", "flash_bwd_blockdiff",
    # EVA's two launches (PR 42): the exact keys a window at a time, the
    # summaries under a q-block's limit (``FlashConfig.tag``)
    "flash_fwd_eva_local", "flash_bwd_eva_local",
    "flash_fwd_eva_far", "flash_bwd_eva_far",
    # the flash pair whose tiles read a learned selection's operand (PR 48)
    "flash_fwd_dsa", "flash_bwd_dsa",
    # the flash pair whose values have a width of their own (PR 55: latent
    # attention's 192 / 128; ``FlashConfig.v_dim``, tag ``mla``)
    "flash_fwd_mla", "flash_bwd_mla",
    # the two-width pair of differential attention (PR 57: keys of 64, a pair's
    # two value heads side by side; tag ``diff``), over whole documents and
    # with its grids cut to a static window
    "flash_fwd_diff", "flash_bwd_diff", "flash_fwd_diff_window", "flash_bwd_diff_window",
    # the selective scan a chunk at a time (PR 57; ``pallas_scan``), under
    # ``ssm/scan``: ``ssm_scan_roofline`` finds the launches by these names
    "ssm_scan_fwd", "ssm_scan_bwd",
    # the state-space-duality core a chunk and a tile of heads at a time (PR 65;
    # ``pallas_ssd``), under ``ssm/ssd``: ``ssm_ssd_roofline`` finds the launches
    # by these names
    "ssd_fwd", "ssd_bwd",
    # the Kimi Delta Attention core a head and a span of rows at a time (PR 68;
    # ``pallas_kda``), under ``attn/core_kda``: ``attn_kda_roofline`` finds the
    # launches by these names
    "kda_fwd", "kda_bwd",
    # ``dO x O``'s row sum for a launch whose operands lie by rows (PR 51): NOT
    # ``flash_bwd*``, whose readers sum the backward launches alone
    "flash_delta",
    # the indexer's KL a tile at a time (PR 49; ``pallas_indexer_kl``): NOT
    # ``flash_*_dsa``, whose reader sums the selected flash pair alone
    "indexer_kl_fwd", "indexer_kl_bwd",
    # the selection a plane at a time (PR 52; ``pallas_select``): the launch
    # the cell's ``breakdown`` shows in the XLA loop's two fusions' place
    "dsa_select",
    # a share's rows back to the tokens (PR 38), under ``mlp/moe/combine`` and
    # ``mlp/moe/dispatch``: ``train_moe_dispatch_ms`` finds it by its scope
    "segment-sum",
    # a hyper-connected sub-layer's coefficients in one pass over vec(X)
    # (PR 56; ``pallas_hc``), under ``hc/coeff``: both ``hc`` metrics find it
    # by its scope
    "hc_coeff_fwd"}


def _names_of(name):
    """The literal names a site can launch under: one string, or a
    conditional between two."""
    if isinstance(name, ast.IfExp):
        return _names_of(name.body) + _names_of(name.orelse)
    if isinstance(name, ast.Constant) and isinstance(name.value, str):
        return [name.value]
    return []


@pytest.mark.parametrize("site", PALLAS_SITES,
                         ids=[f"{p}:{n}" for p, n, _ in PALLAS_SITES])
def test_every_pallas_call_has_a_name(site):
    path, line, name = site
    names = _names_of(name)
    assert names, f"{path}:{line} passes no literal name= to pallas_call"
    assert set(names) <= KERNEL_NAMES, names


def test_kernel_names_are_distinct_and_complete():
    assert len(PALLAS_SITES) == 29
    names = [v for _, _, n in PALLAS_SITES for v in _names_of(n)]
    assert len(set(names)) == len(names) == 45
    assert set(names) == KERNEL_NAMES


# ---------------------------------------------------------------------------
# the program's spans in the profiler trace, telemetry off
# ---------------------------------------------------------------------------

def test_spans_reach_the_profiler_with_telemetry_off(tmp_path):
    from jax.profiler import ProfileData
    engine = _train_engine()
    assert engine.telemetry is NULL_TELEMETRY
    engine.train_batch(_batch())                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            loss = engine.train_batch(_batch())
        jax.block_until_ready((loss, engine.state))
    finally:
        jax.profiler.stop_trace()
    assert get_telemetry() is NULL_TELEMETRY      # nothing was switched on
    (path,) = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
    host = [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    count = {n: sum(1 for e in host if e.name == n)
             for n in ("train_step", "prepare_batch", "fused_dispatch",
                       "post_step")}
    assert count == {"train_step": 2, "prepare_batch": 2,
                     "fused_dispatch": 2, "post_step": 2}
    steps = sorted(dict(e.stats)["step_num"] for e in host
                   if e.name == "train_step")
    assert steps == [1, 2]
    # the program's spans lie inside their step marker
    marks = [(e.start_ns, e.start_ns + e.duration_ns) for e in host
             if e.name == "train_step"]
    for e in host:
        if e.name in ("prepare_batch", "fused_dispatch", "post_step"):
            assert any(s <= e.start_ns and e.start_ns + e.duration_ns <= t
                       for s, t in marks), e.name


# ---------------------------------------------------------------------------
# recorder spans: id, parent, req; the serving tree and its counters
# ---------------------------------------------------------------------------

PROMPTS = (5, 19, 40)       # tokens; pages of 8, atoms of block_q 8


@pytest.fixture
def served():
    """Three requests through the tiny serving engine with telemetry on:
    (engine, recorder events)."""
    reset_telemetry()
    tele = build_telemetry(TelemetryConfig(enabled=True))
    engine = _serve_engine()
    sched = ContinuousBatchingScheduler(engine, token_budget=64)
    rng = np.random.default_rng(0)
    for n in PROMPTS:
        sched.submit(rng.integers(0, 256, size=(n,)), max_new_tokens=6)
    while sched.has_work:
        if sched.step() == 0:
            break
    yield engine, tele.trace.events(), tele
    reset_telemetry()


def test_recorder_spans_carry_id_parent_req(served, tmp_path):
    _, events, tele = served
    spans = [e for e in events if e["kind"] == "span"]
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans)                        # ids are unique
    steps = [s for s in spans if s["name"] == "sched.step"]
    assert steps and all(s["parent"] is None for s in steps)
    for s in spans:
        if s["name"] in ("wave.build", "wave.dispatch", "wave.fetch",
                         "sched.restore", "sched.compose", "sched.sample"):
            parent = by_id[s["parent"]]
            assert parent["name"] == "sched.step"
            # the wave's own step: it encloses the child in time
            assert parent["ts"] <= s["ts"]
            assert s["ts"] + s["dur"] <= parent["ts"] + parent["dur"] + 1e-6
            if s["name"].startswith("wave."):
                assert set(s["req"]) <= set(parent["req"])
    first = steps[0]
    assert first["req"] == [1, 2, 3]
    children = {s["name"] for s in spans if s["parent"] == first["id"]}
    assert children == {"sched.restore", "sched.compose", "wave.build",
                        "wave.dispatch", "wave.fetch", "sched.sample"}
    # both exports keep the three fields
    jsonl, chrome = str(tmp_path / "t.jsonl"), str(tmp_path / "t.json")
    tele.trace.export_jsonl(jsonl)
    tele.trace.export_chrome_trace(chrome)
    with open(jsonl) as f:
        rows = [json.loads(line) for line in f]
    build = next(r for r in rows if r.get("name") == "wave.build")
    assert {"id", "parent", "req"} <= set(build)
    with open(chrome) as f:
        x = next(e for e in json.load(f)["traceEvents"]
                 if e["name"] == "wave.build")
    assert x["args"]["parent"] == build["parent"]
    assert x["args"]["req"] == build["req"]


def test_wave_counters_equal_sums_worked_by_hand(served):
    engine, events, _ = served
    waves = [e for e in events if e["kind"] == "instant"
             and e["name"].startswith("wave:")]
    # Step 1 (prefill): budget 64 takes 5 + 19 + 40 = 64 tokens, but put()
    # cuts chunks at max_prefill_chunk 32, so two dispatches:
    #   A: chunks 5, 19, 32 -> atoms of 8: [5] [8,8,3] [8,8,8,8] = 8 atoms;
    #      kv_len at each atom's end: 5 | 8,16,19 | 8,16,24,32
    #      q x kv = 25 + (64+128+57) + (64+128+192+256) = 914
    #      kv     = 5 + 43 + 80 = 128
    #      pages  = ceil(kv/8) = 1 + (1+2+3) + (1+2+3+4) = 17
    #      buckets: tokens 56 -> 64, atoms 8 -> 8, max pages/seq 5 -> 8, rows 3 -> 8
    #   B: the last 8 tokens of request 3 after 32 seen -> 1 atom, kv 40
    #      q x kv = 320, kv = 40, pages = 5
    #      buckets: tokens 8 -> 16, atoms 1 -> 8, pages 5 -> 8, rows 1 -> 8
    first = waves[0]["args"]["counters"]
    assert waves[0]["name"] == "wave:prefill"
    assert first == {
        "dispatches": 2,
        "tokens": 64, "tokens_bucket": 64 + 16,
        "atoms": 9, "atoms_bucket": 8 + 8,
        "pages": 17 + 5, "pages_bucket": 8 * 8 + 8 * 8,
        "rows": 4, "rows_bucket": 8 + 8,
        "attn_q_kv": 914 + 320, "attn_kv": 128 + 40,
        "buckets": [["wave", 64, 8, 8, 8], ["wave", 16, 8, 8, 8]]}
    # Step 2 (burst of 4 for the three, which hold 5, 19, 40 tokens and one
    # sampled token each not yet in cache): every (sequence, step) is one
    # query over seen + step keys, step = 1..4
    #   kv = (5+19+40) * 4 + 3 * (1+2+3+4) = 286 = q x kv (q_len 1)
    #   pages = ceil over {6..9} {20..23} {41..44} / 8 = (1+1+1+2)+(3+3+3+3)+(6+6+6+6) = 41
    #   buckets: rows 3 -> 16, 16 x 4 slots, pages/seq 6 -> 8
    second = waves[1]["args"]["counters"]
    assert waves[1]["name"] == "wave:burst"
    assert second == {
        "dispatches": 1,
        "tokens": 12, "tokens_bucket": 64, "atoms": 12, "atoms_bucket": 64,
        "pages": 41, "pages_bucket": 64 * 8, "rows": 3, "rows_bucket": 16,
        "attn_q_kv": 286, "attn_kv": 286, "buckets": [["burst", 16, 8, 4]]}
    # the engine's running totals are the sum over every wave, and are kept
    # with no switch (plain ints on the engine)
    for key, total in engine.wave_totals.items():
        assert total == sum(w["args"]["counters"][key] for w in waves), key
    # which step compiled: one instant per distinct bucket, and
    # seen_buckets() is the distinct keys of the waves run
    met = [tuple(b) for w in waves for b in w["args"]["counters"]["buckets"]]
    distinct = list(dict.fromkeys(met))
    assert list(engine.seen_buckets()) == [(b[0], b[1:]) for b in distinct]
    compiles = [e for e in events if e["kind"] == "instant"
                and e["name"].startswith("compile:")]
    assert [(e["name"][8:], *e["args"]["key"]) for e in compiles] == distinct
    assert all(e["args"]["seconds"] > 0 for e in compiles)


def test_counters_are_kept_with_telemetry_off():
    reset_telemetry()
    engine = _serve_engine()
    engine.put([7], [np.arange(5, dtype=np.int32)])
    assert get_telemetry() is NULL_TELEMETRY
    assert engine.wave_totals["tokens"] == 5
    assert engine.wave_totals["attn_q_kv"] == 25
    assert list(engine.seen_buckets()) == [("wave", (16, 8, 4, 8))]
