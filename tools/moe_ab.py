#!/usr/bin/env python
"""A/B microbenchmarks of the expert FFN's matmuls on the attached chip.

``python tools/moe_ab.py gmm`` (PR 33): the in-repo Pallas grouped matmul
(``ops/transformer/pallas_gmm.py``) against ``jax.lax.ragged_dot`` and its
transposes, the three product kinds, at the two MoE cells' shapes (32,768
rows x 2048 <-> 1024 over 64 groups; 36,864 x 2048 <-> 1408 over 8) and at
the loads the cells draw (``moe_expert_rows`` as a run printed them) beside
even, Dirichlet(0.3) and one-group-takes-most loads. ``--sweep`` times every
row tile of ``ROW_TILES`` instead of ``choose_tiles``' own (a shared tile in
parts of 128 rows, the kernel's way), ``--parts`` row tiles 256 and 512 with a
shared tile multiplied whole against in parts. One JSON line a
reading on stdout and in ``chiprun_out/gmm_ab.jsonl``: ms (best of the
windows, host clock around ``block_until_ready``) and the share of the
chip's 197 TFLOP/s.

``python tools/moe_ab.py rows`` (PR 38): a chip's share's rows back to the
tokens at the two share cells' shapes ([36,864, 2048] -> [16,384, 2048] at 6
a token of 64 experts, 8 held; [49,152, 2048] -> [16,384, 2048] at 8 of 128,
16 held): the slab-by-slab gathers the layer had (one ``[tokens, h]`` slab a
slot, kept here as the reference) against ``moe/layer.py``'s
``_sum_held_rows`` (one gather of the buffer's rows, then
``ops/transformer/pallas_segment_sum.py``), with the kernel alone at a few
tiles, the gather alone, the index arithmetic alone and the XLA form. One
JSON line a reading on stdout and in ``chiprun_out/rows_ab.jsonl``.

``python tools/moe_ab.py route`` (PR 54): the no-drop routers' bookkeeping at
the expert cells' three shapes (16,384 tokens x 8 of 128 experts; 16,384 x 6
of 64; 4,096 x 8 of 64): the forms the routers and the two paths had until
PR 53 (the experts' counts by a scatter-add, the inverse permutation by a
scatter, the chosen scores by ``take_along_axis`` or as ``top_k``'s values,
whose gradients are scatter-adds; the whole routers as the tests keep them,
``tests/unit/moe/test_dropless.py`` and ``tests/unit/models/
test_instella_moe.py``) against what ``moe/sharded_moe.py`` and
``moe/layer.py`` have now, forward and under ``jax.grad``, with a few forms
that were not taken. One JSON line a reading on stdout and in
``chiprun_out/route_ab.jsonl``: ms a trip of a loop on the device (64 trips a
dispatch: a call from the host costs 0.2 ms, more than most of these forms),
``floor_ms`` what the loop itself costs a trip, and ``same``: whether the new
form's result is the old one's to the bit ON THE CHIP.

``python tools/moe_ab.py`` (round 2): the capacity-dense batched einsum
against ``ragged_dot`` at the smoke's MoE dims.

Interleaved timed windows (A and B alternate within one process and the
BEST window of each is compared). No cell runs this file.
"""

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the smoke's MoE dims (chip_smoke.moe_train_model): h=1024, f=3584, 8 experts
# top-2, tokens = micro(8) x seq(1024), capacity_factor 1.25
E, H, F = 8, 1024, 3584
TOKENS = 8 * 1024
TOPK = 2
CAP = int(1.25 * TOKENS * TOPK / E)
STEPS = 30

PEAK_FLOPS = 197e12      # one v5e chip, bf16 (benchmark/peaks.py)
ROW_TILES = (128, 256, 512, 1024, 2048)
# the rows each expert drew in one step of a cell's run (my chip runs, PR 32:
# `moe_expert_rows` of olmoe-1b-7b.train.seq4k seed 3200000113, both layers,
# and of instella-moe-16b-a3b.train.seq8k seed 3200000081, layers 0, 3 and 5;
# `_held_rows` gives the last group the buffer's unfilled rows as well)
OLMOE_ROWS = {
    "cell_layer0": [493, 851, 20, 222, 742, 902, 834, 2, 211, 328, 541, 256, 299, 1090,
                    505, 136, 623, 63, 109, 666, 363, 351, 83, 465, 145, 654, 14, 448,
                    293, 963, 1625, 853, 630, 1013, 1152, 459, 765, 382, 778, 993, 527,
                    513, 330, 223, 162, 288, 512, 192, 230, 388, 939, 1357, 622, 291,
                    936, 442, 128, 615, 145, 1074, 458, 111, 128, 835],
    "cell_layer1": [491, 120, 0, 473, 527, 1852, 662, 189, 76, 185, 779, 1579, 240, 488,
                    1253, 1, 125, 628, 387, 853, 1759, 55, 0, 1338, 85, 123, 0, 1066,
                    106, 1127, 732, 112, 411, 869, 74, 153, 488, 254, 128, 275, 199,
                    192, 417, 779, 2288, 81, 102, 19, 46, 716, 3, 157, 588, 17, 23, 113,
                    762, 25, 774, 488, 667, 1279, 179, 2791],
}
INSTELLA_ROWS = {
    "cell_layer0": [1249, 1494, 1503, 1771, 2250, 1117, 2537, 2056],
    "cell_layer3": [534, 2351, 1093, 4555, 1647, 2360, 1892, 5419],
    "cell_layer5": [1621, 8334, 5760, 1781, 373, 1909, 1574, 947],
}
SHAPES = {  # name -> (rows, hidden, expert width, groups, the cell's loads, padded)
    "olmoe": (32768, 2048, 1024, 64, OLMOE_ROWS, False),
    "instella": (36864, 2048, 1408, 8, INSTELLA_ROWS, True),
}


def capacity_dense(expert_in, wi, wo):
    """[e, cap, h] batched einsum — pays cap padding (25% at cf=1.25)."""
    mid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in, wi))
    return jnp.einsum("ecf,efh->ech", mid, wo)


def ragged(tokens_sorted, group_sizes, wi, wo):
    """jax.lax.ragged_dot over expert-sorted rows — no padding FLOPs."""
    mid = jax.nn.gelu(jax.lax.ragged_dot(tokens_sorted, wi, group_sizes))
    return jax.lax.ragged_dot(mid, wo, group_sizes)


def sync(x):
    return float(jax.device_get(jnp.ravel(x)[0]))


def dense_against_ragged():
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16
    expert_in = jnp.asarray(rng.normal(size=(E, CAP, H)), dt)
    wi = jnp.asarray(rng.normal(size=(E, H, F)) * 0.02, dt)
    wo = jnp.asarray(rng.normal(size=(E, F, H)) * 0.02, dt)
    # ragged layout: same real token count (topk*TOKENS), expert-sorted,
    # slightly imbalanced groups like real routing
    n_real = TOPK * TOKENS
    split = rng.multinomial(n_real, [1 / E] * E)
    tokens_sorted = jnp.asarray(rng.normal(size=(n_real, H)), dt)
    group_sizes = jnp.asarray(split, jnp.int32)

    f_dense = jax.jit(capacity_dense)
    f_ragged = jax.jit(ragged)

    # compile + settle
    sync(f_dense(expert_in, wi, wo))
    try:
        sync(f_ragged(tokens_sorted, group_sizes, wi, wo))
        ragged_ok = True
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"variant": "ragged_dot",
                          "error": str(e)[:200]}), flush=True)
        ragged_ok = False

    results = {"dense": [], "ragged": []}
    for _ in range(4):  # interleaved windows
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = f_dense(expert_in, wi, wo)
        sync(out)
        results["dense"].append(time.perf_counter() - t0)
        if ragged_ok:
            t0 = time.perf_counter()
            for _ in range(STEPS):
                out = f_ragged(tokens_sorted, group_sizes, wi, wo)
            sync(out)
            results["ragged"].append(time.perf_counter() - t0)

    flops_real = 2 * n_real * H * F * 2  # two matmuls on real tokens
    for name, times in results.items():
        if not times:
            continue
        best = min(times)
        print(json.dumps({
            "variant": name,
            "dims": {"e": E, "h": H, "f": F, "cap": CAP, "real": n_real},
            "best_window_s": round(best, 4),
            "real_tflops": round(flops_real * STEPS / best / 1e12, 2),
            "padding_flops_frac": round(1 - n_real / (E * CAP), 3)
                if name == "dense" else 0.0,
        }), flush=True)


# ---------------------------------------------------------------------------
# the grouped matmul kernel against ragged_dot (PR 33)
# ---------------------------------------------------------------------------


def loads(m, g, cell_rows, padded, seed=0):
    """name -> group sizes [g] summing to m."""
    rng = np.random.default_rng(seed)

    def scaled(p):
        sizes = np.floor(np.asarray(p, np.float64) / np.sum(p) * m).astype(np.int64)
        sizes[np.argmax(sizes)] += m - sizes.sum()
        return sizes

    hot = np.zeros(g)
    hot[0] = 9 / 16
    rest = np.arange(1, g)[: g - 1 - (g * 23) // 64]     # 23 of 64 groups empty
    hot[rest] = (7 / 16) / len(rest)
    out = {"even": scaled(np.ones(g)),
           "dirichlet0.3": scaled(rng.dirichlet(np.full(g, 0.3))),
           "one_hot_9_16": scaled(hot)}
    for name, rows in cell_rows.items():
        rows = np.asarray(rows, np.int64)
        if padded:
            rows[-1] += m - rows.sum()
        if rows.sum() != m:
            raise ValueError(f"{name}: {rows.sum()} rows for a buffer of {m}")
        out[name] = rows
    return out


def products(kind, tiles, sub_rows=None):
    """(kernel, xla) jitted functions of one product kind: arguments
    (rows [m, k], stack [g, k, n], d_out [m, n], sizes [g]). ``sub_rows``:
    the rows a shared tile is multiplied in parts of (None: the kernel's own)."""
    from deepspeed_tpu.ops.transformer import pallas_gmm as G
    limit = dict(vmem_limit_bytes=tiles.vmem_limit_bytes, interpret=False,
                 sub_rows=sub_rows)
    rdot = jax.lax.ragged_dot
    if kind == "forward":
        kernel = lambda a, w, d, s: G._rows_call(a, w, s, tiles.fwd, transposed=False, **limit)
        xla = lambda a, w, d, s: rdot(a, w, s)
    elif kind == "row_gradient":
        kernel = lambda a, w, d, s: G._rows_call(d, w, s, tiles.dlhs, transposed=True, **limit)
        xla = lambda a, w, d, s: jax.vjp(lambda x: rdot(x, w, s), a)[1](d)[0]
    else:
        kernel = lambda a, w, d, s: G._weights_call(a, d, s, tiles.dw, out_dtype=w.dtype, **limit)
        xla = lambda a, w, d, s: jax.vjp(lambda x: rdot(a, x, s), w)[1](d)[0]
    return jax.jit(kernel), jax.jit(xla)


def best_ms(fn, args, iters=10, windows=3):
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return 1e3 * best


def emitter(name):
    """-> emit(record): one JSON line on stdout and in ``chiprun_out/<name>.jsonl``."""
    os.makedirs("chiprun_out", exist_ok=True)
    log = open(f"chiprun_out/{name}.jsonl", "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()
    return emit


def gmm_against_ragged(sweep: bool, parts: bool, only):
    from deepspeed_tpu.ops.transformer import pallas_gmm as G
    if jax.default_backend() != "tpu":
        raise SystemExit("tools/moe_ab.py gmm measures a TPU; none is attached")
    emit = emitter("gmm_ab")

    dt = jnp.bfloat16
    for shape, (m, h, f, g, cell_rows, padded) in SHAPES.items():
        if only and shape not in only:
            continue
        sizes = {name: jnp.asarray(s, jnp.int32)
                 for name, s in loads(m, g, cell_rows, padded).items()}
        for way, (k, n) in (("up", (h, f)), ("down", (f, h))):
            key = jax.random.split(jax.random.PRNGKey(0), 3)
            a = jax.random.normal(key[0], (m, k), dt)
            w = jax.random.normal(key[1], (g, k, n), dt) * 0.02
            d = jax.random.normal(key[2], (m, n), dt)
            flops = 2 * m * k * n
            chosen = G.choose_tiles(m, k, n, g, 2)
            for kind in G.KINDS:
                variants = {}
                if sweep:
                    for tm in ROW_TILES:
                        if m % tm:
                            continue
                        variants[f"kernel_tm{tm}"] = (G.GmmTiles(
                            (tm, n), (tm, k), (tm, k, n), G.VMEM_CAP), None)
                elif parts:
                    for tm in (256, 512):
                        tiles = G.GmmTiles((tm, n), (tm, k), (tm, k, n), G.VMEM_CAP)
                        variants[f"kernel_tm{tm}_whole"] = (tiles, tm)
                        variants[f"kernel_tm{tm}_parts128"] = (tiles, 128)
                else:
                    variants["kernel"] = (chosen, None)
                xla = None
                for label, (tiles, sub_rows) in variants.items():
                    kernel, xla_fn = products(kind, tiles, sub_rows)
                    xla = xla or xla_fn
                    try:
                        got = kernel(a, w, d, sizes["dirichlet0.3"])
                        want = xla(a, w, d, sizes["dirichlet0.3"])
                        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                                    - want.astype(jnp.float32))))
                        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
                    except Exception as e:  # noqa: BLE001  a tile Mosaic refuses
                        emit({"shape": shape, "way": way, "kind": kind, "variant": label,
                              "error": f"{type(e).__name__}: {str(e)[:300]}"})
                        continue
                    for load, s in sizes.items():
                        ms = best_ms(kernel, (a, w, d, s))
                        emit({"shape": shape, "way": way, "kind": kind, "variant": label,
                              "tiles": [list(tiles.fwd), list(tiles.dlhs), list(tiles.dw)],
                              "load": load, "ms": round(ms, 4),
                              "peak_share": round(flops / (ms / 1e3) / PEAK_FLOPS, 4),
                              "max_abs_err": err, "max_abs": scale})
                for load, s in sizes.items():
                    ms = best_ms(xla, (a, w, d, s))
                    emit({"shape": shape, "way": way, "kind": kind, "variant": "ragged_dot",
                          "load": load, "ms": round(ms, 4),
                          "peak_share": round(flops / (ms / 1e3) / PEAK_FLOPS, 4)})


# ---------------------------------------------------------------------------
# a share's rows back to the tokens (PR 38)
# ---------------------------------------------------------------------------

#: name -> (tokens, hidden, top_k, experts published, experts held)
ROW_SHAPES = {"instella": (16384, 2048, 6, 64, 8), "trinity": (16384, 2048, 8, 128, 16)}
SEGMENT_TILES = ((128, 128), (128, 256), (128, 512), (256, 256))


def slab_combine(rows, weight, inv, held):
    """The combine as the layer had it until PR 38: one slab a slot."""
    cap, k = rows.shape[0], weight.shape[1]
    real = (inv < held).reshape(weight.shape)
    at = jnp.minimum(inv, cap - 1).reshape(weight.shape)
    return sum(jnp.where(real[:, j, None],
                         rows.at[at[:, j]].get(mode="promise_in_bounds")
                         .astype(jnp.float32) * weight[:, j, None], 0.0)
               for j in range(k))


def slab_dispatch_bwd(g, inv, held, top_k):
    """The dispatch's backward as the layer had it until PR 38."""
    picked = g.at[jnp.minimum(inv, g.shape[0] - 1)].get(mode="promise_in_bounds")
    picked = jnp.where((inv < held)[:, None], picked.astype(jnp.float32), 0.0)
    return jnp.sum(picked.reshape(-1, top_k, g.shape[-1]), axis=1).astype(g.dtype)


def routing(n_tok, k, experts, nh, seed):
    """A step's routing as ``MoE._held_rows`` sorts it: Zipf-hot experts."""
    from deepspeed_tpu.moe import layer as L
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = (jax.random.normal(key[0], (n_tok, experts))
              + 0.5 * jax.random.normal(key[1], (experts,)))
    weight, eidx = jax.lax.top_k(jax.nn.softmax(logits), k)
    cap = L.held_capacity(n_tok * k, nh, experts)
    local = eidx.reshape(-1)
    sort_key = jnp.where(local < nh, local, nh)
    order = jnp.argsort(sort_key, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(n_tok * k, dtype=jnp.int32))
    held = jnp.minimum(jnp.sum(sort_key < nh), cap).astype(jnp.int32)
    return weight.astype(jnp.float32), order[:cap], inv, held, cap


def rows_to_tokens(only):
    from deepspeed_tpu.moe import layer as L
    from deepspeed_tpu.ops.transformer import pallas_segment_sum as S
    if jax.default_backend() != "tpu":
        raise SystemExit("tools/moe_ab.py rows measures a TPU; none is attached")
    emit = emitter("rows_ab")

    for shape, (n_tok, h, k, experts, nh) in ROW_SHAPES.items():
        if only and shape not in only:
            continue
        weight, order, inv, held, cap = routing(n_tok, k, experts, nh, seed=38)
        rows = jax.random.normal(jax.random.PRNGKey(1), (cap, h), jnp.bfloat16)
        rows = jnp.where((jnp.arange(cap) < held)[:, None], rows, jnp.nan)
        perm, by_token = jax.jit(L._token_order)(order, held)
        scale = weight.reshape(-1)[by_token]
        in_order = rows[perm]
        base = {"shape": shape, "rows": cap, "tokens": n_tok, "top_k": k,
                "held_rows": int(held)}
        readings = {
            "slab_combine": (jax.jit(slab_combine), (rows, weight, inv, held)),
            "slab_dispatch_bwd": (jax.jit(lambda g, i, f: slab_dispatch_bwd(g, i, f, k)),
                                  (rows, inv, held)),
            "token_order": (jax.jit(L._token_order), (order, held)),
            "gather_rows": (jax.jit(lambda r, p: r.at[p].get(mode="promise_in_bounds", unique_indices=True)),
                            (rows, perm)),
            "sum_scaled": (jax.jit(lambda r, s, p, b, f: L._sum_held_rows(
                r, s, p, b, f, n_tok, k)), (rows, scale, perm, by_token, held)),
            "sum_unscaled": (jax.jit(lambda r, p, b, f: L._sum_held_rows(
                r, None, p, b, f, n_tok, k)), (rows, perm, by_token, held)),
            "xla_scaled": (jax.jit(lambda r, b, s, f: S.xla_segment_sum(
                r, b // k, s, f, n_tok)), (in_order, by_token, scale, held)),
        }
        for ts, tr in SEGMENT_TILES:
            for label, sc in (("scaled", scale), ("unscaled", None)):
                readings[f"kernel_{label}_ts{ts}_tr{tr}"] = (
                    jax.jit(functools.partial(
                        lambda r, b, s, f, tiles: S.kernel_segment_sum(
                            r, b // k, s, f, n_tok, tiles=tiles, interpret=False),
                        tiles=(ts, tr, S.VMEM_CAP))),
                    (in_order, by_token, sc, held))
        want = jnp.where(jnp.isfinite(rows), rows, 0)
        want_c = slab_combine(want, weight, inv, held)
        want_d = slab_dispatch_bwd(want, inv, held, k).astype(jnp.float32)
        for name, (fn, args) in readings.items():
            try:
                got = fn(*args)
                ms = best_ms(fn, args)
            except Exception as e:  # noqa: BLE001  tiles Mosaic refuses
                emit(dict(base, variant=name, error=f"{type(e).__name__}: {str(e)[:300]}"))
                continue
            rec = dict(base, variant=name, ms=round(ms, 4))
            if name not in ("token_order", "gather_rows"):
                ref = want_d if "unscaled" in name or "dispatch" in name else want_c
                rec["max_abs_err"] = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
                rec["finite"] = bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
            emit(rec)


# ---------------------------------------------------------------------------
# the routers' bookkeeping without a scatter or a gather of scalars (PR 54)
# ---------------------------------------------------------------------------

#: name -> (tokens, top_k, experts, sequences a batch): the SDAR, Keye and
#: Trinity cells' layer, the Instella cell's, the OLMoE cell's
ROUTE_SHAPES = {"sdar": (16384, 8, 128, 1), "instella": (16384, 6, 64, 2),
                "olmoe": (4096, 8, 64, 1)}


def route_forms(n_tok, k, experts, seqs):
    """name -> (old, new or {label: new}, arguments): each function of the
    arguments, jitted by the caller. A name ending in ``_grad`` is a gradient
    by its first argument."""
    from deepspeed_tpu.moe import layer as L
    from deepspeed_tpu.moe import sharded_moe as M
    from tests.unit.models.test_instella_moe import sigmoid_router_as_it_was
    from tests.unit.moe.test_dropless import softmax_router_as_it_was, sorted_as_it_was
    key = jax.random.split(jax.random.PRNGKey(54), 4)
    logits = (jax.random.normal(key[0], (n_tok, experts))
              + 0.5 * jax.random.normal(key[1], (experts,)))
    scores = jax.nn.sigmoid(logits)
    eidx = jax.lax.top_k(scores, k)[1].astype(jnp.int32)
    ct = jax.random.normal(key[2], (n_tok, k))
    bias = jnp.zeros((experts,), jnp.float32)
    flat = eidx.reshape(-1)
    per_seq = n_tok // seqs
    weighed = lambda pick: lambda s, i, c: jnp.sum(pick(s, i) * c)
    soft = dict(top_k=k, normalize=True, balance_loss="topk_share")
    sig = dict(top_k=k, normalize=True, routed_scale=2.5, rows_per_seq=per_seq)

    def router_loss(router, **kw):
        def loss(x, c):
            _, weight, losses, _ = router(x, **kw)
            return jnp.sum(weight * c) + jnp.sum(losses)
        return loss

    pick_old = lambda s, i: jnp.take_along_axis(s, i, axis=-1)
    pick_new = lambda s, i: M._picked(s, i, experts)
    pick_sum = lambda s, i: jnp.sum(jnp.where(M._picks(i, experts), s[:, None, :], 0.0), axis=-1)
    values_old = lambda s, i: jax.lax.top_k(s, k)[0]
    values_new = lambda s, i: M._picked(s, jax.lax.top_k(jax.lax.stop_gradient(s), k)[1], experts)
    sigmoid_old = lambda x, **kw: sigmoid_router_as_it_was(x, bias, **kw)
    sigmoid_new = lambda x, **kw: M.sigmoid_bias_router(x, bias, **kw)
    return {
        "rows": (lambda i: jnp.zeros((experts,), jnp.int32).at[i.reshape(-1)].add(1),
                 {"new": lambda i: jnp.sum(M._picks(i, experts), axis=(0, 1), dtype=jnp.int32),
                  "flat_compare": lambda i: jnp.sum(
                      i.reshape(-1)[:, None] == jnp.arange(experts), axis=0, dtype=jnp.int32),
                  "mxu_one_hot": lambda i: jnp.sum(jnp.einsum(
                      "t,tke->ke", jnp.ones((n_tok,), jnp.bfloat16),
                      M._picks(i, experts).astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32), axis=0).astype(jnp.int32)},
                 (eidx,)),
        "seq_rows": (lambda i: jnp.zeros((seqs, experts), jnp.int32).at[
                         jnp.repeat(jnp.arange(seqs, dtype=jnp.int32), per_seq * k),
                         i.reshape(-1)].add(1),
                     lambda i: jnp.sum(M._picks(i, experts).reshape(seqs, per_seq * k, experts),
                                       axis=1, dtype=jnp.int32),
                     (eidx,)),
        "sort_and_inverse": (sorted_as_it_was,
                             {"new": L._sorted_by,
                              "stable_argsorts": lambda key: (
                                  jnp.argsort(key, stable=True).astype(jnp.int32),
                                  jnp.argsort(jnp.argsort(key, stable=True)).astype(jnp.int32))},
                             (flat,)),
        "inverse_alone": (lambda o: jnp.zeros_like(o).at[o].set(
                              jnp.arange(o.size, dtype=jnp.int32), unique_indices=True),
                          {"argsort": lambda o: jnp.argsort(o).astype(jnp.int32)},
                          (jnp.argsort(flat, stable=True).astype(jnp.int32),)),
        "pick": (pick_old, {"new": pick_new, "select_sum": pick_sum}, (scores, eidx)),
        "pick_grad": (jax.grad(weighed(pick_old)),
                      {"new": jax.grad(weighed(pick_new)),
                       "select_sum": jax.grad(weighed(pick_sum))}, (scores, eidx, ct)),
        "top_k_values": (values_old, values_new, (scores, eidx)),
        "top_k_values_grad": (jax.grad(weighed(values_old)), jax.grad(weighed(values_new)),
                              (scores, eidx, ct)),
        "softmax_router": (lambda x: softmax_router_as_it_was(x, **soft),
                           lambda x: M.softmax_topk_router(x, **soft), (logits,)),
        "softmax_router_grad": (jax.grad(router_loss(softmax_router_as_it_was, **soft)),
                                jax.grad(router_loss(M.softmax_topk_router, **soft)),
                                (logits, ct)),
        "sigmoid_router": (lambda x: sigmoid_old(x, **sig), lambda x: sigmoid_new(x, **sig),
                           (logits,)),
        "sigmoid_router_grad": (jax.grad(router_loss(sigmoid_old, **sig)),
                                jax.grad(router_loss(sigmoid_new, **sig)), (logits, ct)),
    }


ROUTE_TRIPS = 64


def in_one_dispatch(fn):
    """``fn`` run ``ROUTE_TRIPS`` times in ONE dispatch (a call from the host
    costs 0.2 ms, ten times what some of these forms take): each trip on
    arguments moved by the trip's number (an index stays an index of its
    range, a permutation a permutation), each result summed into the carry so
    that no trip is dead or another's. What the moving and the summing cost
    is the ``floor_ms`` beside every reading: the same loop around a function
    that hands its first argument back."""
    def run(*args):
        spans = [jnp.max(a) + 1 if jnp.issubdtype(a.dtype, jnp.integer) else None for a in args]

        def trip(i, acc):
            moved = jax.lax.optimization_barrier(
                [a + i.astype(a.dtype) * 1e-6 if m is None else (a + i.astype(a.dtype)) % m
                 for a, m in zip(args, spans)])       # or the moving fuses into what is timed
            return acc + sum(jnp.sum(leaf.astype(jnp.float32))
                             for leaf in jax.tree.leaves(fn(*moved)))
        return jax.lax.fori_loop(0, ROUTE_TRIPS, trip, jnp.zeros((), jnp.float32))
    return jax.jit(run)


def route_bookkeeping(only):
    if jax.default_backend() != "tpu":
        raise SystemExit("tools/moe_ab.py route measures a TPU; none is attached")
    emit = emitter("route_ab")

    def trip_ms(fn, args):
        return round(best_ms(in_one_dispatch(fn), args, iters=3) / ROUTE_TRIPS, 4)

    for shape, (n_tok, k, experts, seqs) in ROUTE_SHAPES.items():
        if only and shape not in only:
            continue
        base = {"shape": shape, "tokens": n_tok, "top_k": k, "experts": experts}
        for name, (old, new, args) in route_forms(n_tok, k, experts, seqs).items():
            floor = trip_ms(lambda *a: a[0], args)
            want = jax.jit(old)(*args)
            emit(dict(base, reading=name, variant="old", ms=trip_ms(old, args), floor_ms=floor))
            for label, fn in (new if isinstance(new, dict) else {"new": new}).items():
                got = jax.jit(fn)(*args)
                same = all(bool(jnp.array_equal(a, b)) for a, b in
                           zip(jax.tree.leaves(got), jax.tree.leaves(want)))
                emit(dict(base, reading=name, variant=label, same=same,
                          ms=trip_ms(fn, args), floor_ms=floor))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", nargs="?", default="dense", choices=("dense", "gmm", "rows", "route"))
    ap.add_argument("--sweep", action="store_true",
                    help="gmm: every row tile of ROW_TILES, not choose_tiles' own")
    ap.add_argument("--parts", action="store_true",
                    help="gmm: row tiles 256 and 512 with a shared tile multiplied "
                         "whole against in parts of 128 rows")
    ap.add_argument("--shape", action="append", choices=sorted({*SHAPES, *ROW_SHAPES, *ROUTE_SHAPES}),
                    help="gmm, rows, route: only this shape (may repeat)")
    args = ap.parse_args()
    if args.mode == "rows":
        rows_to_tokens(args.shape)
    elif args.mode == "route":
        route_bookkeeping(args.shape)
    elif args.mode == "gmm":
        gmm_against_ragged(args.sweep, args.parts, args.shape)
    else:
        dense_against_ragged()


if __name__ == "__main__":
    main()
