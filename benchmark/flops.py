"""What the algorithms REQUIRE: operations per token and the bytes a
kernel must move, computed from shapes. Recomputation is never counted.
Copied in arithmetic from ``bench.py::_flops_per_token`` (6 N + 6 L H S),
kept here so that no later PR can change the yardstick."""

from __future__ import annotations


def gpt2_matmul_params(cfg: dict) -> int:
    """Parameters that multiply each token: the blocks' kernels and the
    tied output head (``wte`` used as a matrix). The token and position
    embeddings are lookups and biases are additions: not counted."""
    H, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    per_layer = 4 * H * H + 2 * H * (4 * H)   # q,k,v,o + MLP in,out
    return L * per_layer + V * H


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs that one trained token requires: 6 per
    matmul parameter (2 forward, 4 backward), plus attention's QK^T and PV
    over the causal half of the sequence, 6 L H S. gpt2-large at S=1024:
    6 x 772,117,760 + 6 x 36 x 1280 x 1024 = 4.916 GFLOP (bench.py also
    counts the 1.3 M position embeddings, a lookup, and gets 4.924)."""
    H, L = cfg["n_embd"], cfg["n_layer"]
    return 6.0 * gpt2_matmul_params(cfg) + 6.0 * L * H * seq


_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "pred": 1}


def custom_call_io_bytes(hlo: str, min_elems: int = 4096) -> float:
    """Bytes of every array result and operand of ONE custom-call
    instruction, read off its HLO text: what a kernel that reads each
    operand once and writes each result once has to move. Arrays under
    ``min_elems`` elements (scalars, hyper-parameters, seeds) are left out."""
    import re
    total = 0.0
    for dtype, dims in re.findall(r"\b([a-z]+[0-9a-z]*)\[([0-9,]*)\]", hlo.split(", custom_call_target")[0]):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n >= min_elems:
            total += n * _DTYPE_BYTES[dtype]
    return total
