"""Host-side ragged wave builder — the TPU-native ``atom_builder``.

Counterpart of the reference's ``inference/v2/kernels/ragged_ops/
atom_builder`` (ragged_ops.cpp:20-47): a scheduled wave — any mix of
prefill chunks and decode tokens — is flattened into ONE token stream plus
the per-atom descriptors the ragged paged attention kernel prefetches as
scalars (``cu_q_lens`` / ``kv_lens`` / ``page_indices``; see
``kernels/ragged_paged_attention.py``). Everything here is numpy on the
host: descriptors are metadata, exactly like the reference's pinned-host
atom buffers.

Shapes are padded to power-of-two buckets so one compiled program per
``(n_tokens, n_atoms, max_pages)`` bucket serves every wave composition —
the property that lets the scheduler drop its three-canonical-shapes
restriction (ISSUE 6). With a data-sharded page pool the builder produces
one sub-wave per shard, all padded to the SAME bucket, concatenated in
shard order for ``shard_map`` to split (``build_sharded_wave``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

from .ragged_wrapper import _next_bucket


@dataclasses.dataclass
class WaveEntry:
    """One scheduled sequence-chunk: ``tokens`` are the new tokens (1 for
    a decode), ``seen`` the tokens already in cache, ``blocks`` the
    sequence's block table in POOL-LOCAL ids (the caller subtracts the
    shard base for a sharded pool)."""
    uid: int
    tokens: np.ndarray
    seen: int
    blocks: List[int]


@dataclasses.dataclass
class WaveDescriptors:
    """Device-ready (still host numpy) arrays for one wave dispatch."""
    tokens: np.ndarray        # [N] i32 flat stream (atom-major)
    positions: np.ndarray     # [N] i32 absolute positions
    write_idx: np.ndarray     # [N] i32 flat slot in the (local) pool
    cu_q_lens: np.ndarray     # [A+1] i32 (per rank: [R*(A+1)] concatenated)
    kv_lens: np.ndarray       # [A] i32
    page_indices: np.ndarray  # [A, MP] i32 (local ids)
    last_rows: np.ndarray     # [R] i32 flat row of each entry's last token
    row_of_uid: Dict[int, int]  # uid -> row in the logits output
    n_tokens: int             # valid (un-padded) token count


def wave_buckets(entries: Sequence[WaveEntry], block_q: int,
                 block_size: int) -> Tuple[int, int, int, int]:
    """(N, A, MP, R) buckets for one shard's entry list."""
    total_q = sum(len(e.tokens) for e in entries)
    n_atoms = sum(-(-len(e.tokens) // block_q) for e in entries)
    max_pages = max((len(e.blocks) for e in entries), default=1)
    N = _next_bucket(max(total_q, 1), lo=16)
    A = _next_bucket(max(n_atoms, 1), lo=8)
    MP = _next_bucket(max(max_pages, 1), lo=4)
    R = _next_bucket(max(len(entries), 1), lo=8)
    return N, A, MP, R


def build_wave(entries: Sequence[WaveEntry], *, block_q: int,
               block_size: int,
               buckets: Tuple[int, int, int, int] = None) -> WaveDescriptors:
    """Flatten one shard's entries into padded wave descriptors.

    Pad rows write to the (local) null block 0 and belong to zero-length
    atoms whose every page the kernel skips.
    """
    N, A, MP, R = buckets or wave_buckets(entries, block_q, block_size)
    ps = block_size
    tokens = np.zeros((N,), np.int32)
    positions = np.zeros((N,), np.int32)
    write_idx = np.zeros((N,), np.int32)   # pad rows -> null block slot 0
    cu = np.zeros((A + 1,), np.int32)
    kv_lens = np.zeros((A,), np.int32)
    pages = np.zeros((A, MP), np.int32)
    last_rows = np.zeros((R,), np.int32)
    row_of_uid: Dict[int, int] = {}

    flat = 0
    atom = 0
    for r, e in enumerate(entries):
        chunk = np.asarray(e.tokens, np.int32)
        q_len = len(chunk)
        assert q_len > 0, f"empty chunk for uid {e.uid}"
        blocks = np.asarray(e.blocks, np.int32)
        pos = e.seen + np.arange(q_len, dtype=np.int32)
        tokens[flat:flat + q_len] = chunk
        positions[flat:flat + q_len] = pos
        write_idx[flat:flat + q_len] = blocks[pos // ps] * ps + pos % ps
        for off in range(0, q_len, block_q):
            al = min(block_q, q_len - off)
            cu[atom + 1] = cu[atom] + al
            kv_lens[atom] = e.seen + off + al
            bt = blocks[:MP]
            pages[atom, :len(bt)] = bt
            atom += 1
        flat += q_len
        last_rows[r] = flat - 1
        row_of_uid[e.uid] = r
    # padding atoms: cu stays flat (zero-length), kv_lens 0 -> every page
    # skipped in-kernel
    cu[atom + 1:] = cu[atom]
    return WaveDescriptors(tokens, positions, write_idx, cu, kv_lens, pages,
                           last_rows, row_of_uid, n_tokens=flat)


def build_sharded_wave(per_shard: Sequence[Sequence[WaveEntry]], *,
                       block_q: int, block_size: int) -> WaveDescriptors:
    """One sub-wave per pool shard, all padded to the SAME bucket shape,
    concatenated in shard order. ``shard_map`` splits every array on its
    leading axis; ``row_of_uid`` maps into the concatenated logits
    ``[n_shards * R, V]``."""
    n = len(per_shard)
    if n == 1:
        return build_wave(per_shard[0], block_q=block_q,
                          block_size=block_size)
    shard_buckets = [wave_buckets(e, block_q, block_size) for e in per_shard]
    buckets = tuple(max(b[i] for b in shard_buckets) for i in range(4))
    waves = [build_wave(e, block_q=block_q, block_size=block_size,
                        buckets=buckets) for e in per_shard]
    N, A, MP, R = buckets
    row_of_uid: Dict[int, int] = {}
    for r, w in enumerate(waves):
        for uid, row in w.row_of_uid.items():
            row_of_uid[uid] = r * R + row
    return WaveDescriptors(
        tokens=np.concatenate([w.tokens for w in waves]),
        positions=np.concatenate([w.positions for w in waves]),
        write_idx=np.concatenate([w.write_idx for w in waves]),
        cu_q_lens=np.concatenate([w.cu_q_lens for w in waves]),
        kv_lens=np.concatenate([w.kv_lens for w in waves]),
        page_indices=np.concatenate([w.page_indices for w in waves]),
        last_rows=np.concatenate([w.last_rows for w in waves]),
        row_of_uid=row_of_uid,
        n_tokens=sum(w.n_tokens for w in waves))


# ---------------------------------------------------------------------------
# what a dispatch counted (engine_v2 keeps the running totals)
# ---------------------------------------------------------------------------

#: real against bucketed sizes, and the attention work of the real atoms:
#: ``attn_q_kv`` is the sum over atoms of q_len x kv_len (the score and value
#: FLOPs are 4 x heads x head_dim times it), ``attn_kv`` the sum of kv_len
#: (the KV bytes read are 2 x kv_heads x head_dim x itemsize times it)
COUNTER_KEYS = ("dispatches", "tokens", "tokens_bucket", "atoms",
                "atoms_bucket", "pages", "pages_bucket", "rows",
                "rows_bucket", "attn_q_kv", "attn_kv")


def _counters(q_lens: np.ndarray, kv_lens: np.ndarray, block_size: int,
              rows: int, bucket: Tuple[int, int, int, int]) -> Dict[str, int]:
    """Counters of one dispatch from its REAL atoms (``q_lens > 0``) and its
    bucket ``(tokens, atoms, pages, rows)``, all shards together."""
    real = q_lens > 0
    q = q_lens[real].astype(np.int64)
    kv = kv_lens[real].astype(np.int64)
    return {"dispatches": 1,
            "tokens": int(q.sum()), "tokens_bucket": int(bucket[0]),
            "atoms": int(real.sum()), "atoms_bucket": int(bucket[1]),
            "pages": int((-(-kv // block_size)).sum()),
            "pages_bucket": int(bucket[2]),
            "rows": int(rows), "rows_bucket": int(bucket[3]),
            "attn_q_kv": int((q * kv).sum()), "attn_kv": int(kv.sum())}


def wave_key(desc: WaveDescriptors) -> Tuple[int, int, int, int]:
    """The per-shard bucket ``(N, A, MP, R)`` the wave program compiled for."""
    shards = len(desc.cu_q_lens) - len(desc.kv_lens)
    return (len(desc.tokens) // shards, len(desc.kv_lens) // shards,
            int(desc.page_indices.shape[1]), len(desc.last_rows) // shards)


def wave_counters(desc: WaveDescriptors, rows: int,
                  block_size: int) -> Dict[str, int]:
    """Counters of one wave dispatch: ``rows`` real sequence-chunks; the
    page bucket is every (atom, page) pair the kernel's grid walks."""
    shards = len(desc.cu_q_lens) - len(desc.kv_lens)
    q_lens = np.diff(desc.cu_q_lens.reshape(shards, -1), axis=1).reshape(-1)
    return _counters(q_lens, desc.kv_lens, block_size, rows,
                     (len(desc.tokens), len(desc.kv_lens),
                      desc.page_indices.size, len(desc.last_rows)))


def burst_counters(seen: Sequence[int], num_steps: int, bucket_rows: int,
                   bucket_pages: int, block_size: int) -> Dict[str, int]:
    """Counters of one fused decode burst: every (sequence, step) is an
    atom of one query over ``seen + step + 1`` keys; padded rows run too."""
    kv = (np.asarray(seen, np.int64)[:, None]
          + np.arange(1, num_steps + 1, dtype=np.int64)[None, :]).reshape(-1)
    slots = bucket_rows * num_steps
    return _counters(np.ones_like(kv), kv, block_size, len(seen),
                     (slots, slots, slots * bucket_pages, bucket_rows))
