"""ZeRO-Offload / ZeRO-Infinity optimizer path.

Counterpart of the reference's offloaded optimizer step
(``stage_1_and_2.py``/``stage3.py`` with ``offload_optimizer`` set: fp32
master params + moments live on the host, updated by the C++ CPU optimizer
while the accelerator holds only bf16/fp16 params; device=nvme additionally
pages the moments through the AIO engine per sub-group —
``swap_tensor/partitioned_optimizer_swapper.py:29``).

TPU shape of the same idea: the jitted micro-step accumulates gradients on
device; at the boundary the engine pulls gradients to host, this runner
updates master params in place (native SIMD kernel), and the engine pushes
re-cast model params back. With NVMe, moments stream through
``OptimizerStateSwapper`` double-buffered so leaf i+1's read overlaps leaf
i's compute (the reference's pipelined swapper).
"""

from __future__ import annotations

import os
import tempfile
from typing import Dict, List, Optional, Tuple

import numpy as np

from ...ops.adam.cpu_adam import DeepSpeedCPUAdagrad, DeepSpeedCPUAdam, DeepSpeedCPULion
from ..swap_tensor.optimizer_swapper import OptimizerStateSwapper


def offload_pipeline_enabled() -> bool:
    """The ISSUE-15 double-buffered offload pipeline's kill switch:
    ``DSTPU_OFFLOAD_PIPELINE=0`` restores the serial
    fetch→compute→writeback schedule BITWISE (the pipeline only reorders
    independent transfers — same chunk boundaries, same arithmetic order
    — so the hatch is a schedule A/B, not a numerics A/B; a CPU-mesh
    parity test pins the bitwise claim)."""
    return os.environ.get("DSTPU_OFFLOAD_PIPELINE", "").strip() not in (
        "0", "off", "false")


class OffloadedOptimizerRunner:

    def __init__(self, opt_type: str, opt_params: Dict, leaves: List[np.ndarray],
                 device: str = "cpu", nvme_path: Optional[str] = None,
                 pipeline: bool = True):
        self.opt_type = opt_type.lower()
        # np.array: writable owned copies (inputs may be read-only device views)
        self.master: List[np.ndarray] = [np.array(l, np.float32) for l in leaves]
        self.device = device
        self.step_count = 0
        self.last_stall_s = 0.0    # NVMe fence-blocked time of the last step
        self.last_compute_s = 0.0  # host optimizer wall time of the last step
        self.last_fetch_s = 0.0    # time blocked pulling grads from a LAZY
        # feed (engine pipeline: the D2H landing of the next bucket) — kept
        # out of last_compute_s so the stall decomposition stays honest

        lr = opt_params.get("lr", 1e-3)
        wd = opt_params.get("weight_decay", 0.0)
        betas = tuple(opt_params.get("betas", (0.9, 0.999)))
        eps = opt_params.get("eps", 1e-8)
        if self.opt_type in ("adam", "adamw"):
            self._opt = DeepSpeedCPUAdam(lr=lr, betas=betas, eps=eps,
                                         weight_decay=wd,
                                         adamw_mode=self.opt_type == "adamw",
                                         _sanctioned=True)
            self._slots = 2  # m, v
        elif self.opt_type == "lion":
            self._opt = DeepSpeedCPULion(lr=lr, betas=betas or (0.9, 0.99),
                                         weight_decay=wd, _sanctioned=True)
            self._slots = 1
        elif self.opt_type == "adagrad":
            self._opt = DeepSpeedCPUAdagrad(lr=lr, eps=eps, weight_decay=wd,
                                            _sanctioned=True)
            self._slots = 1
        else:
            raise ValueError(f"offload unsupported for optimizer '{opt_type}' "
                             f"(cpu kernels: adam/adamw/lion/adagrad)")

        if device == "nvme":
            swap_dir = nvme_path or os.path.join(tempfile.gettempdir(), "dstpu_nvme")
            self._swapper = OptimizerStateSwapper(
                os.path.join(swap_dir, f"opt_{id(self):x}"), pipeline=pipeline)
            max_elems = max((m.size for m in self.master), default=1)
            # 4 rotating buffers, not 2: with 2, the write-back of buffer i
            # must fence before its reuse at group i+2 — every other group
            # serializes behind a write and the read-ahead buys nothing
            # (not measured on the current machine)
            self._buffers = [np.zeros(self._slots * max_elems, np.float32)
                             for _ in range(4)]
            for i, m in enumerate(self.master):
                self._swapper.register(self._key(i), np.zeros(self._slots * m.size,
                                                              np.float32))
            self._state = None
        else:
            self._swapper = None
            self._state = [np.zeros(self._slots * m.size, np.float32)
                           for m in self.master]

    def _key(self, i: int) -> str:
        return f"leaf{i}"

    def _apply(self, i: int, grad: np.ndarray, state: np.ndarray,
               lr: Optional[float], step: int) -> None:
        p = self.master[i]
        n = p.size
        if self._slots == 2:
            m, v = state[:n], state[n:2 * n]
            self._opt.step(p, grad, m, v, step=step, lr=lr)
        elif self.opt_type == "lion":
            self._opt.step(p, grad, state[:n], lr=lr)
        else:
            self._opt.step(p, grad, state[:n], lr=lr)

    def step(self, grads: List[np.ndarray], lr: Optional[float] = None) -> List[np.ndarray]:
        """In-place master update; returns the master leaves. Sets
        ``last_stall_s``/``last_compute_s`` so callers can report the
        paging-stall fraction (time blocked on NVMe fences / step time —
        what the pipelined swapper exists to drive toward zero)."""
        for _ in self.step_iter(grads, lr):
            pass
        return self.master

    def step_iter(self, grads, lr: Optional[float] = None):
        """Generator form of :meth:`step`: yields ``(i, master_i)`` as each
        chunk's update lands, so the caller can begin the H2D param push of
        completed chunks WHILE later chunks are still paging/stepping (the
        reference's overlap of optimizer work with adjacent phases,
        stage_1_and_2.py:1005 — here host compute overlaps device upload).

        ``grads`` may be a list OR a lazy iterable (the engine's pipelined
        schedule feeds chunks as their D2H transfers land, so chunk i's
        host step runs while chunk i+1 is still on the wire). Each chunk
        is pulled only when its update is about to run; time blocked
        inside the feed accumulates in ``last_fetch_s``, never in
        ``last_compute_s``."""
        import time
        if hasattr(grads, "__len__"):
            assert len(grads) == len(self.master)
        self.step_count += 1
        # last_compute_s accumulates ONLY this generator's own work
        # segments — consumer time between yields (the engine's H2D pushes)
        # must not inflate "host optimizer wall time", or stall_frac =
        # stall/compute deflates in the flattering direction
        self.last_compute_s = 0.0
        self.last_stall_s = 0.0
        self.last_fetch_s = 0.0
        grad_it = iter(grads)

        def pull(i: int) -> np.ndarray:
            t0 = time.perf_counter()
            try:
                g = next(grad_it)
            except StopIteration:
                raise ValueError(
                    f"grad feed exhausted at chunk {i} of "
                    f"{len(self.master)}") from None
            self.last_fetch_s += time.perf_counter() - t0
            return np.ascontiguousarray(g, np.float32).reshape(-1)

        seg = time.perf_counter()
        if self._swapper is None:
            for i in range(len(self.master)):
                g = pull(i)
                seg = time.perf_counter()  # fetch wait is not compute
                self._apply(i, g, self._state[i], lr, self.step_count)
                self.last_compute_s += time.perf_counter() - seg
                yield i, self.master[i]
                seg = time.perf_counter()
        else:
            self._swapper.take_stall()  # reset
            keys = [self._key(i) for i in range(len(self.master))]
            it = self._swapper.swap_groups(keys, self._buffers)
            i = 0
            while True:
                try:
                    key, buf = next(it)
                except StopIteration:
                    # swap_groups' exhaustion path fences the tail
                    # write-backs (finish_writes) — that stall belongs to
                    # THIS step, not the next one's reset
                    self.last_stall_s += self._swapper.take_stall()
                    self.last_compute_s += time.perf_counter() - seg
                    break
                g = pull(i)
                seg = time.perf_counter()
                n = self._slots * self.master[i].size
                self._apply(i, g, buf[:n], lr, self.step_count)
                self.last_stall_s += self._swapper.take_stall()
                self.last_compute_s += time.perf_counter() - seg
                yield i, self.master[i]
                seg = time.perf_counter()
                i += 1

    # -- checkpoint support --------------------------------------------------
    def state_dict(self) -> Dict:
        if self._swapper is None:
            states = self._state
        else:
            states = []
            for i in range(len(self.master)):
                buf = np.zeros(self._slots * self.master[i].size, np.float32)
                self._swapper.start_read(self._key(i), buf)
                self._swapper.finish_read()
                states.append(buf)
        return {"step": self.step_count, "master": self.master, "state": states}

    def load_state_dict(self, sd: Dict) -> None:
        self.step_count = sd["step"]
        for dst, src in zip(self.master, sd["master"]):
            dst[...] = np.asarray(src, np.float32).reshape(dst.shape)
        if self._swapper is None:
            for dst, src in zip(self._state, sd["state"]):
                dst[...] = np.asarray(src, np.float32).reshape(dst.shape)
        else:
            for i, src in enumerate(sd["state"]):
                self._swapper.register(self._key(i),
                                       np.asarray(src, np.float32))
