"""Environment/compat report.

Counterpart of the reference ``bin/ds_report`` (+ ``deepspeed/env_report.py``):
prints framework versions, accelerator, op availability. CLI:
``python -m deepspeed_tpu.env_report`` or ``bin/dstpu_report``.
"""

from __future__ import annotations

import importlib
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_FAIL = "\033[91m[FAIL]\033[0m"


def op_report() -> list:
    """Which op implementations are usable here (reference ds_report op table)."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    rows = []
    rows.append(("fused_adam (pallas)", True, "interpret mode on cpu"))
    rows.append(("quantizer int8/int4", True, "XLA"))
    rows.append(("flash_attention (pallas)", on_tpu, "tpu only; XLA fallback elsewhere"))
    try:
        from deepspeed_tpu.ops.aio import AsyncIOBuilder
        rows.append(("async_io (C++)", AsyncIOBuilder().is_compatible(), "NVMe offload tier"))
    except ImportError:
        rows.append(("async_io (C++)", False, "not built"))
    return rows


def main() -> int:
    print("-" * 60)
    print("DeepSpeed-TPU C++/Pallas op report")
    print("-" * 60)
    for name, ok, note in op_report():
        print(f"{name:<28} {GREEN_OK if ok else RED_FAIL:<18} {note}")
    print("-" * 60)
    print("General environment:")
    for mod in ("jax", "jaxlib", "flax", "optax", "numpy"):
        try:
            m = importlib.import_module(mod)
            print(f"{mod:<12} version: {getattr(m, '__version__', '?')}")
        except ImportError:
            print(f"{mod:<12} NOT INSTALLED")
    import jax
    print(f"platform: {jax.default_backend()}")
    try:
        devs = jax.devices()
        print(f"devices: {len(devs)} x {devs[0].device_kind if devs else '?'}")
    except Exception as e:  # pragma: no cover
        print(f"devices: unavailable ({e})")
    import deepspeed_tpu
    print(f"deepspeed_tpu version: {deepspeed_tpu.__version__}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
