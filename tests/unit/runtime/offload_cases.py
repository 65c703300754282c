"""What the offload test files share (no test here: pytest collects none)."""

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import gpt2_model


def make_engine(offload_device=None, nvme_path=None, seed=7):
    zero = {"stage": 1}
    if offload_device:
        zero["offload_optimizer"] = {"device": offload_device,
                                     **({"nvme_path": nvme_path} if nvme_path else {})}
    m = gpt2_model("gpt2-tiny", max_seq_len=16, vocab_size=128, remat=False)
    eng, _, _, _ = deepspeed_tpu.initialize(model=m, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "zero_optimization": zero,
    }, seed=seed)
    return eng
