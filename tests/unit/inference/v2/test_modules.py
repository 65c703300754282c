"""Module registry / heuristics tests (reference
``tests/unit/inference/v2/modules``: per-module implementation selection)."""

import pytest

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.modules import (
    ATTENTION_DECODE_REGISTRY, DSModuleRegistry, LINEAR_REGISTRY,
    ModuleImplementation, instantiate_attention, instantiate_linear)
from deepspeed_tpu.models.gpt2 import gpt2_config
from deepspeed_tpu.models.registry import (get_architecture,
                                           supported_architectures)


def test_attention_selection_by_backend(monkeypatch):
    cfg = RaggedInferenceEngineConfig()
    mcfg = gpt2_config("gpt2-tiny")
    # the Pallas kernel is opt-in (measured slower through this runtime)
    assert instantiate_attention(cfg, mcfg, backend="tpu")["decode"].name == \
        "xla_gather"
    monkeypatch.setenv("DSTPU_PALLAS_PAGED", "1")
    assert instantiate_attention(cfg, mcfg, backend="tpu")["decode"].name == \
        "pallas_paged"
    assert instantiate_attention(cfg, mcfg, backend="cpu")["decode"].name == \
        "xla_gather"


def test_linear_selection_by_quant_mode():
    mcfg = gpt2_config("gpt2-tiny")
    assert instantiate_linear(
        RaggedInferenceEngineConfig(), mcfg).name == "dense"
    assert instantiate_linear(
        RaggedInferenceEngineConfig(quantization_mode="int8"), mcfg).name == \
        "woq_int8"
    assert instantiate_linear(
        RaggedInferenceEngineConfig(quantization_mode="int4"), mcfg).name == \
        "woq_int4"


def test_preference_override_and_unsupported(monkeypatch):
    monkeypatch.setenv("DSTPU_PALLAS_PAGED", "1")
    ctx = {"backend": "cpu"}
    assert ATTENTION_DECODE_REGISTRY.choose(ctx).name == "xla_gather"
    with pytest.raises(ValueError, match="does not support"):
        ATTENTION_DECODE_REGISTRY.choose(ctx, preference="pallas_paged")
    assert ATTENTION_DECODE_REGISTRY.choose(
        {"backend": "tpu"}, preference="pallas_paged").name == "pallas_paged"


def test_custom_registration():
    reg = DSModuleRegistry("test_slot")
    reg.register(ModuleImplementation("a", supports=lambda c: True, priority=1))
    reg.register(ModuleImplementation("b", supports=lambda c: c.get("x"),
                                      priority=9))
    assert reg.choose({}).name == "a"
    assert reg.choose({"x": 1}).name == "b"
    with pytest.raises(ValueError, match="duplicate"):
        reg.register(ModuleImplementation("a", supports=lambda c: True))


def test_architecture_registry_builtin():
    assert supported_architectures() == \
        ["afmoe", "bert", "bloom", "deepseek_v3", "distilbert", "evabyte", "falcon", "gpt2",
         "gpt_neo", "gpt_neox", "gptj", "internlm", "llama", "mistral", "mixtral",
         "opt", "phi", "qwen2", "roberta", "sdar_moe"]
    spec = get_architecture("falcon")
    cfg = spec.config_fn({"model_type": "falcon", "vocab_size": 128,
                          "hidden_size": 64, "num_hidden_layers": 2,
                          "num_attention_heads": 4})
    assert cfg["parallel_block"] is True
    with pytest.raises(ValueError, match="unsupported model_type"):
        get_architecture("mamba")
