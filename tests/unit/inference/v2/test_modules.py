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
    """Every ``models/<arch>.py`` that adapts a configuration and a checkpoint
    (``config_kwargs`` / ``checkpoint_params``) is in the registry under its
    own pair, whatever registered it, and beside them the families whose
    adapters ``state_dict_factory`` holds."""
    import importlib
    import pkgutil

    from deepspeed_tpu import models
    from deepspeed_tpu.runtime import state_dict_factory
    specs = [get_architecture(name) for name in supported_architectures()]
    pairs = {(spec.config_fn, spec.params_fn) for spec in specs}
    found = [importlib.import_module(f"{models.__name__}.{info.name}")
             for info in pkgutil.iter_modules(models.__path__)]
    adapters = [m for m in found if hasattr(m, "config_kwargs")]
    assert len(adapters) >= 4
    assert all((m.config_kwargs, m.checkpoint_params) in pairs for m in adapters)
    homes = {m.__name__ for m in adapters} | {state_dict_factory.__name__}
    assert {spec.config_fn.__module__ for spec in specs} == homes
    assert {"gpt2", "llama", "bert", "deepseek_v3", "evabyte"} <= set(supported_architectures())
    spec = get_architecture("falcon")
    cfg = spec.config_fn({"model_type": "falcon", "vocab_size": 128,
                          "hidden_size": 64, "num_hidden_layers": 2,
                          "num_attention_heads": 4})
    assert cfg["parallel_block"] is True
    with pytest.raises(ValueError, match="unsupported model_type"):
        get_architecture("mamba")
