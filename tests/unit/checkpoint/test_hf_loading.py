"""External-weights ingestion tests (reference tests: inference
test_inference.py HF model matrix + state_dict_factory/MegatronSDLoader TP
resharding; here: real tiny HF checkpoints saved by ``transformers``,
loaded into the TPU pytree, logits compared against the torch forward)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.module_inject.auto_tp import AutoTP, shard_param_tree
from deepspeed_tpu.runtime.state_dict_factory import load_hf_model

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


@pytest.fixture(scope="module")
def gpt2_ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf_gpt2")
    cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=64, n_layer=2, n_head=4)
    torch.manual_seed(0)
    m = transformers.GPT2LMHeadModel(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def llama_ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf_llama")
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=172,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64)
    torch.manual_seed(1)
    m = transformers.LlamaForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def opt_ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf_opt")
    cfg = transformers.OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=256, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        word_embed_proj_dim=64, do_layer_norm_before=True)
    torch.manual_seed(2)
    m = transformers.OPTForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def phi_ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("hf_phi")
    cfg = transformers.PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, partial_rotary_factor=0.5)
    torch.manual_seed(3)
    m = transformers.PhiForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


_FALCON_COMMON = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=4, parallel_attn=True, bias=False,
                      alibi=False, max_position_embeddings=64)


@pytest.fixture(scope="module")
def falcon_mqa_ckpt(tmp_path_factory):
    """falcon-7b-style: multi-query attention, old decoder, one shared norm."""
    path = tmp_path_factory.mktemp("hf_falcon_mqa")
    cfg = transformers.FalconConfig(
        multi_query=True, new_decoder_architecture=False, **_FALCON_COMMON)
    torch.manual_seed(4)
    m = transformers.FalconForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def falcon_gqa_ckpt(tmp_path_factory):
    """falcon-40b-style: grouped KV, new decoder, per-branch parallel norms."""
    path = tmp_path_factory.mktemp("hf_falcon_gqa")
    cfg = transformers.FalconConfig(
        num_kv_heads=2, new_decoder_architecture=True, **_FALCON_COMMON)
    torch.manual_seed(5)
    m = transformers.FalconForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def falcon_bias_ckpt(tmp_path_factory):
    """bias=True exercises the fused query_key_value BIAS split."""
    path = tmp_path_factory.mktemp("hf_falcon_bias")
    cfg = transformers.FalconConfig(
        **{**_FALCON_COMMON, "bias": True},
        multi_query=False, new_decoder_architecture=False)
    torch.manual_seed(6)
    m = transformers.FalconForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def bloom_ckpt(tmp_path_factory):
    """alibi bias + word_embeddings_layernorm + per-head-interleaved QKV."""
    path = tmp_path_factory.mktemp("hf_bloom")
    cfg = transformers.BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4)
    torch.manual_seed(7)
    m = transformers.BloomForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def gpt_neox_ckpt(tmp_path_factory):
    """parallel residual with two norms + partial rotary + untied embed_out."""
    path = tmp_path_factory.mktemp("hf_neox")
    cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=0.25,
        max_position_embeddings=64, use_parallel_residual=True)
    torch.manual_seed(8)
    m = transformers.GPTNeoXForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def gpt_neox_seq_ckpt(tmp_path_factory):
    """pythia-70m-style sequential residual (use_parallel_residual=False)."""
    path = tmp_path_factory.mktemp("hf_neox_seq")
    cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=0.25,
        max_position_embeddings=64, use_parallel_residual=False)
    torch.manual_seed(9)
    m = transformers.GPTNeoXForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def gpt_neox_nobias_ckpt(tmp_path_factory):
    """attention_bias=False strips ONLY the attn projections' biases — the
    MLP keeps its biases (HF GPTNeoXMLP is unconditionally biased)."""
    path = tmp_path_factory.mktemp("hf_neox_nobias")
    cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=0.25,
        max_position_embeddings=64, attention_bias=False)
    torch.manual_seed(11)
    m = transformers.GPTNeoXForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def gptj_ckpt(tmp_path_factory):
    """interleaved partial rotary + bias-free attention + biased lm_head."""
    path = tmp_path_factory.mktemp("hf_gptj")
    cfg = transformers.GPTJConfig(
        vocab_size=128, n_positions=64, n_embd=64, n_layer=2, n_head=4,
        rotary_dim=8)
    torch.manual_seed(10)
    m = transformers.GPTJForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def bert_ckpt(tmp_path_factory):
    """post-LN bidirectional encoder + segment embeddings + cls MLM head."""
    path = tmp_path_factory.mktemp("hf_bert")
    cfg = transformers.BertConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=256,
        max_position_embeddings=64)
    torch.manual_seed(12)
    m = transformers.BertForMaskedLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def roberta_ckpt(tmp_path_factory):
    """bert body with lm_head naming and +2 position padding offset."""
    path = tmp_path_factory.mktemp("hf_roberta")
    cfg = transformers.RobertaConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=256,
        max_position_embeddings=66, type_vocab_size=1)
    torch.manual_seed(13)
    m = transformers.RobertaForMaskedLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def gpt_neo_ckpt(tmp_path_factory):
    """alternating global/local attention (window 4 < seq so it matters),
    UNSCALED attention, bias-free q/k/v with biased out_proj."""
    path = tmp_path_factory.mktemp("hf_gpt_neo")
    cfg = transformers.GPTNeoConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=256, max_position_embeddings=64,
        attention_types=[[["global", "local"], 1]], window_size=4)
    torch.manual_seed(15)
    m = transformers.GPTNeoForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def mistral_sw_ckpt(tmp_path_factory):
    """mistral with a sliding window SMALLER than the test sequence, so the
    window mask actually changes logits."""
    path = tmp_path_factory.mktemp("hf_mistral_sw")
    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=172,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=6)
    torch.manual_seed(16)
    m = transformers.MistralForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def distilbert_ckpt(tmp_path_factory):
    """no token types, q_lin/k_lin naming, vocab_transform MLM head."""
    path = tmp_path_factory.mktemp("hf_distilbert")
    cfg = transformers.DistilBertConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, hidden_dim=256,
        max_position_embeddings=64)
    torch.manual_seed(14)
    m = transformers.DistilBertForMaskedLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def internlm_ckpt(tmp_path_factory):
    """InternLM v1 = the llama block with biased q/k/v/o (reference
    containers/internlm.py). transformers has no native class, but
    LlamaForCausalLM with attention_bias=True IS that architecture — save
    it, then relabel the config to internlm's own spelling (model_type +
    'bias') so the loader's internlm mapping is what gets exercised."""
    import json as _json
    path = tmp_path_factory.mktemp("hf_internlm")
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=172,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=64, attention_bias=True)
    torch.manual_seed(21)
    m = transformers.LlamaForCausalLM(cfg).eval()
    with torch.no_grad():  # saved biases must be nonzero to prove loading
        for layer in m.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj, layer.self_attn.o_proj):
                proj.bias.uniform_(-0.05, 0.05)
    m.save_pretrained(path)
    cfg_path = path / "config.json"
    raw = _json.loads(cfg_path.read_text())
    raw["model_type"] = "internlm"
    raw.pop("attention_bias", None)
    raw["bias"] = True
    cfg_path.write_text(_json.dumps(raw))
    return path, m


@pytest.fixture(scope="module")
def qwen2_ckpt(tmp_path_factory):
    """qwen2: llama family with q/k/v biases but NO o_proj bias, tied
    embeddings, and an inert sliding_window (use_sliding_window=False)
    that must not truncate attention."""
    path = tmp_path_factory.mktemp("hf_qwen2")
    cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=172,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=True,
        use_sliding_window=False, sliding_window=8)
    torch.manual_seed(22)
    m = transformers.Qwen2ForCausalLM(cfg).eval()
    with torch.no_grad():
        for layer in m.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj):
                proj.bias.uniform_(-0.05, 0.05)
    m.save_pretrained(path)
    return path, m


@pytest.fixture(scope="module")
def qwen2_sw_ckpt(tmp_path_factory):
    """qwen2 with the window ACTIVE: use_sliding_window=True and
    max_window_layers=1 means layer 0 attends globally while layer 1 is
    windowed (HF layer_types) — the per-layer attn_windows path."""
    path = tmp_path_factory.mktemp("hf_qwen2_sw")
    cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=172,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=True,
        use_sliding_window=True, sliding_window=8, max_window_layers=1)
    torch.manual_seed(23)
    m = transformers.Qwen2ForCausalLM(cfg).eval()
    m.save_pretrained(path)
    return path, m


def _ref_logits(m, ids):
    with torch.no_grad():
        return m(torch.tensor(ids)).logits.float().numpy()


def _our_logits(path, ids, **overrides):
    model, params = load_hf_model(str(path), dtype=jnp.float32, **overrides)
    logits, _ = model.apply(jax.tree.map(jnp.asarray, params), jnp.asarray(ids))
    return np.asarray(logits)


@pytest.mark.parametrize("ckpt", ["gpt2_ckpt", "llama_ckpt", "opt_ckpt",
                                  "phi_ckpt", "falcon_mqa_ckpt",
                                  "falcon_gqa_ckpt", "falcon_bias_ckpt",
                                  "bloom_ckpt", "gpt_neox_ckpt",
                                  "gpt_neox_seq_ckpt", "gpt_neox_nobias_ckpt",
                                  "gptj_ckpt", "bert_ckpt", "roberta_ckpt",
                                  "distilbert_ckpt", "gpt_neo_ckpt",
                                  "mistral_sw_ckpt", "internlm_ckpt",
                                  "qwen2_ckpt", "qwen2_sw_ckpt"])
def test_hf_logits_parity(request, eight_devices, ckpt):
    """Loaded checkpoints must reproduce the HF forward exactly (fp32)."""
    path, m = request.getfixturevalue(ckpt)
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 16))
    np.testing.assert_allclose(_our_logits(path, ids), _ref_logits(m, ids),
                               rtol=2e-4, atol=2e-4)


def test_init_inference_from_model_path(eight_devices, llama_ckpt):
    """init_inference(model_path=...) end to end, TP=2: sharded placement
    and correct generation-path logits."""
    path, m = llama_ckpt
    engine = deepspeed_tpu.init_inference(
        model_path=str(path), config={"tensor_parallel": {"tp_size": 2},
                                      "dtype": jnp.float32})
    assert engine.topology.model_parallel_size == 2
    # column-parallel leaves must actually be sharded over the model axis
    q_sharding = engine.params["blocks"]["q_proj"]["kernel"].sharding
    assert "model" in str(q_sharding.spec)
    ids = np.random.default_rng(1).integers(0, 128, size=(2, 12))
    np.testing.assert_allclose(np.asarray(engine.forward(ids)),
                               _ref_logits(m, ids), rtol=2e-4, atol=2e-4)


def test_shard_param_tree_matches_device_slices(eight_devices, llama_ckpt):
    """Explicit per-rank TP slicing (MegatronSDLoader equivalent) must agree
    with what the SPMD placement puts on each device."""
    path, _ = llama_ckpt
    model, params = load_hf_model(str(path), dtype=jnp.float32)
    specs = AutoTP(hidden_size=model.config.hidden_size).build_specs(params)
    full = params["blocks"]["q_proj"]["kernel"]  # [L, in, out] column-parallel
    for rank, tp in ((0, 2), (1, 2)):
        shard = shard_param_tree(params, specs, rank, tp)["blocks"]["q_proj"]["kernel"]
        k = full.shape[-1] // tp
        np.testing.assert_array_equal(shard, full[..., rank * k:(rank + 1) * k])


@pytest.mark.parametrize("ckpt", ["llama_ckpt", "opt_ckpt", "phi_ckpt",
                                  "falcon_gqa_ckpt", "bloom_ckpt",
                                  "gpt_neox_ckpt", "gptj_ckpt",
                                  "mistral_sw_ckpt", "gpt_neo_ckpt",
                                  "qwen2_ckpt"])
def test_build_hf_engine_v2_greedy_matches_hf(request, eight_devices, ckpt):
    """The ragged serving engine loaded from the checkpoint must greedy-decode
    the same tokens as HF ``generate`` — across the decoder family matrix."""
    path, m = request.getfixturevalue(ckpt)
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import build_hf_engine
    from deepspeed_tpu.inference.v2.scheduler import generate

    prompt = np.random.default_rng(3).integers(0, 128, size=(12,))
    with torch.no_grad():
        ref = m.generate(torch.tensor(prompt[None]), max_new_tokens=6,
                         do_sample=False).numpy()[0, len(prompt):]
    eng = build_hf_engine(str(path), dtype=jnp.float32,
                          config=RaggedInferenceEngineConfig(
                              kv_cache_dtype=jnp.float32, num_kv_blocks=64))
    out = generate(eng, [prompt], max_new_tokens=6)[0]
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_bert_padded_attention_mask_parity(eight_devices, bert_ckpt):
    """Right-padded batches with attention_mask + token_type_ids must match
    HF on the REAL (non-pad) positions."""
    path, m = bert_ckpt
    model, params = load_hf_model(str(path), dtype=jnp.float32)
    rng = np.random.default_rng(6)
    ids = rng.integers(5, 128, size=(2, 16))
    mask = np.ones((2, 16), np.int32)
    ids[0, 12:] = 0; mask[0, 12:] = 0           # ragged batch, right-padded
    tt = np.zeros((2, 16), np.int32); tt[:, 8:] = 1   # segment B
    with torch.no_grad():
        ref = m(torch.tensor(ids), attention_mask=torch.tensor(mask),
                token_type_ids=torch.tensor(tt)).logits.float().numpy()
    ours, _ = model.apply(jax.tree.map(jnp.asarray, params), jnp.asarray(ids),
                          token_type_ids=jnp.asarray(tt),
                          attention_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(ours)[mask == 1], ref[mask == 1],
                               rtol=2e-4, atol=2e-4)


def test_bloom_padded_attention_mask_parity(eight_devices, bloom_ckpt):
    """attention_mask must also mask padding on the ALiBi branch (it was
    once silently dropped there): right-padded bloom batches match HF on
    real positions."""
    path, m = bloom_ckpt
    model, params = load_hf_model(str(path), dtype=jnp.float32)
    rng = np.random.default_rng(10)
    ids = rng.integers(5, 128, size=(2, 16))
    mask = np.ones((2, 16), np.int32)
    ids[0, 10:] = 0; mask[0, 10:] = 0
    with torch.no_grad():
        ref = m(torch.tensor(ids),
                attention_mask=torch.tensor(mask)).logits.float().numpy()
    ours, _ = model.apply(jax.tree.map(jnp.asarray, params), jnp.asarray(ids),
                          attention_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(ours)[mask == 1], ref[mask == 1],
                               rtol=2e-4, atol=2e-4)


def test_roberta_padded_position_ids_parity(eight_devices, roberta_ckpt):
    """HF roberta derives position ids from pad structure (cumsum over
    non-pad tokens); batches CONTAINING the pad id must still match."""
    path, m = roberta_ckpt
    model, params = load_hf_model(str(path), dtype=jnp.float32)
    rng = np.random.default_rng(8)
    ids = rng.integers(2, 128, size=(2, 16))
    mask = np.ones((2, 16), np.int32)
    ids[0, 11:] = 1; mask[0, 11:] = 0            # right padding with pad id 1
    with torch.no_grad():
        ref = m(torch.tensor(ids),
                attention_mask=torch.tensor(mask)).logits.float().numpy()
    ours, _ = model.apply(jax.tree.map(jnp.asarray, params), jnp.asarray(ids),
                          attention_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(ours)[mask == 1], ref[mask == 1],
                               rtol=2e-4, atol=2e-4)


def test_encoders_rejected_by_generation_paths(eight_devices, bert_ckpt):
    """Autoregressive surfaces must refuse encoders loudly: v2 build and v1
    generate raise; v1 forward (MLM scoring) still works."""
    path, m = bert_ckpt
    from deepspeed_tpu.inference.v2.engine_v2 import build_hf_engine
    with pytest.raises(NotImplementedError, match="bidirectional encoder"):
        build_hf_engine(str(path))
    engine = deepspeed_tpu.init_inference(
        model_path=str(path), config={"dtype": jnp.float32})
    with pytest.raises(ValueError, match="bidirectional"):
        engine.generate(np.zeros((1, 8), np.int32), max_new_tokens=2)
    ids = np.random.default_rng(9).integers(5, 128, size=(1, 12))
    np.testing.assert_allclose(np.asarray(engine.forward(ids)),
                               _ref_logits(m, ids), rtol=2e-4, atol=2e-4)


def test_bert_mlm_trains_under_zero(eight_devices, bert_ckpt):
    """Loaded encoder weights train on masked-LM labels under ZeRO-2."""
    import deepspeed_tpu as ds
    path, _ = bert_ckpt
    model, params = load_hf_model(str(path), dtype=jnp.float32)
    engine, _, _, _ = ds.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2}})
    rng = np.random.default_rng(7)
    ids = rng.integers(5, 128, size=(8, 16))
    labels = np.full_like(ids, -100)
    mask_pos = rng.random(ids.shape) < 0.15
    labels[mask_pos] = ids[mask_pos]
    masked = ids.copy(); masked[mask_pos] = 3   # [MASK]-style corruption
    batch = {"input_ids": masked, "labels": labels}
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert losses[-1] < losses[0], losses


def test_windowed_models_serve_v1(eight_devices, mistral_sw_ckpt,
                                  gpt_neo_ckpt):
    """v1 greedy matches HF generate through windowed layers (mistral
    sub-sequence sliding window; gpt-neo unscaled + alternating local)."""
    prompt = np.random.default_rng(12).integers(0, 128, size=(1, 14))
    for path, m in (mistral_sw_ckpt, gpt_neo_ckpt):
        engine = deepspeed_tpu.init_inference(
            model_path=str(path), config={"dtype": jnp.float32})
        with torch.no_grad():
            ref = m.generate(torch.tensor(prompt), max_new_tokens=6,
                             do_sample=False).numpy()[0, 14:]
        out = np.asarray(engine.generate(jnp.asarray(prompt),
                                         max_new_tokens=6))[0, 14:]
        np.testing.assert_array_equal(out, ref)


def test_v1_inference_alibi(eight_devices, bloom_ckpt):
    """v1 init_inference on an ALiBi model reproduces the HF forward."""
    path, m = bloom_ckpt
    engine = deepspeed_tpu.init_inference(
        model_path=str(path), config={"dtype": jnp.float32})
    ids = np.random.default_rng(5).integers(0, 128, size=(1, 12))
    np.testing.assert_allclose(np.asarray(engine.forward(ids)),
                               _ref_logits(m, ids), rtol=2e-4, atol=2e-4)


def test_bf16_checkpoint_loads_without_upcast(tmp_path, llama_ckpt):
    """bf16 safetensors load through the torch path preserving dtype (no
    fp32 host copy), and still produce close logits."""
    import ml_dtypes
    path, m = llama_ckpt
    bf16_path = tmp_path / "bf16"
    m.to(torch.bfloat16).save_pretrained(bf16_path)
    m.to(torch.float32)  # restore the shared fixture
    model, params = load_hf_model(str(bf16_path), dtype=jnp.float32)
    assert params["blocks"]["q_proj"]["kernel"].dtype == ml_dtypes.bfloat16
    ids = np.random.default_rng(4).integers(0, 128, size=(1, 8))
    ours = model.apply(jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params),
                       jnp.asarray(ids))[0]
    np.testing.assert_allclose(np.asarray(ours), _ref_logits(m, ids),
                               rtol=0.1, atol=0.15)


def test_hf_weights_into_training_engine(eight_devices, gpt2_ckpt):
    """Loaded weights feed deepspeed_tpu.initialize(model_parameters=...) and
    train under ZeRO-2."""
    path, _ = gpt2_ckpt
    model, params = load_hf_model(str(path), dtype=jnp.float32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2}})
    batch = {"input_ids": np.random.default_rng(2).integers(0, 128, size=(8, 16))}
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert losses[-1] < losses[0], losses


def test_gptj_explicit_null_rotary_dim_is_full_head():
    """HF GPT-J applies FULL-head rotary when config.rotary_dim is an
    explicit null; only an ABSENT key falls back to the GPTJConfig default
    of 64 (partial rotary)."""
    from deepspeed_tpu.runtime.state_dict_factory import hf_to_transformer_config
    base = dict(model_type="gptj", vocab_size=128, n_positions=64,
                n_embd=512, n_layer=2, n_head=4)  # head_dim 128 != default 64
    assert hf_to_transformer_config(dict(base, rotary_dim=None)).rope_dim == 128
    assert hf_to_transformer_config(dict(base, rotary_dim=8)).rope_dim == 8
    assert hf_to_transformer_config(base).rope_dim == 64  # GPTJConfig default
