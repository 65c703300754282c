"""External checkpoint ingestion: HF checkpoints → TPU param pytrees.

Counterpart of the reference's weights-ingestion stack:
- ``runtime/state_dict_factory.py:21`` ``SDLoaderFactory`` / ``:190``
  ``MegatronSDLoader`` — load (possibly sharded) checkpoints and reshard
  for a target TP degree;
- ``module_inject/load_checkpoint.py`` — map HF module weights onto the
  injected inference modules;
- ``inference/v2/model_implementations/flat_model_helpers.py`` — flattened
  parameter containers per architecture.

TPU-first redesign: a checkpoint is read on the host into a numpy state
dict (safetensors or torch ``.bin``, single-file or indexed shards), mapped
by architecture into the ``TransformerLM`` scanned-layer pytree, and placed
*sharded* by ``jax.device_put`` with the model's ``specs()`` /
``AutoTP.build_specs`` NamedShardings — the SPMD equivalent of the
reference's per-rank slice loading. Explicit per-rank slicing for
multi-host loading is available via ``module_inject.auto_tp.shard_param_tree``.

Supported architectures: gpt2, llama, mistral, mixtral, opt, phi, falcon,
bloom, gpt_neox, gptj, bert, roberta, distilbert.
"""

from __future__ import annotations

import dataclasses
import json
import os
import struct
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from ..models.transformer import MoEConfig, TransformerConfig, TransformerLM
from ..utils.logging import log_dist


# ---------------------------------------------------------------------------
# raw state-dict loading (reference SDLoaderFactory, state_dict_factory.py:21)
# ---------------------------------------------------------------------------

def _torch_to_numpy(t) -> np.ndarray:
    """Convert preserving dtype: bf16 stays bf16 (ml_dtypes view), never an
    fp32 upcast that would double host RAM for large checkpoints."""
    import torch

    if t.dtype == torch.bfloat16:
        import ml_dtypes
        return t.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
    return t.numpy()


def _safetensors_has_bf16(path: str) -> bool:
    """Read only the file header: {tensor: {dtype, shape, offsets}}."""
    with open(path, "rb") as f:
        n = struct.unpack("<Q", f.read(8))[0]
        header = json.loads(f.read(n))
    return any(v.get("dtype") == "BF16"
               for k, v in header.items() if k != "__metadata__")


def _load_safetensors(path: str) -> Dict[str, np.ndarray]:
    from safetensors import safe_open

    out = {}
    if _safetensors_has_bf16(path):  # numpy has no native bf16 dtype
        with safe_open(path, framework="pt") as f:
            for k in f.keys():
                out[k] = _torch_to_numpy(f.get_tensor(k))
    else:
        with safe_open(path, framework="np") as f:
            for k in f.keys():
                out[k] = f.get_tensor(k)
    return out


def _load_torch_bin(path: str) -> Dict[str, np.ndarray]:
    import torch

    sd = torch.load(path, map_location="cpu", weights_only=True)
    return {k: _torch_to_numpy(v) for k, v in sd.items()}


class HFCheckpointLoader:
    """Read an HF model directory: ``config.json`` + weights in safetensors
    or torch-bin form, single-file or sharded with an ``*.index.json``."""

    def __init__(self, model_path: str):
        self.model_path = model_path
        cfg_path = os.path.join(model_path, "config.json")
        if not os.path.exists(cfg_path):
            raise FileNotFoundError(f"no config.json under {model_path}")
        with open(cfg_path) as f:
            self.config: Dict[str, Any] = json.load(f)

    def _weight_files(self):
        mp = self.model_path
        for index in ("model.safetensors.index.json", "pytorch_model.bin.index.json"):
            ip = os.path.join(mp, index)
            if os.path.exists(ip):
                with open(ip) as f:
                    files = sorted(set(json.load(f)["weight_map"].values()))
                return [os.path.join(mp, f) for f in files]
        for single in ("model.safetensors", "pytorch_model.bin"):
            sp = os.path.join(mp, single)
            if os.path.exists(sp):
                return [sp]
        raise FileNotFoundError(f"no model weights found under {mp}")

    def load_state_dict(self) -> Dict[str, np.ndarray]:
        sd: Dict[str, np.ndarray] = {}
        for path in self._weight_files():
            loader = _load_safetensors if path.endswith(".safetensors") else _load_torch_bin
            sd.update(loader(path))
        return sd


class SDLoaderFactory:
    @staticmethod
    def get_sd_loader(model_path: str) -> HFCheckpointLoader:
        return HFCheckpointLoader(model_path)


# ---------------------------------------------------------------------------
# HF config → TransformerConfig
# ---------------------------------------------------------------------------

def hf_to_transformer_config(hf: Dict[str, Any], dtype=None, **overrides) -> TransformerConfig:
    """HF ``config.json`` dict → :class:`TransformerConfig`, dispatched
    through the architecture registry (``models/registry.py``)."""
    import jax.numpy as jnp

    from ..models.registry import get_architecture

    dtype = dtype if dtype is not None else jnp.bfloat16
    cfg = get_architecture(hf.get("model_type", "gpt2")).config_fn(hf)
    cfg["dtype"] = dtype
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def _gpt2_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("n_positions", 1024),
            num_layers=hf.get("n_layer", 12),
            num_heads=hf.get("n_head", 12),
            hidden_size=hf.get("n_embd", 768),
            intermediate_size=hf.get("n_inner") or 4 * hf.get("n_embd", 768),
            activation="gelu", norm="layernorm", position="learned",
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            tie_embeddings=True)


def _llama_family_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    cfg = dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 4096),
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads"),
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            activation="silu_gated", norm="rmsnorm", position="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_embeddings=hf.get("tie_word_embeddings", False))
    # modern llama configs carry attention_bias; internlm (v1) spells the
    # same architecture choice "bias" (reference container: containers/
    # internlm.py — llama block with biased q/k/v/o); qwen2 always biases
    # q/k/v but never o_proj
    if hf.get("model_type") == "qwen2":
        cfg["attn_bias"] = True
        cfg["attn_out_bias"] = False
    elif hf.get("attention_bias", hf.get("bias", False)):
        cfg["attn_bias"] = True
        cfg["attn_out_bias"] = True
    if hf.get("model_type") == "mixtral":
        cfg["moe"] = MoEConfig(
            num_experts=hf.get("num_local_experts", 8),
            top_k=hf.get("num_experts_per_tok", 2))
    # mistral/mixtral causal sliding window (null in many configs =
    # global). qwen2 configs CARRY a sliding_window value that is inert
    # unless use_sliding_window is set — honoring it unconditionally
    # would silently truncate attention — and even then it applies only
    # to layers >= max_window_layers (HF layer_types: lower layers attend
    # globally); attn_windows takes the per-layer tuple form for that.
    # default matches each family: HF Qwen2Config defaults
    # use_sliding_window=False (its sliding_window field is populated but
    # inert by default); mistral-family configs have no such key and the
    # window is active when present
    sw_default = hf.get("model_type") != "qwen2"
    if hf.get("sliding_window") and hf.get("use_sliding_window", sw_default):
        w = int(hf["sliding_window"])
        mwl = hf.get("max_window_layers")
        if mwl is not None and hf.get("model_type") == "qwen2":
            cfg["attn_windows"] = tuple(
                0 if i < mwl else w for i in range(hf["num_hidden_layers"]))
        else:
            cfg["attn_windows"] = w
    return cfg


def _opt_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    if not hf.get("do_layer_norm_before", True):
        raise ValueError("post-LN OPT variants (opt-350m) are unsupported")
    if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"]:
        raise ValueError("OPT with word_embed_proj_dim != hidden_size "
                         "(project_in/out) is unsupported")
    act = hf.get("activation_function", "relu")
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf.get("ffn_dim", 4 * hf["hidden_size"]),
            # HF "gelu" (galactica) is the exact erf form
            activation="relu" if act == "relu" else
            ("gelu" if act in ("gelu_new", "gelu_pytorch_tanh") else "gelu_exact"),
            norm="layernorm", position="learned",
            # HF OPTLearnedPositionalEmbedding offsets every position by 2
            position_offset=2,
            tie_embeddings=hf.get("tie_word_embeddings", True))


def _phi_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    if hf.get("qk_layernorm", False):
        raise ValueError("Phi variants with qk_layernorm are unsupported")
    head_dim = hf["hidden_size"] // hf["num_attention_heads"]
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads"),
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            activation="gelu", norm="layernorm", position="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_dim=int(head_dim * hf.get("partial_rotary_factor", 0.5)),
            parallel_block=True, lm_head_bias=True,
            norm_eps=hf.get("layer_norm_eps", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", False))


def _falcon_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    if not hf.get("parallel_attn", True) or hf.get("alibi", False):
        raise ValueError("sequential/alibi Falcon variants unsupported")
    new_decoder = hf.get("new_decoder_architecture", False)
    if new_decoder:
        kv = hf.get("num_kv_heads") or hf["num_attention_heads"]
    else:
        kv = 1 if hf.get("multi_query", True) else hf["num_attention_heads"]
    # falcon2-11B: new decoder but ONE norm feeding both branches
    # (HF gates ln_attn/ln_mlp on num_ln_in_parallel_attn == 2)
    num_ln = hf.get("num_ln_in_parallel_attn") or 2
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=kv,
            hidden_size=hf["hidden_size"],
            intermediate_size=hf.get("ffn_hidden_size",
                                     4 * hf["hidden_size"]),
            activation="gelu_exact", norm="layernorm", position="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            parallel_block=True, parallel_norms=new_decoder and num_ln == 2,
            linear_bias=bool(hf.get("bias", False)),
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", True))


def _bloom_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    h = hf.get("hidden_size") or hf["n_embed"]
    return dict(
            vocab_size=hf["vocab_size"],
            # ALiBi extrapolates; max_seq_len only sizes KV/serving buffers
            max_seq_len=hf.get("seq_length", 2048),
            num_layers=hf.get("n_layer") or hf["num_hidden_layers"],
            num_heads=hf.get("n_head") or hf["num_attention_heads"],
            hidden_size=h,
            intermediate_size=4 * h,
            # BloomGelu is the tanh approximation
            activation="gelu", norm="layernorm", position="alibi",
            embedding_norm=True,
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", True))


def _map_activation(name: str) -> str:
    """HF activation name → ours; raise on anything we'd silently get wrong.
    HF ACT2FN "gelu" is the exact erf form; "gelu_new"/tanh variants are the
    approximation (see models/transformer.py ACTIVATIONS)."""
    table = {"gelu": "gelu_exact", "gelu_new": "gelu",
             "gelu_pytorch_tanh": "gelu", "gelu_fast": "gelu",
             "relu": "relu"}
    if name not in table:
        raise ValueError(f"unsupported activation {name!r} "
                         f"(supported: {sorted(table)})")
    return table[name]


def _gpt_neox_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    head_dim = hf["hidden_size"] // hf["num_attention_heads"]
    parallel = hf.get("use_parallel_residual", True)
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            activation=_map_activation(hf.get("hidden_act", "gelu")),
            norm="layernorm", position="rope",
            rope_theta=hf.get("rotary_emb_base", 10000.0),
            rope_dim=int(head_dim * hf.get("rotary_pct", 0.25)),
            # both norms exist in the checkpoint either way; when parallel,
            # they feed the two branches from the block input (our
            # parallel_norms form)
            parallel_block=parallel, parallel_norms=parallel,
            # attention_bias only strips the attn projections' biases — HF
            # GPTNeoXMLP keeps its biases unconditionally
            attn_bias=bool(hf.get("attention_bias", True)),
            norm_eps=hf.get("layer_norm_eps", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", False))


def _gptj_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("n_positions", 2048),
            num_layers=hf["n_layer"],
            num_heads=hf["n_head"],
            hidden_size=hf["n_embd"],
            intermediate_size=hf.get("n_inner") or 4 * hf["n_embd"],
            activation=_map_activation(hf.get("activation_function", "gelu_new")),
            norm="layernorm", position="rope",
            # config.json may omit keys equal to HF defaults; GPTJConfig's
            # rotary_dim default is 64, NOT full-head — but an EXPLICIT
            # null means full-head rotary in HF modeling code
            rope_dim=(hf["n_embd"] // hf["n_head"]
                      if ("rotary_dim" in hf and hf["rotary_dim"] is None)
                      else hf.get("rotary_dim", 64)),
            rope_style="interleaved",
            parallel_block=True, attn_bias=False, lm_head_bias=True,
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", False))


# ---------------------------------------------------------------------------
# HF state dict → TransformerLM pytree
# ---------------------------------------------------------------------------

def _strip_prefix(sd: Dict[str, np.ndarray], prefix: str) -> Dict[str, np.ndarray]:
    if any(k.startswith(prefix) for k in sd):
        return {(k[len(prefix):] if k.startswith(prefix) else k): v for k, v in sd.items()}
    return sd


def _stack(sd, pattern: str, L: int, transform=None) -> np.ndarray:
    layers = []
    for i in range(L):
        # pop: the per-layer tensor is dead once stacked — keeps peak host
        # RAM near one model copy instead of two
        w = sd.pop(pattern.format(i=i))
        layers.append(transform(w) if transform else w)
    return np.stack(layers)


def _gpt2_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """GPT-2 Conv1D stores weights [in, out] — our Linear layout directly."""
    sd = _strip_prefix(sd, "transformer.")
    L, H = cfg.num_layers, cfg.hidden_size

    def split_qkv(w):  # [in, 3H] (or [3H] bias) → 3 × [..., H]
        return np.split(w, 3, axis=-1)

    qs, ks, vs = zip(*(split_qkv(sd.pop(f"h.{i}.attn.c_attn.weight")) for i in range(L)))
    qb, kb, vb = zip(*(split_qkv(sd.pop(f"h.{i}.attn.c_attn.bias")) for i in range(L)))
    blocks = {
        "ln_1": {"scale": _stack(sd, "h.{i}.ln_1.weight", L),
                 "bias": _stack(sd, "h.{i}.ln_1.bias", L)},
        "ln_2": {"scale": _stack(sd, "h.{i}.ln_2.weight", L),
                 "bias": _stack(sd, "h.{i}.ln_2.bias", L)},
        "q_proj": {"kernel": np.stack(qs), "bias": np.stack(qb)},
        "k_proj": {"kernel": np.stack(ks), "bias": np.stack(kb)},
        "v_proj": {"kernel": np.stack(vs), "bias": np.stack(vb)},
        "o_proj": {"kernel": _stack(sd, "h.{i}.attn.c_proj.weight", L),
                   "bias": _stack(sd, "h.{i}.attn.c_proj.bias", L)},
        "fc_in": {"kernel": _stack(sd, "h.{i}.mlp.c_fc.weight", L),
                  "bias": _stack(sd, "h.{i}.mlp.c_fc.bias", L)},
        "fc_out": {"kernel": _stack(sd, "h.{i}.mlp.c_proj.weight", L),
                   "bias": _stack(sd, "h.{i}.mlp.c_proj.bias", L)},
    }
    return {
        "wte": {"embedding": sd["wte.weight"]},
        "wpe": {"embedding": sd["wpe.weight"]},
        "ln_f": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
        "blocks": blocks,
    }


def _llama_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF Linear stores weights [out, in] — transpose into our [in, out]."""
    L = cfg.num_layers
    T = np.transpose
    blocks = {
        "ln_1": {"scale": _stack(sd, "model.layers.{i}.input_layernorm.weight", L)},
        "ln_2": {"scale": _stack(sd, "model.layers.{i}.post_attention_layernorm.weight", L)},
        "q_proj": {"kernel": _stack(sd, "model.layers.{i}.self_attn.q_proj.weight", L, T)},
        "k_proj": {"kernel": _stack(sd, "model.layers.{i}.self_attn.k_proj.weight", L, T)},
        "v_proj": {"kernel": _stack(sd, "model.layers.{i}.self_attn.v_proj.weight", L, T)},
        "o_proj": {"kernel": _stack(sd, "model.layers.{i}.self_attn.o_proj.weight", L, T)},
    }
    # attention-bias models: internlm carries biases on all four
    # projections, qwen2 on q/k/v only — stack whichever are present
    for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
        if f"model.layers.0.self_attn.{name}.bias" in sd:
            blocks[name]["bias"] = _stack(
                sd, "model.layers.{i}.self_attn." + name + ".bias", L)
    if cfg.moe is not None:
        E = cfg.moe.num_experts
        blocks["moe"] = {
            "gate": _stack(sd, "model.layers.{i}.block_sparse_moe.gate.weight", L, T),
            "wi_gate": np.stack([np.stack(
                [T(sd.pop(f"model.layers.{i}.block_sparse_moe.experts.{e}.w1.weight"))
                 for e in range(E)]) for i in range(L)]),
            "wi_up": np.stack([np.stack(
                [T(sd.pop(f"model.layers.{i}.block_sparse_moe.experts.{e}.w3.weight"))
                 for e in range(E)]) for i in range(L)]),
            "wo": np.stack([np.stack(
                [T(sd.pop(f"model.layers.{i}.block_sparse_moe.experts.{e}.w2.weight"))
                 for e in range(E)]) for i in range(L)]),
        }
    else:
        blocks.update({
            "gate_proj": {"kernel": _stack(sd, "model.layers.{i}.mlp.gate_proj.weight", L, T)},
            "up_proj": {"kernel": _stack(sd, "model.layers.{i}.mlp.up_proj.weight", L, T)},
            "down_proj": {"kernel": _stack(sd, "model.layers.{i}.mlp.down_proj.weight", L, T)},
        })
    params = {
        "wte": {"embedding": sd["model.embed_tokens.weight"]},
        "ln_f": {"scale": sd["model.norm.weight"]},
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        lm_head = sd.get("lm_head.weight", sd["model.embed_tokens.weight"])
        params["lm_head"] = {"kernel": T(lm_head)}
    return params


def _lin_stack(sd, pat: str, L: int, bias: bool = True) -> Dict[str, np.ndarray]:
    """Stack L layers of an HF ``nn.Linear`` ([out, in] + optional bias)
    into our [L, in, out] kernel layout."""
    out = {"kernel": _stack(sd, pat + ".weight", L, np.transpose)}
    if bias:
        out["bias"] = _stack(sd, pat + ".bias", L)
    return out


def _ln_stack(sd, pat: str, L: int) -> Dict[str, np.ndarray]:
    return {"scale": _stack(sd, pat + ".weight", L),
            "bias": _stack(sd, pat + ".bias", L)}


def _opt_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF OPT: decoder.* naming, [out, in] linears, fused nothing. The
    position table keeps HF's 2-row offset (embed_positions includes it)."""
    sd = _strip_prefix(sd, "model.")
    L = cfg.num_layers

    def lin(pat):
        return _lin_stack(sd, pat, L)

    def ln(pat):
        return _ln_stack(sd, pat, L)

    blocks = {
        "ln_1": ln("decoder.layers.{i}.self_attn_layer_norm"),
        "ln_2": ln("decoder.layers.{i}.final_layer_norm"),
        "q_proj": lin("decoder.layers.{i}.self_attn.q_proj"),
        "k_proj": lin("decoder.layers.{i}.self_attn.k_proj"),
        "v_proj": lin("decoder.layers.{i}.self_attn.v_proj"),
        "o_proj": lin("decoder.layers.{i}.self_attn.out_proj"),
        "fc_in": lin("decoder.layers.{i}.fc1"),
        "fc_out": lin("decoder.layers.{i}.fc2"),
    }
    params = {
        "wte": {"embedding": sd["decoder.embed_tokens.weight"]},
        "wpe": {"embedding": sd["decoder.embed_positions.weight"]},
        "ln_f": {"scale": sd["decoder.final_layer_norm.weight"],
                 "bias": sd["decoder.final_layer_norm.bias"]},
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": np.transpose(
            sd.get("lm_head.weight", sd["decoder.embed_tokens.weight"]))}
    return params


def _phi_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF Phi: parallel block with ONE input_layernorm, biased linears and
    lm_head, q/k/v unfused, dense == o_proj."""
    L = cfg.num_layers
    T = np.transpose

    def lin(pat):
        return _lin_stack(sd, pat, L)

    blocks = {
        "ln_1": _ln_stack(sd, "model.layers.{i}.input_layernorm", L),
        "q_proj": lin("model.layers.{i}.self_attn.q_proj"),
        "k_proj": lin("model.layers.{i}.self_attn.k_proj"),
        "v_proj": lin("model.layers.{i}.self_attn.v_proj"),
        "o_proj": lin("model.layers.{i}.self_attn.dense"),
        "fc_in": lin("model.layers.{i}.mlp.fc1"),
        "fc_out": lin("model.layers.{i}.mlp.fc2"),
    }
    params = {
        "wte": {"embedding": sd["model.embed_tokens.weight"]},
        "ln_f": {"scale": sd["model.final_layernorm.weight"],
                 "bias": sd["model.final_layernorm.bias"]},
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": T(sd["lm_head.weight"])}
        if cfg.lm_head_bias:
            params["lm_head"]["bias"] = sd["lm_head.bias"]
    return params


def _falcon_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF Falcon: fused query_key_value laid out GROUPED — per kv group,
    (heads_per_group q rows, 1 k row, 1 v row) x head_dim — split into our
    separate q/k/v projections (kernels, and biases when config.bias)."""
    L, H = cfg.num_layers, cfg.hidden_size
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    per = nh // nkv
    T = np.transpose
    use_bias = bool(cfg.linear_bias)

    qkv = {"q_proj": {}, "k_proj": {}, "v_proj": {}}
    parts = ["kernel", "bias"] if use_bias else ["kernel"]
    for part in parts:
        qs, ks, vs = [], [], []
        for i in range(L):
            suffix = "weight" if part == "kernel" else "bias"
            w = sd.pop(f"transformer.h.{i}.self_attention.query_key_value.{suffix}")
            # grouped rows: reshape to [nkv, per+2, hd, ...] then slice roles
            g = w.reshape(nkv, per + 2, hd, *w.shape[1:])
            q, k, v = g[:, :per], g[:, per], g[:, per + 1]
            if part == "kernel":
                qs.append(T(q.reshape(nh * hd, H)))
                ks.append(T(k.reshape(nkv * hd, H)))
                vs.append(T(v.reshape(nkv * hd, H)))
            else:
                qs.append(q.reshape(nh * hd))
                ks.append(k.reshape(nkv * hd))
                vs.append(v.reshape(nkv * hd))
        qkv["q_proj"][part] = np.stack(qs)
        qkv["k_proj"][part] = np.stack(ks)
        qkv["v_proj"][part] = np.stack(vs)

    if cfg.parallel_norms:
        # falcon-40b "new decoder": per-branch norms ln_attn / ln_mlp
        norms = {
            "ln_1": _ln_stack(sd, "transformer.h.{i}.ln_attn", L),
            "ln_2": _ln_stack(sd, "transformer.h.{i}.ln_mlp", L),
        }
    else:
        norms = {
            "ln_1": _ln_stack(sd, "transformer.h.{i}.input_layernorm", L),
        }
    blocks = {
        **norms,
        **qkv,
        "o_proj": _lin_stack(sd, "transformer.h.{i}.self_attention.dense", L, bias=use_bias),
        "fc_in": _lin_stack(sd, "transformer.h.{i}.mlp.dense_h_to_4h", L, bias=use_bias),
        "fc_out": _lin_stack(sd, "transformer.h.{i}.mlp.dense_4h_to_h", L, bias=use_bias),
    }
    params = {
        "wte": {"embedding": sd["transformer.word_embeddings.weight"]},
        "ln_f": {"scale": sd["transformer.ln_f.weight"],
                 "bias": sd["transformer.ln_f.bias"]},
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": T(
            sd.get("lm_head.weight", sd["transformer.word_embeddings.weight"]))}
    return params


def _split_interleaved_qkv(sd, pattern: str, cfg: TransformerConfig,
                           bias: bool) -> Dict[str, Dict[str, np.ndarray]]:
    """Split a fused ``query_key_value`` whose rows are laid out
    [num_heads, 3, head_dim] — the BLOOM/GPT-NeoX per-head interleave (HF
    reshapes the fused output to [..., nh, 3*hd] before slicing roles) —
    into separate q/k/v projections in our [in, out] layout."""
    L, H = cfg.num_layers, cfg.hidden_size
    nh, hd = cfg.num_heads, cfg.head_dim
    T = np.transpose
    out = {"q_proj": {}, "k_proj": {}, "v_proj": {}}
    parts = ["kernel", "bias"] if bias else ["kernel"]
    for part in parts:
        suffix = "weight" if part == "kernel" else "bias"
        qs, ks, vs = [], [], []
        for i in range(L):
            w = sd.pop(pattern.format(i=i) + "." + suffix)
            g = w.reshape(nh, 3, hd, *w.shape[1:])  # rows: [nh, 3, hd]
            q, k, v = g[:, 0], g[:, 1], g[:, 2]
            if part == "kernel":
                qs.append(T(q.reshape(nh * hd, H)))
                ks.append(T(k.reshape(nh * hd, H)))
                vs.append(T(v.reshape(nh * hd, H)))
            else:
                qs.append(q.reshape(nh * hd))
                ks.append(k.reshape(nh * hd))
                vs.append(v.reshape(nh * hd))
        out["q_proj"][part] = np.stack(qs)
        out["k_proj"][part] = np.stack(ks)
        out["v_proj"][part] = np.stack(vs)
    return out


def _bloom_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF BLOOM: transformer.* naming, fused per-head-interleaved QKV,
    word_embeddings_layernorm after the embedding, biases everywhere."""
    sd = _strip_prefix(sd, "transformer.")
    L = cfg.num_layers
    blocks = {
        "ln_1": _ln_stack(sd, "h.{i}.input_layernorm", L),
        "ln_2": _ln_stack(sd, "h.{i}.post_attention_layernorm", L),
        **_split_interleaved_qkv(sd, "h.{i}.self_attention.query_key_value",
                                 cfg, bias=True),
        "o_proj": _lin_stack(sd, "h.{i}.self_attention.dense", L),
        "fc_in": _lin_stack(sd, "h.{i}.mlp.dense_h_to_4h", L),
        "fc_out": _lin_stack(sd, "h.{i}.mlp.dense_4h_to_h", L),
    }
    return {
        "wte": {"embedding": sd["word_embeddings.weight"]},
        "ln_emb": {"scale": sd["word_embeddings_layernorm.weight"],
                   "bias": sd["word_embeddings_layernorm.bias"]},
        "ln_f": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
        "blocks": blocks,
    }


def _gpt_neox_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF GPT-NeoX: gpt_neox.* naming, fused per-head-interleaved QKV, two
    norms per block, untied embed_out head."""
    sd = _strip_prefix(sd, "gpt_neox.")
    L = cfg.num_layers
    use_bias = bool(cfg.attn_bias if cfg.attn_bias is not None else True)
    blocks = {
        "ln_1": _ln_stack(sd, "layers.{i}.input_layernorm", L),
        "ln_2": _ln_stack(sd, "layers.{i}.post_attention_layernorm", L),
        **_split_interleaved_qkv(sd, "layers.{i}.attention.query_key_value",
                                 cfg, bias=use_bias),
        "o_proj": _lin_stack(sd, "layers.{i}.attention.dense", L, bias=use_bias),
        "fc_in": _lin_stack(sd, "layers.{i}.mlp.dense_h_to_4h", L),
        "fc_out": _lin_stack(sd, "layers.{i}.mlp.dense_4h_to_h", L),
    }
    params = {
        "wte": {"embedding": sd["embed_in.weight"]},
        "ln_f": {"scale": sd["final_layer_norm.weight"],
                 "bias": sd["final_layer_norm.bias"]},
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": np.transpose(
            sd.get("embed_out.weight", sd["embed_in.weight"]))}
    return params


def _gptj_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF GPT-J: transformer.* naming, unfused BIAS-FREE attention linears,
    biased MLP, untied lm_head WITH bias."""
    sd = _strip_prefix(sd, "transformer.")
    L = cfg.num_layers
    blocks = {
        "ln_1": _ln_stack(sd, "h.{i}.ln_1", L),
        "q_proj": _lin_stack(sd, "h.{i}.attn.q_proj", L, bias=False),
        "k_proj": _lin_stack(sd, "h.{i}.attn.k_proj", L, bias=False),
        "v_proj": _lin_stack(sd, "h.{i}.attn.v_proj", L, bias=False),
        "o_proj": _lin_stack(sd, "h.{i}.attn.out_proj", L, bias=False),
        "fc_in": _lin_stack(sd, "h.{i}.mlp.fc_in", L),
        "fc_out": _lin_stack(sd, "h.{i}.mlp.fc_out", L),
    }
    params = {
        "wte": {"embedding": sd["wte.weight"]},
        "ln_f": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": np.transpose(sd["lm_head.weight"])}
        if cfg.lm_head_bias:
            params["lm_head"]["bias"] = sd["lm_head.bias"]
    return params


def hf_state_dict_to_params(cfg: TransformerConfig, model_type: str,
                            sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    from ..models.registry import get_architecture
    return get_architecture(model_type).params_fn(cfg, sd)


def _bert_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 512),
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            activation=_map_activation(hf.get("hidden_act", "gelu")),
            norm="layernorm", position="learned", causal=False,
            norm_style="post", embedding_norm=True,
            type_vocab_size=hf.get("type_vocab_size", 2),
            mlm_head=True, tie_embeddings=True,
            norm_eps=hf.get("layer_norm_eps", 1e-12))


def _roberta_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    cfg = _bert_config(hf)
    # HF roberta position ids come from create_position_ids_from_input_ids:
    # cumsum over non-pad tokens + padding_idx (pads land on padding_idx);
    # its 514-row table is 512 usable positions + padding_idx + 1
    pad = hf.get("pad_token_id")
    pad = 1 if pad is None else pad  # 0 is a legal pad id — no `or`
    cfg["pad_based_positions"] = True
    cfg["pad_token_id"] = pad
    cfg["position_offset"] = pad + 1
    cfg["max_seq_len"] = hf.get("max_position_embeddings", 514) - (pad + 1)
    return cfg


def _bert_params_for(prefix: str, head: str):
    """bert. vs roberta. naming differ only in prefix and MLM-head keys."""

    def params_fn(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
        sd = _strip_prefix(sd, prefix)
        L = cfg.num_layers
        blocks = {
            # post-LN: ln_1 = the LN after the attention residual
            "ln_1": _ln_stack(sd, "encoder.layer.{i}.attention.output.LayerNorm", L),
            "ln_2": _ln_stack(sd, "encoder.layer.{i}.output.LayerNorm", L),
            "q_proj": _lin_stack(sd, "encoder.layer.{i}.attention.self.query", L),
            "k_proj": _lin_stack(sd, "encoder.layer.{i}.attention.self.key", L),
            "v_proj": _lin_stack(sd, "encoder.layer.{i}.attention.self.value", L),
            "o_proj": _lin_stack(sd, "encoder.layer.{i}.attention.output.dense", L),
            "fc_in": _lin_stack(sd, "encoder.layer.{i}.intermediate.dense", L),
            "fc_out": _lin_stack(sd, "encoder.layer.{i}.output.dense", L),
        }
        params = {
            "wte": {"embedding": sd["embeddings.word_embeddings.weight"]},
            "wpe": {"embedding": sd["embeddings.position_embeddings.weight"]},
            "wtt": {"embedding": sd["embeddings.token_type_embeddings.weight"]},
            "ln_emb": {"scale": sd["embeddings.LayerNorm.weight"],
                       "bias": sd["embeddings.LayerNorm.bias"]},
            "blocks": blocks,
        }
        if not cfg.mlm_head:   # task checkpoints carry no MLM head
            return params
        # the MLM decoder is scored against the word embeddings; a separate
        # (untied, fine-tuned) decoder matrix in the checkpoint would be
        # silently ignored — detect from the weights, not the config flag
        # (task loads with mlm_head=False never reach here)
        dec_key = ("cls.predictions.decoder.weight" if head == "cls"
                   else "lm_head.decoder.weight")
        dec = sd.get(dec_key)
        if dec is not None and not np.array_equal(
                dec, sd["embeddings.word_embeddings.weight"]):
            raise ValueError("untied-embedding MLM checkpoints (decoder "
                             "weight differs from word embeddings) are "
                             "unsupported")
        if head == "cls":  # bert: cls.predictions.*
            params["mlm"] = {
                "dense": {"kernel": np.transpose(sd["cls.predictions.transform.dense.weight"]),
                          "bias": sd["cls.predictions.transform.dense.bias"]},
                "ln": {"scale": sd["cls.predictions.transform.LayerNorm.weight"],
                       "bias": sd["cls.predictions.transform.LayerNorm.bias"]},
                "bias": sd["cls.predictions.bias"],
            }
        else:              # roberta: lm_head.*
            params["mlm"] = {
                "dense": {"kernel": np.transpose(sd["lm_head.dense.weight"]),
                          "bias": sd["lm_head.dense.bias"]},
                "ln": {"scale": sd["lm_head.layer_norm.weight"],
                       "bias": sd["lm_head.layer_norm.bias"]},
                "bias": sd["lm_head.bias"],
            }
        return params

    return params_fn


def _gpt_neo_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    # attention_types [[["global","local"], N], ...] expands to a per-layer
    # pattern; local layers attend a window_size causal window
    layers = []
    for types, n in hf.get("attention_types") or [[["global"], hf["num_layers"]]]:
        layers.extend(list(types) * n)
    if len(layers) != hf["num_layers"]:
        raise ValueError(f"attention_types expands to {len(layers)} layers, "
                         f"config has {hf['num_layers']}")
    window = int(hf.get("window_size", 256))
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 2048),
            num_layers=hf["num_layers"],
            num_heads=hf["num_heads"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf.get("intermediate_size") or 4 * hf["hidden_size"],
            activation=_map_activation(hf.get("activation_function", "gelu_new")),
            norm="layernorm", position="learned",
            attn_windows=tuple(window if t == "local" else 0 for t in layers),
            attn_scale=1.0,  # gpt-neo applies NO 1/sqrt(d) scaling
            attn_bias=False, attn_out_bias=True,
            norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            tie_embeddings=hf.get("tie_word_embeddings", True))


def _gpt_neo_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF GPT-Neo: transformer.* naming, nn.Linear ([out, in]) everywhere,
    bias-free q/k/v with a biased out_proj."""
    sd = _strip_prefix(sd, "transformer.")
    L = cfg.num_layers
    blocks = {
        "ln_1": _ln_stack(sd, "h.{i}.ln_1", L),
        "ln_2": _ln_stack(sd, "h.{i}.ln_2", L),
        "q_proj": _lin_stack(sd, "h.{i}.attn.attention.q_proj", L, bias=False),
        "k_proj": _lin_stack(sd, "h.{i}.attn.attention.k_proj", L, bias=False),
        "v_proj": _lin_stack(sd, "h.{i}.attn.attention.v_proj", L, bias=False),
        "o_proj": _lin_stack(sd, "h.{i}.attn.attention.out_proj", L),
        "fc_in": _lin_stack(sd, "h.{i}.mlp.c_fc", L),
        "fc_out": _lin_stack(sd, "h.{i}.mlp.c_proj", L),
    }
    return {
        "wte": {"embedding": sd["wte.weight"]},
        "wpe": {"embedding": sd["wpe.weight"]},
        "ln_f": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
        "blocks": blocks,
    }


def _distilbert_config(hf: Dict[str, Any]) -> Dict[str, Any]:
    if hf.get("sinusoidal_pos_embds", False):
        raise ValueError("sinusoidal-position DistilBERT variants are "
                         "unsupported (learned positions only)")
    return dict(
            vocab_size=hf["vocab_size"],
            max_seq_len=hf.get("max_position_embeddings", 512),
            num_layers=hf["n_layers"],
            num_heads=hf["n_heads"],
            hidden_size=hf["dim"],
            intermediate_size=hf["hidden_dim"],
            activation=_map_activation(hf.get("activation", "gelu")),
            norm="layernorm", position="learned", causal=False,
            norm_style="post", embedding_norm=True, type_vocab_size=0,
            mlm_head=True, tie_embeddings=True,
            norm_eps=1e-12)  # hardcoded in HF modeling_distilbert


def _distilbert_params(cfg: TransformerConfig, sd: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """HF DistilBERT: distilbert.* naming, q_lin/k_lin/v_lin/out_lin attn,
    ffn.lin1/lin2 MLP, vocab_transform/vocab_layer_norm/vocab_projector MLM
    head (projector tied to the word embeddings)."""
    sd = _strip_prefix(sd, "distilbert.")
    L = cfg.num_layers
    blocks = {
        "ln_1": _ln_stack(sd, "transformer.layer.{i}.sa_layer_norm", L),
        "ln_2": _ln_stack(sd, "transformer.layer.{i}.output_layer_norm", L),
        "q_proj": _lin_stack(sd, "transformer.layer.{i}.attention.q_lin", L),
        "k_proj": _lin_stack(sd, "transformer.layer.{i}.attention.k_lin", L),
        "v_proj": _lin_stack(sd, "transformer.layer.{i}.attention.v_lin", L),
        "o_proj": _lin_stack(sd, "transformer.layer.{i}.attention.out_lin", L),
        "fc_in": _lin_stack(sd, "transformer.layer.{i}.ffn.lin1", L),
        "fc_out": _lin_stack(sd, "transformer.layer.{i}.ffn.lin2", L),
    }
    params = {
        "wte": {"embedding": sd["embeddings.word_embeddings.weight"]},
        "wpe": {"embedding": sd["embeddings.position_embeddings.weight"]},
        "ln_emb": {"scale": sd["embeddings.LayerNorm.weight"],
                   "bias": sd["embeddings.LayerNorm.bias"]},
        "blocks": blocks,
    }
    if cfg.mlm_head:
        proj = sd.get("vocab_projector.weight")
        if proj is not None and not np.array_equal(
                proj, sd["embeddings.word_embeddings.weight"]):
            raise ValueError("untied-embedding MLM checkpoints (projector "
                             "weight differs from word embeddings) are "
                             "unsupported")
        params["mlm"] = {
            "dense": {"kernel": np.transpose(sd["vocab_transform.weight"]),
                      "bias": sd["vocab_transform.bias"]},
            "ln": {"scale": sd["vocab_layer_norm.weight"],
                   "bias": sd["vocab_layer_norm.bias"]},
            "bias": sd["vocab_projector.bias"],
        }
    return params


# ---------------------------------------------------------------------------
# Megatron sharded checkpoints (reference MegatronSDLoader,
# state_dict_factory.py:190)
# ---------------------------------------------------------------------------

class MegatronSDLoader:
    """Merge TP-sharded Megatron GPT checkpoints into one full state dict.

    Counterpart of the reference ``MegatronSDLoader``: given the ``mp_rank_XX``
    shard files of a Megatron-style GPT checkpoint, reassemble the full
    (tp=1) flat state dict — column-parallel weights (``query_key_value``,
    ``dense_h_to_4h``, ``word_embeddings``) concatenate on axis 0 with the
    three historical Q/K/V row layouts handled per ``checkpoint_version``
    (0: ``[3, np, hn]``; 1.0: ``[np, hn, 3]``; 2.0: ``[np, 3, hn]``), and
    row-parallel weights (``attention.dense``, ``dense_4h_to_h``) on axis 1.
    The reference also re-splits for a target TP degree; here resharding is
    the placement layer's job (``AutoTP.build_specs`` /
    ``module_inject.auto_tp.shard_param_tree``), so merge is enough.
    """

    COLUMN_PARALLEL = ("attention.query_key_value", "mlp.dense_h_to_4h")
    ROW_PARALLEL = ("attention.dense.weight", "mlp.dense_4h_to_h.weight")

    def __init__(self, ckpt_list, version: Optional[float] = None):
        if isinstance(ckpt_list, (str, os.PathLike)):
            import glob
            root = ckpt_list
            files = sorted(glob.glob(os.path.join(root, "mp_rank_*")))
            ckpt_list = [os.path.join(f, "model_optim_rng.pt")
                         if os.path.isdir(f) else f for f in files]
            if not ckpt_list:
                raise FileNotFoundError(f"no mp_rank_* shards under {root!r}")
        self.ckpt_list = list(ckpt_list)
        self.version = version

    @staticmethod
    def _flatten(sd) -> Dict[str, np.ndarray]:
        """Accept the flat DeepSpeed-Megatron layout or one nested under
        'model'; drop non-tensor bookkeeping entries."""
        if "model" in sd and isinstance(sd["model"], dict):
            sd = sd["model"]
        return {k: v for k, v in sd.items()
                if hasattr(v, "shape")}  # skip rng states / iteration etc.

    def _load_shards(self):
        import torch

        shards, version = [], self.version
        for path in self.ckpt_list:
            raw = torch.load(path, map_location="cpu", weights_only=False)
            if version is None:
                version = raw.get("checkpoint_version")
            shards.append({k: _torch_to_numpy(v)
                           for k, v in self._flatten(raw).items()})
        # Pre-versioning Megatron checkpoints carry no checkpoint_version and
        # use the version-0 row layout [3, np, hn] (reference
        # megatron/checkpointing.py get_checkpoint_version defaults to 0)
        return shards, (version if version is not None else 0)

    @staticmethod
    def merge_query_key_value(params, version: float) -> np.ndarray:
        """Merge per-partition fused QKV (reference ``merge_query_key_value``):
        version 0 is role-major per shard, so roles concatenate across
        shards; 1.0/2.0 are head-major, a plain concat."""
        if version == 0:
            parts = [np.split(p, 3, axis=0) for p in params]
            return np.concatenate(
                [np.concatenate([p[i] for p in parts], axis=0)
                 for i in range(3)], axis=0)
        if version in (1.0, 2.0):
            return np.concatenate(params, axis=0)
        raise ValueError(f"unsupported Megatron checkpoint version {version}")

    def merge_state_dict(self) -> Tuple[Dict[str, np.ndarray], float]:
        shards, version = self._load_shards()
        if len(shards) == 1:
            return dict(shards[0]), version
        out: Dict[str, np.ndarray] = {}
        for key in shards[0]:
            vals = [s[key] for s in shards]
            if any(p in key for p in self.COLUMN_PARALLEL):
                if "query_key_value" in key:
                    out[key] = self.merge_query_key_value(vals, version)
                else:
                    out[key] = np.concatenate(vals, axis=0)
            elif any(p in key for p in self.ROW_PARALLEL):
                out[key] = np.concatenate(vals, axis=1)
            elif "word_embeddings.weight" in key:
                out[key] = np.concatenate(vals, axis=0)  # vocab-parallel
            else:
                out[key] = vals[0]  # replicated
        return out, version


def _megatron_split_qkv(w: np.ndarray, cfg: TransformerConfig,
                        version: float):
    """Full merged fused-QKV rows → (q, k, v) each [nh*hn(, h)] rows."""
    nh, hn = cfg.num_heads, cfg.head_dim
    tail = w.shape[1:]
    if version == 0:         # [3, nh, hn]
        g = w.reshape(3, nh, hn, *tail)
        q, k, v = g[0], g[1], g[2]
    elif version == 1.0:     # [nh, hn, 3]
        g = w.reshape(nh, hn, 3, *tail)
        q = np.ascontiguousarray(np.take(g, 0, axis=2))
        k = np.ascontiguousarray(np.take(g, 1, axis=2))
        v = np.ascontiguousarray(np.take(g, 2, axis=2))
    else:                    # 2.0: [nh, 3, hn]
        g = w.reshape(nh, 3, hn, *tail)
        q, k, v = g[:, 0], g[:, 1], g[:, 2]
    return (x.reshape(nh * hn, *tail) for x in (q, k, v))


def load_megatron_model(ckpt, config: TransformerConfig,
                        version: Optional[float] = None,
                        dtype=None) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Megatron GPT shard files (dir with ``mp_rank_*`` or explicit list) +
    a :class:`TransformerConfig` → (TransformerLM, host param pytree).

    The model dims come from ``config`` (Megatron checkpoints don't carry a
    portable config.json); the checkpoint supplies the weights. Megatron
    pads the vocab-parallel embedding — rows beyond ``config.vocab_size``
    are trimmed, mirroring the reference loader.
    """
    loader = MegatronSDLoader(ckpt, version)
    sd, ver = loader.merge_state_dict()
    cfg = config if dtype is None else dataclasses.replace(config, dtype=dtype)
    L = cfg.num_layers
    T = np.transpose

    qkv = {"q_proj": {}, "k_proj": {}, "v_proj": {}}
    for part, suffix in (("kernel", "weight"), ("bias", "bias")):
        qs, ks, vs = [], [], []
        for i in range(L):
            w = sd.pop(f"transformer.layers.{i}.attention.query_key_value.{suffix}")
            q, k, v = _megatron_split_qkv(w, cfg, ver)
            qs.append(T(q) if part == "kernel" else q)
            ks.append(T(k) if part == "kernel" else k)
            vs.append(T(v) if part == "kernel" else v)
        qkv["q_proj"][part] = np.stack(qs)
        qkv["k_proj"][part] = np.stack(ks)
        qkv["v_proj"][part] = np.stack(vs)

    blocks = {
        "ln_1": _ln_stack(sd, "transformer.layers.{i}.input_layernorm", L),
        "ln_2": _ln_stack(sd, "transformer.layers.{i}.post_attention_layernorm", L),
        **qkv,
        "o_proj": _lin_stack(sd, "transformer.layers.{i}.attention.dense", L),
        "fc_in": _lin_stack(sd, "transformer.layers.{i}.mlp.dense_h_to_4h", L),
        "fc_out": _lin_stack(sd, "transformer.layers.{i}.mlp.dense_4h_to_h", L),
    }
    wte = sd["word_embeddings.weight"]
    wpe = sd["position_embeddings.weight"]
    # the config is hand-authored (no config.json in Megatron checkpoints):
    # an undersized table would silently clamp lookups, so fail loudly
    if wte.shape[0] < cfg.vocab_size:
        raise ValueError(
            f"checkpoint embedding has {wte.shape[0]} rows < config "
            f"vocab_size {cfg.vocab_size} — wrong config for this checkpoint")
    if wpe.shape[0] < cfg.max_seq_len:
        raise ValueError(
            f"checkpoint position table has {wpe.shape[0]} rows < config "
            f"max_seq_len {cfg.max_seq_len} — wrong config for this checkpoint")
    if wte.shape[0] > cfg.vocab_size:  # vocab-parallel padding
        wte = wte[:cfg.vocab_size]
    params = {
        "wte": {"embedding": wte},
        "wpe": {"embedding": wpe},
        "ln_f": {"scale": sd["transformer.final_layernorm.weight"],
                 "bias": sd["transformer.final_layernorm.bias"]},
        "blocks": blocks,
    }
    n = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))
    log_dist(f"loaded Megatron checkpoint ({len(loader.ckpt_list)} TP shards, "
             f"version {ver}, {n / 1e6:.1f}M params)", ranks=[0])
    return TransformerLM(cfg), params


# the families whose adapters are this file's register here; an architecture
# with a module of its own (models/<arch>.py) registers itself there
def _register_builtins() -> None:
    from ..models.registry import register_architecture
    register_architecture("gpt2", _gpt2_config, _gpt2_params)
    for mt in ("llama", "mistral", "mixtral", "internlm", "qwen2"):
        register_architecture(mt, _llama_family_config, _llama_params)
    register_architecture("opt", _opt_config, _opt_params)
    register_architecture("phi", _phi_config, _phi_params)
    register_architecture("falcon", _falcon_config, _falcon_params)
    register_architecture("bloom", _bloom_config, _bloom_params)
    register_architecture("gpt_neox", _gpt_neox_config, _gpt_neox_params)
    register_architecture("gptj", _gptj_config, _gptj_params)
    register_architecture("bert", _bert_config, _bert_params_for("bert.", "cls"))
    register_architecture("roberta", _roberta_config,
                          _bert_params_for("roberta.", "lm_head"))
    register_architecture("distilbert", _distilbert_config, _distilbert_params)
    register_architecture("gpt_neo", _gpt_neo_config, _gpt_neo_params)


_register_builtins()


# ---------------------------------------------------------------------------
# front door
# ---------------------------------------------------------------------------

def load_hf_model(model_path: str, dtype=None,
                  **config_overrides) -> Tuple[TransformerLM, Dict[str, Any]]:
    """HF model directory → (TransformerLM, host param pytree).

    The returned params are numpy (host) arrays in the model's pytree
    layout; hand them to ``init_inference(..)``/``initialize(
    model_parameters=...)`` to get sharded device placement, or to
    ``auto_tp.shard_param_tree`` for explicit per-rank slices.
    """
    loader = SDLoaderFactory.get_sd_loader(model_path)
    mt = loader.config.get("model_type", "gpt2")
    cfg = hf_to_transformer_config(loader.config, dtype=dtype, **config_overrides)
    sd = loader.load_state_dict()
    params = hf_state_dict_to_params(cfg, mt, sd)
    n = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))
    log_dist(f"loaded HF checkpoint {model_path} ({mt}, {n / 1e6:.1f}M params)",
             ranks=[0])
    return TransformerLM(cfg), params
