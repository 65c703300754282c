"""Task heads over the shared encoder body.

Counterpart of the reference's HF-pipeline coverage: its kernel-injection
inference tests drive bert/roberta through fill-mask, text-classification,
token-classification, and question-answering pipelines
(``tests/unit/inference/test_inference.py:62`` task×model matrix; the
injected ``BertLayerPolicy`` accelerates whatever head the HF model
carries). Here the heads are explicit modules over ``TransformerLM``'s
``return_hidden`` output, loading the matching ``*For*`` HF checkpoints.

Head shapes follow the HF architectures exactly:
- bert sequence classification: pooler (dense→tanh on [CLS]) → classifier
- roberta sequence classification: classifier.dense→tanh→out_proj on [CLS]
- distilbert sequence classification: pre_classifier→relu → classifier
- token classification: per-token classifier (all archs)
- question answering: per-token ``qa_outputs`` → (start, end) logits
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nn import layers as nn
from .transformer import Params, TransformerLM, masked_cross_entropy

TASKS = ("sequence_classification", "token_classification",
         "question_answering")


class EncoderTaskModel:
    """An encoder body + one task head.

    ``head_params`` layouts:
    - sequence_classification: optional ``pooler`` (bert) or ``dense``
      (roberta two-layer head), then ``classifier``
    - token_classification: ``classifier``
    - question_answering: ``qa_outputs`` (out_features=2)
    """

    def __init__(self, lm: TransformerLM, task: str, num_labels: int = 2,
                 head_style: str = "bert"):
        if task not in TASKS:
            raise ValueError(f"unknown task {task!r} (one of {TASKS})")
        if lm.config.causal:
            raise ValueError("task heads expect a bidirectional encoder body")
        self.lm = lm
        self.config = lm.config
        self.task = task
        self.num_labels = 2 if task == "question_answering" else num_labels
        self.head_style = head_style
        H = lm.config.hidden_size
        self._mid = nn.Linear(H, H)        # pooler / dense / pre_classifier
        self._cls = nn.Linear(H, self.num_labels)

    # -- params --------------------------------------------------------------
    def init(self, rng: jax.Array, dtype=jnp.float32) -> Params:
        body = self.lm.init(rng, dtype)
        r = jax.random.fold_in(rng, 11)
        head: Params = {"classifier": self._cls.init(r, dtype)}
        if self.task == "sequence_classification":
            head["mid"] = self._mid.init(jax.random.fold_in(r, 1), dtype)
        body["head"] = head
        return body

    def specs(self) -> Params:
        specs = self.lm.specs()
        head = {"classifier": self._cls.specs()}
        if self.task == "sequence_classification":
            head["mid"] = self._mid.specs()
        specs["head"] = head
        return specs

    # -- forward -------------------------------------------------------------
    def apply(self, params: Params, input_ids: jax.Array,
              token_type_ids: Optional[jax.Array] = None,
              attention_mask: Optional[jax.Array] = None,
              remat_budget=None) -> jax.Array:
        """sequence_classification -> [B, num_labels];
        token_classification -> [B, S, num_labels];
        question_answering -> (start [B, S], end [B, S])."""
        hidden, _ = self.lm.apply(params, input_ids,
                                  token_type_ids=token_type_ids,
                                  attention_mask=attention_mask,
                                  return_hidden=True,
                                  remat_budget=remat_budget)
        head = params["head"]
        if self.task == "sequence_classification":
            x = hidden[:, 0]                     # [CLS]
            x = self._mid(head["mid"], x)
            # bert's pooler and roberta's classifier.dense both tanh;
            # distilbert's pre_classifier uses relu
            x = jax.nn.relu(x) if self.head_style == "distilbert" else jnp.tanh(x)
            return self._cls(head["classifier"], x).astype(jnp.float32)
        logits = self._cls(head["classifier"], hidden).astype(jnp.float32)
        if self.task == "question_answering":
            return logits[..., 0], logits[..., 1]
        return logits

    def loss(self, params: Params, batch: Dict[str, jax.Array],
             remat_budget=None) -> jax.Array:
        """Cross-entropy per task; QA averages start+end position losses
        with HF's ignore convention (positions clamped to [0, S]; S =
        ignored — truncated/impossible answer spans contribute no loss).
        ``remat_budget`` as in ``TransformerLM.apply``."""
        out = self.apply(params, batch["input_ids"],
                         token_type_ids=batch.get("token_type_ids"),
                         attention_mask=batch.get("attention_mask"),
                         remat_budget=remat_budget)
        if self.task == "question_answering":
            start, end = out
            S = start.shape[-1]

            def qa_labels(pos):
                clamped = jnp.clip(pos, 0, S)
                return jnp.where(clamped == S, -100, clamped)

            return 0.5 * (masked_cross_entropy(start, qa_labels(batch["start_positions"]))
                          + masked_cross_entropy(end, qa_labels(batch["end_positions"])))
        return masked_cross_entropy(out, batch["labels"])


# ---------------------------------------------------------------------------
# HF checkpoint ingestion for task models
# ---------------------------------------------------------------------------

_SEQ_CLS_HEADS = {
    # arch -> (mid-layer key or None, classifier key)
    "bert": ("bert.pooler.dense", "classifier"),
    "roberta": ("classifier.dense", "classifier.out_proj"),
    "distilbert": ("pre_classifier", "classifier"),
}


def load_hf_task_model(model_path: str, task: str, dtype=None,
                       **config_overrides) -> Tuple[EncoderTaskModel, Params]:
    """HF ``*ForSequenceClassification`` / ``*ForTokenClassification`` /
    ``*ForQuestionAnswering`` checkpoint directory → (EncoderTaskModel,
    host param pytree). Counterpart of serving those models through the
    reference's injected-BERT path."""
    from ..runtime.state_dict_factory import (SDLoaderFactory,
                                              hf_state_dict_to_params,
                                              hf_to_transformer_config)

    loader = SDLoaderFactory.get_sd_loader(model_path)
    mt = loader.config.get("model_type", "bert")
    if mt not in _SEQ_CLS_HEADS:
        raise ValueError(f"task heads support bert/roberta/distilbert, "
                         f"not {mt!r}")
    cfg = hf_to_transformer_config(loader.config, dtype=dtype,
                                   mlm_head=False, **config_overrides)
    sd = loader.load_state_dict()

    num_labels = loader.config.get("num_labels") or (
        len(loader.config.get("id2label") or {}) or 2)
    lm = TransformerLM(cfg)
    model = EncoderTaskModel(lm, task, num_labels=num_labels, head_style=mt)
    params = hf_state_dict_to_params(cfg, mt, {
        k: v for k, v in sd.items()
        if not _is_head_key(k)})
    T = np.transpose

    def lin(key):
        return {"kernel": T(sd[key + ".weight"]), "bias": sd[key + ".bias"]}

    if task == "sequence_classification":
        mid_key, cls_key = _SEQ_CLS_HEADS[mt]
        params["head"] = {"mid": lin(mid_key), "classifier": lin(cls_key)}
    elif task == "token_classification":
        params["head"] = {"classifier": lin("classifier")}
    else:  # question_answering
        params["head"] = {"classifier": lin("qa_outputs")}
    return model, params


def _is_head_key(k: str) -> bool:
    return k.startswith(("classifier", "pre_classifier", "qa_outputs",
                         "bert.pooler", "roberta.pooler", "cls.seq_relationship"))
