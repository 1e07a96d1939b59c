"""BERT as a pipeline-parallel module.

Per-layer LayerSpecs over the fused ``DeepSpeedTransformerLayer`` block
(the reference's BERT + PipelineModule combination; pattern:
deepspeed/runtime/pipe/module.py:85).  The word-embedding table is a
TiedLayerSpec read again by the MLM head through the 3-ary loss — gradient
tying falls out of AD (replacing the tied-weight allreduce, reference
pipe/module.py:405-474).

Batches: ``(input_ids [B, T], masked_lm_labels [B, T])`` with -100 at
unmasked label positions (``split_bert_batch`` builds the pair from a
dict batch).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.transformer import (DeepSpeedTransformerConfig,
                               DeepSpeedTransformerLayer)
from ..parallel.mesh import MODEL_AXIS
from ..pipe.module import LayerSpec, TiedLayerSpec, PipelineModule
from ..ops.dropout import dropout
from .bert import BertConfig, _layer_norm


def _layer_cfg(cfg: BertConfig) -> DeepSpeedTransformerConfig:
    return DeepSpeedTransformerConfig(
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        heads=cfg.num_attention_heads,
        attn_dropout_ratio=cfg.attention_probs_dropout_prob,
        hidden_dropout_ratio=cfg.hidden_dropout_prob,
        num_hidden_layers=cfg.num_hidden_layers,
        initializer_range=cfg.initializer_range,
        pre_layer_norm=cfg.pre_layer_norm,
        normalize_invertible=cfg.normalize_invertible,
        gelu_checkpoint=cfg.gelu_checkpoint,
        attn_dropout_checkpoint=cfg.attn_dropout_checkpoint,
        stochastic_mode=cfg.stochastic_mode)


class BertEmbeddingPipe:
    def __init__(self, cfg: BertConfig):
        self.cfg = cfg

    def init(self, rng):
        cfg = self.cfg
        d = cfg.hidden_size
        k = jax.random.split(rng, 3)
        n = jax.random.normal
        std = cfg.initializer_range
        return {
            "wte": n(k[0], (cfg.vocab_size, d), jnp.float32) * std,
            "wpe": n(k[1], (cfg.max_position_embeddings, d),
                     jnp.float32) * std,
            "tte": n(k[2], (cfg.type_vocab_size, d), jnp.float32) * std,
            "ln_scale": jnp.ones((d,), jnp.float32),
            "ln_bias": jnp.zeros((d,), jnp.float32),
        }

    def param_partition_specs(self):
        return {"wte": P(MODEL_AXIS, None), "wpe": P(), "tte": P(),
                "ln_scale": P(), "ln_bias": P()}

    def apply(self, params, input_ids, rng, train: bool = True):
        T = input_ids.shape[1]
        if T > self.cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {T} exceeds max_position_embeddings="
                f"{self.cfg.max_position_embeddings}")
        # pipe batches carry no token_type_ids: segment 0 for every token,
        # which is tte row 0 broadcast (no per-token gather needed).
        # one-hot contraction for the word lookup — scatter-free VJP under
        # the pipeline's manual/auto nesting (see gpt2_pipe equivalent)
        wte = params["wte"]
        onehot = jax.nn.one_hot(input_ids, wte.shape[0], dtype=wte.dtype)
        x = (onehot @ wte + params["wpe"][:T][None]
             + params["tte"][0][None, None])
        x = _layer_norm(x, params["ln_scale"], params["ln_bias"])
        return dropout(x, self.cfg.hidden_dropout_prob if train else 0.0,
                       rng)


class BertLayerPipe:
    """One fused encoder block (unstacked DeepSpeedTransformerLayer)."""

    def __init__(self, cfg: BertConfig, layer_idx: int):
        self.cfg = cfg
        self.layer_idx = layer_idx
        self.layer = DeepSpeedTransformerLayer(_layer_cfg(cfg))

    def init(self, rng):
        return self.layer.init(rng)

    def param_partition_specs(self):
        m = MODEL_AXIS
        return {
            "attn_qkvw": P(None, None, m), "attn_qkvb": P(None, m),
            "attn_ow": P(m, None), "attn_ob": P(),
            "attn_nw": P(), "attn_nb": P(),
            "inter_w": P(None, m), "inter_b": P(m),
            "output_w": P(m, None), "output_b": P(),
            "norm_w": P(), "norm_b": P(),
        }

    def apply(self, bp, x, rng, train: bool = True):
        return self.layer(bp, x, attention_mask=None, rng=rng, train=train)


class BertMLMTransformPipe:
    """MLM head transform + LN (the decoder matmul happens in the tied
    loss head so it can read the embedding table)."""

    def __init__(self, cfg: BertConfig):
        self.cfg = cfg

    def init(self, rng):
        cfg = self.cfg
        d = cfg.hidden_size
        return {
            "w": jax.random.normal(rng, (d, d), jnp.float32)
            * cfg.initializer_range,
            "b": jnp.zeros((d,), jnp.float32),
            "ln_scale": jnp.ones((d,), jnp.float32),
            "ln_bias": jnp.zeros((d,), jnp.float32),
        }

    def apply(self, params, x, rng, train: bool = True):
        h = x @ params["w"].astype(x.dtype) + params["b"].astype(x.dtype)
        h = jax.nn.gelu(h, approximate=False)
        return _layer_norm(h, params["ln_scale"], params["ln_bias"])


def bert_mlm_loss_head(params, hidden, labels):
    """Tied MLM decoder + masked cross-entropy (labels -100 = unmasked;
    decoder weights are the embedding table — the per-vocab decoder bias
    the non-pipe BertModel carries is omitted here, GPT-2 style)."""
    wte = params["tied"]["embed"]["wte"]
    logits = (hidden @ wte.astype(hidden.dtype).T).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = labels != -100
    safe = jnp.where(mask, labels, 0)
    # one-hot contraction (scatter-free VJP; see gpt2_pipe.gpt2_loss_head)
    onehot = jax.nn.one_hot(safe, logp.shape[-1], dtype=logp.dtype)
    nll = -jnp.sum(logp * onehot, axis=-1)
    denom = jnp.maximum(mask.sum(), 1)
    return jnp.sum(jnp.where(mask, nll, 0.0)) / denom


def build_bert_pipe(cfg: BertConfig, num_stages: int,
                    partition_method: str = "type:BertLayerPipe",
                    activation_checkpoint_interval: int = 0
                    ) -> PipelineModule:
    layers = [TiedLayerSpec("embed", BertEmbeddingPipe, cfg)]
    layers += [LayerSpec(BertLayerPipe, cfg, i)
               for i in range(cfg.num_hidden_layers)]
    layers += [LayerSpec(BertMLMTransformPipe, cfg)]
    return PipelineModule(
        layers, num_stages=num_stages, loss_fn=bert_mlm_loss_head,
        partition_method=partition_method,
        activation_checkpoint_interval=activation_checkpoint_interval)


def split_bert_batch(batch):
    """dict batch → (input_ids, masked_lm_labels) for the pipeline."""
    return batch["input_ids"], batch["masked_lm_labels"]
