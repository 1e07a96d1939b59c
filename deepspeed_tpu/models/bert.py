"""BERT model family — the reference's headline pretraining workload.

The reference's fastest-BERT results come from the fused transformer
kernel applied to BERT-large (reference:
docs/_posts/2020-05-28-fastest-bert-training.md; the model itself lives in
the vendored test copy tests/unit/modeling.py:1578).  Here the encoder
stacks ``DeepSpeedTransformerLayer`` blocks under ``lax.scan`` with
layer-stacked parameters (one compiled block for any depth), with
embeddings, MLM + NSP pretraining heads, and Megatron-style tensor-parallel
partition specs — same structure as the GPT-2 family (models/gpt2.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.transformer import (DeepSpeedTransformerConfig,
                               DeepSpeedTransformerLayer)
from ..ops import mlm_head
from ..ops.dropout import dropout
from ..ops.transformer.transformer import _layer_norm
from ..parallel.mesh import MODEL_AXIS
from ..runtime.activation_checkpointing.block_remat import checkpoint_block
from ..runtime.module import TrainModule, mark_subtrees
from ..runtime.zero import gather_layer


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    pre_layer_norm: bool = False      # classic BERT is post-LN
    remat: Optional[str] = "block"    # None | 'block'
    # memory knobs forwarded to the layer (reference config surface)
    normalize_invertible: bool = False
    gelu_checkpoint: bool = False
    attn_dropout_checkpoint: bool = False
    stochastic_mode: bool = False
    # 'flash' (Pallas kernel, the fused path the reference's CUDA BERT
    # always takes) | 'dense' (jnp softmax); mirrors GPT2Config.attn_impl
    attn_impl: str = "flash"
    scan_layers: bool = True          # False: unroll the stack (XLA then
                                      # optimizes across layer boundaries,
                                      # ≈25% faster on TPU like
                                      # GPT2Config.scan_layers, at
                                      # depth-linear compile cost)


BERT_BASE = BertConfig()
BERT_LARGE = BertConfig(hidden_size=1024, num_hidden_layers=24,
                        num_attention_heads=16, intermediate_size=4096)


class BertModel(TrainModule):
    """BERT encoder with MLM + NSP pretraining loss.

    Batches: dict with ``input_ids`` [B, T]; optional ``token_type_ids``,
    ``attention_mask`` (1 keep / 0 pad), ``masked_lm_labels`` [B, T] with
    -100 for unmasked positions, ``next_sentence_label`` [B].

    ``loss_fn`` reads the labelled rows only: the MLM head (transform,
    tied decoder, float32 softmax, and their gradient) runs over the
    positions that carry a label, a block of rows at a time, and never
    holds logits for every position (ops/mlm_head.py).  ``apply`` still
    returns ``mlm_logits`` ``[B, T, V]`` for callers that want them
    (fine-tuning, inference).
    """

    def __init__(self, config: BertConfig):
        self.config = config
        self.layer = DeepSpeedTransformerLayer(
            DeepSpeedTransformerConfig(
                hidden_size=config.hidden_size,
                intermediate_size=config.intermediate_size,
                heads=config.num_attention_heads,
                attn_dropout_ratio=config.attention_probs_dropout_prob,
                hidden_dropout_ratio=config.hidden_dropout_prob,
                num_hidden_layers=config.num_hidden_layers,
                initializer_range=config.initializer_range,
                pre_layer_norm=config.pre_layer_norm,
                normalize_invertible=config.normalize_invertible,
                gelu_checkpoint=config.gelu_checkpoint,
                attn_dropout_checkpoint=config.attn_dropout_checkpoint,
                stochastic_mode=config.stochastic_mode,
                attn_impl=config.attn_impl))

    # ---------------- init ----------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.config
        d, L = cfg.hidden_size, cfg.num_hidden_layers
        keys = jax.random.split(rng, 6 + L)
        std = cfg.initializer_range
        n = jax.random.normal

        layer_params = [self.layer.init(keys[6 + i]) for i in range(L)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params)

        return {
            "word_embeddings": n(keys[0], (cfg.vocab_size, d)) * std,
            "position_embeddings": n(
                keys[1], (cfg.max_position_embeddings, d)) * std,
            "token_type_embeddings": n(
                keys[2], (cfg.type_vocab_size, d)) * std,
            "emb_ln_scale": jnp.ones((d,), jnp.float32),
            "emb_ln_bias": jnp.zeros((d,), jnp.float32),
            "layers": stacked,
            "pooler_w": n(keys[3], (d, d)) * std,
            "pooler_b": jnp.zeros((d,), jnp.float32),
            # MLM head: transform + LN + decoder bias (decoder weights tied
            # to word embeddings)
            "mlm_transform_w": n(keys[4], (d, d)) * std,
            "mlm_transform_b": jnp.zeros((d,), jnp.float32),
            "mlm_ln_scale": jnp.ones((d,), jnp.float32),
            "mlm_ln_bias": jnp.zeros((d,), jnp.float32),
            "mlm_bias": jnp.zeros((cfg.vocab_size,), jnp.float32),
            "nsp_w": n(keys[5], (d, 2)) * std,
            "nsp_b": jnp.zeros((2,), jnp.float32),
        }

    # ---------------- TP declaration ----------------
    def param_partition_specs(self, params) -> Dict[str, Any]:
        m = MODEL_AXIS
        return {
            "word_embeddings": P(m, None),
            "position_embeddings": P(),
            "token_type_embeddings": P(),
            "emb_ln_scale": P(), "emb_ln_bias": P(),
            "layers": {
                "attn_qkvw": P(None, None, None, m),
                "attn_qkvb": P(None, None, m),
                "attn_ow": P(None, m, None), "attn_ob": P(),
                "attn_nw": P(), "attn_nb": P(),
                "inter_w": P(None, None, m), "inter_b": P(None, m),
                "output_w": P(None, m, None), "output_b": P(),
                "norm_w": P(), "norm_b": P(),
            },
            "pooler_w": P(), "pooler_b": P(),
            "mlm_transform_w": P(), "mlm_transform_b": P(),
            "mlm_ln_scale": P(), "mlm_ln_bias": P(),
            "mlm_bias": P(m),
            "nsp_w": P(), "nsp_b": P(),
        }

    def stacked_param_spec(self, params):
        """The encoder leaves are stacked ``[L, ...]`` and ``encode``
        scans over L."""
        if not self.config.scan_layers:
            return None
        return mark_subtrees(params, {"layers"})

    # ---------------- forward ----------------
    def encode(self, params, input_ids, token_type_ids=None,
               attention_mask=None, rng=None, train: bool = True,
               pld_theta=None):
        """→ sequence output [B, T, D].

        ``pld_theta``: progressive-layer-drop keep-probability scalar (the
        engine injects it per step when ``progressive_layer_drop`` is
        enabled, runtime/engine.py; schedule in
        runtime/progressive_layer_drop.py — reference engine.py:189-190,
        787-788).  Layer i keeps with p_i = 1 - (i/L)(1-θ) — deeper
        layers drop more, per the PLD paper's depth schedule; dropped
        layers pass the residual through unchanged.  Eval ignores it."""
        cfg = self.config
        B, T = input_ids.shape
        if T > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {T} exceeds max_position_embeddings="
                f"{cfg.max_position_embeddings}")
        if rng is None:
            rng = jax.random.PRNGKey(0)
        tt = (token_type_ids if token_type_ids is not None
              else jnp.zeros_like(input_ids))
        with jax.named_scope("embed"):
            x = (params["word_embeddings"][input_ids]
                 + params["position_embeddings"][:T][None]
                 + params["token_type_embeddings"][tt])
            x = _layer_norm(x, params["emb_ln_scale"],
                            params["emb_ln_bias"])
        x = dropout(x, cfg.hidden_dropout_prob if train else 0.0,
                    jax.random.fold_in(rng, 997))

        # HF-style additive mask [B, 1, 1, T]
        add_mask = None
        if attention_mask is not None:
            add_mask = (1.0 - attention_mask.astype(jnp.float32)
                        )[:, None, None, :] * -10000.0

        layer = self.layer
        L = cfg.num_hidden_layers

        def body(carry, xs):
            h = carry
            lp, i = xs
            lrng = jax.random.fold_in(rng, i)
            if pld_theta is not None and train:
                # lax.cond (not where): a dropped layer must SKIP its
                # FLOPs at runtime — the throughput gain is the point of
                # PLD, not just the regularization
                p_keep = 1.0 - (i.astype(jnp.float32) / L) * (
                    1.0 - pld_theta.astype(jnp.float32))
                keep = jax.random.bernoulli(
                    jax.random.fold_in(lrng, 131), p_keep)
                y = jax.lax.cond(
                    keep,
                    lambda hh: layer(lp, hh, add_mask, lrng, train),
                    lambda hh: hh, h)
            else:
                y = layer(lp, h, add_mask, lrng, train)
            return y, None

        # whether a block keeps the flash kernel's results besides its
        # input follows the engine's memory budget (block_remat.py)
        remat = checkpoint_block(
            x, trips=L, heads=cfg.num_attention_heads,
            ffn_width=cfg.intermediate_size, head_width=cfg.vocab_size,
            head_rows=mlm_head.HEAD_BLOCK_ROWS,
            attn_sites=int(cfg.attn_impl == "flash")
        ) if cfg.remat == "block" else (lambda f: f)

        if cfg.scan_layers:
            # under ZeRO the layer is gathered here, inside the remat'd
            # body (runtime/zero.py::gather_layer)
            layer_specs = self.param_partition_specs(params)["layers"]

            def body_gather(carry, xs):
                lp, i = xs
                return body(carry, (gather_layer(lp, layer_specs), i))

            with jax.named_scope("layer"):
                x, _ = jax.lax.scan(
                    remat(body_gather), x, (params["layers"], jnp.arange(L)))
        else:
            body_fn = remat(body)
            for i in range(L):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                x, _ = body_fn(x, (lp, jnp.asarray(i, jnp.int32)))
        return x

    def _encode_batch(self, params, batch, rng, train: bool):
        pld = batch.get("pld_theta")
        return self.encode(params, batch["input_ids"],
                           batch.get("token_type_ids"),
                           batch.get("attention_mask"), rng, train,
                           pld_theta=(pld.reshape(-1)[0]
                                      if pld is not None else None))

    @staticmethod
    def _nsp_logits(params, seq):
        """NSP head on pooled [CLS]."""
        pooled = jnp.tanh(
            seq[:, 0] @ params["pooler_w"].astype(seq.dtype)
            + params["pooler_b"].astype(seq.dtype))
        return pooled @ params["nsp_w"].astype(seq.dtype) \
            + params["nsp_b"].astype(seq.dtype)

    def apply(self, params, batch, rng=None, train: bool = True):
        """→ (mlm_logits [B, T, V], nsp_logits [B, 2])."""
        seq = self._encode_batch(params, batch, rng, train)
        with jax.named_scope("mlm_head"):
            h = mlm_head.mlm_transform(seq, params)
            mlm_logits = h @ params["word_embeddings"].astype(h.dtype).T \
                + params["mlm_bias"].astype(h.dtype)
        return mlm_logits, self._nsp_logits(params, seq)

    def loss_fn(self, params, batch, rng, train: bool = True):
        seq = self._encode_batch(params, batch, rng, train)
        loss = jnp.asarray(0.0, jnp.float32)
        labels = batch.get("masked_lm_labels")
        if labels is not None:
            with jax.named_scope("mlm_head"):
                loss = loss + mlm_head.masked_lm_loss(
                    seq, labels,
                    {k: params[k] for k in mlm_head.HEAD_LEAVES},
                    mlm_head.HEAD_BLOCK_ROWS)
        nsl = batch.get("next_sentence_label")
        if nsl is not None:
            logp = jax.nn.log_softmax(
                self._nsp_logits(params, seq).astype(jnp.float32), -1)
            loss = loss - jnp.mean(
                jnp.take_along_axis(logp, nsl[:, None], -1))
        return loss

    def labelled_rows(self, batch) -> Optional[int]:
        """Positions of a host batch that carry a masked-LM label, the
        rows the head's walk runs (``train_head_rows{kind="labelled"}``);
        None for a batch that is on the device already (reading it would
        wait for the device)."""
        labels = batch.get("masked_lm_labels")
        if labels is None or isinstance(labels, jax.Array):
            return None
        return int(np.sum(np.asarray(labels) >= 0))
