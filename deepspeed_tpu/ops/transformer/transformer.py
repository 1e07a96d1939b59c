"""DeepSpeed transformer layer — the fused BERT encoder block, TPU-native.

The reference implements this as ONE CUDA autograd function composing
cuBLAS GEMMs with hand-fused bias/GELU/dropout/LayerNorm/softmax kernels
and a 17-tensor save-list for backward (reference:
deepspeed/ops/transformer/transformer.py:150-418,
csrc/transformer/ds_transformer_cuda.cpp).  On TPU the fusion is XLA's:
the whole block compiles into MXU GEMMs with the elementwise chains fused
into them, so the value preserved here is

  - the exact math (BERT self-attention + FFN, pre- or post-LN, additive
    attention mask, fp32 softmax/LN accumulation for low-precision inputs);
  - the config surface (``DeepSpeedTransformerConfig`` key-for-key,
    transformer.py:93-134 there);
  - the *memory knobs*: ``normalize_invertible`` / ``gelu_checkpoint`` /
    ``attn_dropout_checkpoint`` drop saved intermediates in the reference;
    here they become ``jax.checkpoint`` (rematerialization) of the same
    segments, trading the identical FLOPs for the identical memory.
  - ``stochastic_mode`` relaxes RNG reproducibility for speed in the
    reference; here dropout keys are always cheap (counter-based TPU PRNG),
    so the flag is accepted and only recorded.

Differential tests against an independent jnp BERT encoder mirror the
reference's kernel-vs-HuggingFace tests (tests/unit/test_cuda_forward.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..dropout import dropout


@dataclasses.dataclass
class DeepSpeedTransformerConfig:
    """Key-for-key port of the reference config
    (reference transformer.py:93-134)."""
    batch_size: int = -1
    max_seq_length: int = -1
    hidden_size: int = -1
    intermediate_size: int = -1
    heads: int = -1
    attn_dropout_ratio: float = 0.0
    hidden_dropout_ratio: float = 0.0
    num_hidden_layers: int = -1
    initializer_range: float = 0.02
    local_rank: int = -1          # accepted for parity; no device meaning
    seed: int = -1
    fp16: bool = False            # parity alias: prefer dtype=jnp.bfloat16
    pre_layer_norm: bool = True
    normalize_invertible: bool = False
    gelu_checkpoint: bool = False
    adjust_init_range: bool = True
    attn_dropout_checkpoint: bool = False
    stochastic_mode: bool = False
    # TPU extension mirroring models/gpt2.py:44 — the reference has no
    # such knob because its fused CUDA attention IS the only path
    # (csrc/transformer/ds_transformer_cuda.cpp:99-121); here 'flash'
    # runs the Pallas flash kernel (O(T·D) memory, no seq cap, in-kernel
    # dropout) and 'dense' the jnp softmax path.
    attn_impl: str = "flash"

    def __post_init__(self):
        if self.intermediate_size <= 0 < self.hidden_size:
            self.intermediate_size = 4 * self.hidden_size

    @classmethod
    def from_dict(cls, json_object: Dict[str, Any]):
        cfg = cls()
        for k, v in json_object.items():
            setattr(cfg, k, v)
        cfg.__post_init__()  # re-derive intermediate_size from hidden_size
        return cfg

    @classmethod
    def from_json_file(cls, json_file: str):
        import json
        with open(json_file, "r", encoding="utf-8") as f:
            return cls.from_dict(json.load(f))


def _layer_norm(x, scale, bias, eps: float = 1e-12):
    """fp32-accumulated LayerNorm (the reference kernel accumulates fp32
    for fp16 inputs, csrc/transformer/normalize_kernels.cu); BERT eps."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dt)


class DeepSpeedTransformerLayer:
    """Functional BERT encoder layer.

    ``__call__(params, hidden_states, attention_mask, rng, train)`` with
    hidden_states [B, T, H] and an additive attention mask broadcastable
    to [B, 1, 1, T] (HF convention: 0 keep, large-negative drop).

    Parameter names follow the reference layer's registry
    (transformer.py:437-466 there) so checkpoints map one-to-one:
    attn_qkvw/attn_qkvb, attn_ow/attn_ob, attn_nw/attn_nb (attention LN),
    inter_w/inter_b, output_w/output_b, norm_w/norm_b (output LN).
    """

    def __init__(self, config: DeepSpeedTransformerConfig,
                 initial_weights: Optional[Dict[str, Any]] = None):
        assert config.hidden_size > 0, "hidden_size must be set"
        assert config.heads > 0, "heads must be set"
        assert config.hidden_size % config.heads == 0, \
            f"hidden {config.hidden_size} not divisible by heads {config.heads}"
        self.config = config
        self.initial_weights = initial_weights

    # ------------------------------------------------------------------
    def init(self, rng) -> Dict[str, jnp.ndarray]:
        if self.initial_weights is not None:
            return dict(self.initial_weights)
        cfg = self.config
        d, i = cfg.hidden_size, cfg.intermediate_size
        std = cfg.initializer_range
        out_std = std
        if cfg.adjust_init_range and cfg.num_hidden_layers > 0:
            # output_std = initializer_range / sqrt(2 * num_layers)
            # (reference transformer.py docstring, adjust_init_range)
            out_std = std / float(2.0 * cfg.num_hidden_layers) ** 0.5
        ks = jax.random.split(rng, 4)
        n = jax.random.normal
        return {
            # [d, 3, d]: q/k/v on a dedicated dim so a TP 'model' shard of
            # the feature dim never straddles the q/k/v boundary (the
            # fused-[3d] layout forces GSPMD halo exchanges at the split)
            "attn_qkvw": n(ks[0], (d, 3, d), jnp.float32) * std,
            "attn_qkvb": jnp.zeros((3, d), jnp.float32),
            "attn_ow": n(ks[1], (d, d), jnp.float32) * out_std,
            "attn_ob": jnp.zeros((d,), jnp.float32),
            "attn_nw": jnp.ones((d,), jnp.float32),
            "attn_nb": jnp.zeros((d,), jnp.float32),
            "inter_w": n(ks[2], (d, i), jnp.float32) * std,
            "inter_b": jnp.zeros((i,), jnp.float32),
            "output_w": n(ks[3], (i, d), jnp.float32) * out_std,
            "output_b": jnp.zeros((d,), jnp.float32),
            "norm_w": jnp.ones((d,), jnp.float32),
            "norm_b": jnp.zeros((d,), jnp.float32),
        }

    # ------------------------------------------------------------------
    @staticmethod
    def _key_mask_rows(attention_mask, B, H, T):
        """HF additive mask (broadcastable to [B, 1|H, 1, T]) → [B, T]
        (shared across heads) or [B·H, T] (per-head) additive rows for
        the flash kernel's per-key mask.  Masks with a genuine
        q-position dimension cannot be expressed as a key mask — those
        callers need attn_impl='dense'."""
        m = jnp.asarray(attention_mask)
        while m.ndim < 4:
            m = m[:, None]
        if m.shape[2] != 1:
            raise ValueError(
                f"attn_impl='flash' supports key-padding masks "
                f"(broadcastable to [B, 1|H, 1, T]); got mask shape "
                f"{attention_mask.shape} with a q-position dimension — "
                "use attn_impl='dense' for arbitrary 2-D masks")
        if m.shape[1] == 1:
            return jnp.broadcast_to(m[:, 0, 0, :], (B, T)).astype(
                jnp.float32)
        # per-head masks keep their head dimension ([B·H, T] rows)
        rows = jnp.broadcast_to(m[:, :, 0, :], (B, H, T))
        return rows.reshape(B * H, T).astype(jnp.float32)

    @jax.named_scope("attn")
    def _attention(self, params, h, attention_mask, rng, train):
        cfg = self.config
        B, T, D = h.shape
        H = cfg.heads
        Dh = D // H
        qkv = (jnp.einsum("btd,dke->btke",
                          h, params["attn_qkvw"].astype(h.dtype))
               + params["attn_qkvb"].astype(h.dtype))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        split = lambda t: t.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
        q, k, v = split(q), split(k), split(v)

        if cfg.attn_impl == "flash":
            # Pallas flash kernel: dropout fused in-kernel, padding mask
            # as the per-key operand.  attn_dropout_checkpoint is
            # structurally satisfied here — flash never materializes the
            # [T, T] probabilities, in forward OR backward.
            from ...parallel.attention import sharded_flash_attention
            km = (None if attention_mask is None
                  else self._key_mask_rows(attention_mask, B, H, T))
            ctx = sharded_flash_attention(
                q, k, v, causal=False,
                dropout_rate=cfg.attn_dropout_ratio if train else 0.0,
                dropout_rng=rng, key_mask=km)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, D)
            return ctx @ params["attn_ow"].astype(h.dtype) \
                + params["attn_ob"].astype(h.dtype)
        if cfg.attn_impl != "dense":
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r}: expected 'flash' or "
                "'dense'")

        def probs_ctx(q, k, v):
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores * (float(Dh) ** -0.5)
            if attention_mask is not None:
                mask = attention_mask.astype(jnp.float32)
                while mask.ndim < 4:
                    mask = mask[:, None]
                scores = scores + mask
            probs = jax.nn.softmax(scores, axis=-1)
            probs = dropout(probs.astype(q.dtype),
                            cfg.attn_dropout_ratio if train else 0.0, rng)
            return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

        if cfg.attn_dropout_checkpoint:
            # the reference drops the attn-dropout/softmax intermediates and
            # recomputes them in backward (ds_transformer_cuda.cpp); remat
            # of this segment is the same trade
            probs_ctx = jax.checkpoint(probs_ctx)
        ctx = probs_ctx(q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, D)
        return ctx @ params["attn_ow"].astype(h.dtype) \
            + params["attn_ob"].astype(h.dtype)

    @jax.named_scope("mlp")
    def _ffn(self, params, h):
        def inner(h):
            x = h @ params["inter_w"].astype(h.dtype) \
                + params["inter_b"].astype(h.dtype)
            return jax.nn.gelu(x, approximate=False)

        if self.config.gelu_checkpoint:
            inner = jax.checkpoint(inner)
        x = inner(h)
        return x @ params["output_w"].astype(h.dtype) \
            + params["output_b"].astype(h.dtype)

    @jax.named_scope("layer")
    def __call__(self, params, hidden_states, attention_mask=None,
                 rng=None, train: bool = True):
        cfg = self.config
        x = hidden_states
        drop = cfg.hidden_dropout_ratio if train else 0.0
        if rng is None:
            rng = jax.random.PRNGKey(max(cfg.seed, 0))
        r_attn, r1, r2 = jax.random.split(rng, 3)

        ln1 = lambda t: _layer_norm(t, params["attn_nw"], params["attn_nb"])
        ln2 = lambda t: _layer_norm(t, params["norm_w"], params["norm_b"])
        if cfg.normalize_invertible:
            # reference: drop LN inputs, recompute from outputs
            # (normalize_invertible); remat of the LN segment ≡ same memory
            ln1, ln2 = jax.checkpoint(ln1), jax.checkpoint(ln2)

        if cfg.pre_layer_norm:
            attn_out = self._attention(params, ln1(x), attention_mask,
                                       r_attn, train)
            x = x + dropout(attn_out, drop, r1)
            ffn_out = self._ffn(params, ln2(x))
            return x + dropout(ffn_out, drop, r2)
        # post-LN (classic BERT)
        attn_out = self._attention(params, x, attention_mask, r_attn, train)
        x = ln1(x + dropout(attn_out, drop, r1))
        ffn_out = self._ffn(params, x)
        return ln2(x + dropout(ffn_out, drop, r2))

    forward = __call__
