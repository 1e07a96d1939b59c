"""Plain reference for OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; HF
``modeling_olmoe.py``): the forward pass in straightforward jax.numpy and
float32.  No cache, no kernel, no sort, no dispatch: EVERY expert runs on
EVERY token and the top-k mask weights the sum.  It reads the program's
parameter tree (``deepspeed_tpu/models/olmoe.py``: the names are the
program's) and nothing else of it.  Weights arrive in the dtype they are
served in and are raised to float32 as they are used, one expert at a
time.  Callers run it under ``jax.default_matmul_precision("highest")``.

Departures from the published description, each for a stated reason:
* the router's logits are computed in float32 from float32 activations (HF
  runs ``mlp.gate`` in the model's dtype and only the softmax in float):
  the reference is the float32 mathematics;
* the routing weights stay float32 (HF casts them to the hidden dtype);
* ``norm_topk_prob`` false, ``clip_qkv`` null, no bias, untied head: as the
  configuration states; grouped keys are not modelled (16 of 16).
QK-norm (RMSNorm over the whole 2,048-wide q and k projections, before the
split into heads) and ``head_dim`` = hidden / heads are from the paper and
the HF code; the catalog row has no key for them (``assumed``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """x [B, H, T, Dh]: HF's form, cos/sin of [T, Dh] = the Dh/2
    frequencies twice, ``x * cos + rotate_half(x) * sin``."""
    T, dh = x.shape[2], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    freqs = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def olmoe_logits(params, tokens, m: dict):
    """tokens [B, T] -> float32 logits [B, T, V].  ``m``: the
    configuration's numbers under the source's keys."""
    B, T = tokens.shape
    H, eps = m["num_attention_heads"], m["rms_norm_eps"]
    K = m["num_experts_per_tok"]
    x = params["wte"][tokens].astype(F32)
    dh = x.shape[-1] // H

    def heads(t):
        return t.reshape(B, T, H, dh).transpose(0, 2, 1, 3)

    # every layer's experts in one row, one expert sliced out at a time:
    # a layer's worth (0.8 GB at the published widths) is never copied
    big = ("gate_w", "up_w", "down_w")
    blocks = params["blocks"]
    experts = {k: blocks[k].reshape((-1,) + blocks[k].shape[2:])
               for k in big}
    E = blocks["gate_w"].shape[1]
    layers = ({k: v for k, v in blocks.items() if k not in big},
              jnp.arange(blocks["gate_w"].shape[0]))

    def block(x, xs):
        bp, layer = xs
        h = _rms(x, bp["ln1"], eps)
        q = _rms(h @ bp["q_w"].astype(F32), bp["q_norm"], eps)
        k = _rms(h @ bp["k_w"].astype(F32), bp["k_norm"], eps)
        v = h @ bp["v_w"].astype(F32)
        q, k = _rope(heads(q), m["rope_theta"]), _rope(heads(k),
                                                       m["rope_theta"])
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(F32(dh))
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s,
                      -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), heads(v))
        x = x + a.transpose(0, 2, 1, 3).reshape(B, T, H * dh) \
            @ bp["o_w"].astype(F32)

        h = _rms(x, bp["ln2"], eps)
        probs = jax.nn.softmax(h @ bp["router_w"].astype(F32), axis=-1)
        kth = jax.lax.top_k(probs, K)[0][..., K - 1:]
        gates = jnp.where(probs >= kth, probs, 0.0)         # [B, T, E]
        if m.get("norm_topk_prob"):
            gates = gates / jnp.sum(gates, -1, keepdims=True)

        def expert(acc, xs):
            e, gate = xs
            wg, wu, wd = (jax.lax.dynamic_index_in_dim(
                experts[k], layer * E + e, keepdims=False).astype(F32)
                for k in big)
            y = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
            return acc + gate[..., None] * y, None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (jnp.arange(E), jnp.moveaxis(gates, -1, 0)))
        return x + y, None

    x, _ = jax.lax.scan(block, x, layers)
    return _rms(x, params["norm_f"], eps) @ params["lm_head"].astype(F32)
