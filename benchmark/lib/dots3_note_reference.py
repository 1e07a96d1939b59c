"""Plain reference for dots3-note-prev (HF ``model_type: dots3_note``; the row
of ``model-configs/architectures.jsonl`` is the source there is, and where it
names a mechanism without defining it, the published form in the family the
key comes from: ``configs/dots3-note-prev.json`` ``assumed``): the forward
pass in straightforward jax.numpy and float32.  No cache, no ring, no page, no
kernel, no absorbed matrices: every query scores the whole sequence under a
MASK (a sliding layer's band, a full layer's picked set), and EVERY held
expert runs on EVERY token with the routing as a mask.  It reads the program's
parameter tree (``deepspeed_tpu/models/dots3_note.py``: the names are the
program's) and nothing else of it; the pieces that are GLM-5.2's reference's
to the letter (the indexer, the expert layer, RoPE, the blocked head) are
taken from ``lib/glm_dsa_reference.py``, another file of this benchmark.
Weights arrive in the dtype they are served in and are raised to float32 as
they are used.  Callers run it under ``jax.default_matmul_precision
("highest")``.

    x <- x + Attn_kind(RMSNorm(x)); x <- x + FFN(RMSNorm(x));
    logits = RMSNorm(x) W_head          (eps rms_norm_eps, untied)

With ``h = RMSNorm(x)`` and, by kind, ``(H, r_q, r_kv, d_n, d_r, d_v, theta)``
= full ``(num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, rope_theta)``, sliding the same keys under
``swa_``:

* ``c_q = s_q RMSNorm(h W_qa)``, ``s_q = sqrt(hidden / r_q)``; a head's
  ``[q_nope ; q_rope] = c_q W_qb,j``, ``q_rope`` rotated at ``theta``;
* ``[c ; k_r] = h W_kva``; ``c_kv = s_kv RMSNorm(c)``, ``s_kv = sqrt(hidden /
  r_kv)``; ``k_rope = RoPE_theta(k_r)``, one a token for all heads;
* ``k_j = [c_kv W_UK,j ; k_rope]``, ``v_j = c_kv W_UV,j``; ``o_j[t] =
  softmax_{s in A(t)}((d_n + d_r)**-0.5 q_j[t] . k_j[s]) v_j``;
* ``A(t)``, sliding: ``0 <= t - s < sliding_window_size``; full: the
  ``index_topk`` positions ``s <= t`` of largest ``I[t, s]`` (GLM-5.2's
  indexer at this model's widths, from the same rescaled ``c_q``, on EVERY
  full layer), ties to the lower ``s``, every ``s <= t`` while ``t + 1 <=
  index_topk``;
* ``g = sigmoid(h W_g)`` in ``R^H``; ``Attn = concat_j(g_j o_j) W_o``.

FFN: layer ``< first_k_dense_replace`` SwiGLU at ``intermediate_size``; every
other layer GLM-5.2's expert layer (sigmoid scores, the ``num_experts_per_tok``
largest of ``score + router_bias``, weights ``score / sum *
routed_scaling_factor``, the held experts' part of the sum, the shared expert
whole).

The readings that must come out as NOT correct (``lib/dots3_note_family.py``)
are switches of this same forward, all traced so that one program gives them:
``round_acts`` (the residual stream rounded to ``act_dtype``), ``low_keys``
(the indexer's keys rounded to 8 bits, float8 e4m3, before they score),
``no_gate`` (``g = 1``), ``no_rescale`` (``s_q = s_kv = 1``); read and not
judged: ``bf16_index`` (the indexer's queries, keys and head weights rounded
to bfloat16, what the program's indexer works in).  ``window`` (static)
overrides ``sliding_window_size``: a test's, for the window's edge.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .glm_dsa_reference import (F32, _EXPERT_LEAVES, _blocks, _experts, _head,
                                _index_masks, _rms, _rope, _round_to, _swiglu)

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


def widths(m: dict, kind: str):
    """(H, r_q, r_kv, d_n, d_r, d_v, theta) of an attention kind."""
    pre = "" if kind == "full" else "swa_"
    return tuple(m[pre + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")) \
        + (float(m[pre + "rope_theta"]),)


def _attention(p, c_q, h, mask, w, s_kv, gate, eps, block):
    """h [T, d] (normed) of ONE sequence, the expanded form over the keys
    ``mask`` [T, T] lets a query see, a head and a block of queries at a
    time; ``gate`` [T, H] multiplies a head's output before ``W_o``."""
    T = h.shape[0]
    H, _, C, nope, rot, dv, theta = w
    kv = h @ p["kv_a_w"].astype(F32)
    c_kv = s_kv * _rms(kv[:, :C], p["kv_a_norm"], eps)
    k_rope = _rope(kv[:, None, C:], theta, rot)[:, 0]        # [T, rot]
    scale = (nope + rot) ** -0.5
    block = min(block, T)
    mask_b = _blocks(mask, block)

    def head(acc, args):
        j, w_uk, w_uv = args
        q_h = c_q @ jax.lax.dynamic_slice_in_dim(
            p["q_b_w"], j * (nope + rot), nope + rot, axis=1).astype(F32)
        q_h = jnp.concatenate(
            [q_h[:, :nope], _rope(q_h[:, None, nope:], theta, rot)[:, 0]], -1)
        k_h = jnp.concatenate([c_kv @ w_uk.astype(F32).T, k_rope], -1)
        v_h = c_kv @ w_uv.astype(F32)

        def rows(args):
            qb, ok = args
            s = jnp.where(ok, (qb @ k_h.T) * scale, -jnp.inf)
            e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
            return (e / jnp.sum(e, -1, keepdims=True)) @ v_h

        o_h = jax.lax.map(rows, (_blocks(q_h, block), mask_b))
        o_h = o_h.reshape(-1, dv)[:T] * jax.lax.dynamic_slice_in_dim(
            gate, j, 1, axis=1)
        w_o = jax.lax.dynamic_slice_in_dim(p["o_w"], j * dv, dv, axis=0)
        return acc + o_h @ w_o.astype(F32), None

    return jax.lax.scan(head, jnp.zeros_like(h),
                        (jnp.arange(H), p["k_b_w"], p["v_b_w"]))[0]


def dots3_note_logits(params, tokens, m: dict, act_dtype=F32,
                      round_acts=False, low_keys=False, no_gate=False,
                      no_rescale=False, bf16_index=False, block: int = 128,
                      pick_rows=None, logit_rows=None, window=None):
    """tokens [B, T] -> float32 logits [B, T, V]; with ``pick_rows`` [R]
    (positions) also the picked sets of those queries at each full layer,
    bool [B, full layers, R, T]; with ``logit_rows`` (first (may be
    traced), count) the logits of those rows alone, [B, count, V].  ``m``:
    the configuration's values under the source's keys (``layer_types`` one
    entry a layer), + ``experts_held``.  The switches (module docstring;
    each but ``window`` may be traced)."""
    eps, d = m["rms_norm_eps"], m["hidden_size"]
    kinds = [_KINDS[t] for t in m["layer_types"]]
    n_dense = m["first_k_dense_replace"]
    W = m["sliding_window_size"] if window is None else window
    rescaled = bool(m.get("apply_mla_qkv_lora_rescale", True))
    # the indexer is GLM-5.2's at the full layers' widths and base
    im = {**m, "rope_parameters": {"rope_theta": m["rope_theta"]}}
    T = tokens.shape[1]
    at = jnp.arange(T)
    band = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - W)

    def rounded(x):
        return jnp.where(round_acts, _round_to(x, act_dtype), x)

    def scale_of(rank):
        s = (d / rank) ** 0.5 if rescaled else 1.0
        return jnp.where(no_rescale, 1.0, s)

    stacked = {k: params["moe"][k].reshape(
        (-1,) + params["moe"][k].shape[2:]) for k in _EXPERT_LEAVES} \
        if "moe" in params else None

    def one(seq):
        x = rounded(params["wte"][seq].astype(F32))
        picks = []
        for layer, kind in enumerate(kinds):
            i = kinds[:layer].count(kind)
            w = widths(m, kind)
            # a layer's weights wait for its input: their float32 copies
            # are then made a layer at a time, not all at once
            p, x = jax.lax.optimization_barrier(
                ({k: v[i] for k, v in params[kind].items()}, x))
            h = _rms(x, p["ln1"], eps)
            c_q = scale_of(w[1]) * _rms(h @ p["q_a_w"].astype(F32),
                                        p["q_a_norm"], eps)
            mask = band
            if kind == "full":
                ip = {k: v[i] for k, v in params["indexer"].items()}
                mask = _index_masks(ip, h, c_q, im, low_keys, bf16_index,
                                    block)
                if pick_rows is not None:
                    picks.append(mask[pick_rows])
            gate = jnp.where(no_gate, 1.0, jax.nn.sigmoid(
                h @ p["attn_gate_w"].astype(F32)))
            x = x + _attention(p, c_q, h, mask, w, scale_of(w[2]), gate, eps,
                               block)
            dense = layer < n_dense
            j = layer if dense else layer - n_dense
            p, x = jax.lax.optimization_barrier(
                ({k: v[j] for k, v in params["dense" if dense
                                             else "moe"].items()
                  if k not in _EXPERT_LEAVES or dense}, x))
            h = _rms(x, p["ln2"], eps)
            x = rounded(x + (
                _swiglu(h, p["gate_w"], p["up_w"], p["down_w"], slices=4)
                if dense else _experts(p, stacked, j, h, m)))
        if logit_rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, *logit_rows)
        head, x = jax.lax.optimization_barrier((params["lm_head"], x))
        logits = _head(_rms(x, params["norm_f"], eps), head)
        return (logits, jnp.stack(picks)) if pick_rows is not None \
            else logits

    return jax.lax.map(one, tokens)
