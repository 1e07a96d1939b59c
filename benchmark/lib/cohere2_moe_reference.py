"""Plain reference for Cohere Command A+ (HF ``model_type: cohere2_moe``;
the row ``command-a-plus-05-2026`` of ``model-configs/architectures.jsonl``
is the source there is): the forward pass in straightforward jax.numpy and
float32.  No cache, no kernel, no page, no ring, no chunk, no sort, no
dispatch: every query sees the whole sequence under a mask, EVERY held
expert runs on EVERY token with the routing as a mask, and the shared
experts are run one by one and averaged, as published.  It reads the
program's parameter tree (``deepspeed_tpu/models/cohere2_moe.py``: the names
are the program's) and nothing else of it.  Weights arrive in the dtype they
are served in and are raised to float32 as they are used.  Callers run it
under ``jax.default_matmul_precision("highest")``.

    h = LN_l(x);  x <- x + attn_l(h) + ffn_l(h)          (ONE norm a layer)
    logits = logit_scale * LN_f(x) W_emb^T                (tied)
    LN(x) = w * (x - mean) / sqrt(var + eps)

Attention, layer ``l`` of kind ``layer_types[l]``: ``q = h W_q`` [Hq, D],
``k = h W_k``, ``v = h W_v`` [Hkv, D]; query head ``i`` on key head ``i //
(Hq / Hkv)``; scores ``q . k / sqrt(D)``; ``o W_o``.  ``sliding_attention``
rotates q and k in interleaved pairs ``(2i, 2i + 1)`` over the whole head
at ``rope_theta`` and sees ``t - sliding_window < j <= t``;
``full_attention`` rotates nothing and sees ``j <= t``.  FFN of the same
``h``: ``s = sigmoid(h W_r)`` over all experts, the ``num_experts_per_tok``
largest, weights ``s / sum`` (``norm_topk_prob``), SwiGLU experts; plus the
mean of ``num_shared_experts`` shared SwiGLU experts.

The share (``m["experts_held"] = [first, count]``; the vocabulary slice is
the parameter tree's own width): the router ranges over ALL experts, the sum
runs over the held ones only, the shared experts are whole, and that part
goes on to the next layer, exactly as the program does.  Nothing stands in
for the other chips.

So that some thousands of positions at the published widths fit beside the
engine, the wide intermediates are computed in blocks: attention a block of
query rows at a time, the experts (routed and shared) one at a time.

Departures from the published description, each for a stated reason (the
configuration file's ``assumed`` says the same):
* ``shared_expert_combination_strategy: average`` is read as the mean of
  the shared experts' outputs, added to the routed sum;
* ``first_k_dense_replace: 0`` is read literally (no dense prefix layer;
  ``prefix_dense_intermediate_size`` is then unused);
* no selection bias in the router (the row names none); scores float32
  from float32 activations;
* the window counts the query's own token;
* the vision tower is not run.

The readings that must come out as NOT correct
(``lib/cohere2_moe_family.py``) are switches of this same forward, all
traced so that one program gives them: ``round_acts`` (the residual stream
rounded to ``act_dtype``), ``window`` other than the configuration's (wide:
the window layers read as full), ``rotate_full`` (the full layers rotated
as the window layers are), ``low`` (router, softmax and LayerNorm in
bfloat16).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def _round_to(x, dtype):
    """float32 x rounded to ``dtype``'s precision and kept in float32
    (``reduce_precision``: a cast down and up again is dropped under
    XLA's excess precision)."""
    info = jnp.finfo(dtype)
    if info.bits == 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _low(x, low):
    """x at bfloat16's precision where the traced switch says so."""
    return jnp.where(low, _round_to(x, jnp.bfloat16), x)


def _layer_norm(x, w, eps, low):
    x = _low(x, low)
    c = x - _low(jnp.mean(x, -1, keepdims=True), low)
    var = _low(jnp.mean(jnp.square(c), -1, keepdims=True), low)
    return _low(c / jnp.sqrt(var + eps), low) * w.astype(F32)


def rope_interleaved(t, theta):
    """t [T, H, D], position = row: pair ``i`` is ``(t[2i], t[2i + 1])``
    at angle ``pos * theta**(-2i/D)`` (GPT-J's layout, ``rope_gptj``)."""
    T, H, D = t.shape
    freq = theta ** (-jnp.arange(D // 2, dtype=F32) / (D // 2))
    ang = jnp.arange(T, dtype=F32)[:, None, None] * freq        # [T, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # a pair's halves picked out and put back by 0/1 matrices (exact under
    # "highest"): a minor axis of two would be padded to a tile on the chip
    even = jax.nn.one_hot(2 * jnp.arange(D // 2), D, dtype=F32)  # [D/2, D]
    odd = jax.nn.one_hot(2 * jnp.arange(D // 2) + 1, D, dtype=F32)
    a, b = t @ even.T, t @ odd.T
    return (a * cos - b * sin) @ even + (b * cos + a * sin) @ odd


def _attention(p, x, m, window, rotate, low, block):
    """x [T, d] (normed) of ONE sequence.  ``window``: a traced count of
    keys (the query's own included) or None: all; ``rotate``: traced."""
    T = x.shape[0]
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    q = (x @ p["q_w"].astype(F32)).reshape(T, hq, dh)
    k = (x @ p["k_w"].astype(F32)).reshape(T, hkv, dh)
    v = (x @ p["v_w"].astype(F32)).reshape(T, hkv, dh)
    q = jnp.where(rotate, rope_interleaved(q, m["rope_theta"]), q)
    k = jnp.where(rotate, rope_interleaved(k, m["rope_theta"]), k)
    block = min(block, T)
    pad = -T % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, hkv, hq // hkv, dh)
    at = jnp.arange(T)

    def rows(args):
        q_i, first = args                       # [block, Hkv, rep, D]
        t = first + jnp.arange(block)
        s = jnp.einsum("bgrd,tgd->grbt", q_i, k) / jnp.sqrt(F32(dh))
        ok = at[None, :] <= t[:, None]
        if window is not None:
            ok &= at[None, :] > t[:, None] - window
        s = jnp.where(ok[None, None], _low(s, low), -jnp.inf)
        w = _low(jax.nn.softmax(s, axis=-1), low)
        return jnp.einsum("grbt,tgv->bgrv", w, v).reshape(block, hq * dh)

    out = jax.lax.map(rows, (qb, jnp.arange(qb.shape[0]) * block))
    return out.reshape(-1, hq * dh)[:T] @ p["o_w"].astype(F32)


def _swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w.astype(F32)) * (x @ up_w.astype(F32))) \
        @ down_w.astype(F32)


def _shared(p, x, m):
    """The mean of the shared experts, one at a time: expert ``j`` is
    columns ``j * f ..`` of the program's ``shared_*`` matrices."""
    n, f = m["num_shared_experts"], m["intermediate_size"]

    def one(acc, j):
        gate_w, up_w = (jax.lax.dynamic_slice_in_dim(p[k], j * f, f, axis=1)
                        for k in ("shared_gate_w", "shared_up_w"))
        down_w = jax.lax.dynamic_slice_in_dim(p["shared_down_w"], j * f, f,
                                              axis=0)
        return acc + _swiglu(x, gate_w, up_w, down_w), None

    return jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n))[0] / n


def _routed(p, stacked, layer, x, m, low):
    """x [T, d] (normed): this share's part of the routed sum.
    ``stacked``: every layer's held experts in one row (no layer is
    sliced out); ``layer`` says which layer's."""
    e_all, k = m["num_experts"], m["num_experts_per_tok"]
    first, count = m.get("experts_held") or (0, e_all)
    scores = _low(jax.nn.sigmoid(_low(
        _low(x, low) @ _low(p["router_w"].astype(F32), low), low)), low)
    _, chosen = jax.lax.top_k(scores, k)
    mask = jnp.sum(jax.nn.one_hot(chosen, e_all, dtype=F32), axis=-2)
    gates = scores * mask
    if m.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, -1, keepdims=True)

    def expert(acc, e):
        # one expert's matrices are raised to float32 inside the loop
        ws = (jax.lax.dynamic_index_in_dim(stacked[k], layer * count + e,
                                           keepdims=False)
              for k in _EXPERT_LEAVES)
        gate = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1,
                                            keepdims=False)
        return acc + gate[:, None] * _swiglu(x, *ws), None

    return jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(count))[0]


def cohere2_moe_logits(params, tokens, m: dict, act_dtype=F32,
                       round_acts=False, window=None, rotate_full=False,
                       low=False, block: int = 256, parallel: bool = True):
    """tokens [B, T] -> float32 logits [B, T, V].  ``m``: the
    configuration's values under the source's keys, + ``experts_held``.
    The switches (module docstring; each may be traced): ``round_acts``
    rounds the residual stream to ``act_dtype`` from the embedding on and
    after every layer; ``window`` (None: ``sliding_window``) is the window
    layers'; ``rotate_full`` rotates the full layers too; ``low`` computes
    router, softmax and LayerNorm at bfloat16's precision.  ``parallel``
    false (static; the tests') is the SEQUENTIAL block on the same norm
    weight, ``x <- x + attn(LN x); x <- x + ffn(LN x)``."""
    eps = m["layer_norm_eps"]
    if window is None:
        window = m["sliding_window"]

    def rounded(x):
        return jnp.where(round_acts, _round_to(x, act_dtype), x)

    stacked = {k: params["experts"][k].reshape(
        (-1,) + params["experts"][k].shape[2:]) for k in _EXPERT_LEAVES}

    def one(seq):
        x = rounded(params["wte"][seq].astype(F32))
        seen = {"full": 0, "window": 0}
        for layer, kind in enumerate(m["layer_types"]):
            name = "window" if kind == "sliding_attention" else "full"
            # a layer's weights wait for its input: their float32 copies
            # are then made a layer at a time, not all at once
            p, x = jax.lax.optimization_barrier(
                ({k: v[seen[name]] for k, v in params[name].items()}, x))
            h = _layer_norm(x, p["ln"], eps, low)
            attn = _attention(
                p, h, m, window if name == "window" else None,
                True if name == "window" else rotate_full, low, block)
            if not parallel:
                x = x + attn
                h = _layer_norm(x, p["ln"], eps, low)
                attn = 0.0
            ffn = _routed(p, stacked, layer, h, m, low) + _shared(p, h, m)
            x = rounded(x + attn + ffn)
            seen[name] += 1
        wte, x = jax.lax.optimization_barrier((params["wte"], x))
        return m.get("logit_scale", 1) * (
            _layer_norm(x, params["norm_f"], eps, low) @ wte.astype(F32).T)

    return jax.lax.map(one, tokens)
