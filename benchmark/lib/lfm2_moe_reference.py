"""Plain reference for Liquid AI LFM2 MoE (HF ``model_type: lfm2_moe``; the
row ``LFM2-24B-A2B`` of ``model-configs/architectures.jsonl`` and HF
``modeling_lfm2_moe.py`` are the sources there are): the forward pass in
straightforward jax.numpy and float32.  No cache, no kernel, no page, no
chunk, no batching of experts: the short convolution is an explicit sum over
``conv_L_cache`` shifted copies of its input, attention is a full softmax
with every query seeing the whole sequence under a mask, the expert layer is
a loop over ALL experts with a dense mask of who chose whom.  It reads the
program's parameter tree (``deepspeed_tpu/models/lfm2_moe.py``: the names
are the program's) and nothing else of it.  Weights arrive in the dtype they
are served in and are raised to float32 as they are used.  Callers run it
under ``jax.default_matmul_precision("highest")``.

    h <- x + mixer(RMSNorm(x));  x <- h + ffn(RMSNorm(h))
    logits = RMSNorm(x) E^T        (eps norm_eps, no bias, the head tied)
    ffn(x) = W_2 (SiLU(W_1 x) * W_3 x): dense in the first
    ``num_dense_layers`` layers, the expert layer after them

``layer_types`` says which mixer a layer takes.

``conv`` (K = ``conv_L_cache`` taps): ``[B | C | u] = x W_in`` (thirds, in
that order); ``z = B * u``; ``c_t = sum_j w_j z_{t-K+1+j}`` a channel, zeros
before the sequence, no activation; out ``W_out (C * c)``.

``full_attention`` (``Hq`` heads on ``Hkv`` key heads of ``D``): ``q, k``
RMS-normed a head (weights [D]) and then rotated (rotate-half RoPE over all
``D`` at ``rope_theta``); query head ``h`` reads key head ``h // (Hq /
Hkv)``; scores ``q . k * D**-0.5`` over ``j <= t``, softmax, ``W_out``.

expert layer: ``s = sigmoid(x W_r)`` over all ``E``; the ``k`` largest of
``s + expert_bias``; weights ``s[chosen] / (sum + 1e-6)`` times
``routed_scaling_factor``; a SwiGLU an expert.

So that 1,280 positions at the published widths fit beside the engine, the
wide intermediates are computed in blocks: attention a block of query rows
at a time, the dense feed-forward and the head a slice of their width at a
time, the experts one at a time.

Departures from HF ``modeling_lfm2_moe.py``, each for a stated reason (the
configuration file's ``assumed`` says the same): the experts' three
matrices are the program's ``gate_w`` / ``up_w`` / ``down_w`` (HF ``w1`` /
``w3`` / ``w2``), input-major; the final norm is HF's ``embedding_norm``,
applied to the OUTPUT; ``expert_bias`` is ``router_bias``; the depthwise
filter is ``conv_w`` [K, d], tap ``j`` a row (torch holds ``[d, 1, K]``).

The readings that must come out as NOT correct (``lib/lfm2_moe_family.py``)
are switches of this same forward, each may be traced: ``reverse_taps`` (the
filter read newest-first), ``use_bias`` false (the experts chosen by score
alone) and ``round_acts`` (the residual stream rounded to ``act_dtype`` from
the embedding on and after every layer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kimi_linear_reference import _rms, _round_to, _swiglu

F32 = jnp.float32
_KINDS = {"conv": "conv", "full_attention": "full"}
_EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def kinds(m: dict):
    """(mixer 'conv' | 'full', feed-forward 'dense' | 'moe') of each layer,
    in order."""
    return [(_KINDS[t], "dense" if i < m["num_dense_layers"] else "moe")
            for i, t in enumerate(m["layer_types"])]


def short_conv(p, x, m, reverse_taps=False):
    """x [T, d] (normed) of ONE sequence -> (the mixer's output [T, d],
    ``z = B * u`` [T, d]: what a request keeps the last rows of)."""
    K, T = m["conv_L_cache"], x.shape[0]
    b, c, u = jnp.split(x @ p["in_w"].astype(F32), 3, axis=-1)
    z = b * u
    w = p["conv_w"].astype(F32)
    w = jnp.where(reverse_taps, w[::-1], w)
    padded = jnp.pad(z, ((K - 1, 0), (0, 0)))
    conv = sum(padded[j:j + T] * w[j] for j in range(K))
    return (c * conv) @ p["out_w"].astype(F32), z


def _rope(x, theta: float):
    """x [T, H, D] at positions 0 .. T-1: pair i is (x[i], x[i + D/2])."""
    T, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, m, block):
    """x [T, d] (normed) of ONE sequence: a full softmax a block of query
    rows at a time."""
    T, d = x.shape
    Hq, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    D = m.get("head_dim") or d // Hq
    eps = m["norm_eps"]
    theta = float(m["rope_parameters"]["rope_theta"])
    q = (x @ p["q_w"].astype(F32)).reshape(T, Hq, D)
    k = (x @ p["k_w"].astype(F32)).reshape(T, Hkv, D)
    v = (x @ p["v_w"].astype(F32)).reshape(T, Hkv, D)
    q = _rope(_rms(q, p["q_norm"], eps), theta)
    k = _rope(_rms(k, p["k_norm"], eps), theta)
    k, v = (jnp.repeat(t, Hq // Hkv, axis=1) for t in (k, v))
    block = min(block, T)
    pad = -T % block
    at = jnp.arange(T)

    def rows(args):
        qb, first = args                        # [block, Hq, D]
        t = first + jnp.arange(block)
        s = jnp.einsum("bhd,thd->hbt", qb, k) * D ** -0.5
        s = jnp.where((at[None, :] <= t[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hbt,thd->bhd", jax.nn.softmax(s, axis=-1),
                          v).reshape(block, -1)

    n = (T + pad) // block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n, block, Hq, D)
    out = jax.lax.map(rows, (qb, jnp.arange(n) * block))
    return out.reshape(n * block, -1)[:T] @ p["o_w"].astype(F32)


def gates(p, x, m, use_bias=True):
    """x [T, d] (normed) -> every expert's weight on every token [T, E]
    float32, 0 where the token did not choose it."""
    e, k = m["num_experts"], m["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ p["router_w"].astype(F32))
    bias = p["router_bias"].astype(F32) if m.get("use_expert_bias", True) \
        else jnp.zeros((e,), F32)
    _, chosen = jax.lax.top_k(scores + jnp.where(use_bias, bias, 0.0), k)
    picked = scores * jnp.sum(jax.nn.one_hot(chosen, e, dtype=F32), axis=-2)
    if m.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
    return picked * float(m.get("routed_scaling_factor", 1.0))


def expert_layer(p, stacked, index, x, m, use_bias=True):
    """x [T, d] (normed) -> the expert layer's output: a loop over ALL
    experts, each on every token, under the mask of :func:`gates`.
    ``stacked``: every expert layer's experts in one row (no layer is
    sliced out); ``index`` says which layer's."""
    e = m["num_experts"]
    g = gates(p, x, m, use_bias)

    def expert(acc, j):
        # one expert's matrices are raised to float32 inside the loop
        gate_w, up_w, down_w = (
            jax.lax.dynamic_index_in_dim(stacked[k], index * e + j,
                                         keepdims=False).astype(F32)
            for k in _EXPERT_LEAVES)
        y = (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w
        return acc + jax.lax.dynamic_index_in_dim(
            g, j, axis=1, keepdims=True) * y, None

    return jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(e))[0]


def _head(x, wte, slices: int = 4):
    """x @ E^T, a slice of the vocabulary at a time."""
    V = wte.shape[0]
    n = slices if V % slices == 0 else 1
    parts = jax.lax.map(
        lambda j: x @ jax.lax.dynamic_slice_in_dim(
            wte, j * (V // n), V // n, axis=0).astype(F32).T,
        jnp.arange(n))                                      # [n, T, V/n]
    return parts.transpose(1, 0, 2).reshape(x.shape[0], V)


def lfm2_moe_logits(params, tokens, m: dict, *, reverse_taps=False,
                    use_bias=True, round_acts=False,
                    act_dtype=jnp.float8_e5m2, logit_rows=None,
                    block: int = 128):
    """tokens [B, T] -> (float32 logits [B, T, V], every conv layer's ``z``
    [B, conv layers, T, d]).  ``m``: the configuration's values under the
    source's keys.  The switches are the module docstring's.
    ``logit_rows`` = (first (may be traced), count): the head on that span
    of rows only, logits [B, count, V]."""
    eps = m["norm_eps"]

    def rounded(x):
        return jnp.where(round_acts, _round_to(x, act_dtype), x)

    def leaves(kind, i):
        return {k: v[i] for k, v in params[kind].items()
                if isinstance(v, (tuple, list))}

    stacked = {k: params["moe"][k].reshape(
        (-1,) + params["moe"][k].shape[2:]) for k in _EXPERT_LEAVES} \
        if "moe" in params else None

    def one(seq):
        x = rounded(params["wte"][seq].astype(F32))
        seen = {"conv": 0, "full": 0, "dense": 0, "moe": 0}
        zs = []
        for kind, ffn in kinds(m):
            # a layer's weights wait for its input: their float32 copies
            # are then made a layer at a time, not all at once
            p, x = jax.lax.optimization_barrier(
                (leaves(kind, seen[kind]), x))
            h = _rms(x, p["ln1"], eps)
            if kind == "conv":
                out, z = short_conv(p, h, m, reverse_taps)
                zs.append(z)
            else:
                out = _attention(p, h, m, block)
            x = x + out
            p, x = jax.lax.optimization_barrier((leaves(ffn, seen[ffn]), x))
            h = _rms(x, p["ln2"], eps)
            if ffn == "dense":
                x = x + _swiglu(h, p["gate_w"], p["up_w"], p["down_w"],
                                slices=4)
            else:
                x = x + expert_layer(p, stacked, seen[ffn], h, m, use_bias)
            x = rounded(x)
            seen[kind] += 1
            seen[ffn] += 1
        wte, x = jax.lax.optimization_barrier((params["wte"], x))
        if logit_rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, logit_rows[0],
                                             logit_rows[1], axis=0)
        return (_head(_rms(x, params["norm_f"], eps), wte),
                jnp.stack(zs) if zs else jnp.zeros((0,) + x.shape, F32))

    return jax.lax.map(one, tokens)
