"""Plain reference for AllenAI Olmo Hybrid (HF ``model_type: olmo_hybrid``;
the row ``Olmo-Hybrid-7B`` of ``model-configs/architectures.jsonl`` and the
Gated DeltaNet report, arXiv:2412.06464, are the sources there are): the
forward pass in straightforward jax.numpy and float32.  No cache, no
kernel, no page, no chunk: the delta rule runs TOKEN BY TOKEN (a
``lax.scan`` over the equations below, the state BY HEAD ``[H, dk, dv]``,
never in the program's layout at rest), attention is a full softmax with
every query seeing the whole sequence under a mask.  It reads the program's
parameter tree (``deepspeed_tpu/models/olmo_hybrid.py``: the names are the
program's) and nothing else of it.  Weights arrive in the dtype they are
served in and are raised to float32 as they are used.  Callers run it under
``jax.default_matmul_precision("highest")``.

    x <- x + RMSNorm(mixer(x));  x <- x + RMSNorm(mlp(x))
    logits = RMSNorm(x) W_head      (eps rms_norm_eps, no bias, untied)
    mlp(x) = W_down (SiLU(W_gate x) * W_up x)

``layer_types`` says which mixer a layer takes.

``linear_attention`` (``H`` heads, keys ``dk``, values ``dv``): ``[q~ | k~ |
v~] = SiLU(conv(x W_qkv))``, depthwise causal convolutions of
``linear_conv_kernel_dim`` over time, zeros before the sequence, no bias;
``q = q~ / |q~| * dk**-0.5``, ``k = k~ / |k~|`` a head; ``g = -exp(A_log) *
softplus(W_a x + dt_bias)`` ONE a head; ``b = 2 sigmoid(W_b x)``
(``linear_allow_neg_eigval``; the switch ``step_factor`` is this 2); per
head, float32, from ``S = 0``:

    S' = exp(g_t) S_{t-1};  u = b_t (v_t - S'^T k_t);
    S_t = S' + k_t u^T;     o_t = S_t^T q_t

out ``W_o [RMSNorm_head(o) * SiLU(W_g x)]``, the norm's weight one vector
of ``dv``.

``full_attention`` (``H`` heads of ``hidden / H``): ``q, k = RMSNorm(W_q
x), RMSNorm(W_k x)`` over the whole projection, ``v = W_v x``; scores ``q_h
. k_h,j * head_dim**-0.5`` over ``j <= t``, softmax, ``W_o``.  Nothing is
rotated.

So that 1,280 positions at the published widths fit beside the engine, the
wide intermediates are computed in blocks: attention a block of query rows
at a time, the MLP and the head a slice of their width at a time.

Departures from the published description, each for a stated reason (the
configuration file's ``assumed`` says the same): ``W_q``, ``W_k``, ``W_v``
of a linear layer and their three convolutions are held side by side in one
leaf each (``qkv_w``, ``conv_w``): a concatenation of the file; ``l2norm``
an eps of 1e-6 under the root.

The readings that must come out as NOT correct
(``lib/olmo_hybrid_family.py``) are switches of this same forward:
``state_dtype`` (the recurrence's state kept in another precision) and
``step_factor`` (1: ``b`` without its factor 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kimi_linear_reference import _head, _rms, _round_to, _swiglu

F32 = jnp.float32
_KINDS = {"linear_attention": "gdn", "full_attention": "full"}


def kinds(m: dict):
    """Each layer's mixer kind ('gdn' | 'full'), in order."""
    return [_KINDS[t] for t in m["layer_types"]]


def recurrence(q, k, v, g, b, state_dtype=F32, h0=None, live=None):
    """The delta rule token by token, one decay a head.  q, k [T, H, dk],
    v [T, H, dv], g, b [T, H]; ``h0`` [H, dk, dv] (default zeros);
    ``live`` [T] bool: a position that is not live leaves the state as it
    is.  The state is kept in ``state_dtype`` between steps.  Returns
    (final state, o [T, H, dv])."""
    T, H, dk = k.shape
    if h0 is None:
        h0 = jnp.zeros((H, dk, v.shape[-1]), F32)
    if live is None:
        live = jnp.ones((T,), bool)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t, on = xs
        decayed = s * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
        new = _round_to(decayed + k_t[..., None] * u[:, None, :],
                        state_dtype)
        return (jnp.where(on, new, s),
                jnp.einsum("hkv,hk->hv", new, q_t))

    return jax.lax.scan(step, _round_to(h0.astype(F32), state_dtype),
                        (q, k, v, g, b, live))


def _gdn(p, x, m, live, state_dtype, step_factor):
    """x [T, d] of ONE sequence -> (the mixer's output [T, d], the state
    after the last live position [H, dk, dv])."""
    H, dk, dv, K = (m["linear_num_key_heads"], m["linear_key_head_dim"],
                    m["linear_value_head_dim"], m["linear_conv_kernel_dim"])
    T = x.shape[0]
    qkv = x @ p["qkv_w"].astype(F32)
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    conv = jax.nn.silu(sum(padded[j:j + T] * w[j] for j in range(K)))
    q = conv[:, :H * dk].reshape(T, H, dk)
    k = conv[:, H * dk:2 * H * dk].reshape(T, H, dk)
    v = conv[:, 2 * H * dk:].reshape(T, H, dv)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        x @ p["a_w"].astype(F32) + p["dt_bias"].astype(F32))
    b = step_factor * jax.nn.sigmoid(x @ p["b_w"].astype(F32))
    final, o = recurrence(unit(q) * dk ** -0.5, unit(k), v, g, b,
                          state_dtype=state_dtype, live=live)
    gate = jax.nn.silu(x @ p["g_w"].astype(F32)).reshape(T, H, dv)
    y = _rms(o, p["o_norm"], m["rms_norm_eps"]) * gate
    return y.reshape(T, H * dv) @ p["o_w"].astype(F32), final


def _attention(p, x, m, block):
    """x [T, d] of ONE sequence: a full softmax a block of query rows at
    a time.  (If the family's code rotates, the rotation of q and k goes
    after the two norms here.)"""
    T, d = x.shape
    H = m["num_attention_heads"]
    D = m.get("head_dim") or d // H
    eps = m["rms_norm_eps"]
    q = _rms(x @ p["q_w"].astype(F32), p["q_norm"], eps).reshape(T, H, D)
    k = _rms(x @ p["k_w"].astype(F32), p["k_norm"], eps).reshape(T, H, D)
    v = (x @ p["v_w"].astype(F32)).reshape(T, H, D)
    block = min(block, T)
    pad = -T % block
    at = jnp.arange(T)

    def rows(args):
        qb, first = args                        # [block, H, D]
        t = first + jnp.arange(block)
        s = jnp.einsum("bhd,thd->hbt", qb, k) * D ** -0.5
        s = jnp.where((at[None, :] <= t[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hbt,thd->bhd", jax.nn.softmax(s, axis=-1),
                          v).reshape(block, -1)

    n = (T + pad) // block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n, block, H, D)
    out = jax.lax.map(rows, (qb, jnp.arange(n) * block))
    return out.reshape(n * block, -1)[:T] @ p["o_w"].astype(F32)


def olmo_hybrid_logits(params, tokens, m: dict, state_dtype=F32,
                       step_factor=None, length=None, block: int = 128):
    """tokens [B, T] -> (float32 logits [B, T, V], the linear layers'
    states BY HEAD after ``length`` positions [B, linear layers, H, dk,
    dv]; default: after all).  ``m``: the configuration's values under the
    source's keys.  ``state_dtype``: the recurrence's state between steps;
    ``step_factor`` (may be traced): the factor on ``sigmoid(W_b x)``
    (default: 2 where ``linear_allow_neg_eigval``, else 1)."""
    eps = m["rms_norm_eps"]
    T = tokens.shape[1]
    live = jnp.arange(T) < (T if length is None else length)
    if step_factor is None:
        step_factor = 2.0 if m.get("linear_allow_neg_eigval", True) else 1.0

    def leaves(kind, i):
        return {k: v[i] for k, v in params[kind].items()}

    def one(seq):
        x = params["wte"][seq].astype(F32)
        seen, states = {"gdn": 0, "full": 0}, []
        for layer, kind in enumerate(kinds(m)):
            # a layer's weights wait for its input: their float32 copies
            # are then made a layer at a time, not all at once
            p, x = jax.lax.optimization_barrier(
                (leaves(kind, seen[kind]), x))
            seen[kind] += 1
            if kind == "gdn":
                out, final = _gdn(p, x, m, live, state_dtype, step_factor)
                states.append(final)
            else:
                out = _attention(p, x, m, block)
            x = x + _rms(out, p["ln1"], eps)
            p, x = jax.lax.optimization_barrier((leaves("ffn", layer), x))
            x = x + _rms(_swiglu(x, p["gate_w"], p["up_w"], p["down_w"],
                                 slices=4), p["ln2"], eps)
        head, x = jax.lax.optimization_barrier((params["lm_head"], x))
        return (_head(_rms(x, params["norm_f"], eps), head),
                jnp.stack(states))

    return jax.lax.map(one, tokens)
