"""Plain reference for Nemotron-H as NVIDIA-Nemotron-3-Super-120B-A12B
configures it (Nemotron-H, arXiv:2504.03624; Mamba-2, arXiv:2405.21060; HF
``modeling_nemotron_h.py``): the forward pass in straightforward jax.numpy
and float32.  No cache, no kernel, no chunked scan, no sort, no dispatch:
the recurrence is a ``lax.scan`` over TIME, one token a step, and EVERY held
expert runs on EVERY token with the routing as a mask.  It reads the
program's parameter tree (``deepspeed_tpu/models/nemotron_h.py``: the names
are the program's) and nothing else of it.  Weights arrive in the dtype
they are served in and are raised to float32 as they are used, one expert
at a time.  Callers run it under ``jax.default_matmul_precision("highest")``.

    x <- x + part(RMSNorm(x; eps))  for each character of the pattern;
    final RMSNorm; untied head.

``M``: ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv1d(xBC) + bias)``
(causal, depthwise); ``h_t = exp(dt A) h_{t-1} + dt x_t (outer) B_t``,
``y_t = h_t C_t + D x_t``; ``RMSNorm`` over groups of ``d_inner / n_groups``
of ``y * silu(z)``, times its weight; ``W_out``.  ``*``: grouped-key causal
softmax attention, no bias, nothing rotated.  ``E``: sigmoid scores over all
routed experts, the top ``num_experts_per_tok`` of score + bias, weights
renormalised and scaled; ``relu(u U_e)**2 D_e`` in the latent width between
``W_down`` and ``W_up``; a shared expert at the hidden width.

The share (``m["experts_held"] = [first, count]``; the vocabulary slice is
the parameter tree's own width): the router ranges over ALL experts, the
sum runs over the held ones only, and that part goes on to the next layer,
exactly as the program does.  Nothing stands in for the other chips.

Departures from the published description, each for a stated reason:
* nothing is rotated in attention: the family's published forward
  (``NemotronHAttention``) applies no positional embedding although the
  configuration carries ``rope_theta``.  Should the source turn out to
  rotate, the change is one line in ``_attention`` (q and k, rotate-half
  over every pair at ``rope_theta``, before the keys are repeated) and one
  in the program's ``_qkv``;
* the router's scores are float32 from float32 activations (HF computes
  the gate in float32 as well);
* the recurrent state is float32 (HF's cache holds it in the model's dtype
  unless told otherwise; the family's serving notes ask for float32).
  ``state_dtype`` rounds it after every step: bfloat16 is the reading that
  must come out as NOT correct (PERF.md section 6, PR 34);
* the multi-token-prediction module is not run (the base forward does not
  use it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def _round_to(x, dtype):
    """float32 x rounded to ``dtype``'s precision and kept in float32.
    ``reduce_precision`` and not a pair of casts: under XLA's excess
    precision a cast down and up again is dropped."""
    info = jnp.finfo(dtype)
    if info.bits == 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def recurrence(xs, dt, a, bm, cm, state_dtype=F32, length=None, h0=None):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t``, ``y_t = h_t
    C_t``, one token a step.  xs [B, T, H, P], dt [B, T, H], a [H], bm /
    cm [B, T, G, N] (head ``h`` reads group ``h // (H / G)``), ``h0`` the
    state to start from (zero).  Returns (the state after ``length``
    tokens [B, H, P, N], y [T, B, H, P]).  ``state_dtype`` rounds the
    state after every step."""
    B, T, H, P = xs.shape
    G, N = bm.shape[2], bm.shape[3]
    rep = H // G
    if length is None:
        length = T

    def step(carry, xs_t):
        h, kept = carry
        t, x_t, dt_t, b_t, c_t = xs_t
        b_h = jnp.repeat(b_t, rep, axis=1)                      # [B, H, N]
        c_h = jnp.repeat(c_t, rep, axis=1)
        h = h * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :]
        h = _round_to(h, state_dtype)
        kept = jnp.where(t < length, h, kept)
        return (h, kept), jnp.sum(h * c_h[:, :, None, :], axis=-1)

    time_major = (jnp.arange(T),) + tuple(
        jnp.moveaxis(t, 1, 0) for t in (xs, dt, bm, cm))
    zero = jnp.zeros((B, H, P, N), F32) if h0 is None else h0
    (_, final), y = jax.lax.scan(step, (zero, zero), time_major)
    return final, y


def _mamba(p, x, m, state_dtype, length):
    """x [B, T, d] (normed) -> (out [B, T, d], the state [B, H, P, N]
    after ``length`` tokens)."""
    B, T, _ = x.shape
    H, P = m["mamba_num_heads"], m["mamba_head_dim"]
    G, N, K = m["n_groups"], m["ssm_state_size"], m["conv_kernel"]
    d_inner = H * P
    zxbcdt = x @ p["in_w"].astype(F32)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + d_inner + 2 * G * N]
    dt = zxbcdt[..., -H:]
    # causal depthwise conv: output t sees inputs t-K+1 .. t
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    conv = sum(padded[:, j:j + T] * w[j] for j in range(K)) \
        + p["conv_b"].astype(F32)
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_inner].reshape(B, T, H, P)
    bm = xbc[..., d_inner:d_inner + G * N].reshape(B, T, G, N)
    cm = xbc[..., d_inner + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))         # [B, T, H]
    a = -jnp.exp(p["A_log"].astype(F32))                        # [H]
    final, y = recurrence(xs, dt, a, bm, cm, state_dtype, length)
    y = jnp.moveaxis(y, 0, 1) + p["D"].astype(F32)[:, None] * xs
    y = y.reshape(B, T, d_inner) * jax.nn.silu(z)
    g = y.reshape(B, T, G, d_inner // G)
    g = g / jnp.sqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                     + m["layer_norm_epsilon"])
    y = g.reshape(B, T, d_inner) * p["gate_norm"].astype(F32)
    return y @ p["out_w"].astype(F32), final


def _attention(p, x, m):
    B, T, _ = x.shape
    hq, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])

    def heads(t, n):
        return t.reshape(B, T, n, dh).transpose(0, 2, 1, 3)

    q = heads(x @ p["q_w"].astype(F32), hq)
    k = heads(x @ p["k_w"].astype(F32), hkv)
    v = heads(x @ p["v_w"].astype(F32), hkv)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(F32(dh))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return a.transpose(0, 2, 1, 3).reshape(B, T, hq * dh) \
        @ p["o_w"].astype(F32)


def _balanced_bias(scores, bias, k: int, steps: int, rate: float):
    """The source's auxiliary-loss-free load balancing on one layer's
    ``scores`` [..., E]: ``steps`` times, choose every token's ``k``
    experts under the bias as it stands (in its own dtype, as it is
    served) and lower the bias of every expert that got more than the mean
    load, raise it for those that got less, by ``rate`` times the relative
    excess and at most ``rate``.  Returns the bias [E] in its dtype."""
    e_all = scores.shape[-1]

    def step(_, b):
        _, chosen = jax.lax.top_k(scores + b.astype(bias.dtype).astype(F32),
                                  k)
        loads = jnp.zeros((e_all,), F32).at[chosen.reshape(-1)].add(1.0)
        return b - rate * jnp.clip(loads / jnp.mean(loads) - 1.0, -1.0, 1.0)

    return jax.lax.fori_loop(0, steps, step,
                             bias.astype(F32)).astype(bias.dtype)


def _experts(p, up_all, down_all, index, x, m, balance=None):
    """x [B, T, d] (normed).  ``up_all`` / ``down_all``: every ``E``
    layer's held experts in one row; ``index`` says which layer's.
    ``balance``: (steps, rate) to put :func:`_balanced_bias` in the place
    of the layer's own.  Returns (the layer's output, the bias it used)."""
    e_all, k = m["n_routed_experts"], m["num_experts_per_tok"]
    first, count = m.get("experts_held") or (0, e_all)
    scores = jax.nn.sigmoid(x @ p["router_w"].astype(F32))      # [B, T, E]
    bias = p["router_bias"]
    if balance is not None:
        bias = _balanced_bias(scores, bias, k, *balance)
    _, chosen = jax.lax.top_k(scores + bias.astype(F32), k)
    mask = jnp.sum(jax.nn.one_hot(chosen, e_all, dtype=F32), axis=-2)
    gates = scores * mask
    if m.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * m["routed_scaling_factor"]
    held = gates[..., first:first + count]
    u = x @ p["latent_down"].astype(F32)

    def expert(acc, xs):
        e, gate = xs
        up = jax.lax.dynamic_index_in_dim(
            up_all, index * count + e, keepdims=False).astype(F32)
        down = jax.lax.dynamic_index_in_dim(
            down_all, index * count + e, keepdims=False).astype(F32)
        y = jnp.square(jnp.maximum(u @ up, 0.0)) @ down
        return acc + gate[..., None] * y, None

    r, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                        (jnp.arange(count), jnp.moveaxis(held, -1, 0)))
    shared = jnp.square(jnp.maximum(x @ p["shared_up"].astype(F32), 0.0)) \
        @ p["shared_down"].astype(F32)
    return r @ p["latent_up"].astype(F32) + shared, bias


def nemotron_h_logits(params, tokens, m: dict, state_dtype=F32,
                      length=None, act_dtype=F32, round_acts=True):
    """tokens [B, T] -> (float32 logits [B, T, V], the ``M`` layers'
    recurrent states [Lm, B, H, P, N] after ``length`` tokens; None: after
    all T).  ``m``: the configuration's values under the source's keys, +
    ``experts_held``.  ``act_dtype`` rounds the residual stream, from the
    embedding on and after every layer (float32: not at all): the reading
    "activations one precision below bfloat16" of PERF.md section 6;
    ``round_acts`` (may be
    traced) switches that rounding off, so that one program gives the
    reference and the reading below it."""
    return _forward(params, tokens, m, state_dtype, length, act_dtype,
                    round_acts)[:2]


def balance_router_bias(params, tokens, m: dict, steps: int = 48,
                        rate: float = 0.02):
    """``e_score_correction_bias`` of every ``E`` layer [Le, E] as the
    source's training leaves it (:func:`_balanced_bias`), for weights
    drawn from a seed: in the order of the forward pass over ``tokens``
    [B, T], each layer balanced on the scores of the input it gets once
    the layers before it are balanced.  A trained checkpoint ships a bias
    that has been through this; with the bias 0 a seed's router sends
    most tokens to the same few experts (the ``relu2`` parts give every
    token's hidden state a common component).  Nothing of the program
    runs here."""
    return _forward(params, tokens, m, balance=(steps, rate))[2]


def _forward(params, tokens, m, state_dtype=F32, length=None,
             act_dtype=F32, round_acts=True, balance=None):
    """(logits, the mixers' states, the ``E`` layers' biases as used)."""
    if length is None:
        length = tokens.shape[1]
    eps = m["layer_norm_epsilon"]
    def rounded(x):
        return jnp.where(round_acts, _round_to(x, act_dtype), x)

    x = rounded(params["wte"][tokens].astype(F32))
    seen = {"M": 0, "E": 0, "*": 0}
    finals, biases = [], []
    if "moe" in params:
        moe = params["moe"]
        up_all = moe["up_w"].reshape((-1,) + moe["up_w"].shape[2:])
        down_all = moe["down_w"].reshape((-1,) + moe["down_w"].shape[2:])
    for kind in m["hybrid_override_pattern"]:
        i = seen[kind]
        seen[kind] += 1
        name = {"M": "mamba", "E": "moe", "*": "attn"}[kind]
        p = {k: v[i] for k, v in params[name].items()
             if k not in ("up_w", "down_w")}
        h = _rms(x, p["norm"], eps)
        if kind == "M":
            out, final = _mamba(p, h, m, state_dtype, length)
            finals.append(final)
        elif kind == "*":
            out = _attention(p, h, m)
        else:
            out, bias = _experts(p, up_all, down_all, i, h, m, balance)
            biases.append(bias)
        x = rounded(x + out)
    logits = _rms(x, params["norm_f"], eps) @ params["lm_head"].astype(F32)
    return (logits, jnp.stack(finals) if finals else None,
            jnp.stack(biases) if biases else None)
