"""Plain reference for Moonshot Kimi Linear (HF ``model_type: kimi_linear``;
the row of ``model-configs/architectures.jsonl`` and the family's report,
arXiv:2510.26692, are the sources there are): the forward pass in
straightforward jax.numpy and float32.  No cache, no kernel, no page, no
chunk, no absorbed matrices, no sort, no dispatch: the KDA recurrence runs
TOKEN BY TOKEN (a ``lax.scan`` over the equations below), the latent
attention is the expanded form with every query seeing the whole sequence
under a mask, and EVERY held expert runs on EVERY token with the routing as
a mask.  It reads the program's parameter tree
(``deepspeed_tpu/models/kimi_linear.py``: the names are the program's) and
nothing else of it.  Weights arrive in the dtype they are served in and are
raised to float32 as they are used.  Callers run it under
``jax.default_matmul_precision("highest")``.

    x <- x + mixer(RMSNorm(x)); x <- x + ffn(RMSNorm(x));
    logits = RMSNorm(x) W_head          (eps rms_norm_eps, no bias, untied)

Layers are numbered from 1; ``linear_attn_config.kda_layers`` take the KDA
mixer, ``full_attn_layers`` the latent one.

KDA (``H`` heads, keys and values ``head_dim`` wide): ``[q~ | k~ | v] =
SiLU(conv(h W_qkv))``, a depthwise causal convolution of
``short_conv_kernel_size`` over time, zeros before the sequence, no bias;
``q = q~ / |q~| * head_dim**-0.5``, ``k = k~ / |k~|`` a head; ``g = -exp(
A_log) * softplus(W_f2 (W_f1 h) + dt_bias)``, ``a = exp(g)``; ``b =
sigmoid(W_b h)``; per head, float32, from ``S = 0``:

    S' = a_t (rowwise) S_{t-1};  u = b_t (v_t - S'^T k_t);
    S_t = S' + k_t u^T;          o_t = S_t^T q_t

out ``W_o [RMSNorm_head(o) * sigmoid(W_g2 (W_g1 h))]``, the norm's weight
one vector for all heads.

Latent attention (``H`` heads): ``q = h W_q`` -> a head ``[q_nope ; q_pe]``;
``[c_kv ; k_pe] = h W_kva``; ``c_kv <- RMSNorm(c_kv)``; ``k_pe`` one for all
heads; NOTHING is rotated (``mla_use_nope``); ``k_nope_h = c_kv W_UK_h``,
``v_h = c_kv W_UV_h``.  Scores ``(q_nope_h . k_nope_h,j + q_pe_h . k_pe_j)
* (nope + pe)**-0.5`` over ``j <= t``, softmax, ``o = sum p v_h``, ``W_o``.

FFN: the first ``first_k_dense_replace`` layers dense ``down(silu(gate x) *
up x)``; the others ``s = sigmoid(x W_r)`` over all experts, the
``num_experts_per_token`` largest of ``s + e_score_correction_bias``
(``router_bias``; one group: no limit), weights ``s / sum *
routed_scaling_factor``, SwiGLU experts, plus the shared SwiGLU expert on
every token.

The share (``m["experts_held"] = [first, count]``; the vocabulary slice is
the parameter tree's own width): the router ranges over ALL experts, the sum
runs over the held ones only, the shared expert is whole, and that goes on
to the next layer, exactly as the program does.  Nothing stands in for the
other chips.

So that 4,352 positions at the published widths fit beside the engine, the
wide intermediates are computed in blocks: attention a block of query rows
at a time, the dense FFN and the head a slice of their width at a time, the
experts one at a time.

Departures from the published description, each for a stated reason (the
configuration file's ``assumed`` says the same):
* ``W_q``, ``W_k``, ``W_v`` of a KDA layer and their three convolutions are
  held side by side in one leaf each (``qkv_w``, ``conv_w``): a
  concatenation of the file;
* the two low-rank gates have rank ``linear_attn_config.head_dim`` (the
  family's code; the source gives no rank), the convolutions no bias and
  SiLU after them, ``l2norm`` an eps of 1e-6 under the root;
* the router's scores are float32 from float32 activations, and its
  selection bias is not the checkpoint's but the source's balancing rule
  run on the seed's weights (:func:`balance_router_bias`);
* ``kv_b_proj`` is held as ``k_b_w`` [H, nope, C] and ``v_b_w`` [H, C, v]:
  a split of the file.

The readings that must come out as NOT correct
(``lib/kimi_linear_family.py``) are switches of this same forward:
``round_acts`` (traced: the residual stream rounded to ``act_dtype``) and
``state_dtype`` (the recurrence's state kept in another precision).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .nemotron_h_reference import _balanced_bias

F32 = jnp.float32
_EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def _round_to(x, dtype):
    """float32 x rounded to ``dtype``'s precision and kept in float32.
    ``reduce_precision`` and not a pair of casts: under XLA's excess
    precision a cast down and up again is dropped."""
    info = jnp.finfo(dtype)
    if info.bits == 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def mixers(m: dict):
    """Each layer's mixer kind, in order (layers numbered from 1)."""
    kda = set(m["linear_attn_config"]["kda_layers"])
    return ["kda" if layer in kda else "mla"
            for layer in range(1, m["num_hidden_layers"] + 1)]


def recurrence(q, k, v, g, b, state_dtype=F32, h0=None, live=None):
    """The delta rule token by token.  q, k, g [T, H, dk], v [T, H, dv], b
    [T, H]; ``h0`` [H, dk, dv] (default zeros); ``live`` [T] bool: a
    position that is not live leaves the state as it is.  The state is
    kept in ``state_dtype`` between steps.  Returns (final state, o [T, H,
    dv])."""
    T, H, dk = k.shape
    if h0 is None:
        h0 = jnp.zeros((H, dk, v.shape[-1]), F32)
    if live is None:
        live = jnp.ones((T,), bool)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t, on = xs
        decayed = s * jnp.exp(g_t)[..., None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
        new = _round_to(decayed + k_t[..., None] * u[:, None, :],
                        state_dtype)
        return (jnp.where(on, new, s),
                jnp.einsum("hkv,hk->hv", new, q_t))

    return jax.lax.scan(step, _round_to(h0.astype(F32), state_dtype),
                        (q, k, v, g, b, live))


def _kda(p, x, m, live):
    """x [T, d] (normed) of ONE sequence -> (the mixer's output [T, d],
    the state after the last live position [H, dk, dv])."""
    lin = m["linear_attn_config"]
    H, dk, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    T = x.shape[0]
    qkv = x @ p["qkv_w"].astype(F32)
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    conv = jax.nn.silu(sum(padded[j:j + T] * w[j] for j in range(K)))
    q, k, v = (t.reshape(T, H, dk) for t in jnp.split(conv, 3, axis=-1))

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    f = (x @ p["f_a_w"].astype(F32)) @ p["f_b_w"].astype(F32) \
        + p["dt_bias"].astype(F32)
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] \
        * jax.nn.softplus(f).reshape(T, H, dk)
    b = jax.nn.sigmoid(x @ p["b_w"].astype(F32))
    final, o = recurrence(unit(q) * dk ** -0.5, unit(k), v, g, b, live=live)
    gate = jax.nn.sigmoid((x @ p["g_a_w"].astype(F32))
                          @ p["g_b_w"].astype(F32)).reshape(T, H, dk)
    y = _rms(o, p["o_norm"], m["rms_norm_eps"]) * gate
    return y.reshape(T, H * dk) @ p["o_w"].astype(F32), final


def _attention(p, x, m, block):
    """x [T, d] (normed) of ONE sequence, the expanded form."""
    T = x.shape[0]
    H, nope, pe = (m["num_attention_heads"], m["qk_nope_head_dim"],
                   m["qk_rope_head_dim"])
    C = m["kv_lora_rank"]
    q = (x @ p["q_w"].astype(F32)).reshape(T, H, nope + pe)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv = x @ p["kv_a_w"].astype(F32)
    c_kv = _rms(kv[:, :C], p["kv_a_norm"], m["rms_norm_eps"])
    k_pe = kv[:, C:]                                            # [T, pe]
    k_nope = jnp.einsum("tc,hnc->thn", c_kv, p["k_b_w"].astype(F32))
    v = jnp.einsum("tc,hcv->thv", c_kv, p["v_b_w"].astype(F32))
    scale = (nope + pe) ** -0.5
    block = min(block, T)
    pad = -T % block

    def blocks(t):
        return jnp.pad(t, ((0, pad), (0, 0), (0, 0))).reshape(
            (-1, block) + t.shape[1:])

    at = jnp.arange(T)

    def rows(args):
        qn, qp, first = args                    # [block, H, .]
        t = first + jnp.arange(block)
        s = (jnp.einsum("bhn,thn->hbt", qn, k_nope)
             + jnp.einsum("bhr,tr->hbt", qp, k_pe)) * scale
        s = jnp.where((at[None, :] <= t[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hbt,thv->bhv", jax.nn.softmax(s, axis=-1),
                          v).reshape(block, -1)

    n = (T + pad) // block
    out = jax.lax.map(rows, (blocks(q_nope), blocks(q_pe),
                             jnp.arange(n) * block))
    return out.reshape(n * block, -1)[:T] @ p["o_w"].astype(F32)


def _swiglu(x, gate_w, up_w, down_w, slices: int = 1):
    """``down(silu(gate x) * up x)``, a slice of the intermediate width
    at a time (cut inside the loop, so that its float32 copy is made there
    and not of the whole matrix before it)."""
    width = gate_w.shape[-1]
    n = slices if width % slices == 0 else 1
    w = width // n

    def part(acc, j):
        g, u = (jax.lax.dynamic_slice_in_dim(t, j * w, w, axis=1).astype(F32)
                for t in (gate_w, up_w))
        d = jax.lax.dynamic_slice_in_dim(down_w, j * w, w, axis=0)
        return acc + (jax.nn.silu(x @ g) * (x @ u)) @ d.astype(F32), None

    return jax.lax.scan(part, jnp.zeros_like(x), jnp.arange(n))[0]


def expert_layer(p, stacked, index, x, m, balance=None, used=None):
    """x [T, d] (normed): this share's part of the routed sum + the shared
    expert.  ``stacked``: every expert layer's held experts in one row (no
    layer is sliced out); ``index`` says which layer's.  ``balance``:
    (steps, rate) to put the source's balanced bias in the place of the
    layer's own (:func:`balance_router_bias`); the bias that was used is
    appended to ``used``."""
    e_all, k = m["num_experts"], m["num_experts_per_token"]
    first, count = m.get("experts_held") or (0, e_all)
    scores = jax.nn.sigmoid(x @ p["router_w"].astype(F32))      # [T, E]
    bias = p["router_bias"]
    if balance is not None:
        bias = _balanced_bias(scores, bias, k, *balance)
    if used is not None:
        used.append(bias)
    _, chosen = jax.lax.top_k(scores + bias.astype(F32), k)
    mask = jnp.sum(jax.nn.one_hot(chosen, e_all, dtype=F32), axis=-2)
    gates = scores * mask
    if m.get("moe_renormalize", True):
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * m["routed_scaling_factor"]

    def expert(acc, e):
        # one expert's matrices are raised to float32 inside the loop
        gate_w, up_w, down_w = (
            jax.lax.dynamic_index_in_dim(stacked[k], index * count + e,
                                         keepdims=False)
            for k in _EXPERT_LEAVES)
        gate = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1,
                                            keepdims=False)
        return acc + gate[:, None] * _swiglu(x, gate_w, up_w, down_w), None

    out = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(count))[0]
    return out + _swiglu(x, p["shared_gate_w"], p["shared_up_w"],
                         p["shared_down_w"])


def _head(x, lm_head, slices: int = 4):
    """x @ W_head, a slice of the vocabulary at a time."""
    V = lm_head.shape[-1]
    n = slices if V % slices == 0 else 1
    parts = jax.lax.map(
        lambda j: x @ jax.lax.dynamic_slice_in_dim(
            lm_head, j * (V // n), V // n, axis=1).astype(F32),
        jnp.arange(n))                                      # [n, T, V/n]
    return parts.transpose(1, 0, 2).reshape(x.shape[0], V)


def balance_router_bias(params, tokens, m: dict, steps: int = 48,
                        rate: float = 0.02):
    """``e_score_correction_bias`` of every expert layer [layers, E] as the
    source's training leaves it, for weights drawn from a seed: the rule of
    ``lib/nemotron_h_reference.py`` (its ``_balanced_bias``: auxiliary-
    loss-free load balancing, imported, not repeated) in the order of the
    forward pass over ONE sequence ``tokens`` [1, T], each layer balanced
    on the scores of the input it gets once the layers before it are
    balanced.  A trained checkpoint ships a bias that has been through
    this; with the bias 0 a seed's router sends its tokens to a few
    experts (the busiest held one 4.4 x the mean, read on the chip, PR
    52).  Nothing of the program runs here."""
    return _forward(params, tokens, m, balance=(steps, rate))[2][0]


def kimi_linear_logits(params, tokens, m: dict, act_dtype=F32,
                       round_acts=False, length=None, block: int = 128):
    """tokens [B, T] -> (float32 logits [B, T, V], the KDA layers' states
    after ``length`` positions [B, kda layers, H, dk, dv]; default: after
    all).  ``m``: the configuration's values under the source's keys, +
    ``experts_held``.  ``round_acts`` (may be traced) rounds the residual
    stream to ``act_dtype`` from the embedding on and after every layer."""
    return _forward(params, tokens, m, act_dtype, round_acts, length,
                    block)[:2]


def _forward(params, tokens, m, act_dtype=F32, round_acts=False,
             length=None, block: int = 128, balance=None):
    """(logits, the KDA layers' states, the expert layers' biases as
    used), each with the sequences' axis first."""
    eps, dense = m["rms_norm_eps"], m["first_k_dense_replace"]
    T = tokens.shape[1]
    live = jnp.arange(T) < (T if length is None else length)

    def rounded(x):
        return jnp.where(round_acts, _round_to(x, act_dtype), x)

    stacked = {k: params["moe"][k].reshape(
        (-1,) + params["moe"][k].shape[2:]) for k in _EXPERT_LEAVES} \
        if "moe" in params else None

    def leaves(kind, i, but=()):
        return {k: v[i] for k, v in params[kind].items() if k not in but}

    def one(seq):
        x = rounded(params["wte"][seq].astype(F32))
        seen, states, biases = {"kda": 0, "mla": 0}, [], []
        for layer, kind in enumerate(mixers(m)):
            # a layer's weights wait for its input: their float32 copies
            # are then made a layer at a time, not all at once
            p, x = jax.lax.optimization_barrier(
                (leaves(kind, seen[kind]), x))
            seen[kind] += 1
            h = _rms(x, p["ln1"], eps)
            if kind == "kda":
                out, final = _kda(p, h, m, live)
                states.append(final)
            else:
                out = _attention(p, h, m, block)
            x = x + out
            kind, i = ("dense", layer) if layer < dense \
                else ("moe", layer - dense)
            p, x = jax.lax.optimization_barrier(
                (leaves(kind, i, () if kind == "dense" else _EXPERT_LEAVES),
                 x))
            h = _rms(x, p["ln2"], eps)
            x = rounded(x + (
                _swiglu(h, p["gate_w"], p["up_w"], p["down_w"], slices=4)
                if kind == "dense" else expert_layer(p, stacked, i, h, m,
                                                     balance, biases)))
        head, x = jax.lax.optimization_barrier((params["lm_head"], x))
        return (_head(_rms(x, params["norm_f"], eps), head),
                jnp.stack(states), jnp.stack(biases))

    return jax.lax.map(one, tokens)
