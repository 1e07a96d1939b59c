"""Plain reference for SK Telecom A.X-K1 (HF ``model_type: axk1``; the row
of ``model-configs/architectures.jsonl`` is the source there is): the forward
pass in straightforward jax.numpy and float32, in the EXPANDED form of the
latent attention only.  No cache, no kernel, no page, no absorbed matrices,
no sort, no dispatch: every query sees the whole sequence under a mask, and
EVERY held expert runs on EVERY token with the routing as a mask.  It reads
the program's parameter tree (``deepspeed_tpu/models/axk1.py``: the names are
the program's) and nothing else of it; the YaRN frequencies and the softmax
scale are computed here, not imported.  Weights arrive in the dtype they are
served in and are raised to float32 as they are used.  Callers run it under
``jax.default_matmul_precision("highest")``.

    x <- x + attn(RMSNorm(x)); x <- x + ffn(RMSNorm(x));
    logits = RMSNorm(x) W_head          (eps rms_norm_eps, no bias, untied)

Attention (every layer, ``H`` heads): ``c_q = RMSNorm(x W_qa)``; ``q = c_q
W_qb`` -> a head ``[q_nope ; q_rope]``; ``[c_kv ; k_r] = x W_kva``; ``c_kv <-
RMSNorm(c_kv)``; ``k_rope = RoPE(k_r)``, one for all heads; ``q_rope <-
RoPE(q_rope)``; ``k_nope_h = c_kv W_UK_h`` (``k_b_w[h]``), ``v_h = c_kv
W_UV_h`` (``v_b_w[h]``).  Scores ``(q_nope_h . k_nope_h,j + q_rope_h .
k_rope_j) * s`` over ``j <= t``, softmax, ``o = sum p v_h``, ``W_o``.
``s = (nope + rope)**-0.5 * m**2``, ``m = 0.1 * mscale_all_dim * ln(factor)
+ 1``; cos and sin are multiplied by ``y(mscale) / y(mscale_all_dim)``,
``y(a) = 0.1 a ln(factor) + 1`` (1 as published).  RoPE is rotate-half over
the rotated dims with YaRN's frequencies (:func:`yarn_inv_freq`).

FFN: the first ``first_k_dense_replace`` layers dense ``down(silu(gate x) *
up x)``; the others ``s = sigmoid(x W_r)`` over all routed experts, the
``num_experts_per_tok`` largest (``topk_method "none"``: no bias, no group
limit), weights ``s / sum * routed_scaling_factor``, SwiGLU experts, plus the
shared SwiGLU expert on every token.

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
* ``topk_method "none"`` beside ``n_group 8``, ``topk_group 4`` is read
  literally: the 8 largest of all 192, no group limit, no selection bias;
* RoPE pairs are (i, i + rot/2) (rotate-half); the checkpoint interleaves
  them, a permutation of ``W_qb``'s and ``W_kva``'s columns that weights
  drawn from a seed do not see;
* the router's scores are float32 from float32 activations;
* ``kv_b_proj`` is held as ``k_b_w`` [H, nope, C] and ``v_b_w`` [H, C, v]:
  a split of the file.

The readings that must come out as NOT correct (``lib/axk1_family.py``) are
switches of this same forward, all traced so that one program gives them:
``round_acts`` (the residual stream rounded to ``act_dtype``), ``rope_term``
false (the scores' ``q_rope . k_rope`` term left out), ``low`` (the router,
the softmax and the norms in bfloat16 where the configuration says float32).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def yarn_inv_freq(m: dict) -> np.ndarray:
    """The rotated pairs' frequencies [rot / 2], float64 -> float32: pair
    ``i`` of ``theta**(-2i/rot)`` keeps its own below pair ``low`` (it
    turns more than ``beta_fast`` times in the original context), has
    ``1 / factor`` of it above pair ``high`` (fewer than ``beta_slow``
    turns), and in between ``(1 - r) own + r own / factor`` with ``r`` the
    linear ramp over the pair index; ``low`` / ``high`` are the bounds' pair
    indices ``rot ln(orig / (2 pi turns)) / (2 ln theta)`` rounded down /
    up.  No ``rope_scaling``: every pair its own."""
    rot, theta = m["qk_rope_head_dim"], float(m["rope_theta"])
    own = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    rs = m.get("rope_scaling")
    if not rs:
        return own.astype(np.float32)
    orig = rs["original_max_position_embeddings"]
    bound = [rot * math.log(orig / (2 * math.pi * turns))
             / (2 * math.log(theta))
             for turns in (rs["beta_fast"], rs["beta_slow"])]
    low, high = max(math.floor(bound[0]), 0), min(math.ceil(bound[1]),
                                                  rot - 1)
    r = np.clip((np.arange(rot // 2) - low) / max(high - low, 0.001), 0, 1)
    return ((1 - r) * own + r * own / rs["factor"]).astype(np.float32)


def _yarn_y(a: float, factor: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(m: dict) -> float:
    rs = m.get("rope_scaling") or {}
    y = _yarn_y(rs["mscale_all_dim"], rs["factor"]) \
        if rs.get("mscale_all_dim") else 1.0
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * y * y


def _rotary_mscale(m: dict) -> float:
    rs = m.get("rope_scaling") or {}
    if not rs:
        return 1.0
    return _yarn_y(rs.get("mscale", 1), rs["factor"]) \
        / _yarn_y(rs.get("mscale_all_dim", 1), rs["factor"])


def _round_to(x, dtype):
    """float32 x rounded to ``dtype``'s precision and kept in float32.
    ``reduce_precision`` and not a pair of casts: under XLA's excess
    precision a cast down and up again is dropped."""
    info = jnp.finfo(dtype)
    if info.bits == 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _low(x, on):
    """x in bfloat16's precision where ``on`` (traced)."""
    return jnp.where(on, _round_to(x, jnp.bfloat16), x)


def _rms(x, w, eps, low):
    x = _low(x, low)
    ms = _low(jnp.mean(_low(jnp.square(x), low), -1, keepdims=True), low)
    return _low(_low(x / jnp.sqrt(ms + eps), low) * w.astype(F32), low)


def _rope(t, inv_freq, mscale):
    """t [T, H, rot]: rotate-half, position = row."""
    half = t.shape[-1] // 2
    ang = jnp.arange(t.shape[0], dtype=F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, x, m, rope_term, low, block):
    """x [T, d] (normed) of ONE sequence, the expanded form."""
    T = x.shape[0]
    H, nope, rot = (m["num_attention_heads"], m["qk_nope_head_dim"],
                    m["qk_rope_head_dim"])
    C, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    inv_freq, ms = jnp.asarray(yarn_inv_freq(m)), _rotary_mscale(m)
    c_q = _rms(x @ p["q_a_w"].astype(F32), p["q_a_norm"], eps, low)
    q = (c_q @ p["q_b_w"].astype(F32)).reshape(T, H, nope + rot)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], inv_freq, ms)
    kv = x @ p["kv_a_w"].astype(F32)
    c_kv = _rms(kv[:, :C], p["kv_a_norm"], eps, low)
    k_rope = _rope(kv[:, None, C:], inv_freq, ms)[:, 0]          # [T, rot]
    k_nope = jnp.einsum("tc,hnc->thn", c_kv, p["k_b_w"].astype(F32))
    v = jnp.einsum("tc,hcv->thv", c_kv, p["v_b_w"].astype(F32))
    scale = softmax_scale(m)
    block = min(block, T)
    pad = -T % block

    def blocks(t):
        return jnp.pad(t, ((0, pad), (0, 0), (0, 0))).reshape(
            (-1, block) + t.shape[1:])

    at = jnp.arange(T)

    def rows(args):
        qn, qr, first = args                    # [block, H, .]
        t = first + jnp.arange(block)
        s = jnp.einsum("bhn,thn->hbt", qn, k_nope) + jnp.where(
            rope_term, jnp.einsum("bhr,tr->hbt", qr, k_rope), 0.0)
        s = _low(s * scale, low)
        s = jnp.where((at[None, :] <= t[:, None])[None], s, -jnp.inf)
        e = _low(jnp.exp(s - jnp.max(s, -1, keepdims=True)), low)
        w = _low(e / _low(jnp.sum(e, -1, keepdims=True), low), low)
        return jnp.einsum("hbt,thv->bhv", w, v).reshape(block, -1)

    n = (T + pad) // block
    out = jax.lax.map(rows, (blocks(q_nope), blocks(q_rope),
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


def _experts(p, stacked, index, x, m, low):
    """x [T, d] (normed): this share's part of the routed sum + the shared
    expert.  ``stacked``: every expert layer's held experts in one row (no
    layer is sliced out); ``index`` says which layer's."""
    e_all, k = m["n_routed_experts"], m["num_experts_per_tok"]
    first, count = m.get("experts_held") or (0, e_all)
    logits = _low(_low(x, low) @ _low(p["router_w"].astype(F32), low), low)
    scores = _low(jax.nn.sigmoid(logits), low)                  # [T, E]
    _, chosen = jax.lax.top_k(scores, k)
    mask = jnp.sum(jax.nn.one_hot(chosen, e_all, dtype=F32), axis=-2)
    gates = scores * mask
    if m.get("norm_topk_prob", True):
        gates = _low(gates / jnp.sum(gates, -1, keepdims=True), low)
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
    if m.get("n_shared_experts"):
        out = out + _swiglu(x, p["shared_gate_w"], p["shared_up_w"],
                            p["shared_down_w"])
    return out


def _head(x, lm_head, slices: int = 4):
    """x @ W_head, a slice of the vocabulary at a time."""
    V = lm_head.shape[-1]
    n = slices if V % slices == 0 else 1
    parts = jax.lax.map(
        lambda j: x @ jax.lax.dynamic_slice_in_dim(
            lm_head, j * (V // n), V // n, axis=1).astype(F32),
        jnp.arange(n))                                      # [n, T, V/n]
    return parts.transpose(1, 0, 2).reshape(x.shape[0], V)


def axk1_logits(params, tokens, m: dict, act_dtype=F32, round_acts=False,
                rope_term=True, low=False, block: int = 128):
    """tokens [B, T] -> float32 logits [B, T, V].  ``m``: the
    configuration's values under the source's keys, + ``experts_held``.
    The switches (module docstring; each may be traced): ``round_acts``
    rounds the residual stream to ``act_dtype`` from the embedding on and
    after every layer; ``rope_term`` false leaves ``q_rope . k_rope`` out
    of the scores; ``low`` computes the router, the softmax and the norms
    in bfloat16's precision."""
    eps, dense = m["rms_norm_eps"], m["first_k_dense_replace"]

    def rounded(x):
        return jnp.where(round_acts, _round_to(x, act_dtype), x)

    stacked = {k: params["moe"][k].reshape(
        (-1,) + params["moe"][k].shape[2:]) for k in _EXPERT_LEAVES} \
        if "moe" in params else None

    def one(seq):
        x = rounded(params["wte"][seq].astype(F32))
        for layer in range(m["num_hidden_layers"]):
            # a layer's weights wait for its input: their float32 copies
            # are then made a layer at a time, not all at once
            p, x = jax.lax.optimization_barrier(
                ({k: v[layer] for k, v in params["attn"].items()}, x))
            x = x + _attention(p, _rms(x, p["ln1"], eps, low), m, rope_term,
                               low, block)
            kind, i = ("dense", layer) if layer < dense \
                else ("moe", layer - dense)
            p, x = jax.lax.optimization_barrier(
                ({k: v[i] for k, v in params[kind].items()
                  if k not in _EXPERT_LEAVES or kind == "dense"}, x))
            h = _rms(x, p["ln2"], eps, low)
            x = rounded(x + (
                _swiglu(h, p["gate_w"], p["up_w"], p["down_w"], slices=4)
                if kind == "dense" else _experts(p, stacked, i, h, m, low)))
        head, x = jax.lax.optimization_barrier((params["lm_head"], x))
        return _head(_rms(x, params["norm_f"], eps, low), head)

    return jax.lax.map(one, tokens)
