"""Plain reference for Xiaomi MiMo-V2 as MiMo-V2.5 configures it (HF
``model_type: mimo_v2``; the row of ``model-configs/architectures.jsonl`` is
the source there is): the forward pass in straightforward jax.numpy and
float32.  No cache, no kernel, no page, no ring, no sort, no dispatch: every
query sees the whole sequence under a mask, and EVERY held expert runs on
EVERY token with the routing as a mask.  It reads the program's parameter
tree (``deepspeed_tpu/models/mimo_v2.py``: the names are the program's) and
nothing else of it.  Weights arrive in the dtype they are served in and are
raised to float32 as they are used.  Callers run it under
``jax.default_matmul_precision("highest")``.

    x <- x + attn_l(RMSNorm(x)); x <- x + ffn_l(RMSNorm(x));
    logits = RMSNorm(x) W_head

Attention, layer ``l`` of kind ``hybrid_layer_pattern[l]`` (0 full, 1
window): ``q = x W_q`` [Hq, Dk], ``k = x W_k`` [Hkv, Dk] (``Hkv`` the
kind's), ``v = attention_value_scale * x W_v`` [Hkv, Dv]; rotate-half RoPE
on the first ``int(Dk * partial_rotary_factor)`` dims of q and k at the
kind's theta; query head ``h`` on key head ``h // (Hq / Hkv)``; scores
``q . k / sqrt(Dk)`` over ``j <= t`` (full) or ``t - sliding_window < j <=
t`` (window); a window layer's softmax has one more column a head, its
``sink``, dropped after normalising; ``o W_o``.  FFN: dense
``down(silu(gate x) * up x)`` where ``moe_layer_freq[l]`` is 0; else
``s = sigmoid(x W_r)`` over all routed experts, the ``num_experts_per_tok``
largest of ``s + bias``, weights ``s / sum`` (``norm_topk_prob``) times
``routed_scaling_factor`` (null: 1), SwiGLU experts.

The share (``m["experts_held"] = [first, count]``; the vocabulary slice is
the parameter tree's own width): the router ranges over ALL experts, the sum
runs over the held ones only, and that part goes on to the next layer,
exactly as the program does.  Nothing stands in for the other chips.

So that 8,192 positions at the published widths fit beside the engine, the
wide intermediates are computed in blocks: attention a block of query rows
at a time, the dense FFN a slice of its intermediate width at a time, the
experts one at a time.

Departures from the published description, each for a stated reason (the
configuration file's ``assumed`` says the same):
* which dims are rotated (the first), the sink as a softmax column, the
  value scale applied to ``v`` before anything keeps it, the window counting
  its own token: the row fixes none of them; the family's convention;
* the router's scores are float32 from float32 activations;
* ``attention_chunk_size``, ``hybrid_block_size`` and
  ``attention_projection_layout`` change nothing in the forward;
* the multi-token-prediction layers and the towers are not run.

The readings that must come out as NOT correct (``lib/mimo_v2_family.py``)
are switches of this same forward, all traced so that one program gives
them: ``round_acts`` (the residual stream rounded to ``act_dtype``),
``sink_on`` false (the sink left out), ``window`` other than the
configuration's.
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


def _rope(t, theta, rot):
    """t [T, H, D]: rotate-half over the first ``rot`` dims, position =
    row."""
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t.shape[0], dtype=F32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :half], t[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            t[..., rot:]], axis=-1)


def _attention(p, x, m, window, sink_on, hkv, theta, block):
    """x [T, d] (normed) of ONE sequence.  ``window``: a traced count of
    keys (the query's own included), or None for a full layer."""
    T = x.shape[0]
    hq, dk, dv = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    rot = int(dk * m["partial_rotary_factor"])
    q = _rope((x @ p["q_w"].astype(F32)).reshape(T, hq, dk), theta, rot)
    k = _rope((x @ p["k_w"].astype(F32)).reshape(T, hkv, dk), theta, rot)
    v = (x @ p["v_w"].astype(F32)).reshape(T, hkv, dv) \
        * m["attention_value_scale"]
    block = min(block, T)
    pad = -T % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, hkv, hq // hkv, dk)
    at = jnp.arange(T)

    def rows(args):
        q_i, first = args                       # [block, Hkv, rep, Dk]
        t = first + jnp.arange(block)
        s = jnp.einsum("bgrd,tgd->grbt", q_i, k) / jnp.sqrt(F32(dk))
        ok = at[None, :] <= t[:, None]
        if window is not None:
            ok &= at[None, :] > t[:, None] - window
        s = jnp.where(ok[None, None], s, -jnp.inf)
        if window is not None:
            col = jnp.where(sink_on, p["sink"].astype(F32), -jnp.inf)
            col = jnp.broadcast_to(
                col.reshape(hkv, hq // hkv, 1, 1), s.shape[:3] + (1,))
            s = jnp.concatenate([s, col], axis=-1)
        w = jax.nn.softmax(s, axis=-1)[..., :T]
        return jnp.einsum("grbt,tgv->bgrv", w, v).reshape(block, hq * dv)

    out = jax.lax.map(rows, (qb, jnp.arange(qb.shape[0]) * block))
    return out.reshape(-1, hq * dv)[:T] @ p["o_w"].astype(F32)


def _dense_ffn(p, x, slices: int = 4):
    """``down(silu(gate x) * up x)``, a slice of the intermediate width
    at a time."""
    width = p["gate_w"].shape[-1]
    n = slices if width % slices == 0 else 1
    w = width // n

    def part(acc, j):
        # the slice is cut inside the loop, so that its float32 copy is
        # made there and not of the whole matrix before it
        g, u = (jax.lax.dynamic_slice_in_dim(p[k], j * w, w, axis=1)
                .astype(F32) for k in ("gate_w", "up_w"))
        d = jax.lax.dynamic_slice_in_dim(p["down_w"], j * w, w, axis=0)
        return acc + (jax.nn.silu(x @ g) * (x @ u)) @ d.astype(F32), None

    return jax.lax.scan(part, jnp.zeros_like(x), jnp.arange(n))[0]


_EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def _experts(p, stacked, index, x, m):
    """x [T, d] (normed): this share's part of the expert layer.
    ``stacked``: every expert layer's held experts in one row (no layer
    is sliced out: a copy of 0.8 GB); ``index`` says which layer's."""
    e_all, k = m["n_routed_experts"], m["num_experts_per_tok"]
    first, count = m.get("experts_held") or (0, e_all)
    scores = jax.nn.sigmoid(x @ p["router_w"].astype(F32))      # [T, E]
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(F32), k)
    mask = jnp.sum(jax.nn.one_hot(chosen, e_all, dtype=F32), axis=-2)
    gates = scores * mask
    if m.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * (m.get("routed_scaling_factor") or 1.0)

    def expert(acc, e):
        # one expert's matrices are raised to float32 inside the loop
        gate_w, up_w, down_w = (
            jax.lax.dynamic_index_in_dim(stacked[k], index * count + e,
                                         keepdims=False).astype(F32)
            for k in _EXPERT_LEAVES)
        y = (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w
        gate = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1,
                                            keepdims=False)
        return acc + gate[:, None] * y, None

    return jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(count))[0]


def mimo_v2_logits(params, tokens, m: dict, act_dtype=F32, round_acts=False,
                   sink_on=True, window=None, block: int = 256):
    """tokens [B, T] -> float32 logits [B, T, V].  ``m``: the
    configuration's values under the source's keys, + ``experts_held``.
    The switches (module docstring; each may be traced): ``round_acts``
    rounds the residual stream to ``act_dtype`` from the embedding on and
    after every layer; ``sink_on`` false leaves the window layers' sink
    out; ``window`` (None: ``sliding_window``) is the window layers'."""
    eps = m["layernorm_epsilon"]
    if window is None:
        window = m["sliding_window"]

    def rounded(x):
        return jnp.where(round_acts, _round_to(x, act_dtype), x)

    stacked = {k: params["moe"][k].reshape(
        (-1,) + params["moe"][k].shape[2:]) for k in _EXPERT_LEAVES} \
        if "moe" in params else None

    def one(seq):
        x = rounded(params["wte"][seq].astype(F32))
        seen = {"full": 0, "window": 0, "dense": 0, "moe": 0}
        for a, f in zip(m["hybrid_layer_pattern"], m["moe_layer_freq"]):
            kind, ffn = ("window" if a else "full"), ("moe" if f else "dense")
            # a layer's weights wait for its input: their float32
            # copies are then made a layer at a time, not all at once
            p, x = jax.lax.optimization_barrier(
                ({k: v[seen[kind]] for k, v in params[kind].items()}, x))
            x = x + _attention(
                p, _rms(x, p["ln1"], eps), m,
                window if a else None, sink_on,
                m["swa_num_key_value_heads" if a else "num_key_value_heads"],
                m["swa_rope_theta" if a else "rope_theta"], block)
            p, x = jax.lax.optimization_barrier(
                ({k: v[seen[ffn]] for k, v in params[ffn].items()
                  if not (f and k in _EXPERT_LEAVES)}, x))
            h = _rms(x, p["ln2"], eps)
            x = rounded(x + (_experts(p, stacked, seen[ffn], h, m) if f
                             else _dense_ffn(p, h)))
            seen[kind] += 1
            seen[ffn] += 1
        head, x = jax.lax.optimization_barrier((params["lm_head"], x))
        return _rms(x, params["norm_f"], eps) @ head.astype(F32)

    return jax.lax.map(one, tokens)
