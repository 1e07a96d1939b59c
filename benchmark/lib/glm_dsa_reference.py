"""Plain reference for Zhipu GLM-5.2 (HF ``model_type: glm_moe_dsa``; the row
of ``model-configs/architectures.jsonl`` is the source there is, and where it
gives only widths, DeepSeek-V3.2-Exp's published sparse attention, the family
the model type names): the forward pass in straightforward jax.numpy and
float32.  No cache, no kernel, no page, no absorbed matrices, no gather: every
query scores the whole sequence, its picks are a MASK over the sequence, and
EVERY held expert runs on EVERY token with the routing as a mask.  It reads
the program's parameter tree (``deepspeed_tpu/models/glm_dsa.py``: the names
are the program's) and nothing else of it.  Weights arrive in the dtype they
are served in and are raised to float32 as they are used.  Callers run it
under ``jax.default_matmul_precision("highest")``.

    x <- x + attn(RMSNorm(x)); x <- x + ffn(RMSNorm(x));
    logits = RMSNorm(x) W_head          (eps rms_norm_eps, untied)

Latent attention (every layer, ``H`` heads): ``c_q = RMSNorm(x W_qa)``; ``q =
c_q W_qb`` -> a head ``[q_nope ; RoPE(q_rope)]``; ``[c_kv ; k_r] = x W_kva``;
``c_kv <- RMSNorm(c_kv)``; ``k_rope = RoPE(k_r)``, one for all heads; ``k_h =
[c_kv W_UK_h ; k_rope]``, ``v_h = c_kv W_UV_h``.  ``o_t,h = sum_{s in S_t}
softmax_{s in S_t}(qk_head_dim**-0.5 q_t,h . k_s,h) v_s,h``; ``W_o``.  RoPE is
rotate-half over the rotated dims at ``rope_theta`` (``rope_type`` default).

The indexer, on a layer whose ``indexer_types`` entry is ``full``: ``q_I =
c_q W_Iq`` (``index_n_heads`` heads of ``index_head_dim``, the first
``qk_rope_head_dim`` dims rotated); ``k_I = LayerNorm(x W_Ik)`` (eps 1e-6,
ONE a token, rotated alike); ``w = x W_Iw * index_n_heads**-0.5 *
index_head_dim**-0.5``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``
for ``s <= t``; ``S_t`` = the ``min(index_topk, t + 1)`` positions of largest
``I[t, s]``, ties to the lower ``s``.  A ``shared`` layer has no indexer and
takes the ``S_t`` of the nearest ``full`` layer before it.

FFN: a ``dense`` layer (``mlp_layer_types``) ``down(silu(gate x) * up x)``;
a ``sparse`` one ``s = sigmoid(x W_r)`` over all routed experts, the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
(``router_bias``), weights ``s / sum * routed_scaling_factor``, SwiGLU
experts, plus the shared SwiGLU expert on every token.

The share (``m["experts_held"] = [first, count]``; the vocabulary slice is
the parameter tree's own width): the router ranges over ALL experts, the sum
runs over the held ones only, the shared expert is whole, and that goes on to
the next layer, exactly as the program does.  Nothing stands in for the other
chips.

So that 24,576 positions at the published widths fit beside the weights
(the benchmark calls it while no engine holds the pages' memory), the wide
intermediates are computed in blocks: the
indexer's scores and the attention a block of query rows at a time (the
attention a head at a time too), the dense FFN and the head a slice of their
width at a time, the experts one at a time.  What is carried from a ``full``
layer to the ``shared`` ones is its mask ``[T, T]``.

Departures from the published description, each for a stated reason (the
configuration file's ``assumed`` says the same): the indexer's ReLU,
LayerNorm and scales are DeepSeek-V3.2-Exp's; RoPE pairs are (i, i + rot/2)
(rotate-half: the checkpoint's interleaving is a permutation of columns that
weights drawn from a seed do not see); that release's Hadamard rotation of the
indexer's queries and keys (orthogonal on both sides of a dot product) and its
FP8 keys (a storage format) are not here; ``kv_b_proj`` is held as ``k_b_w``
[H, nope, C] and ``v_b_w`` [H, C, v].

The readings that must come out as NOT correct (``lib/glm_dsa_family.py``)
are switches of this same forward, all traced so that one program gives them:
``round_acts`` (the residual stream rounded to ``act_dtype``), ``low_keys``
(the indexer's keys rounded to 8 bits, float8 e4m3, before they score),
``skip_indexer`` (no indexer: a query takes the ``index_topk`` positions
nearest before it), ``stale_picks`` (only the FIRST ``full`` layer picks;
every later layer, ``full`` or not, takes its sets).  One more is read and
not judged: ``bf16_index`` (the indexer's queries, keys and head weights
rounded to bfloat16, the precision the program's indexer works in by the
configuration's own statement: how many picks that alone flips).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_EXPERT_LEAVES = ("gate_w", "up_w", "down_w")
LOW_KEY_DTYPE = jnp.float8_e4m3fn
_LAYER_NORM_EPS = 1e-6


def _round_to(x, dtype):
    """float32 x rounded to ``dtype``'s precision and kept in float32
    (``reduce_precision``: under XLA's excess precision a cast down and up
    again is dropped)."""
    info = jnp.finfo(dtype)
    if info.bits == 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(F32)


def _rope(t, theta: float, rot: int):
    """t [T, heads, width]: rotate-half over the first ``rot`` dims,
    position = row."""
    half = rot // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t.shape[0], dtype=F32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :half], t[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            t[..., rot:]], axis=-1)


def _blocks(t, block: int):
    """[T, ...] -> [n, block, ...], zero rows after the last."""
    pad = -t.shape[0] % block
    t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
    return t.reshape((-1, block) + t.shape[1:])


def pick_mask(scores, k: int):
    """scores [R, T] (``-inf``: not a candidate) -> bool [R, T]: the
    ``min(k, candidates)`` largest of a row, ties to the lower index."""
    k = min(k, scores.shape[-1])
    tau = -jnp.sort(-scores, axis=-1)[:, k - 1:k]           # k-th largest
    above = scores > tau
    wanted = k - jnp.sum(above, axis=-1, keepdims=True)
    tie = scores == tau
    return (above | (tie & (jnp.cumsum(tie, axis=-1) <= wanted))) \
        & (scores > -jnp.inf)


def _index_masks(ip, x, c_q, m, low_keys, bf16_index, block):
    """The indexer of one ``full`` layer on x [T, d] (normed): -> the
    picked sets as a mask [T, T] (row t: ``S_t``)."""
    T = x.shape[0]
    J, D, rot = m["index_n_heads"], m["index_head_dim"], m["qk_rope_head_dim"]
    theta = float(m["rope_parameters"]["rope_theta"])
    q_i = _rope((c_q @ ip["wq_b_w"].astype(F32)).reshape(T, J, D), theta, rot)
    k = x @ ip["wk_w"].astype(F32)
    mu = jnp.mean(k, -1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(jnp.mean(jnp.square(k - mu), -1,
                                          keepdims=True) + _LAYER_NORM_EPS)
    k = k * ip["k_norm_w"].astype(F32) + ip["k_norm_b"].astype(F32)
    k_i = _rope(k[:, None], theta, rot)[:, 0]                # [T, D]
    k_i = jnp.where(low_keys, _round_to(k_i, LOW_KEY_DTYPE), k_i)
    w = (x @ ip["weights_proj_w"].astype(F32)) * (J ** -0.5 * D ** -0.5)
    q_i, k_i, w = (jnp.where(bf16_index, _round_to(t, jnp.bfloat16), t)
                   for t in (q_i, k_i, w))
    at = jnp.arange(T)

    def rows(args):
        q, wb, first = args                    # [block, J, D], [block, J]
        s = jnp.einsum("bjd,td->bjt", q, k_i)
        score = jnp.sum(jnp.maximum(s, 0.0) * wb[:, :, None], axis=1)
        t = first + jnp.arange(q.shape[0])
        score = jnp.where(at[None, :] <= t[:, None], score, -jnp.inf)
        return pick_mask(score, m["index_topk"])

    block = min(block, T)
    n = -(-T // block)
    masks = jax.lax.map(rows, (_blocks(q_i, block), _blocks(w, block),
                               jnp.arange(n) * block))
    return masks.reshape(n * block, T)[:T]


def _attention(p, c_q, x, mask, m, block):
    """x [T, d] (normed) of ONE sequence, the expanded form over the
    picked keys ``mask`` [T, T], a head and a block of queries at a time;
    a head's slices of ``W_qb`` and ``W_o`` are cut inside the loop, so
    that nothing of 64 heads' width is held."""
    T = x.shape[0]
    H, nope, rot = (m["num_attention_heads"], m["qk_nope_head_dim"],
                    m["qk_rope_head_dim"])
    C, dv, eps = m["kv_lora_rank"], m["v_head_dim"], m["rms_norm_eps"]
    theta = float(m["rope_parameters"]["rope_theta"])
    kv = x @ p["kv_a_w"].astype(F32)
    c_kv = _rms(kv[:, :C], p["kv_a_norm"], eps)
    k_rope = _rope(kv[:, None, C:], theta, rot)[:, 0]        # [T, rot]
    scale = (nope + rot) ** -0.5
    block = min(block, T)
    mask_b = _blocks(mask, block)

    def head(acc, args):
        h, w_uk, w_uv = args
        q_h = c_q @ jax.lax.dynamic_slice_in_dim(
            p["q_b_w"], h * (nope + rot), nope + rot, axis=1).astype(F32)
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
        w_o = jax.lax.dynamic_slice_in_dim(p["o_w"], h * dv, dv, axis=0)
        return acc + o_h.reshape(-1, dv)[:T] @ w_o.astype(F32), None

    return jax.lax.scan(head, jnp.zeros_like(x),
                        (jnp.arange(H), p["k_b_w"], p["v_b_w"]))[0]


def _swiglu(x, gate_w, up_w, down_w, slices: int = 1):
    """``down(silu(gate x) * up x)``, a slice of the intermediate width at
    a time (cut inside the loop, so that its float32 copy is made there)."""
    width = gate_w.shape[-1]
    n = slices if width % slices == 0 else 1
    w = width // n

    def part(acc, j):
        g, u = (jax.lax.dynamic_slice_in_dim(t, j * w, w, axis=1).astype(F32)
                for t in (gate_w, up_w))
        d = jax.lax.dynamic_slice_in_dim(down_w, j * w, w, axis=0)
        return acc + (jax.nn.silu(x @ g) * (x @ u)) @ d.astype(F32), None

    return jax.lax.scan(part, jnp.zeros_like(x), jnp.arange(n))[0]


def _experts(p, stacked, index, x, m):
    """x [T, d] (normed): this share's part of the routed sum + the shared
    expert.  ``stacked``: every expert layer's held experts in one row;
    ``index`` says which layer's."""
    e_all, k = m["n_routed_experts"], m["num_experts_per_tok"]
    first, count = m.get("experts_held") or (0, e_all)
    scores = jax.nn.sigmoid(x @ p["router_w"].astype(F32))      # [T, E]
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(F32), k)
    mask = jnp.sum(jax.nn.one_hot(chosen, e_all, dtype=F32), axis=-2)
    gates = scores * mask
    if m.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * m["routed_scaling_factor"]

    def expert(acc, e):
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


def glm_dsa_logits(params, tokens, m: dict, act_dtype=F32, round_acts=False,
                   low_keys=False, skip_indexer=False, stale_picks=False,
                   bf16_index=False, block: int = 128, pick_rows=None,
                   logit_rows=None):
    """tokens [B, T] -> float32 logits [B, T, V]; with ``pick_rows`` [R]
    (positions) also the picked sets of those queries at each ``full``
    layer, bool [B, full layers, R, T]; with ``logit_rows`` (first
    (may be traced), count) the logits of those rows alone, [B, count, V]
    (the head of a 24,576-token sequence is 1.9 GB).  ``m``: the
    configuration's values
    under the source's keys (``indexer_types`` and ``mlp_layer_types`` one
    entry a layer), + ``experts_held``.  The switches (module docstring;
    each may be traced)."""
    eps, topk = m["rms_norm_eps"], m["index_topk"]
    kinds, ffns = list(m["indexer_types"]), list(m["mlp_layer_types"])
    T = tokens.shape[1]
    at = jnp.arange(T)
    nearest = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - topk)

    def rounded(x):
        return jnp.where(round_acts, _round_to(x, act_dtype), x)

    stacked = {k: params["moe"][k].reshape(
        (-1,) + params["moe"][k].shape[2:]) for k in _EXPERT_LEAVES} \
        if "moe" in params else None

    def one(seq):
        x = rounded(params["wte"][seq].astype(F32))
        mask, picks = None, []
        for layer in range(m["num_hidden_layers"]):
            # a layer's weights wait for its input: their float32 copies
            # are then made a layer at a time, not all at once
            p, x = jax.lax.optimization_barrier(
                ({k: v[layer] for k, v in params["attn"].items()}, x))
            h = _rms(x, p["ln1"], eps)
            c_q = _rms(h @ p["q_a_w"].astype(F32), p["q_a_norm"], eps)
            if kinds[layer] == "full":
                ip = {k: v[kinds[:layer].count("full")]
                      for k, v in params["indexer"].items()}
                own = jnp.where(skip_indexer, nearest,
                                _index_masks(ip, h, c_q, m, low_keys,
                                             bf16_index, block))
                mask = own if mask is None \
                    else jnp.where(stale_picks, mask, own)
                if pick_rows is not None:
                    picks.append(mask[pick_rows])
            x = x + _attention(p, c_q, h, mask, m, block)
            dense = ffns[layer] == "dense"
            kind = "dense" if dense else "moe"
            i = ffns[:layer].count("dense" if dense else "sparse")
            p, x = jax.lax.optimization_barrier(
                ({k: v[i] for k, v in params[kind].items()
                  if k not in _EXPERT_LEAVES or dense}, x))
            h = _rms(x, p["ln2"], eps)
            x = rounded(x + (
                _swiglu(h, p["gate_w"], p["up_w"], p["down_w"], slices=4)
                if dense else _experts(p, stacked, i, h, m)))
        if logit_rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, *logit_rows)
        head, x = jax.lax.optimization_barrier((params["lm_head"], x))
        logits = _head(_rms(x, params["norm_f"], eps), head)
        return (logits, jnp.stack(picks)) if pick_rows is not None \
            else logits

    return jax.lax.map(one, tokens)
