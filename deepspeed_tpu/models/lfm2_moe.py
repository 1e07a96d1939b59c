"""Liquid AI LFM2 MoE (HF ``model_type: lfm2_moe``; the row
``LFM2-24B-A2B`` of ``model-configs/architectures.jsonl``): a pre-norm
decoder whose mixers are of two kinds by a published list (``layer_types``:
``conv`` four times in five, ``full_attention`` the fifth), whose first
``num_dense_layers`` feed-forwards are dense and the rest expert layers, and
whose head is the embedding.

    h <- x + mixer(RMSNorm(x));  x <- h + ffn(RMSNorm(h))
    logits = RMSNorm(x) E^T             (tied, no bias anywhere)
    ffn(x) = W_2 (SiLU(W_1 x) * W_3 x)

* a ``conv`` layer (HF ``Lfm2MoeShortConv``, ``conv_L_cache`` taps K):
  ``[B | C | u] = x W_in`` (three thirds, in that order); ``z = B * u``; a
  depthwise causal convolution of K taps over time, zeros before the
  sequence, NO activation: ``c_t = sum_j w_j z_{t-K+1+j}``; out ``W_out (C *
  c)``.  All a request keeps of it is ``z``'s last ``K - 1`` rows.
* a ``full_attention`` layer: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` key heads of ``head_dim``; ``q`` and ``k``
  RMS-normed A HEAD (one weight vector of ``head_dim`` each) BEFORE the
  rotation; rotate-half RoPE over the whole head at
  ``rope_parameters.rope_theta``; causal softmax in float32 at
  ``head_dim**-0.5``; ``W_out``.
* an expert layer: ``s = sigmoid(x_f32 W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + expert_bias``
  (``use_expert_bias``: the bias steers the choice only); weights ``s[chosen]
  / (sum + 1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``;
  SwiGLU experts at ``moe_intermediate_size``; no shared expert.  EVERY
  expert of a layer is held here (``moe/dropless.py`` without
  ``experts_held``).

Not built, refused at construction: ``conv_bias``, a ``layer_types`` entry
of another kind, untied embeddings, more dense layers than layers.

This file is the model's SERVING surface (``ServeEngine``'s protocol).  A
request keeps two kinds of thing.  By slot (``serving_state``): ``"conv"``
``[conv layers, slots, (K - 1) d]``, the last rows of ``z`` in the model's
dtype, side by side on the lanes (``walked.shift_tail_lanes`` says why):
the third model whose request state holds a convolution's rows and
the first where that is ALL a layer keeps.  In the two page pools: a
``full_attention`` layer's keys and values (``config.n_layer`` counts those
layers only), 8 key heads of 64 resting as 4 PAIRED heads of 128
(``walked.PairedPagePool``: ``config.n_kv_head`` and ``config.d_head`` say
the pool's shape, ``num_key_value_heads`` and ``head_dim`` the model's): the
published bytes, and the decode kernel's grouped body copies whole lane
tiles.  The prefill takes a CHUNK of a prompt (``prefix_len`` > 0) as
``models/olmo_hybrid.py``'s does: a conv layer starts from the slot's rows,
a full layer writes the chunk's keys and attends the request's pages
gathered ahead of them (``walked.context_attention``, on the model's own 8
heads of 64); a request's first chunk starts from zeros, whatever the
slot's last occupant left there.

Parameter tree: ``wte`` [V, d] (also the head), ``norm_f``; the layers by
kind in the order they occur: ``conv`` (``in_w`` [d, 3 d], ``conv_w`` [K,
d], ``out_w`` [d, d], ``ln1``), ``full`` (``q_w`` [d, Hq D], ``k_w`` /
``v_w`` [d, Hkv D], ``o_w`` [Hq D, d], ``q_norm`` / ``k_norm`` [D],
``ln1``), ``dense`` (``gate_w`` = W_1, ``up_w`` = W_3 [d, f], ``down_w`` =
W_2, ``ln2``), ``moe`` (``router_w`` [d, E], ``router_bias`` [E] float32,
``ln2``; ``gate_w`` / ``up_w`` [layers, E, d, f'], ``down_w`` [layers, E,
f', d]).  Every matrix input-major, a leaf a layer but for the experts,
which reach their kernels whole (``models/mimo_v2.py``'s rule).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..moe.dropless import dropless_moe, route_sigmoid_topk
from .walked import (F32, PairedPagePool, ServedConfig, WalkedModel, at,
                     causal_self_attention, context_attention, conv_taps,
                     decode_index, default_scale, dense_ffn, draw_layers,
                     lane_pairs, merge_heads, prefill_index, prefix_keys,
                     project_heads, rms_norm, rope, shift_tail_lanes,
                     stacked_experts, tied_head, write_slot_state)

_KINDS = {"conv": "conv", "full_attention": "full"}
#: the call's counters (``serving_aux``)
_COUNTERS = ("moe_experts_hit", "moe_load_imbalance", "moe_rows",
             "full_kv_tokens", "conv_slot_layers")
#: under the renormalisation of the picked scores (HF
#: ``Lfm2MoeSparseMoeBlock``)
_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776      # the dense FFN's
    moe_intermediate_size: int = 1536   # ONE expert's
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None      # null in the source: hidden / heads
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    rope_parameters: Optional[Dict[str, Any]] = None
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    # the program's
    initializer_range: float = 0.02
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes

    def __post_init__(self):
        types = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types)
        unbuilt = {
            "conv_bias": self.conv_bias,
            "a layer_types entry that is neither 'conv' nor "
            "'full_attention'": bool(set(types) - set(_KINDS)),
            "tie_word_embeddings false (an untied head)":
                not self.tie_word_embeddings,
            "a dense layer after an expert layer (num_dense_layers beyond "
            "the layers)": not 0 <= self.num_dense_layers
                <= self.num_hidden_layers,
        }
        self.check(unbuilt)
        if len(types) != self.num_hidden_layers:
            raise ValueError(f"layer_types: {self.num_hidden_layers} "
                             f"entries, one a layer; got {len(types)}")
        if self.head_dim is None and \
                self.hidden_size % self.num_attention_heads:
            raise ValueError("head_dim null: hidden_size must be whole "
                             "heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache: at least 2 taps")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer 'conv' | 'full', feed-forward 'dense' | 'moe') of each
        layer, in order."""
        return tuple(
            (_KINDS[t], "dense" if layer < self.num_dense_layers else "moe")
            for layer, t in enumerate(self.layer_types))

    def count(self, kind: str) -> int:
        return sum(kind in pair for pair in self.kinds)

    @property
    def key_dim(self) -> int:
        """A head's width, the model's."""
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def rope_theta(self) -> float:
        return float((self.rope_parameters or {}).get("rope_theta", 1e6))

    @property
    def pairs(self) -> int:
        """Key heads a row of the pool holds (``walked.lane_pairs``)."""
        return lane_pairs(self.num_key_value_heads, self.key_dim)

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_layer(self) -> int:
        """Layers that keep every key: the page pools' depth."""
        return self.count("full")

    @property
    def n_kv_head(self) -> int:
        """The pool's heads: the key heads, paired."""
        return self.num_key_value_heads // self.pairs

    @property
    def d_head(self) -> int:
        """The pool's row: a pair of heads."""
        return self.key_dim * self.pairs


class Lfm2MoeModel(WalkedModel):
    #: ``serving_unsupported`` is the common one: the prefill takes a chunk
    #: (module docstring); the engine refuses the prefix cache, KV tiering
    #: and speculation for any model with ``serving_state``
    serving_aux = _COUNTERS
    #: ``walked.serving_layouts``: from the default layout the tick copied
    #: each full layer's ``q_w`` before its matmul (described-v5e compile,
    #: PR 65); output-major it copies none
    query_projections = ("q_w",)

    def serving_cache_layers(self) -> Dict[str, int]:
        """Layers by the kind of cache they keep."""
        return {k: self.config.count(k) for k in ("full", "conv")}

    def serving_state(self, slots: int) -> Dict[str, Any]:
        """What a request keeps beside its pages, by slot (axis 1): the
        last ``conv_L_cache - 1`` rows of each conv layer's ``z``, oldest
        first, side by side."""
        cfg = self.config
        return {"conv": jax.ShapeDtypeStruct(
            (cfg.count("conv"), slots,
             (cfg.conv_L_cache - 1) * cfg.hidden_size),
            jnp.dtype(cfg.param_dtype))}

    def init(self, rng) -> Dict[str, Any]:
        """Every matrix normal(0, initializer_range), norm weights 1, the
        depthwise filter torch's default for its fan-in (uniform in +-
        K**-0.5), ``router_bias`` (``expert_bias``) float32 zeros as the
        source starts it.  Drawn a layer at a time in ``param_dtype``."""
        cfg = self.config
        d, dt, std = cfg.hidden_size, jnp.dtype(cfg.param_dtype), \
            cfg.initializer_range
        hq, hkv, D, K = (cfg.n_head, cfg.num_key_value_heads, cfg.key_dim,
                         cfg.conv_L_cache)
        f, fe, e = (cfg.intermediate_size, cfg.moe_intermediate_size,
                    cfg.num_experts)
        keys = jax.random.split(rng, 5)

        def norm(key, shape):
            return (jax.random.normal(key, shape, F32) * std).astype(dt)

        def conv(key):
            k = jax.random.split(key, 3)
            bound = 1.0 / math.sqrt(K)
            return {"in_w": norm(k[0], (d, 3 * d)),
                    "conv_w": jax.random.uniform(
                        k[1], (K, d), F32, -bound, bound).astype(dt),
                    "out_w": norm(k[2], (d, d))}

        def full(key):
            k = jax.random.split(key, 4)
            return {"q_w": norm(k[0], (d, hq * D)),
                    "k_w": norm(k[1], (d, hkv * D)),
                    "v_w": norm(k[2], (d, hkv * D)),
                    "o_w": norm(k[3], (hq * D, d))}

        def dense(key):
            k = jax.random.split(key, 3)
            return {"gate_w": norm(k[0], (d, f)), "up_w": norm(k[1], (d, f)),
                    "down_w": norm(k[2], (f, d))}

        def moe(key):                   # of the layer's four keys, the first
            return {"router_w": norm(jax.random.split(key, 4)[0], (d, e))}

        def experts(key):               # the other three
            k = jax.random.split(key, 4)
            return {"gate_w": norm(k[1], (e, d, fe)),
                    "up_w": norm(k[2], (e, d, fe)),
                    "down_w": norm(k[3], (e, fe, d))}

        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "norm_f": jnp.ones((d,), dt)}
        for name, layer, ones, key, whole in (
                ("conv", conv, {"ln1": d}, keys[1], None),
                ("full", full, {"ln1": d, "q_norm": D, "k_norm": D},
                 keys[2], None),
                ("dense", dense, {"ln2": d}, keys[3], None),
                ("moe", moe, {"ln2": d}, keys[4], experts)):
            n = cfg.count(name)
            if n:
                of = jax.random.split(key, n)
                out[name] = draw_layers(layer, of, ones, dt)
                if whole:
                    out[name]["router_bias"] = tuple(
                        jnp.zeros((e,), F32) for _ in range(n))
                    out[name].update(jax.lax.map(whole, of))
        return out

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        from empty state (no cache, every position live)."""
        cfg = self.config
        B, T = tokens.shape
        K = cfg.conv_L_cache
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

        def conv(i, cp, h):
            gate, z = _conv_in(cp, h)
            padded = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
            return _conv_out(cp, gate, [padded[:, j:j + T]
                                        for j in range(K)])

        def full(i, q, k, v):
            return causal_self_attention(
                q, k, v, cfg.attn_impl == "flash",
                sm_scale=default_scale(cfg.key_dim))

        logits, stats = _layers(cfg, params, tokens, positions, None, conv,
                                full)
        return (logits, _aux(cfg, stats)) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, state,
                          impl: Optional[str] = None, aux: bool = False,
                          **unbuilt):
        """One decode tick of every slot: the conv layers on the slots'
        kept rows and ``ds_paged_decode_attn`` over the two paired pools
        ``[full layers, pages, Hkv / pairs, page_len, pairs * D]``;
        ``gpt2_decode_step_paged``'s contract plus the request state.
        Returns (logits [S, V], k_pool, v_pool, state, new_lengths) and,
        with ``aux``, the tick's counters.  An inactive slot's pages and
        rows are neither read nor written."""
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        lengths, positions, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, k_pool.shape[3], cfg.n_positions)
        pool = PairedPagePool((k_pool, v_pool), page_ids, offs, active,
                              kv_heads=cfg.num_key_value_heads)
        scale = default_scale(cfg.key_dim)
        # the rows are read from the leaf as it came and written once,
        # stacked, at the end (``walked.shift_tail`` says why)
        kept = []

        def conv(i, cp, h):
            gate, z = _conv_in(cp, h)
            taps, tail = shift_tail_lanes(state["conv"][i], z[:, 0], active)
            kept.append(tail)
            return _conv_out(cp, gate, [t[:, None] for t in taps])

        def full(i, q, k, v):
            pool.write(i, k[:, :, 0], v[:, :, 0])
            return pool.attend(i, q[:, :, 0], page_table, att_len,
                               impl=impl, sm_scale=scale)[:, :, None]

        logits, stats = _layers(cfg, params, tokens[:, None],
                                positions[:, None], active, conv, full)
        new = {"conv": jnp.stack(kept)} if kept else dict(state)
        out = (logits[:, 0], *pool.arrays(), new,
               lengths + active.astype(jnp.int32))
        if aux:
            live = jnp.sum(active.astype(jnp.int32))
            out += (_aux(cfg, stats,
                         full_kv_tokens=jnp.sum(att_len) * cfg.count("full"),
                         conv_slot_layers=live * cfg.count("conv")),)
        return out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool, *, state, slot, aux: bool = False,
                      **unbuilt):
        """Prefill of one request, or of one CHUNK of its prompt, into the
        pools and into ``slot`` of the request state.  tokens [1, Tq] are
        the prompt's tokens from ``prefix_len`` on, right-padded to the
        bucket; ``delta_len``, ``prefix_len``, ``page_row`` [max_pages] and
        ``slot`` traced.  With ``prefix_len`` 0 a conv layer starts from
        zero rows, whatever the slot holds, and the full layers read no
        page; otherwise from what the chunk before left in the slot and in
        the request's pages.  Returns (logits [1, Tq, V], k_pool, v_pool,
        state); ``logits[0, delta_len - 1]`` scores the first generated
        token.  The slot's rows are OVERWRITTEN with ``z`` at the last
        ``K - 1`` positions before ``prefix_len + delta_len``."""
        self.refuse(unbuilt)
        cfg = self.config
        Tq, K = tokens.shape[1], cfg.conv_L_cache
        page_len = k_pool.shape[3]
        cap = page_row.shape[0] * page_len
        i32 = jnp.int32
        prefix_len = jnp.asarray(prefix_len, i32)
        delta_len = jnp.asarray(delta_len, i32)
        slot = jnp.asarray(slot, i32)
        valid, page_ids, offs, _, positions = prefill_index(
            page_row, delta_len, Tq, page_len, prefix_len, cfg.n_positions)
        pool = PairedPagePool((k_pool, v_pool), page_ids, offs, valid,
                              kv_heads=cfg.num_key_value_heads)
        flash, scale = cfg.attn_impl == "flash", default_scale(cfg.key_dim)
        first = prefix_len == 0
        kept = {"conv": []}

        def conv(i, cp, h):
            gate, z = _conv_in(cp, h)                       # [1, Tq, d]
            # one slice of the leaf (``leaf[i]`` first would copy the
            # layer); zeros for a request's first chunk
            tail = jax.lax.dynamic_slice(
                state["conv"], (i, slot, 0),
                (1, 1) + state["conv"].shape[2:]).reshape(K - 1, -1)
            tail = jnp.where(first, jnp.zeros_like(tail), tail)
            padded = jnp.concatenate([tail.astype(z.dtype), z[0]])
            kept["conv"].append(jax.lax.dynamic_slice_in_dim(
                padded, delta_len, K - 1, axis=0).reshape(-1))
            return _conv_out(cp, gate, [padded[None, j:j + Tq]
                                        for j in range(K)])

        def full(i, q, k, v):
            pool.write(i, k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))

            def context():
                with jax.named_scope("chunk_context"):
                    # the request's pages of this layer out of every
                    # layer's in one row (``t[i]`` first would copy the
                    # layer), then the model's own heads
                    ctx_k, ctx_v = (pool.unpaired(prefix_keys(
                        t, i * pool.per_layer + page_row, prefix_len))
                        for t in pool.flat())
                return context_attention(
                    q, k, v, ctx_k, ctx_v, jnp.minimum(prefix_len, cap),
                    flash, sm_scale=scale)

            return jax.lax.cond(
                first, lambda: causal_self_attention(q, k, v, flash,
                                                     sm_scale=scale),
                context)

        logits, stats = _layers(cfg, params, tokens, positions,
                                valid, conv, full)
        out = (logits, *pool.arrays(), write_slot_state(state, kept, slot))
        if aux:
            out += (_aux(cfg, stats),)
        return out


# -- the layer's parts ----------------------------------------------------

def _conv_in(cp, h):
    """h [..., d] (normed) -> the output gate ``C`` [..., d] and the
    convolution's input ``z = B * u`` [..., d], both in h's dtype (what a
    request keeps is ``z``)."""
    with jax.named_scope("conv_in"):
        bcu = h @ cp["in_w"].astype(h.dtype)
    with jax.named_scope("conv_gate"):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        return c, b * u


def _conv_out(cp, gate, taps):
    """The K rows of ``z`` under the filter, oldest first, each [..., d] ->
    the mixer's output [..., d]: the convolution (float32, no activation),
    the gate, ``W_out``."""
    with jax.named_scope("conv_gate"):
        y = (gate.astype(F32) * conv_taps(cp["conv_w"], taps)
             ).astype(gate.dtype)
    with jax.named_scope("conv_out"):
        return y @ cp["out_w"].astype(y.dtype)


def _full_qkv(cfg: Lfm2MoeConfig, fp, h, positions):
    """h [B, T, d] (normed), positions [B, T] -> q [B, Hq, T, D], k, v [B,
    Hkv, T, D]; q and k normed a head, then rotated."""
    eps, theta = cfg.norm_eps, cfg.rope_theta
    q = project_heads(h, fp["q_w"], cfg.n_head)
    k = project_heads(h, fp["k_w"], cfg.num_key_value_heads)
    v = project_heads(h, fp["v_w"], cfg.num_key_value_heads)
    return (rope(rms_norm(q, fp["q_norm"], eps), positions, theta),
            rope(rms_norm(k, fp["k_norm"], eps), positions, theta), v)


def _experts(cfg: Lfm2MoeConfig, ep, stacked, index: int, x, valid):
    """Expert layer ``index`` (among the expert layers) on normed x [N, d]:
    -> (the sum [N, d], MoEStats).  ``stacked``: every layer's experts
    flat; the kernel finds this layer's at ``index * num_experts``."""
    with jax.named_scope("moe"):
        routing = route_sigmoid_topk(
            x, ep["router_w"],
            ep["router_bias"] if cfg.use_expert_bias else None,
            cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            renormalize=cfg.norm_topk_prob, eps=_NORM_EPS)
        return dropless_moe(
            x, ep["router_w"], stacked["gate_w"], stacked["up_w"],
            stacked["down_w"], cfg.num_experts_per_tok,
            expert_offset=index * cfg.num_experts, valid=valid,
            routing=routing)


def _aux(cfg: Lfm2MoeConfig, stats, **counted) -> Dict[str, jnp.ndarray]:
    """The call's counters: the expert layers' (experts hit and live
    assignments summed over layers; the busiest expert's rows over the mean
    rows an expert, largest over layers), and of a tick ``full_kv_tokens``
    (the live keys ``ds_paged_decode_attn`` read, summed over the full
    layers) and ``conv_slot_layers`` (live slots x conv layers: the rows
    kept)."""
    zero = jnp.zeros((), jnp.int32)
    imb = [s.max_rows / (jnp.maximum(s.rows, 1).astype(F32)
                         / cfg.num_experts) for s in stats]
    out = {"moe_experts_hit": sum((s.experts_hit for s in stats), zero),
           "moe_rows": sum((s.rows for s in stats), zero),
           "moe_load_imbalance": jnp.max(jnp.stack(imb)) if imb
           else jnp.zeros((), F32)}
    out.update({name: jnp.asarray(counted.get(name, 0), jnp.int32)
                for name in ("full_kv_tokens", "conv_slot_layers")})
    return out


def _layers(cfg: Lfm2MoeConfig, params, tokens, positions, valid, conv,
            full):
    """The forward over sequences tokens [B, T] at ``positions`` [B, T]:
    ``conv(i, cp, h)`` -> [B, T, d] and ``full(i, q, k, v)`` -> [B, Hq, T,
    D] are the caller's forms of the two mixers (``i``: the layer's index
    among its kind; they keep what a cache keeps); ``valid`` [B * T] bool
    or None: the rows that reach an expert.  Returns (logits, the expert
    layers' statistics)."""
    eps = cfg.norm_eps
    B, T = tokens.shape
    stacked = stacked_experts(params) if cfg.count("moe") else None
    seen = {"conv": 0, "full": 0, "dense": 0, "moe": 0}
    stats = []
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for kind, ffn in cfg.kinds:
        with jax.named_scope("layer"):
            i = seen[kind]
            mp = at(params[kind], i)
            h = rms_norm(x, mp["ln1"], eps)
            if kind == "conv":
                with jax.named_scope("conv"):
                    x = x + conv(i, mp, h)
            else:
                with jax.named_scope("attn"), jax.named_scope("full_attn"):
                    out = full(i, *_full_qkv(cfg, mp, h, positions))
                    x = x + merge_heads(out) @ mp["o_w"].astype(x.dtype)
            fp = at(params[ffn], seen[ffn])
            h = rms_norm(x, fp["ln2"], eps)
            if ffn == "dense":
                x = x + dense_ffn(fp, h)
            else:
                y, st = _experts(cfg, fp, stacked, seen[ffn],
                                 h.reshape(B * T, -1), valid)
                stats.append(st)
                x = x + y.reshape(x.shape)
            seen[kind] += 1
            seen[ffn] += 1
    return tied_head(x, params["norm_f"], params["wte"], eps), stats
