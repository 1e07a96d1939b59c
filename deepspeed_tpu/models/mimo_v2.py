"""Xiaomi MiMo-V2 (HF ``model_type: mimo_v2``; MiMo-V2-Flash, MiMo-V2.5):
a pre-norm decoder whose attention layers are of two kinds, told apart by
a published list (``hybrid_layer_pattern``: 0 full, 1 sliding window), and
whose feed-forward layers are dense first and expert layers after
(``moe_layer_freq``).

    x <- x + attn_l(RMSNorm(x)); x <- x + ffn_l(RMSNorm(x));
    logits = RMSNorm(x) W_head          (untied)

* attention: ``q = x W_q`` on ``num_attention_heads`` heads of
  ``head_dim``; ``k = x W_k`` on the kind's key heads
  (``num_key_value_heads`` full, ``swa_num_key_value_heads`` window) of
  ``head_dim``; ``v = attention_value_scale * x W_v`` of ``v_head_dim``,
  NARROWER than the keys; no bias.  Rotate-half RoPE on the first
  ``int(head_dim * partial_rotary_factor)`` dims of q and k, the rest
  untouched, at ``rope_theta`` (full) or ``swa_rope_theta`` (window).
  Query head ``h`` reads key head ``h // (Hq / Hkv)``.  Scores at
  ``1 / sqrt(head_dim)`` over ``j <= t`` (full) or ``t - sliding_window <
  j <= t`` (window).  A window layer's softmax has one more column a
  head, its learned ``attention_sink_bias``: it takes weight and gives no
  value.  ``W_o`` from ``Hq * v_head_dim``.
* dense FFN (``moe_layer_freq[l] == 0``): ``down(silu(gate x) * up x)``
  at ``intermediate_size``.
* expert layer: ``s = sigmoid(x_f32 W_r)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  weights ``s[chosen] / sum`` (``norm_topk_prob``) times
  ``routed_scaling_factor`` (null: 1); SwiGLU experts at
  ``moe_intermediate_size``; no shared expert.  ``experts_held=(first,
  count)`` is this chip's share (``moe/dropless.py``): the weights hold
  those only and the layer returns their part of the sum.

Not built, refused at construction: group-limited routing, shared
experts, a sink in the full layers, biases, tied embeddings, kinds of
different head widths.  Not here at all: the multi-token-prediction
layers and the vision and audio towers of the published model.

This file is the model's SERVING surface (``ServeEngine``'s protocol).
The two kinds of layer keep two kinds of cache:

* a FULL layer keeps every key: the engine's page pool, whose depth
  ``config.n_layer`` counts the full layers only, ``n_kv_head`` key heads,
  keys ``d_head`` wide at rest and values ``d_head_v``;
* a WINDOW layer keeps its last ``sliding_window`` keys and values BY
  SLOT, as request state (``serving_state``): ``window_k [Lw, slots, Hkv,
  W, Dk]``, ``window_v [..., Dv]``, position ``p`` at row ``p % W``, keys
  rotated before they are stored.  Its bytes a slot do not grow with the
  context.  The decode tick writes one row a slot in place; the prefill
  of a request overwrites the ring of the slot it is admitted to.

Keys at rest are ``k_width`` wide: ``head_dim`` rounded up to whole
128-lane tiles where it is wider than one (192 -> 256, the upper lanes
zero), which is how the TPU's tiled layout would hold a 192-wide row
anyway; said once here, every DMA and matmul of the kernels is then
lane-aligned.  PERF.md (section 7) has what it costs and the layouts
that would not pad.

Parameter tree: ``wte``, ``lm_head`` [d, V], ``norm_f``; the layers by
kind in the order they occur: ``full`` and ``window`` (``ln1``, ``q_w``,
``k_w``, ``v_w``, ``o_w``; ``window`` also ``sink`` [Hq]), ``dense``
(``ln2``, ``gate_w``, ``up_w``, ``down_w``), ``moe`` (``ln2``,
``router_w`` [d, E], ``router_bias`` [E], ``gate_w`` / ``up_w`` [held, d,
f], ``down_w`` [held, f, d]); every matrix input-major.

ONE rule for the layout: what a layer reads by its own index is a leaf
of its own.  ``params[kind][name]`` is a TUPLE of one array a layer
(``params["window"]["q_w"][i]`` is a leaf, vectors and norm weights
included: no size decides), because the layers are walked in Python and
never scanned, so nothing needs a ``[layers, ...]`` axis, and a static
slice of one is a copy: XLA wrote every ``q_w`` of a decode tick to HBM
transposed, and passed every window layer's ``k_w`` and ``v_w`` through
fast memory, before the matmul read it (0.83 GB a tick at MiMo-V2.5's
widths, 2 of its 21.5 ms; PERF.md section 6, PR 39).  ``walked.at`` is
the one place that picks a layer.  A leaf a layer still left one copy a
``q_w`` a tick: the compiler wants the wide operand of ``h @ q_w``
output-major and may not choose an entry parameter's layout.  Where a
``q_w`` RESTS is decided in one place since PR 55,
``WalkedModel.serving_layouts`` (``query_projections`` below names the
leaves): ``ServeEngine`` makes its copy of them output-major once, at
set-up (``walked.OutputMajor``: ``w.T``), and ``project_heads`` reads
either; the tree here and its shapes are as they were.  The experts alone
stay stacked, ``moe``'s ``gate_w`` / ``up_w`` / ``down_w`` as ``[layers,
held, ...]`` arrays: they reach their kernels whole, every layer's held experts flat
(``walked.stacked_experts``, a reshape of the leading axes), and the
kernel finds a layer's by ``expert_offset``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .walked import (F32, PagePool, Rings, ServedConfig, WalkedModel, at,
                     causal_self_attention, decode_index, default_scale,
                     dense_ffn, draw_layers, held_expert_counters, lm_head,
                     merge_heads, prefill_index, project_heads, ring_positions,
                     rms_norm, rope, routed_experts, stacked_experts,
                     write_slot_state)

_LANES = 128


@dataclasses.dataclass(frozen=True)
class MimoV2Config(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384      # the dense FFN's
    moe_intermediate_size: int = 2048   # ONE expert's
    num_hidden_layers: int = 48
    hybrid_layer_pattern: Tuple[int, ...] = ()      # 0 full, 1 window
    moe_layer_freq: Tuple[int, ...] = ()            # 0 dense, 1 experts
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    n_shared_experts: Optional[int] = None
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = None
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    # the program's
    experts_held: Optional[Tuple[int, int]] = None    # (first, count)
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes

    def __post_init__(self):
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        unbuilt = {
            "n_group / topk_group != 1 (group-limited routing)":
                (self.n_group, self.topk_group) != (1, 1),
            "n_shared_experts (a shared expert)":
                bool(self.n_shared_experts),
            "add_full_attention_sink_bias": self.add_full_attention_sink_bias,
            "add_swa_attention_sink_bias false":
                not self.add_swa_attention_sink_bias,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            f"scoring_func {self.scoring_func!r} (only 'sigmoid')":
                self.scoring_func != "sigmoid",
            f"topk_method {self.topk_method!r} (only 'noaux_tc')":
                self.topk_method != "noaux_tc",
            f"hidden_act {self.hidden_act!r} (only 'silu')":
                self.hidden_act != "silu",
            "window layers of other head counts or widths than the full "
            "layers' (swa_num_attention_heads, swa_head_dim, "
            "swa_v_head_dim)":
                (self.swa_num_attention_heads, self.swa_head_dim,
                 self.swa_v_head_dim) != (self.num_attention_heads,
                                          self.head_dim, self.v_head_dim),
            "num_nextn_predict_layers (the multi-token-prediction "
            "layers)": self.num_nextn_predict_layers != 0,
        }
        self.check(unbuilt, self.n_routed_experts)
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            got = getattr(self, name)
            if len(got) != self.num_hidden_layers or set(got) - {0, 1}:
                raise ValueError(
                    f"{name}: {self.num_hidden_layers} entries of 0 or 1, "
                    f"one a layer; got {got}")
        for heads in (self.num_key_value_heads,
                      self.swa_num_key_value_heads):
            if self.num_attention_heads % heads:
                raise ValueError("num_attention_heads must be a multiple "
                                 "of each kind's key heads")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"rotary width {self.rotary_dim} of head_dim "
                             f"{self.head_dim}: even, at most the head")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def k_width(self) -> int:
        """A key at rest (module docstring): ``head_dim``, in whole lane
        tiles where it is wider than one."""
        d = self.head_dim
        return d if d <= _LANES else -(-d // _LANES) * _LANES

    def count(self, kind: str) -> int:
        """Layers of an attention kind ('full', 'window') or an FFN
        kind ('dense', 'moe')."""
        return sum(kind in pair for pair in _layers(self))

    def kv_heads(self, kind: str) -> int:
        return (self.num_key_value_heads if kind == "full"
                else self.swa_num_key_value_heads)

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_layer(self) -> int:
        """Layers that keep every key: the page pool's depth."""
        return self.count("full")

    @property
    def d_head(self) -> int:
        """The pool's key width."""
        return self.k_width

    @property
    def d_head_v(self) -> int:
        """The pool's value width."""
        return self.v_head_dim


def _layers(cfg: MimoV2Config):
    """(attention kind, FFN kind) of each layer, in order."""
    for a, f in zip(cfg.hybrid_layer_pattern, cfg.moe_layer_freq):
        yield ("window" if a else "full"), ("moe" if f else "dense")


class MimoV2Model(WalkedModel):
    #: the engine refuses the prefix cache, KV tiering and migration for
    #: any model with ``serving_state``: a page of full-layer keys is no
    #: prefix without the window layers' last keys at its boundary; the
    #: rest are arms these paged steps do not have (chunked prefill: the
    #: prefill takes no prefix, ``models/cohere2_moe.py``'s does)
    serving_unsupported = WalkedModel.serving_unsupported + (
        "prefill_chunk_len",)
    serving_aux = WalkedModel.serving_aux + ("full_kv_tokens",
                                             "window_kv_rows")
    query_projections = ("q_w",)      # walked.serving_layouts

    def serving_cache_layers(self) -> Dict[str, int]:
        """Layers by the kind of cache they keep."""
        return {k: self.config.count(k) for k in ("full", "window")}

    def serving_state(self, slots: int) -> Dict[str, Any]:
        """What a request keeps beside its pages, by slot (axis 1): the
        window layers' rings of their last ``sliding_window`` keys and
        values."""
        cfg = self.config
        lead = (cfg.count("window"), slots, cfg.kv_heads("window"),
                cfg.sliding_window)
        dt = jnp.dtype(cfg.param_dtype)
        return {"window_k": jax.ShapeDtypeStruct(lead + (cfg.k_width,), dt),
                "window_v": jax.ShapeDtypeStruct(lead + (cfg.v_head_dim,),
                                                 dt)}

    def init(self, rng) -> Dict[str, Any]:
        """Every matrix normal(0, initializer_range), norm weights 1,
        ``router_bias`` (``e_score_correction_bias``) 0 as the source
        starts it.  The sinks normal(ln(sliding_window), 1): beside a
        window of keys that all score alike (weights drawn from a seed
        give scores near 0) such a sink takes about half the softmax's
        weight, so leaving it out shows; around 0 it would take a
        hundredth and hide in bfloat16's rounding.  Drawn a layer at a
        time in ``param_dtype``, each layer from its own key of the
        kind's: a kind's per-layer leaves, stacked, are what one draw over
        the ``[layers]`` axis gives (the module docstring has the
        layout)."""
        cfg = self.config
        d, dt = cfg.hidden_size, jnp.dtype(cfg.param_dtype)
        std = cfg.initializer_range
        hq, dk, dv = cfg.n_head, cfg.head_dim, cfg.v_head_dim
        f, e, held = (cfg.moe_intermediate_size, cfg.n_routed_experts,
                      cfg.held[1])
        keys = jax.random.split(rng, 6)

        def norm(key, shape, scale=std, mean=0.0):
            return (jax.random.normal(key, shape, F32) * scale
                    + mean).astype(dt)

        def attn(hkv):
            def layer(key):
                k = jax.random.split(key, 5)
                return {"q_w": norm(k[0], (d, hq * dk)),
                        "k_w": norm(k[1], (d, hkv * dk)),
                        "v_w": norm(k[2], (d, hkv * dv)),
                        "o_w": norm(k[3], (hq * dv, d)),
                        "sink": norm(k[4], (hq,), 1.0,
                                     math.log(cfg.sliding_window))}
            return layer

        def dense(key):
            k = jax.random.split(key, 3)
            return {"gate_w": norm(k[0], (d, cfg.intermediate_size)),
                    "up_w": norm(k[1], (d, cfg.intermediate_size)),
                    "down_w": norm(k[2], (cfg.intermediate_size, d))}

        def moe(key):                   # of the layer's four keys, the first
            return {"router_w": norm(jax.random.split(key, 4)[0], (d, e)),
                    "router_bias": jnp.zeros((e,), dt)}

        def experts(key):               # the other three
            k = jax.random.split(key, 4)
            return {"gate_w": norm(k[1], (held, d, f)),
                    "up_w": norm(k[2], (held, d, f)),
                    "down_w": norm(k[3], (held, f, d))}

        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "lm_head": norm(keys[1], (d, cfg.vocab_size)),
               "norm_f": jnp.ones((d,), dt)}
        for name, layer, ln, key, whole in (
                ("full", attn(cfg.kv_heads("full")), "ln1", keys[2], None),
                ("window", attn(cfg.kv_heads("window")), "ln1", keys[3],
                 None),
                ("dense", dense, "ln2", keys[4], None),
                ("moe", moe, "ln2", keys[5], experts)):
            n = cfg.count(name)
            if n:
                of = jax.random.split(key, n)
                out[name] = draw_layers(layer, of, {ln: d}, dt)
                if whole:
                    out[name].update(jax.lax.map(whole, of))
        out.get("full", {}).pop("sink", None)
        return out

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        (no cache, every position live)."""
        logits, _, stats = _sequence(self.config, params, tokens, None)
        return (logits, _aux(self.config, stats, 0, 0)) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, state,
                          impl: Optional[str] = None, aux: bool = False,
                          **unbuilt):
        """One decode tick of every slot; ``gpt2_decode_step_paged``'s
        contract plus the request state.  Returns (logits [S, V], k_pool,
        v_pool, state, new_lengths) and, with ``aux``, the tick's
        counters.  An inactive slot's pages and rings are neither read
        nor written."""
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        S = page_table.shape[0]
        eps, scale = cfg.layernorm_epsilon, default_scale(cfg.head_dim)
        lengths, positions, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, k_pool.shape[3], cfg.n_positions)
        pool = PagePool((k_pool, v_pool), page_ids, offs, active)
        rings = Rings(state["window_k"], state["window_v"], positions,
                      active)
        stacked = stacked_experts(params) if cfg.count("moe") else None
        seen = {"full": 0, "window": 0, "dense": 0, "moe": 0}
        stats = []
        with jax.named_scope("embed"):
            x = params["wte"][tokens]                       # [S, d]
        for kind, ffn in _layers(cfg):
            with jax.named_scope("layer"):
                i = seen[kind]
                ap = at(params[kind], i)
                with jax.named_scope("attn"), \
                        jax.named_scope("attn_" + kind):
                    h = rms_norm(x, ap["ln1"], eps)
                    q, k, v = (t[:, :, 0] for t in _qkv(
                        cfg, kind, ap, h[:, None], positions[:, None]))
                    if kind == "full":
                        pool.write(i, k, v)
                        attn = pool.attend(i, q, page_table, att_len,
                                           impl=impl, sm_scale=scale)
                    else:
                        rings.write(i, k, v)
                        attn = rings.attend(i, q, att_len, ap["sink"],
                                            impl=impl, sm_scale=scale)
                    x = x + attn.reshape(S, -1) @ ap["o_w"].astype(x.dtype)
                x = _ffn(cfg, params, stacked, ffn, seen[ffn], x, active,
                         stats)
                seen[kind] += 1
                seen[ffn] += 1
        logits = lm_head(x, params["norm_f"], params["lm_head"], eps)
        out = (logits, *pool.arrays(),
               dict(zip(("window_k", "window_v"), rings.arrays())),
               lengths + active.astype(jnp.int32))
        if aux:
            out += (_aux(cfg, stats, jnp.sum(att_len),
                         jnp.sum(jnp.minimum(att_len, rings.length))),)
        return out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool, *, state, slot, aux: bool = False,
                      **unbuilt):
        """Prefill of one request into the pool (the full layers' keys)
        and into ``slot`` of the request state (the window layers'
        rings).  tokens [1, Tq] right-padded to the bucket; ``delta_len``,
        ``page_row`` [max_pages] and ``slot`` traced.  No cached prefix
        (``prefix_len`` is not read): the engine refuses the prefix cache
        for this model.  Returns (logits [1, Tq, V], k_pool, v_pool,
        state); ``logits[0, delta_len - 1]`` scores the first generated
        token.  The slot's rings are OVERWRITTEN with the last
        ``sliding_window`` positions before ``delta_len``, each at its row
        ``p % W``; with fewer, rows ``delta_len ..`` hold nothing a decode
        tick reads."""
        self.refuse(unbuilt)
        cfg, Tq = self.config, tokens.shape[1]
        W = state["window_k"].shape[3]
        delta_len = jnp.asarray(delta_len, jnp.int32)
        slot = jnp.asarray(slot, jnp.int32)
        valid, page_ids, offs, _, _ = prefill_index(
            page_row, delta_len, Tq, k_pool.shape[3])
        pool = PagePool((k_pool, v_pool), page_ids, offs, valid)
        # ring row r: the last position before delta_len that is r mod W
        ring_pos = jnp.clip(ring_positions(delta_len, W), 0, Tq - 1)
        logits, kept, stats = _sequence(cfg, params, tokens, delta_len)
        rings, i = {"window_k": [], "window_v": []}, 0
        for kind, k, v in kept:                 # [Hkv, Tq, Kw], [.., Dv]
            if kind == "window":
                rings["window_k"].append(k[:, ring_pos])
                rings["window_v"].append(v[:, ring_pos])
                continue
            pool.write(i, k.transpose(1, 0, 2), v.transpose(1, 0, 2))
            i += 1
        out = (logits, *pool.arrays(), write_slot_state(state, rings, slot))
        if aux:
            out += (_aux(cfg, stats, delta_len, jnp.minimum(delta_len, W)),)
        return out


# -- the layer's parts ----------------------------------------------------

def _qkv(cfg: MimoV2Config, kind: str, ap, h, positions):
    """h [B, T, d] (normed), positions [B, T] -> q [B, Hq, T, Kw], k
    [B, Hkv, T, Kw] (both rotated, then widened to the key's width at
    rest with zeros: scores do not change), v [B, Hkv, T, Dv] (scaled by
    ``attention_value_scale``: what the cache holds)."""
    hkv = cfg.kv_heads(kind)
    theta = cfg.rope_theta if kind == "full" else cfg.swa_rope_theta

    def rotated(name, n):
        t = rope(project_heads(h, ap[name], n), positions, theta,
                 rotary_dim=cfg.rotary_dim)
        return jnp.pad(t, ((0, 0),) * 3 + ((0, cfg.k_width - cfg.head_dim),))

    q, k = rotated("q_w", cfg.n_head), rotated("k_w", hkv)
    v = project_heads(h, ap["v_w"], hkv)
    return q, k, v * jnp.asarray(cfg.attention_value_scale, v.dtype)


def _self_attention(cfg: MimoV2Config, kind: str, ap, q, k, v):
    window = cfg.sliding_window if kind == "window" else None
    sink = ap["sink"] if kind == "window" else None
    # a window layer's band is two blocks of 256 a query block
    block = 256 if kind == "window" else 512
    return causal_self_attention(
        q, k, v, cfg.attn_impl == "flash", window=window, sink=sink,
        sm_scale=default_scale(cfg.head_dim), block_q=block, block_k=block)


def _experts(cfg: MimoV2Config, ep, stacked, index: int, x, valid):
    """The expert layer on normed x [N, d]: this share's part of the
    sum.  ``stacked``: every layer's held experts flat."""
    with jax.named_scope("moe"):
        return routed_experts(
            x, ep["router_w"], ep["router_bias"], stacked, index,
            top_k=cfg.num_experts_per_tok, held=cfg.held, valid=valid,
            act="swiglu", scale=cfg.routed_scaling_factor or 1.0,
            renormalize=cfg.norm_topk_prob)


def _ffn(cfg: MimoV2Config, params, stacked, kind: str, i: int, x, valid,
         stats):
    """x [N, d] -> x + ffn(norm(x)); an expert layer's statistics are
    appended to ``stats``."""
    fp = at(params[kind], i)
    h = rms_norm(x, fp["ln2"], cfg.layernorm_epsilon)
    if kind == "dense":
        return x + dense_ffn(fp, h)
    out, st = _experts(cfg, fp, stacked, i, h, valid)
    stats.append(st)
    return x + out


def _aux(cfg: MimoV2Config, stats, full_kv_tokens,
         window_kv_rows) -> Dict[str, jnp.ndarray]:
    """The call's counters: the expert layers' as ``NemotronHModel``'s
    (of the HELD experts), and what the two kinds of cache held for the
    call's live sequences: ``full_kv_tokens`` keys a full layer,
    ``window_kv_rows`` ring rows a window layer."""
    return {**held_expert_counters(stats, cfg.held[1]),
            "full_kv_tokens": jnp.asarray(full_kv_tokens, jnp.int32),
            "window_kv_rows": jnp.asarray(window_kv_rows, jnp.int32)}


def _sequence(cfg: MimoV2Config, params, tokens, delta_len):
    """The forward over whole sequences tokens [B, T] from nothing.
    ``delta_len`` (traced, B == 1) is the live length inside a padded
    bucket (the padding lies after it, where a causal layer keeps it out
    of every live row); None: every position is live.  Returns (logits,
    what a cache keeps of sequence 0: per attention layer its kind and
    (k [Hkv, T, Kw], v [Hkv, T, Dv]), the expert layers' statistics)."""
    B, T = tokens.shape
    eps = cfg.layernorm_epsilon
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    valid = None if delta_len is None \
        else jnp.tile(jnp.arange(T) < delta_len, B)
    stacked = stacked_experts(params) if cfg.count("moe") else None
    seen = {"full": 0, "window": 0, "dense": 0, "moe": 0}
    kept, stats = [], []
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for kind, ffn in _layers(cfg):
        with jax.named_scope("layer"):
            ap = at(params[kind], seen[kind])
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                h = rms_norm(x, ap["ln1"], eps)
                q, k, v = _qkv(cfg, kind, ap, h, positions)
                kept.append((kind, k[0], v[0]))
                attn = _self_attention(cfg, kind, ap, q, k, v)
                x = x + merge_heads(attn) @ ap["o_w"].astype(x.dtype)
            x = _ffn(cfg, params, stacked, ffn, seen[ffn],
                     x.reshape(B * T, -1), valid, stats).reshape(x.shape)
            seen[kind] += 1
            seen[ffn] += 1
    logits = lm_head(x, params["norm_f"], params["lm_head"], eps)
    return logits, kept, stats
