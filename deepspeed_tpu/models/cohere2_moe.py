"""Cohere Command A+ (HF ``model_type: cohere2_moe``): a decoder whose
block is PARALLEL on one LayerNorm, whose attention layers are of two
kinds by a published list (``layer_types``: ``sliding_attention`` |
``full_attention``), and whose every layer is an expert layer with
shared experts beside the routed ones.

    h = LN_l(x);  x <- x + attn_l(h) + ffn_l(h)
    logits = logit_scale * LN_f(x) W_emb^T          (tied)
    LN(x) = w * (x - mean) / sqrt(var + eps)        (float32, no bias)

* attention: ``q = h W_q`` on ``num_attention_heads`` heads of
  ``head_dim``; ``k = h W_k``, ``v = h W_v`` on ``num_key_value_heads``;
  no bias, no QK-norm; query head ``i`` reads key head ``i // (Hq /
  Hkv)``; scores at ``head_dim ** -0.5``, softmax in float32; ``W_o`` from
  ``Hq * head_dim``.  A WINDOW layer rotates q and k over all of the head
  in INTERLEAVED pairs ``(2i, 2i + 1)`` (``position_embedding_type:
  rope_gptj``) at ``rope_theta``, and query ``t`` sees keys ``t -
  sliding_window < j <= t``.  A FULL layer rotates nothing (no position
  at all) and sees every ``j <= t``.
* FFN, of the SAME ``h``: ``s = sigmoid(h_f32 W_r)`` over all
  ``num_experts``; the ``num_experts_per_tok`` largest; weights
  ``s[chosen] / sum`` (``norm_topk_prob``); SwiGLU experts at
  ``intermediate_size``; plus the ``num_shared_experts`` shared SwiGLU
  experts of the same width on every token, AVERAGED
  (``shared_expert_combination_strategy``): one SwiGLU of
  ``num_shared_experts * intermediate_size`` times ``1 /
  num_shared_experts``, which is how they are kept and run.
  ``experts_held=(first, count)`` is this chip's share
  (``moe/dropless.py``): the held experts' part of the routed sum, the
  shared experts whole.

Not built, refused at construction: a sequential block, RMSNorm
(``rms_norm_eps``), QK-norm, biases, an untied head, dense prefix layers
(``first_k_dense_replace``), another selection function or combination of
the shared experts, a partial or rotate-half rotation.  Not here at all:
the vision tower of the published model.

This file is the model's SERVING surface (``ServeEngine``'s protocol).
The two kinds of layer keep two kinds of cache, as ``models/mimo_v2.py``:

* a FULL layer keeps every key: the engine's page pool, whose depth
  ``config.n_layer`` counts the full layers only;
* a WINDOW layer keeps its last ``sliding_window`` keys and values BY
  SLOT, as request state (``serving_state``): ``window_k`` / ``window_v``
  ``[Lw, slots, Hkv, W, D]``, position ``p`` at row ``p % W``, keys
  rotated before they are stored.

A prompt longer than a prefill program is prefilled in CHUNKS
(``serving.prefill_chunk_len``): the engine runs them in order into ONE
slot, and what a chunk's queries need of the tokens before it is what the
chunks before it left in that slot: the window layers' rings (the last
``W`` keys before the chunk, read BEFORE the chunk's own are written) and
the full layers' pages.  ``prefill_paged`` therefore takes ``prefix_len``
(traced): with 0 it is the whole-sequence forward; else each layer's keys
are ``[context ; chunk]`` for ``flash_attention_fwd(ctx_live=)``: the
ring rolled into position order, or the prefix gathered from the pages
and rolled to the context's end.  Nothing is snapshot or copied aside.

Parameter tree: ``wte`` [V, d] (also the head), ``norm_f``; the layers by
kind in the order they occur, ``full`` and ``window``: ``ln``, ``q_w``,
``k_w``, ``v_w``, ``o_w``, ``router_w`` [d, E], ``shared_gate_w`` /
``shared_up_w`` [d, n_shared * f], ``shared_down_w`` [n_shared * f, d],
each a TUPLE of one array a layer (``models/mimo_v2.py``'s rule: what a
layer reads by its own index is a leaf of its own); ``experts``:
``gate_w`` / ``up_w`` [L, held, d, f], ``down_w`` [L, held, f, d] over ALL
layers in order, stacked: they reach their kernels whole.  Every matrix is
input-major; the form a ``q_w`` rests in inside an engine is
``WalkedModel.serving_layouts``'s to say (output-major: from this one
the tick wrote all four, 134 MB each, to HBM transposed before the matmul
that read it, 1.7 ms of ~21; PERF.md section 6, PR 55).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .walked import (F32, PagePool, Rings, ServedConfig, WalkedModel, at,
                     causal_self_attention, context_attention, decode_index,
                     default_scale, draw_layers, held_expert_counters,
                     merge_heads, prefill_index, prefix_keys, project_heads,
                     ring_positions, routed_experts, stacked_experts, swiglu,
                     write_slot_state)

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096       # ONE expert's, routed or shared
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    rotary_pct: float = 1.0
    position_embedding_type: str = "rope_gptj"
    layer_norm_eps: float = 1e-5
    rms_norm_eps: Optional[float] = None
    logit_scale: float = 1.0
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    expert_selection_fn: str = "sigmoid"
    shared_expert_combination_strategy: str = "average"
    first_k_dense_replace: int = 0
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    use_gated_activation: bool = True
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 200000
    # the program's
    initializer_range: float = 0.02
    experts_held: Optional[Tuple[int, int]] = None    # (first, count)
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unbuilt = {
            "use_parallel_block false (a sequential block)":
                not self.use_parallel_block,
            "rms_norm_eps (RMSNorm for the LayerNorm)":
                self.rms_norm_eps is not None,
            "use_qk_norm": self.use_qk_norm,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings false (an untied head)":
                not self.tie_word_embeddings,
            "first_k_dense_replace (dense prefix layers)":
                self.first_k_dense_replace != 0,
            f"expert_selection_fn {self.expert_selection_fn!r} (only "
            "'sigmoid')": self.expert_selection_fn != "sigmoid",
            "shared_expert_combination_strategy "
            f"{self.shared_expert_combination_strategy!r} (only 'average')":
                self.shared_expert_combination_strategy != "average",
            f"position_embedding_type {self.position_embedding_type!r} "
            "(only 'rope_gptj', interleaved pairs)":
                self.position_embedding_type != "rope_gptj",
            "rotary_pct other than 1 (a partial rotation)":
                self.rotary_pct != 1,
            f"hidden_act {self.hidden_act!r} ungated or not 'silu'":
                self.hidden_act != "silu" or not self.use_gated_activation,
        }
        self.check(unbuilt, self.num_experts)
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - set(_KINDS):
            raise ValueError(
                f"layer_types: {self.num_hidden_layers} entries of "
                f"{sorted(_KINDS)}, one a layer; got {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even: it is rotated in pairs")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """'full' | 'window' of each layer, in order."""
        return tuple(_KINDS[t] for t in self.layer_types)

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_layer(self) -> int:
        """Layers that keep every key: the page pool's depth."""
        return self.count("full")

    @property
    def d_head(self) -> int:
        return self.head_dim


class Cohere2MoeModel(WalkedModel):
    #: ``serving_unsupported`` is the common one: chunked prefill works
    #: (module docstring).  The prefix cache, KV tiering, the slot cache
    #: and speculation are refused by the engine for any model with
    #: ``serving_state``; int8 and LoRA are arms these paged steps do not
    #: have
    serving_aux = WalkedModel.serving_aux + (
        "full_kv_tokens", "window_kv_rows", "window_wrapped_slots",
        "flash_q_rows", "flash_live_keys")
    query_projections = ("q_w",)      # walked.serving_layouts

    def serving_cache_layers(self) -> Dict[str, int]:
        """Layers by the kind of cache they keep."""
        return {k: self.config.count(k) for k in ("full", "window")}

    def serving_state(self, slots: int) -> Dict[str, Any]:
        """What a request keeps beside its pages, by slot (axis 1): the
        window layers' rings of their last ``sliding_window`` keys and
        values."""
        cfg = self.config
        ring = jax.ShapeDtypeStruct(
            (cfg.count("window"), slots, cfg.n_kv_head, cfg.sliding_window,
             cfg.head_dim), jnp.dtype(cfg.param_dtype))
        return {"window_k": ring, "window_v": ring}

    def init(self, rng) -> Dict[str, Any]:
        """Every matrix normal(0, initializer_range), norm weights 1.
        Drawn a layer at a time in ``param_dtype``, each layer from its
        own key of its kind's (``models/mimo_v2.py::init`` has why)."""
        cfg = self.config
        d, dt = cfg.hidden_size, jnp.dtype(cfg.param_dtype)
        hq, hkv, dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        f, fs = cfg.intermediate_size, \
            cfg.intermediate_size * cfg.num_shared_experts
        held = cfg.held[1]
        keys = jax.random.split(rng, 4)

        def norm(key, shape):
            return (jax.random.normal(key, shape, F32)
                    * cfg.initializer_range).astype(dt)

        def layer(key):
            k = jax.random.split(key, 8)
            return {"q_w": norm(k[0], (d, hq * dh)),
                    "k_w": norm(k[1], (d, hkv * dh)),
                    "v_w": norm(k[2], (d, hkv * dh)),
                    "o_w": norm(k[3], (hq * dh, d)),
                    "router_w": norm(k[4], (d, cfg.num_experts)),
                    "shared_gate_w": norm(k[5], (d, fs)),
                    "shared_up_w": norm(k[6], (d, fs)),
                    "shared_down_w": norm(k[7], (fs, d))}

        def experts(key):
            k = jax.random.split(key, 3)
            return {"gate_w": norm(k[0], (held, d, f)),
                    "up_w": norm(k[1], (held, d, f)),
                    "down_w": norm(k[2], (held, f, d))}

        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "norm_f": jnp.ones((d,), dt),
               "experts": jax.lax.map(experts, jax.random.split(
                   keys[1], cfg.num_hidden_layers))}
        for name, key in (("full", keys[2]), ("window", keys[3])):
            n = cfg.count(name)
            if n:
                out[name] = draw_layers(layer, jax.random.split(key, n),
                                        {"ln": d}, dt)
        return out

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        (no cache, every position live)."""
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

        def attend(kind, i, q, k, v):
            return _self_attention(self.config, kind, q, k, v)

        logits, stats = _layers(self.config, params, tokens, positions,
                                None, attend)
        return (logits, _aux(self.config, stats)) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, state,
                          impl: Optional[str] = None, aux: bool = False,
                          **unbuilt):
        """One decode tick of every slot; ``gpt2_decode_step_paged``'s
        contract plus the request state.  Returns (logits [S, V], k_pool,
        v_pool, state, new_lengths) and, with ``aux``, the tick's
        counters.  An inactive slot's pages and rings are neither read
        nor written (a free slot's, and one that is still prefilling in
        chunks)."""
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        scale = default_scale(cfg.head_dim)
        lengths, positions, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, k_pool.shape[3], cfg.n_positions)
        pool = PagePool((k_pool, v_pool), page_ids, offs, active)
        rings = Rings(state["window_k"], state["window_v"], positions,
                      active)

        def attend(kind, i, q, k, v):
            q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
            if kind == "full":
                pool.write(i, k, v)
                out = pool.attend(i, q, page_table, att_len, impl=impl,
                                  sm_scale=scale)
            else:
                rings.write(i, k, v)
                out = rings.attend(i, q, att_len, None, impl=impl,
                                   sm_scale=scale)
            return out[:, :, None]

        logits, stats = _layers(cfg, params, tokens[:, None],
                                positions[:, None], active, attend)
        out = (logits[:, 0], *pool.arrays(),
               dict(zip(("window_k", "window_v"), rings.arrays())),
               lengths + active.astype(jnp.int32))
        if aux:
            W = rings.length
            out += (_aux(cfg, stats, jnp.sum(att_len),
                         jnp.sum(jnp.minimum(att_len, W)),
                         jnp.sum(att_len > W)),)
        return out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool, *, state, slot, aux: bool = False,
                      **unbuilt):
        """Prefill of one request, or of one CHUNK of it, into the pool
        (the full layers' keys) and into ``slot`` of the request state
        (the window layers' rings).  tokens [1, Tq] are positions
        ``prefix_len ..``, right-padded to the bucket; ``delta_len``,
        ``prefix_len``, ``page_row`` [max_pages] and ``slot`` traced.
        With ``prefix_len`` 0 nothing is read of either cache.  Else the
        chunks before this one (the engine runs a request's in order,
        into this slot) left what its queries need: a window layer reads
        the slot's ring, a full layer the prefix's pages (module
        docstring).  Returns (logits [1, Tq, V], k_pool, v_pool, state);
        ``logits[0, delta_len - 1]`` scores the next token.  Of the
        slot's rings, row ``r`` takes the last position before
        ``prefix_len + delta_len`` that is ``r mod W`` if this call
        computed it, and keeps what it held if not."""
        self.refuse(unbuilt)
        cfg, Tq = self.config, tokens.shape[1]
        wk, wv = state["window_k"], state["window_v"]
        W = wk.shape[3]
        cap = page_row.shape[0] * k_pool.shape[3]
        i32 = jnp.int32
        delta_len = jnp.asarray(delta_len, i32)
        prefix_len = jnp.asarray(prefix_len, i32)
        slot = jnp.asarray(slot, i32)
        valid, page_ids, offs, _, positions = prefill_index(
            page_row, delta_len, Tq, k_pool.shape[3], prefix_len,
            cfg.n_positions)
        pool = PagePool((k_pool, v_pool), page_ids, offs, valid)
        # ring row r: the last position before the end that is r mod W
        ring_pos = ring_positions(prefix_len + delta_len, W)
        ring_new = (ring_pos >= prefix_len)[None, :, None]
        ring_src = jnp.clip(ring_pos - prefix_len, 0, Tq - 1)
        rings = {"window_k": [], "window_v": []}

        def slot_ring(leaf, i):
            # one slice of the leaf: ``leaf[i]`` first would copy the layer
            return jax.lax.dynamic_slice(
                leaf, (i, slot, 0, 0, 0), (1, 1) + leaf.shape[2:])[0, 0]

        def attend(kind, i, q, k, v):
            if kind == "window":
                old_k, old_v = slot_ring(wk, i), slot_ring(wv, i)
                rings["window_k"].append(
                    jnp.where(ring_new, k[0][:, ring_src], old_k))
                rings["window_v"].append(
                    jnp.where(ring_new, v[0][:, ring_src], old_v))

                def context():
                    with jax.named_scope("chunk_context"):
                        # into position order: row (prefix_len + kk) % W
                        shift = -jnp.mod(prefix_len, W)
                        ctx_k, ctx_v = (jnp.roll(t, shift, axis=1)
                                        for t in (old_k, old_v))
                    return _context_attention(
                        cfg, kind, q, k, v, ctx_k, ctx_v,
                        jnp.minimum(prefix_len, W))
            else:
                pool.write(i, k[0].transpose(1, 0, 2),
                           v[0].transpose(1, 0, 2))

                def context():
                    with jax.named_scope("chunk_context"):
                        ctx_k, ctx_v = (
                            prefix_keys(t[i], page_row, prefix_len)
                            for t in pool.arrays())
                    return _context_attention(
                        cfg, kind, q, k, v, ctx_k, ctx_v,
                        jnp.minimum(prefix_len, cap))

            return jax.lax.cond(prefix_len == 0,
                                lambda: _self_attention(cfg, kind, q, k, v),
                                context)

        logits, stats = _layers(cfg, params, tokens, positions, valid, attend)
        out = (logits, *pool.arrays(), write_slot_state(state, rings, slot))
        if aux:
            total = prefix_len + delta_len
            pairs = (cfg.count("full") * _live_pairs(delta_len, prefix_len)
                     + cfg.count("window") * _live_pairs(delta_len,
                                                         prefix_len, W))
            out += (_aux(cfg, stats, total, jnp.minimum(total, W),
                         (total > W).astype(i32),
                         delta_len * cfg.num_hidden_layers, pairs / 1024),)
        return out


# -- the layer's parts ----------------------------------------------------

def layer_norm(x, weight, eps: float):
    """LayerNorm with a weight and no bias: the mean taken out, in
    float32, back to x's type, then the weight."""
    xf = x.astype(F32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * weight.astype(x.dtype)


def rope_interleaved(x, positions, theta: float):
    """Interleaved RoPE (GPT-J's): x [B, H, T, D], positions [B, T].  Pair
    ``i`` is ``(x[2i], x[2i + 1])`` at angle ``pos * theta**(-2i/D)``.
    Computed in place on the lanes: each lane's partner is its neighbour,
    reached by a roll either way and a select on the lane's parity, so
    nothing is reshaped to a minor axis of two."""
    D = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(D // 2, dtype=F32) / (D // 2))
    ang = positions.astype(F32)[:, None, :, None] * jnp.repeat(inv_freq, 2)
    even = jnp.arange(D) % 2 == 0
    xf = x.astype(F32)
    partner = jnp.where(even, jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * jnp.cos(ang)
            + partner * jnp.where(even, -1.0, 1.0) * jnp.sin(ang)
            ).astype(x.dtype)


def _qkv(cfg: Cohere2MoeConfig, kind: str, lp, h, positions):
    """h [B, T, d] (normed), positions [B, T] -> q [B, Hq, T, D], k, v
    [B, Hkv, T, D]; a window layer's q and k rotated, a full layer's
    not."""
    q, k, v = (project_heads(h, lp["q_w"], cfg.n_head),
               project_heads(h, lp["k_w"], cfg.n_kv_head),
               project_heads(h, lp["v_w"], cfg.n_kv_head))
    if kind == "window":
        q, k = (rope_interleaved(t, positions, cfg.rope_theta)
                for t in (q, k))
    return q, k, v


def _self_attention(cfg: Cohere2MoeConfig, kind: str, q, k, v):
    """A whole sequence's attention from nothing ahead of it."""
    return causal_self_attention(
        q, k, v, cfg.attn_impl == "flash",
        window=cfg.sliding_window if kind == "window" else None,
        sm_scale=default_scale(cfg.head_dim))


def _context_attention(cfg: Cohere2MoeConfig, kind: str, q, k, v, ctx_k,
                       ctx_v, live):
    """A chunk's attention with keys ahead of it
    (``walked.context_attention``), a window layer's inside its window."""
    return context_attention(
        q, k, v, ctx_k, ctx_v, live, cfg.attn_impl == "flash",
        window=cfg.sliding_window if kind == "window" else None,
        sm_scale=default_scale(cfg.head_dim))


@jax.named_scope("shared_expert")
def _shared_experts(cfg: Cohere2MoeConfig, lp, x):
    """The shared experts averaged: one SwiGLU as wide as all of them,
    times ``1 / num_shared_experts``."""
    y = swiglu(x, lp["shared_gate_w"], lp["shared_up_w"],
               lp["shared_down_w"])
    return y * jnp.asarray(1.0 / cfg.num_shared_experts, y.dtype)


def _ffn(cfg: Cohere2MoeConfig, params, lp, layer: int, h, valid, stats):
    """h [N, d] (normed) -> the layer's FFN branch: this share's part of
    the routed sum + the shared experts whole.  The experts reach their
    kernels whole, every layer's held experts flat (a reshape of the
    leading axes), and the kernel finds a layer's by ``expert_offset``."""
    flat = stacked_experts(params, "experts")
    with jax.named_scope("moe"):
        routed, st = routed_experts(
            h, lp["router_w"], jnp.zeros((cfg.num_experts,), F32), flat,
            layer, top_k=cfg.num_experts_per_tok, held=cfg.held,
            valid=valid, act="swiglu", renormalize=cfg.norm_topk_prob)
    stats.append(st)
    return routed + _shared_experts(cfg, lp, h)


def _aux(cfg: Cohere2MoeConfig, stats, full_kv_tokens=0, window_kv_rows=0,
         window_wrapped_slots=0, flash_q_rows=0,
         flash_live_keys=0) -> Dict[str, jnp.ndarray]:
    """The call's counters: the expert layers' as ``NemotronHModel``'s (of
    the HELD experts); what the two kinds of cache held for the call's
    live sequences (``full_kv_tokens`` keys a full layer,
    ``window_kv_rows`` ring rows a window layer: ``MimoV2Model``'s
    names); ``window_wrapped_slots``: active slots whose context has
    passed ``sliding_window`` (their rings hold the window, not the
    context); and of a prefill, ``flash_q_rows`` live query rows and
    ``flash_live_keys`` the (query, key) pairs the masks let through a
    head, summed over layers (counted in units of 1,024: a float32 on
    the way to the host)."""
    i32 = jnp.int32
    return {**held_expert_counters(stats, cfg.held[1]),
            "full_kv_tokens": jnp.asarray(full_kv_tokens, i32),
            "window_kv_rows": jnp.asarray(window_kv_rows, i32),
            "window_wrapped_slots": jnp.asarray(window_wrapped_slots, i32),
            "flash_q_rows": jnp.asarray(flash_q_rows, i32),
            "flash_live_keys": jnp.asarray(flash_live_keys, F32)}


def _layers(cfg: Cohere2MoeConfig, params, tokens, positions, valid,
            attend):
    """The forward over tokens [B, T] at ``positions`` [B, T]:
    ``attend(kind, index of the kind, q, k, v) -> [B, Hq, T, D]`` is the
    caller's (it keeps what a cache keeps).  ``valid`` [B * T] bool or
    None: the rows that reach an expert.  Returns (logits [B, T, V], the
    expert layers' statistics)."""
    B, T = tokens.shape
    seen = {"full": 0, "window": 0}
    stats = []
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for layer, kind in enumerate(cfg.kinds):
        with jax.named_scope("layer"):
            lp = at(params[kind], seen[kind])
            h = layer_norm(x, lp["ln"], cfg.layer_norm_eps)
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                q, k, v = _qkv(cfg, kind, lp, h, positions)
                attn = attend(kind, seen[kind], q, k, v)
                branch = merge_heads(attn) @ lp["o_w"].astype(x.dtype)
            ffn = _ffn(cfg, params, lp, layer, h.reshape(B * T, -1), valid,
                       stats).reshape(x.shape)
            x = x + branch + ffn
            seen[kind] += 1
    with jax.named_scope("lm_head"):
        x = layer_norm(x, params["norm_f"], cfg.layer_norm_eps)
        logits = x @ params["wte"].astype(x.dtype).T
        if cfg.logit_scale != 1:
            logits = logits * jnp.asarray(cfg.logit_scale, logits.dtype)
    return logits, stats


# -- the paged steps' parts ---------------------------------------------

def _live_pairs(delta_len, prefix_len, window=None):
    """(query, key) pairs the masks let through a head for ``delta_len``
    queries after ``prefix_len`` keys (float32: a 12,288-token prompt's
    full layer passes 2**24)."""
    n, p = delta_len.astype(F32), prefix_len.astype(F32)
    pairs = n * p + n * (n + 1) / 2
    if window is None:
        return pairs
    # query at position t sees min(t + 1, window) keys
    over = jnp.maximum(p + n - window, 0) - jnp.maximum(p - window, 0)
    under = n - over                # queries whose whole past is in reach
    return under * p + under * (under + 1) / 2 + over * window
