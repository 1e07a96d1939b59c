"""dots3-note-prev (HF ``model_type: dots3_note``): a pre-norm decoder whose
layers attend in one of two kinds of multi-head LATENT attention
(``layer_types``), each with a sigmoid gate a head on its output, over a
dense SwiGLU feed-forward in the first ``first_k_dense_replace`` layers and
sigmoid-routed SwiGLU experts beside a shared expert in the others.

    x <- x + attn_kind(RMSNorm(x)); x <- x + ffn(RMSNorm(x));
    logits = RMSNorm(x) W_head          (untied, no bias but the indexer's
                                         LayerNorm's)

* latent attention of a kind ``(H, r_q, r_kv, d_n, d_r, d_v, theta)``, the
  ``full_attention`` layers' from the plain keys of the config, the
  ``sliding_attention`` layers' from the ``swa_*`` keys: ``c_q = s_q
  RMSNorm(h W_qa)``; a head's ``[q_nope ; RoPE(q_rope)] = c_q W_qb``;
  ``[c ; k_r] = h W_kva``, ``c_kv = s_kv RMSNorm(c)``, ``k_rope =
  RoPE(k_r)``, ONE a token; ``k_j = [c_kv W_UK_j ; k_rope]``, ``v_j = c_kv
  W_UV_j``; float32 softmax at scale ``(d_n + d_r)**-0.5``.  ``s_q =
  sqrt(hidden / r_q)``, ``s_kv = sqrt(hidden / r_kv)`` where
  ``apply_mla_qkv_lora_rescale`` (LongCat-Flash's ``mla_scale_*_lora``),
  else 1.  RoPE is rotate-half at the kind's ``theta``, no scaling.
* which keys: a sliding layer's query at ``t`` sees ``0 <= t - s <
  sliding_window_size``; a full layer's the ``index_topk`` positions ``s <=
  t`` an INDEXER of its own scores highest (``models/glm_dsa.py``'s
  equations at this model's widths, from the same rescaled ``c_q``; every
  ``s <= t`` while there are no more than ``index_topk``).
* the gate (``attention_gate_type`` / ``swa_attention_gate_type``
  ``"headwise"``): ``g = sigmoid(h W_g)``, one value a head, on the head's
  output before ``W_o``.
* expert layer: sigmoid scores over all ``n_routed_experts`` in float32, the
  ``num_experts_per_tok`` largest of ``score + e_score_correction_bias``
  (``noaux_tc``, no groups), weights ``score / sum * routed_scaling_factor``;
  plus the shared expert.  ``experts_held = (first, count)`` is this chip's
  share (``moe/dropless.py``).

Not built, refused at construction: a gate type other than ``"headwise"``, a
``rope_scaling``, softmax scoring, biases, tied embeddings, heads that share
keys.  The vision and audio towers and the prediction layer are not in the
language model's config and no weights are made for them.

This file is the model's SERVING surface (``ServeEngine``'s protocol), and
the first to keep THREE kinds of cache at once:

* request state by slot (``serving_state``): ``window_latent`` ``[sliding
  layers, slots, 1, R, width]``, a RING of each sliding layer's last
  ``sliding_window_size`` rows ``[c_kv ; k_rope ; 0]`` (``walked.
  LatentRing``: position ``p`` at row ``p % window``; ``R`` whole granules
  of 64 rows, ``width`` whole lane tiles: 576 x 1,152 for 513 x 1,088);
* ONE page pool of the full layers' latent rows (``values_in_keys``,
  ``config.n_layer`` counts the full layers alone) and
* beside it, under the same page ids, an indexer key a token of every full
  layer (``n_index_layer``).

A request keeps state, so the prefix cache and the KV tier are refused
(``ServeEngine``); chunked prefill works.  The steps:

* the decode tick: a sliding layer writes its row to the slot's ring and
  ``ds_window_latent_decode_attn`` attends the ring's live rows in the
  ABSORBED form (the ring read once for the scores of every head and for
  their values); a full layer writes row and indexer key to the pages,
  ``ds_index_score`` scores the slot's context, the picks become a mask
  (``walked.pick_mask``) and ``ds_sparse_latent_decode_attn`` walks the
  slot's live rows under it.
* the prefill (a whole prompt, a chunk: one program): a sliding layer
  attends in the EXPANDED form under the band, over the chunk alone where
  nothing lies ahead of it and else over the last ``window - 1`` rows of
  the slot's ring + the chunk (``ds_flash_fwd`` / ``ds_flash_fwd_ctx``), and
  leaves the ring holding the last ``window`` positions; a full layer as
  ``models/glm_dsa.py``'s: the chunk's picks over the request's pages as a
  mask, then ``ds_latent_context_attn`` under it.

Parameter tree: ``wte``, ``lm_head`` [d, V], ``norm_f``; ``full`` and
``window``, the attention of each kind in order (``ln1``, ``q_a_w``,
``q_a_norm``, ``q_b_w``, ``kv_a_w``, ``kv_a_norm``, ``k_b_w`` [H, d_n,
r_kv], ``v_b_w`` [H, r_kv, d_v], ``attn_gate_w`` [d, H], ``o_w``);
``indexer`` of the full layers (``models/glm_dsa.py``'s leaves); ``dense``
and ``moe`` as ``models/glm_dsa.py``'s.  A ``q_b_w`` rests output-major
inside an engine (``WalkedModel.serving_layouts``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .walked import (F32, LatentRing, PagePool, ServedConfig, WalkedModel, at,
                     chunk_picks, decode_index, default_scale, dense_ffn,
                     draw_layers, expand_latents, held_expert_counters,
                     index_projections, latent_context_attention,
                     latent_context_pairs, latent_projections, latent_rows,
                     latent_self_attention, lm_head, merge_heads, pick_mask,
                     prefill_index, ring_positions, rms_norm, routed_experts,
                     shared_expert, stacked_experts, whole_tiles,
                     write_slot_state)

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


class LatentWidths(NamedTuple):
    """One kind of latent attention."""
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rot: int
    v: int
    theta: float

    @property
    def qk(self) -> int:
        return self.nope + self.rot

    @property
    def row(self) -> int:
        """A cached row at rest: ``[c_kv ; k_rope]`` in whole lane tiles."""
        return whole_tiles(self.kv_rank + self.rot)


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824      # the dense FFN's
    moe_intermediate_size: int = 1536   # ONE expert's
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 128
    num_key_value_heads: int = 128      # MLA: every head has its own k, v
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    attention_gate_type: str = "headwise"
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    swa_attention_gate_type: str = "headwise"
    sliding_window_size: int = 513
    apply_mla_qkv_lora_rescale: bool = True
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_layer_freq: int = 1
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    rope_scaling: Optional[Dict[str, Any]] = None
    max_position_embeddings: int = 524288
    initializer_range: float = 0.02
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # the program's
    experts_held: Optional[Tuple[int, int]] = None    # (first, count)
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unbuilt = {
            f"topk_method {self.topk_method!r} (only 'noaux_tc')":
                self.topk_method != "noaux_tc",
            f"scoring_func {self.scoring_func!r} (only 'sigmoid')":
                self.scoring_func != "sigmoid",
            f"hidden_act {self.hidden_act!r} (only 'silu')":
                self.hidden_act != "silu",
            "attention_gate_type / swa_attention_gate_type other than "
            "'headwise'": (self.attention_gate_type,
                           self.swa_attention_gate_type)
            != ("headwise", "headwise"),
            "rope_scaling": self.rope_scaling is not None,
            "moe_layer_freq other than 1": self.moe_layer_freq != 1,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "num_key_value_heads != num_attention_heads":
                (self.num_key_value_heads, self.swa_num_key_value_heads)
                != (self.num_attention_heads, self.swa_num_attention_heads),
        }
        self.check(unbuilt, self.n_routed_experts)
        n = self.num_hidden_layers
        if len(self.layer_types) != n or set(self.layer_types) - set(_KINDS):
            raise ValueError(f"layer_types: {n} entries of {sorted(_KINDS)}, "
                             f"one a layer; got {self.layer_types}")
        for w in (self.full, self.window):
            if w.rot % 2 or w.rot > self.index_head_dim:
                raise ValueError("qk_rope_head_dim: even, and the indexer's "
                                 "heads rotate that many of their dims")
        if self.sliding_window_size < 2:
            raise ValueError("sliding_window_size: the token and at least "
                             "one before it")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """'full' | 'window' of each layer, in order."""
        return tuple(_KINDS[t] for t in self.layer_types)

    @property
    def full(self) -> LatentWidths:
        return LatentWidths(
            self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            float(self.rope_theta))

    @property
    def window(self) -> LatentWidths:
        return LatentWidths(
            self.swa_num_attention_heads, self.swa_q_lora_rank,
            self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
            self.swa_qk_rope_head_dim, self.swa_v_head_dim,
            float(self.swa_rope_theta))

    def widths(self, kind: str) -> LatentWidths:
        return self.full if kind == "full" else self.window

    def rescale(self, rank: int) -> Optional[float]:
        """``sqrt(hidden / rank)`` on a normed latent, where the source
        rescales them."""
        return math.sqrt(self.hidden_size / rank) \
            if self.apply_mla_qkv_lora_rescale else None

    def count(self, kind: str) -> int:
        """Layers of an attention kind ('full', 'window') or an FFN kind
        ('dense', 'moe')."""
        if kind in ("full", "window"):
            return self.kinds.count(kind)
        dense = min(self.first_k_dense_replace, self.num_hidden_layers)
        return dense if kind == "dense" else self.num_hidden_layers - dense

    @property
    def ring_rows(self) -> int:
        """A ring at rest: the window in whole granules of 64 rows (of 8
        where it is shorter than one): 513 -> 576."""
        g = 64 if self.sliding_window_size >= 64 else 8
        return -(-self.sliding_window_size // g) * g

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_layer(self) -> int:
        """Layers that keep every row: the page pool's depth."""
        return self.count("full")

    @property
    def n_kv_head(self) -> int:
        """The pool's rows belong to no head: one a token."""
        return 1

    @property
    def d_head(self) -> int:
        return self.full.row

    @property
    def d_head_v(self) -> int:
        return self.kv_lora_rank

    @property
    def values_in_keys(self) -> bool:
        """ONE pool of rows (``PagedKVCacheSpec.values_in_keys``)."""
        return True

    @property
    def n_index_layer(self) -> int:
        """Every full layer keeps an indexer key a token
        (``PagedKVCacheSpec.index_layers``)."""
        return self.count("full")

    @property
    def d_index(self) -> int:
        return self.index_head_dim


class Dots3NoteModel(WalkedModel):
    #: the rings are request state: the engine refuses the prefix cache and
    #: the KV tier for any ``serving_state`` model (ROADMAP M6 / M7); the
    #: common arms these paged steps do not have are refused here
    serving_aux = WalkedModel.serving_aux + (
        "window_latent_rows", "window_wrapped_slots", "latent_kv_tokens",
        "index_scored_rows", "index_selected_rows", "latent_context_rows",
        "latent_context_pairs")
    query_projections = ("q_b_w",)      # walked.serving_layouts

    def serving_cache_layers(self) -> Dict[str, int]:
        """Layers by the kind of cache they keep."""
        cfg = self.config
        return {"latent": cfg.count("full"),
                "window_latent": cfg.count("window"),
                "index": cfg.count("full")}

    def serving_state(self, slots: int) -> Dict[str, Any]:
        """What a request keeps beside its pages, by slot (axis 1): the
        sliding layers' rings of their last ``sliding_window_size`` latent
        rows."""
        cfg = self.config
        return {"window_latent": jax.ShapeDtypeStruct(
            (cfg.count("window"), slots, 1, cfg.ring_rows, cfg.window.row),
            jnp.dtype(cfg.param_dtype))}

    def init(self, rng) -> Dict[str, Any]:
        """Every matrix normal(0, initializer_range), norm weights 1, the
        LayerNorm's and the router's bias 0, drawn a layer at a time in
        ``param_dtype``."""
        cfg = self.config
        d, dt = cfg.hidden_size, jnp.dtype(cfg.param_dtype)
        std = cfg.initializer_range
        J, D = cfg.index_n_heads, cfg.index_head_dim
        f, e, held = (cfg.moe_intermediate_size, cfg.n_routed_experts,
                      cfg.held[1])
        fs = f * cfg.n_shared_experts
        keys = jax.random.split(rng, 7)

        def norm(key, shape):
            return (jax.random.normal(key, shape, F32) * std).astype(dt)

        def attn(w: LatentWidths):
            def layer(key):
                k = jax.random.split(key, 7)
                return {"q_a_w": norm(k[0], (d, w.q_rank)),
                        "q_b_w": norm(k[1], (w.q_rank, w.heads * w.qk)),
                        "kv_a_w": norm(k[2], (d, w.kv_rank + w.rot)),
                        "k_b_w": norm(k[3], (w.heads, w.nope, w.kv_rank)),
                        "v_b_w": norm(k[4], (w.heads, w.kv_rank, w.v)),
                        "attn_gate_w": norm(k[5], (d, w.heads)),
                        "o_w": norm(k[6], (w.heads * w.v, d))}
            return layer

        def indexer(key):
            k = jax.random.split(key, 3)
            return {"wq_b_w": norm(k[0], (cfg.q_lora_rank, J * D)),
                    "wk_w": norm(k[1], (d, D)),
                    "weights_proj_w": norm(k[2], (d, J)),
                    "k_norm_b": jnp.zeros((D,), dt)}

        def dense(key):
            k = jax.random.split(key, 3)
            return {"gate_w": norm(k[0], (d, cfg.intermediate_size)),
                    "up_w": norm(k[1], (d, cfg.intermediate_size)),
                    "down_w": norm(k[2], (cfg.intermediate_size, d))}

        def moe(key):
            k = jax.random.split(key, 7)
            return {"router_w": norm(k[0], (d, e)),
                    "router_bias": jnp.zeros((e,), F32),
                    "shared_gate_w": norm(k[1], (d, fs)),
                    "shared_up_w": norm(k[2], (d, fs)),
                    "shared_down_w": norm(k[3], (fs, d))}

        def experts(key):               # the layer's other three keys
            k = jax.random.split(key, 7)
            return {"gate_w": norm(k[4], (held, d, f)),
                    "up_w": norm(k[5], (held, d, f)),
                    "down_w": norm(k[6], (held, f, d))}

        def attn_ones(w: LatentWidths):
            return {"ln1": d, "q_a_norm": w.q_rank, "kv_a_norm": w.kv_rank}

        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "lm_head": norm(keys[1], (d, cfg.vocab_size)),
               "norm_f": jnp.ones((d,), dt)}
        for name, layer, ones, key, whole in (
                ("full", attn(cfg.full), attn_ones(cfg.full), keys[2], None),
                ("window", attn(cfg.window), attn_ones(cfg.window), keys[3],
                 None),
                ("indexer", indexer, {"k_norm_w": D}, keys[4], None),
                ("dense", dense, {"ln2": d}, keys[5], None),
                ("moe", moe, {"ln2": d}, keys[6], experts)):
            n = cfg.count("full" if name == "indexer" else name)
            if not n:
                continue
            of = jax.random.split(key, n)
            out[name] = draw_layers(layer, of, ones, dt)
            if whole:
                out[name].update(jax.lax.map(whole, of))
        return out

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        with no cache, dense (XLA) throughout: a sliding layer's scores
        under the band, a full layer's under its picks as a mask, the
        expanded form."""
        cfg = self.config
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

        def attend(kind, i, ap, ip, h, lat):
            return _dense_attention(cfg, kind, ap, ip, h, lat, positions)

        logits, stats = _layers(cfg, params, tokens, positions, None, attend)
        return (logits, _aux(cfg, stats)) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, state,
                          impl: Optional[str] = None, aux: bool = False,
                          index_pool=None, **unbuilt):
        """One decode tick of every slot: ``gpt2_decode_step_paged``'s
        contract with None where a second pool would be, the indexer's keys
        ``index_pool`` ``[full layers, pages, 1, page_len, index_head_dim]``
        after it and the request state after them.  Returns (logits [S, V],
        pool, None, index_pool, state, new_lengths) and, with ``aux``, the
        tick's counters and beside them ``"index_picks"`` (each full
        layer's picked positions ``[full layers, S, K]``, as
        ``GlmDsaModel``'s).  An inactive slot's pages and rings are neither
        read nor written."""
        from ..ops.pallas.decode_attention import (
            index_score, sparse_latent_decode_attention)
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        page_len = k_pool.shape[3]
        lengths, positions, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, page_len, cfg.n_positions)
        pool = PagePool((k_pool,), page_ids, offs, active)
        index = PagePool((index_pool,), page_ids, offs, active)
        ring = LatentRing(state["window_latent"], cfg.sliding_window_size,
                          positions, active)
        K = min(cfg.index_topk, page_table.shape[1] * page_len)
        masks = []      # each full layer's picks [S, cap] bool

        def attend(kind, i, ap, ip, h, lat):
            c_q, q_nope, q_rope, c_kv, k_rope = lat
            w = cfg.widths(kind)
            row = latent_rows(c_kv[:, 0], k_rope[:, 0], w.row)
            with jax.named_scope("absorb"):
                q_lat = jnp.einsum("shn,hnc->shc", q_nope[:, :, 0],
                                   ap["k_b_w"].astype(q_nope.dtype))
            q_row = latent_rows(q_lat, q_rope[:, :, 0], w.row)
            scale = default_scale(w.qk)
            if kind == "window":
                ring.write(i, row)
                with jax.named_scope("window_latent"):
                    o_lat = ring.attend(i, q_row, att_len, w.kv_rank,
                                        impl=impl, sm_scale=scale)
            else:
                pool.write(i, row)
                with jax.named_scope("indexer"):
                    q_i, k_i, wt = _index_projections(cfg, ip, h, c_q,
                                                      positions[:, None])
                    index.write(i, k_i[:, 0])
                    with jax.named_scope("index_score"):
                        scores = index_score(
                            q_i[:, :, 0], wt[:, 0],
                            index.rows[0].reshape(-1, page_len,
                                                  cfg.index_head_dim),
                            page_table + i * index.per_layer, att_len,
                            impl=impl)
                    with jax.named_scope("index_topk"):
                        masks.append(pick_mask(scores, K))
                o_lat = sparse_latent_decode_attention(
                    q_row, pool.rows[0].reshape(-1, page_len, w.row),
                    page_table + i * pool.per_layer, att_len, masks[-1],
                    w.kv_rank, sm_scale=scale, impl=impl)
            with jax.named_scope("absorb"):
                out = jnp.einsum("shc,hcv->shv", o_lat,
                                 ap["v_b_w"].astype(o_lat.dtype))
            return out[:, :, None]

        logits, stats = _layers(cfg, params, tokens[:, None],
                                positions[:, None], active, attend)
        out = (logits[:, 0], *pool.arrays(), None, *index.arrays(),
               {"window_latent": ring.arrays()[0]},
               lengths + active.astype(jnp.int32))
        if aux:
            W, live = cfg.sliding_window_size, jnp.sum(att_len)
            counters = _aux(
                cfg, stats,
                window_rows=jnp.sum(jnp.minimum(att_len, W))
                * cfg.count("window"),
                wrapped=jnp.sum(att_len > W),
                latent_kv_tokens=live * cfg.count("full"),
                scored=live * cfg.count("full"),
                selected=jnp.sum(jnp.minimum(att_len, K)) * cfg.count("full"))
            # a mask's positions in order, the picked ones first
            counters["index_picks"] = jnp.stack([
                jnp.argsort(~m, axis=1, stable=True)[:, :K]
                for m in masks]).astype(jnp.int32)
            out += (counters,)
        return out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool=None, *, state, slot, aux: bool = False,
                      index_pool=None, **unbuilt):
        """Prefill of one request, or of one CHUNK of it: the full layers'
        latent rows and indexer keys into the two paged arrays, the sliding
        layers' rows into ``slot`` of the request state.  tokens [1, Tq] are
        positions ``prefix_len ..``, right-padded to the bucket;
        ``delta_len``, ``prefix_len``, ``page_row`` [max_pages] and ``slot``
        traced.  With ``prefix_len`` 0 a sliding layer reads nothing of the
        state; else the chunks before this one (the engine runs a request's
        in order, into this slot) left the slot's rings holding what its
        queries need.  Returns (logits [1, Tq, V], pool, None, index_pool,
        state).  Of a slot's ring, row ``r`` takes the last position before
        ``prefix_len + delta_len`` that is ``r mod window`` if this call
        computed it, and keeps what it held if not.  Padding rows reach no
        expert and write no page."""
        self.refuse(unbuilt)
        cfg, Tq = self.config, tokens.shape[1]
        page_len, width = k_pool.shape[3], k_pool.shape[4]
        i32, W = jnp.int32, cfg.sliding_window_size
        prefix_len = jnp.asarray(prefix_len, i32)
        delta_len = jnp.asarray(delta_len, i32)
        slot = jnp.asarray(slot, i32)
        valid, page_ids, offs, abs_pos, positions = prefill_index(
            page_row, delta_len, Tq, page_len, prefix_len, cfg.n_positions)
        pool = PagePool((k_pool,), page_ids, offs, valid)
        index = PagePool((index_pool,), page_ids, offs, valid)
        context_len = prefix_len + delta_len
        # a padding row sees no key: whole blocks of them are skipped
        q_pos = jnp.where(valid, abs_pos, -1)
        rings = state["window_latent"]
        # ring row r: the last position before the end that is r mod W
        ring_pos = ring_positions(context_len, W)
        ring_new = (ring_pos >= prefix_len)[:, None]
        ring_src = jnp.clip(ring_pos - prefix_len, 0, Tq - 1)
        kept = {"window_latent": []}
        pairs = []      # a full layer's (query, key) pairs under its mask

        def attend(kind, i, ap, ip, h, lat):
            c_q, q_nope, q_rope, c_kv, k_rope = lat
            w = cfg.widths(kind)
            scale = default_scale(w.qk)
            rows = latent_rows(c_kv[0], k_rope[0], w.row)
            if kind == "window":
                # one slice of the leaf: ``rings[i]`` first would copy the
                # layer
                old = jax.lax.dynamic_slice(
                    rings, (i, slot, 0, 0, 0), (1, 1) + rings.shape[2:])[0, 0]
                kept["window_latent"].append(jnp.concatenate([
                    jnp.where(ring_new, rows[ring_src], old[0, :W])[None],
                    old[:, W:]], axis=1))

                def context():
                    with jax.named_scope("chunk_context"):
                        # into position order, then the W - 1 positions a
                        # chunk's first query can still see
                        ahead = jnp.roll(old[0, :W], -jnp.mod(prefix_len, W),
                                         axis=0)[1:]
                    return _band_context_attention(
                        cfg, ap, q_nope, q_rope, c_kv, k_rope, ahead,
                        jnp.minimum(prefix_len, W - 1))

                with jax.named_scope("window_expand"):
                    return jax.lax.cond(
                        prefix_len == 0,
                        lambda: latent_self_attention(
                            ap, q_nope, q_rope, c_kv, k_rope,
                            flash=cfg.attn_impl == "flash", sm_scale=scale,
                            window=W), context)
            pool.write(i, rows)
            with jax.named_scope("indexer"):
                q_i, k_i, wt = _index_projections(cfg, ip, h, c_q, positions)
                index.write(i, k_i[0])
                mask = chunk_picks(
                    q_i[0], wt[0],
                    index.rows[0].reshape(-1, page_len, cfg.index_head_dim),
                    i * index.per_layer + page_row, abs_pos, context_len,
                    cfg.index_topk)
            if aux:
                # counted before the mask is used: else the old mask lives
                # on beside the next until its sum is taken
                mask, count = jax.lax.optimization_barrier((
                    mask, latent_context_pairs(q_pos, context_len, mask)))
                pairs.append(count)
            return latent_context_attention(
                ap, q_nope[0], q_rope[0],
                pool.rows[0].reshape(-1, page_len, width),
                i * pool.per_layer + page_row, q_pos, context_len,
                kv_rank=w.kv_rank, sm_scale=scale, allowed=mask)[None]

        logits, stats = _layers(cfg, params, tokens, positions, valid, attend)
        out = (logits, *pool.arrays(), None, *index.arrays(),
               write_slot_state(state, kept, slot))
        if aux:
            out += (_aux(cfg, stats,
                         context_rows=context_len * cfg.count("full"),
                         context_pairs=sum(pairs)),)
        return out


# -- the layer's parts ----------------------------------------------------

def _index_projections(cfg: Dots3NoteConfig, ip, h, c_q, positions):
    """:func:`walked.index_projections` at the full layers' widths."""
    return index_projections(
        ip, h, c_q, positions, heads=cfg.index_n_heads,
        dim=cfg.index_head_dim, rot=cfg.full.rot, theta=cfg.full.theta)


def _dense_attention(cfg: Dots3NoteConfig, kind: str, ap, ip, h, lat,
                     positions):
    """A whole sequence's attention of a layer from nothing ahead of it,
    dense (XLA): h [B, T, d] (normed), ``lat`` the layer's latents; the
    scores of every (query, key) under a sliding layer's band or a full
    layer's picks as a mask, the expanded form -> [B, H, T, v_head_dim]."""
    c_q, q_nope, q_rope, c_kv, k_rope = lat
    t = jnp.arange(h.shape[1])
    seen = (t[None, :] <= t[:, None])[None]
    if kind == "full":
        q_i, k_i, w = _index_projections(cfg, ip, h, c_q, positions)
        scores = jnp.einsum("bjtd,bsd->bjts", q_i, k_i,
                            preferred_element_type=F32)
        scores = jnp.sum(jnp.maximum(scores, 0.0)
                         * w.transpose(0, 2, 1)[..., None], axis=1)
        seen = pick_mask(jnp.where(seen, scores, -jnp.inf), cfg.index_topk)
    else:
        seen &= t[None, :] > t[:, None] - cfg.sliding_window_size
    k_nope, v = expand_latents(ap, c_kv, q_nope.dtype)
    s = (jnp.einsum("bhtn,bhsn->bhts", q_nope, k_nope,
                    preferred_element_type=F32)
         + jnp.einsum("bhtr,bsr->bhts", q_rope, k_rope,
                      preferred_element_type=F32)) \
        * default_scale(cfg.widths(kind).qk)
    s = jnp.where(seen[:, None], s, jnp.finfo(F32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bhsv->bhtv", p, v)


def _band_context_attention(cfg: Dots3NoteConfig, ap, q_nope, q_rope, c_kv,
                            k_rope, ahead, live):
    """A sliding layer's chunk with rows ahead of it, in the expanded form:
    ``ahead`` [Tc, width] latent rows at rest of which the LAST ``live``
    (traced) are the positions just before the chunk, in order; the chunk's
    own q_* [1, H, Tq, .], c_kv [1, Tq, C], k_rope [1, Tq, rot].  Context
    and chunk are expanded together (``walked.expand_latents``) and the
    band is ``ds_flash_fwd_ctx``'s (the dense arm: XLA under the same
    mask)."""
    w, W = cfg.window, cfg.sliding_window_size
    dt, scale = q_nope.dtype, default_scale(cfg.window.qk)
    Tc = ahead.shape[0]
    c_all = jnp.concatenate([ahead[None, :, :w.kv_rank].astype(dt), c_kv],
                            axis=1)
    r_all = jnp.concatenate(
        [ahead[None, :, w.kv_rank:w.kv_rank + w.rot].astype(dt), k_rope],
        axis=1)
    k_nope, v = expand_latents(ap, c_all, dt)
    pad = ((0, 0),) * 3 + ((0, whole_tiles(w.qk) - w.qk),)
    q = jnp.pad(jnp.concatenate([q_nope, q_rope], axis=-1), pad)
    k = jnp.pad(jnp.concatenate([k_nope, jnp.broadcast_to(
        r_all[:, None], k_nope.shape[:3] + (w.rot,))], axis=-1), pad)
    if cfg.attn_impl == "flash":
        from ..ops.pallas.flash_attention import flash_attention_fwd
        return flash_attention_fwd(q, k, v, window=W, sm_scale=scale,
                                   ctx_live=live)
    Tq = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=F32) * scale
    kk, qq = jnp.arange(Tc + Tq)[None, :], Tc + jnp.arange(Tq)[:, None]
    seen = (kk <= qq) & (kk >= Tc - live) & (kk > qq - W)
    s = jnp.where(seen[None, None], s, jnp.finfo(F32).min)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(s, axis=-1).astype(dt), v)


def _experts(cfg: Dots3NoteConfig, ep, stacked, index: int, x, valid):
    """The expert layer on normed x [N, d]: this share's part of the routed
    sum (selection on score + ``router_bias``) and the shared expert whole.
    ``stacked``: every layer's held experts flat."""
    with jax.named_scope("moe"):
        routed, st = routed_experts(
            x, ep["router_w"], ep["router_bias"], stacked, index,
            top_k=cfg.num_experts_per_tok, held=cfg.held, valid=valid,
            act="swiglu", scale=cfg.routed_scaling_factor,
            renormalize=cfg.norm_topk_prob)
    if cfg.n_shared_experts:
        routed = routed + shared_expert(ep, x)
    return routed, st


def _ffn(cfg: Dots3NoteConfig, params, stacked, layer: int, x, valid, stats):
    """x [N, d] -> x + ffn(norm(x)); an expert layer's statistics are
    appended to ``stats``."""
    dense = layer < cfg.first_k_dense_replace
    i = layer if dense else layer - cfg.count("dense")
    fp = at(params["dense" if dense else "moe"], i)
    h = rms_norm(x, fp["ln2"], cfg.rms_norm_eps)
    if dense:
        return x + dense_ffn(fp, h)
    out, st = _experts(cfg, fp, stacked, i, h, valid)
    stats.append(st)
    return x + out


def _aux(cfg: Dots3NoteConfig, stats, window_rows=0, wrapped=0,
         latent_kv_tokens=0, scored=0, selected=0, context_rows=0,
         context_pairs=0) -> Dict[str, jnp.ndarray]:
    """The call's counters: the expert layers' (of the HELD experts); of a
    tick (0 in a prefill) ``window_latent_rows``: the live ring rows
    ``ds_window_latent_decode_attn`` reads, over slots and sliding layers;
    ``window_wrapped_slots``: active slots whose context has passed the
    window; ``latent_kv_tokens``, ``index_scored_rows``,
    ``index_selected_rows``: ``GlmDsaModel``'s, summed over the FULL
    layers; and of a prefill (0 in a tick) ``latent_context_rows`` /
    ``latent_context_pairs``: what ``ds_latent_context_attn`` walked and
    let through a head, summed over the full layers."""
    i32 = jnp.int32
    return {**held_expert_counters(stats, cfg.held[1]),
            "window_latent_rows": jnp.asarray(window_rows, i32),
            "window_wrapped_slots": jnp.asarray(wrapped, i32),
            "latent_kv_tokens": jnp.asarray(latent_kv_tokens, i32),
            "index_scored_rows": jnp.asarray(scored, i32),
            "index_selected_rows": jnp.asarray(selected, i32),
            "latent_context_rows": jnp.asarray(context_rows, i32),
            "latent_context_pairs": jnp.asarray(context_pairs, F32)}


def _layers(cfg: Dots3NoteConfig, params, tokens, positions, valid, attend):
    """The forward over sequences tokens [B, T] at ``positions`` [B, T];
    ``attend(kind, index of the kind, ap, ip, h, latents)`` -> [B, H, T,
    v_head_dim] is the caller's form of the attention (it keeps what a
    cache keeps); ``ip`` is a full layer's indexer, None on a sliding
    layer.  Returns (logits, the expert layers' statistics)."""
    B, T = tokens.shape
    stacked = stacked_experts(params) if cfg.count("moe") else None
    seen = {"full": 0, "window": 0}
    stats = []
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for layer, kind in enumerate(cfg.kinds):
        with jax.named_scope("layer"):
            i, w = seen[kind], cfg.widths(kind)
            ap = at(params[kind], i)
            ip = at(params["indexer"], i) if kind == "full" else None
            with jax.named_scope("attn"), jax.named_scope("attn_" + kind):
                h = rms_norm(x, ap["ln1"], cfg.rms_norm_eps)
                out = attend(kind, i, ap, ip, h, latent_projections(
                    ap, h, positions, heads=w.heads, nope=w.nope,
                    kv_rank=w.kv_rank, eps=cfg.rms_norm_eps, theta=w.theta,
                    q_scale=cfg.rescale(w.q_rank),
                    kv_scale=cfg.rescale(w.kv_rank)))
                with jax.named_scope("attn_gate"):
                    g = jax.nn.sigmoid(
                        (h @ ap["attn_gate_w"].astype(h.dtype)).astype(F32))
                    out = out * g.transpose(0, 2, 1)[..., None].astype(
                        out.dtype)
                x = x + merge_heads(out) @ ap["o_w"].astype(x.dtype)
            x = _ffn(cfg, params, stacked, layer, x.reshape(B * T, -1),
                     valid, stats).reshape(x.shape)
            seen[kind] += 1
    logits = lm_head(x, params["norm_f"], params["lm_head"],
                     cfg.rms_norm_eps)
    return logits, stats
