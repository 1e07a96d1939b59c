"""Zhipu GLM-5.2 (HF ``model_type: glm_moe_dsa``): a pre-norm decoder with
multi-head LATENT attention (as ``axk1.py``'s, at other widths) that reads
only the rows a learned INDEXER picks (DeepSeek sparse attention), a dense
SwiGLU feed-forward in the layers ``mlp_layer_types`` calls ``dense`` and,
in the others, sigmoid-routed SwiGLU experts beside a shared expert.

    x <- x + attn(RMSNorm(x)); x <- x + ffn(RMSNorm(x));
    logits = RMSNorm(x) W_head          (untied, no bias but the indexer's
                                         LayerNorm's)

* latent attention, ``H`` heads: ``c_q = RMSNorm(x W_qa)``; ``q = c_q
  W_qb``, a head ``[q_nope ; RoPE(q_rope)]``; ``[c_kv ; k_r] = x W_kva``,
  ``c_kv`` RMS-normed, ``k_rope = RoPE(k_r)``, ONE a token; ``k_h = [c_kv
  W_UK_h ; k_rope]``, ``v_h = c_kv W_UV_h``; softmax over the PICKED keys
  ``S_t`` only, scale ``qk_head_dim**-0.5``, float32; ``W_o``.  RoPE is
  rotate-half at ``rope_theta`` (``rope_type`` default).
* the indexer, on a layer whose ``indexer_types`` entry is ``full``:
  ``q_I = c_q W_Iq`` (``index_n_heads`` heads of ``index_head_dim``, the
  first ``qk_rope_head_dim`` dims rotated); ``k_I = LayerNorm(x W_Ik)``
  (ONE a token, rotated alike); ``w = x W_Iw * index_n_heads**-0.5 *
  index_head_dim**-0.5``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] .
  k_I[s])`` for ``s <= t`` in float32; ``S_t`` the ``min(index_topk, t +
  1)`` positions of largest ``I[t, s]``, ties to the lower ``s``.
* a ``shared`` layer runs no indexer: its ``S_t`` is that of the nearest
  ``full`` layer before it (layer 0 must be ``full``).
* expert layer: ``s = sigmoid(x_f32 W_r)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  (``noaux_tc``; ``n_group`` 1: no group limit); weights ``s[chosen] / sum
  * routed_scaling_factor``; plus the shared expert.  ``experts_held=
  (first, count)`` is this chip's share (``moe/dropless.py``).

Not built, refused at construction: group-limited routing (``n_group`` or
``topk_group`` other than 1), softmax scoring, biases, tied embeddings, a
``rope_type`` other than default, the multi-token-prediction layer (no
weights are made for it; ``num_nextn_predict_layers`` is not a field).

This file is the model's SERVING surface (``ServeEngine``'s protocol).
Two paged arrays under ONE page table: the latent rows ``[c_kv ; k_rope ;
0]`` of every layer (``config.values_in_keys``, ``latent_width`` wide as
``axk1.py`` keeps them) and the indexer's keys ``k_I`` of the ``full``
layers (``config.n_index_layer``, ``d_index``;
``PagedKVCacheSpec.index_layers``).  A request keeps pages and nothing
else, so chunked prefill works; the steps:

* the decode tick, a layer: the new row (and key) written; on a ``full``
  layer ``ds_index_score`` scores the slot's whole context and the picks
  become a mask over positions (``_pick_mask``: exact, ties to the lower
  position, no sort); every layer's latent kernel, as
  ``ds_sparse_latent_decode_attn``, walks every live row of the slot where
  it lies and scores the allowed ones (ABSORBED form), a ``shared`` layer
  under its ``full`` layer's mask.  A context of ``index_topk`` rows or
  fewer takes every row.  Reading a slot's ~7,700 rows in place costs less
  than sorting, then fetching 2,048 of them by index (whole ticks at the
  GLM-5.2 cell's shapes: 12.98 ms against 17.84; my chip run, PR 50), and
  so it does up to ~20,700 rows a slot, past what 32 slots of that pool
  hold (``PERF.md`` section 7 has the readings, and the cell that would
  want the gather back).
* the prefill (a whole prompt, a chunk, the same program): a ``full``
  layer scores the chunk's queries against the request's cached keys a
  block of pages at a time and turns the ``index_topk``-th largest score
  of each query into a mask ``[Tq, context]``; every layer attends in the
  EXPANDED form over the pages under that mask
  (``walked.latent_context_attention``), keys ahead of the chunk too: one
  kernel, ``ds_latent_context_attn``, that reads a block of whole pages
  where they lie, expands it once for a group of heads and walks the
  chunk's query blocks with the scores, the softmax's running max and sum
  and the accumulator in VMEM; blocks past the context or wholly ahead of
  a query block are skipped.

Parameter tree: ``wte``, ``lm_head`` [d, V], ``norm_f``; ``attn`` (as
``axk1.py``'s); ``indexer``, of the ``full`` layers only (``wq_b_w``
[q_lora_rank, J * D], ``wk_w`` [d, D], ``k_norm_w`` / ``k_norm_b`` [D],
``weights_proj_w`` [d, J]); ``dense`` and ``moe`` as ``axk1.py``'s, with
``router_bias`` [E] float32 (``e_score_correction_bias``, drawn zero).
The form a ``q_b_w`` rests in inside an engine is
``WalkedModel.serving_layouts``'s to say (output-major: from this one
the tick copied all seven, 67 MB each, before the matmul that read it;
PERF.md section 6, PR 55).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .walked import (F32, PagePool, ServedConfig, WalkedModel, at,
                     decode_index, default_scale, dense_ffn, draw_layers,
                     expand_latents, held_expert_counters, index_projections,
                     latent_context_attention, latent_context_pairs,
                     latent_projections, latent_rows, lm_head, merge_heads,
                     prefill_index, rms_norm, routed_experts, shared_expert,
                     stacked_experts, whole_tiles)
from .walked import chunk_picks as _chunk_picks, pick_mask as _pick_mask


def _place(kinds, layer: int) -> int:
    """``layer``'s place among the layers of its own kind."""
    return kinds[:layer].count(kinds[layer])


def _published_indexer_types(n: int) -> Tuple[str, ...]:
    """Layers 0-2 ``full``, then ``full`` at 6, 10, ... (every fourth)."""
    return tuple("full" if i < 3 or (i - 2) % 4 == 0 else "shared"
                 for i in range(n))


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288      # the dense FFN's
    moe_intermediate_size: int = 2048   # ONE expert's
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    mlp_layer_types: Optional[Tuple[str, ...]] = None   # 'dense' | 'sparse'
    indexer_types: Optional[Tuple[str, ...]] = None     # 'full' | 'shared'
    num_attention_heads: int = 64
    num_key_value_heads: int = 64       # MLA: every head has its own k, v
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    rope_parameters: Optional[Dict[str, Any]] = None
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # the program's
    experts_held: Optional[Tuple[int, int]] = None    # (first, count)
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.mlp_layer_types is None:
            object.__setattr__(self, "mlp_layer_types", tuple(
                "dense" if i < self.first_k_dense_replace else "sparse"
                for i in range(n)))
        if self.indexer_types is None:
            object.__setattr__(self, "indexer_types",
                               _published_indexer_types(n))
        for name in ("mlp_layer_types", "indexer_types"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        rp = self.rope_parameters or {}
        unbuilt = {
            f"topk_method {self.topk_method!r} (only 'noaux_tc')":
                self.topk_method != "noaux_tc",
            "n_group / topk_group other than 1 (group-limited routing)":
                (self.n_group, self.topk_group) != (1, 1),
            f"scoring_func {self.scoring_func!r} (only 'sigmoid')":
                self.scoring_func != "sigmoid",
            f"hidden_act {self.hidden_act!r} (only 'silu')":
                self.hidden_act != "silu",
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "num_key_value_heads != num_attention_heads":
                self.num_key_value_heads != self.num_attention_heads,
            f"rope_type {rp.get('rope_type')!r} (only 'default')":
                rp.get("rope_type", "default") != "default",
        }
        self.check(unbuilt, self.n_routed_experts)
        if len(self.mlp_layer_types) != n or len(self.indexer_types) != n \
                or set(self.mlp_layer_types) - {"dense", "sparse"} \
                or set(self.indexer_types) - {"full", "shared"}:
            raise ValueError(
                "mlp_layer_types ('dense' | 'sparse') and indexer_types "
                f"('full' | 'shared') name each of the {n} layers")
        if n and self.indexer_types[0] != "full":
            raise ValueError("indexer_types: layer 0 shares no layer's "
                             "picks, it must be 'full'")
        if self.qk_rope_head_dim % 2 \
                or self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("qk_rope_head_dim: even, and the indexer's "
                             "heads rotate that many of their dims")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def rope_theta(self) -> float:
        return float((self.rope_parameters or {}).get("rope_theta", 10000.0))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A cached row at rest: ``[c_kv ; k_rope]`` in whole lane tiles
        (576 -> 640), as ``AxK1Config.latent_width`` keeps it."""
        return whole_tiles(self.kv_lora_rank + self.qk_rope_head_dim)

    def count(self, kind: str) -> int:
        """Layers of an FFN kind ('dense', 'moe') or that score
        ('full')."""
        if kind == "full":
            return self.indexer_types.count("full")
        dense = self.mlp_layer_types.count("dense")
        return dense if kind == "dense" else self.num_hidden_layers - dense

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_kv_head(self) -> int:
        """The pool's rows belong to no head: one a token."""
        return 1

    @property
    def d_head(self) -> int:
        return self.latent_width

    @property
    def d_head_v(self) -> int:
        return self.kv_lora_rank

    @property
    def values_in_keys(self) -> bool:
        """ONE pool of rows (``PagedKVCacheSpec.values_in_keys``)."""
        return True

    @property
    def n_index_layer(self) -> int:
        """Layers that keep an indexer key a token
        (``PagedKVCacheSpec.index_layers``)."""
        return self.count("full")

    @property
    def d_index(self) -> int:
        return self.index_head_dim


class GlmDsaModel(WalkedModel):
    #: ``serving_unsupported`` is the common one: arms these paged steps
    #: do not have (the prefix cache, chunked prefill and page migration
    #: they do: a request keeps pages and nothing else, and both arrays
    #: go by the same page ids)
    serving_aux = WalkedModel.serving_aux + (
        "latent_kv_tokens", "index_scored_rows", "index_selected_rows",
        "latent_context_rows", "latent_context_pairs")
    query_projections = ("q_b_w",)      # walked.serving_layouts

    def serving_cache_layers(self) -> Dict[str, int]:
        """Layers by the kind of cache they keep."""
        return {"latent": self.config.num_hidden_layers,
                "index": self.config.count("full")}

    def init(self, rng) -> Dict[str, Any]:
        """Every matrix normal(0, initializer_range), norm weights 1, the
        LayerNorm's and the router's bias 0, drawn a layer at a time in
        ``param_dtype``."""
        cfg = self.config
        d, dt = cfg.hidden_size, jnp.dtype(cfg.param_dtype)
        std = cfg.initializer_range
        H, nope, rot = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rq, rkv, dv = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.v_head_dim
        J, D = cfg.index_n_heads, cfg.index_head_dim
        f, e, held = (cfg.moe_intermediate_size, cfg.n_routed_experts,
                      cfg.held[1])
        fs = f * cfg.n_shared_experts
        keys = jax.random.split(rng, 6)

        def norm(key, shape):
            return (jax.random.normal(key, shape, F32) * std).astype(dt)

        def attn(key):
            k = jax.random.split(key, 6)
            return {"q_a_w": norm(k[0], (d, rq)),
                    "q_b_w": norm(k[1], (rq, H * (nope + rot))),
                    "kv_a_w": norm(k[2], (d, rkv + rot)),
                    "k_b_w": norm(k[3], (H, nope, rkv)),
                    "v_b_w": norm(k[4], (H, rkv, dv)),
                    "o_w": norm(k[5], (H * dv, d))}

        def indexer(key):
            k = jax.random.split(key, 3)
            return {"wq_b_w": norm(k[0], (rq, J * D)),
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

        ones = {"attn": {"ln1": d, "q_a_norm": rq, "kv_a_norm": rkv},
                "indexer": {"k_norm_w": D},
                "dense": {"ln2": d}, "moe": {"ln2": d}}
        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "lm_head": norm(keys[1], (d, cfg.vocab_size)),
               "norm_f": jnp.ones((d,), dt)}
        for name, layer, n, key, whole in (
                ("attn", attn, cfg.num_hidden_layers, keys[2], None),
                ("indexer", indexer, cfg.count("full"), keys[5], None),
                ("dense", dense, cfg.count("dense"), keys[3], None),
                ("moe", moe, cfg.count("moe"), keys[4], experts)):
            if not n:
                continue
            of = jax.random.split(key, n)
            out[name] = draw_layers(layer, of, ones[name], dt)
            if whole:
                out[name].update(jax.lax.map(whole, of))
        return out

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        with no cache, dense (XLA) throughout: every query's scores over
        the whole sequence, the picks as a mask, the expanded form."""
        cfg = self.config
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        causal = jnp.tril(jnp.ones((T, T), bool))
        picked = [None]

        def attend(layer, ap, ip, h, lat):
            c_q, q_nope, q_rope, c_kv, k_rope = lat
            if ip is not None:
                q_i, k_i, w = _index_projections(cfg, ip, h, c_q, positions)
                scores = jnp.einsum(
                    "bjtd,bsd->bjts", q_i, k_i, preferred_element_type=F32)
                scores = jnp.sum(jnp.maximum(scores, 0.0)
                                 * w.transpose(0, 2, 1)[..., None], axis=1)
                picked[0] = _pick_mask(
                    jnp.where(causal[None], scores, -jnp.inf),
                    cfg.index_topk)
            k_nope, v = expand_latents(ap, c_kv, q_nope.dtype)
            s = (jnp.einsum("bhtn,bhsn->bhts", q_nope, k_nope,
                            preferred_element_type=F32)
                 + jnp.einsum("bhtr,bsr->bhts", q_rope, k_rope,
                              preferred_element_type=F32)) \
                * default_scale(cfg.qk_head_dim)
            s = jnp.where(picked[0][:, None], s, jnp.finfo(F32).min)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return jnp.einsum("bhts,bhsv->bhtv", p, v)

        logits, stats = _layers(cfg, params, tokens, positions, None, attend)
        return (logits, _aux(cfg, stats)) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, impl: Optional[str] = None,
                          aux: bool = False, index_pool=None, **unbuilt):
        """One decode tick of every slot: ``gpt2_decode_step_paged``'s
        contract with None where a second pool would be and the indexer's
        keys ``index_pool`` ``[full layers, pages, 1, page_len,
        index_head_dim]`` after it.  Returns (logits [S, V], pool, None,
        index_pool, new_lengths) and, with ``aux``, the tick's counters
        and beside them ``"index_picks"``: each ``full`` layer's picked
        positions ``[full layers, S, K]``, the first ``min(K, context)``
        of a slot live, in the positions' order (what a probe compares as
        sets; the engine fetches the counters ``serving_aux`` names and
        nothing else).  An inactive slot's pages are neither read nor
        written."""
        from ..ops.pallas.decode_attention import (
            index_score, sparse_latent_decode_attention)
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        page_len = k_pool.shape[3]
        scale = default_scale(cfg.qk_head_dim)
        lengths, positions, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, page_len, cfg.n_positions)
        pool = PagePool((k_pool,), page_ids, offs, active)
        index = PagePool((index_pool,), page_ids, offs, active)
        K = min(cfg.index_topk, page_table.shape[1] * page_len)
        masks = []      # a full layer's picks [S, cap] bool, newest last

        def attend(layer, ap, ip, h, lat):
            c_q, q_nope, q_rope, c_kv, k_rope = lat
            pool.write(layer, latent_rows(c_kv[:, 0], k_rope[:, 0],
                                          cfg.latent_width))
            if ip is not None:
                full = len(masks)
                with jax.named_scope("indexer"):
                    q_i, k_i, w = _index_projections(cfg, ip, h, c_q,
                                                     positions[:, None])
                    index.write(full, k_i[:, 0])
                    with jax.named_scope("index_score"):
                        scores = index_score(
                            q_i[:, :, 0], w[:, 0],
                            index.rows[0].reshape(-1, page_len,
                                                  cfg.index_head_dim),
                            page_table + full * index.per_layer, att_len,
                            impl=impl)
                    with jax.named_scope("index_topk"):
                        masks.append(_pick_mask(scores, K))
            with jax.named_scope("absorb"):
                q_lat = jnp.einsum("shn,hnc->shc", q_nope[:, :, 0],
                                   ap["k_b_w"].astype(q_nope.dtype))
            o_lat = sparse_latent_decode_attention(
                latent_rows(q_lat, q_rope[:, :, 0], cfg.latent_width),
                pool.rows[0].reshape(-1, page_len, cfg.latent_width),
                page_table + layer * pool.per_layer, att_len, masks[-1],
                cfg.kv_lora_rank, sm_scale=scale, impl=impl)
            with jax.named_scope("absorb"):
                out = jnp.einsum("shc,hcv->shv", o_lat,
                                 ap["v_b_w"].astype(o_lat.dtype))
            return out[:, :, None]

        logits, stats = _layers(cfg, params, tokens[:, None],
                                positions[:, None], active, attend)
        out = (logits[:, 0], *pool.arrays(), None, *index.arrays(),
               lengths + active.astype(jnp.int32))
        if aux:
            live = jnp.sum(att_len)
            counters = _aux(cfg, stats, live * cfg.n_layer,
                            live * cfg.count("full"),
                            jnp.sum(jnp.minimum(att_len, K)) * cfg.n_layer)
            # a mask's positions in order, the picked ones first
            counters["index_picks"] = jnp.stack([
                jnp.argsort(~m, axis=1, stable=True)[:, :K]
                for m in masks]).astype(jnp.int32)
            out += (counters,)
        return out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool=None, *, aux: bool = False,
                      index_pool=None, **unbuilt):
        """Delta-aware prefill of one request (a whole prompt or a chunk),
        its latent rows and indexer keys written to the two arrays;
        ``gpt2_prefill_paged``'s contract.  Returns (logits [1, Tq, V],
        pool, None, index_pool).  Padding rows reach no expert and write
        no page."""
        self.refuse(unbuilt)
        cfg = self.config
        page_len, width = k_pool.shape[3], k_pool.shape[4]
        prefix_len = jnp.asarray(prefix_len, jnp.int32)
        delta_len = jnp.asarray(delta_len, jnp.int32)
        valid, page_ids, offs, abs_pos, positions = prefill_index(
            page_row, delta_len, tokens.shape[1], page_len, prefix_len,
            cfg.n_positions)
        pool = PagePool((k_pool,), page_ids, offs, valid)
        index = PagePool((index_pool,), page_ids, offs, valid)
        context_len = prefix_len + delta_len
        # a padding row sees no key: whole blocks of them are skipped
        q_pos = jnp.where(valid, abs_pos, -1)
        picked = []     # a full layer's (mask, pairs it lets through)
        pairs = []      # a layer's, as its full layer counted them

        def attend(layer, ap, ip, h, lat):
            c_q, q_nope, q_rope, c_kv, k_rope = lat
            pool.write(layer, latent_rows(c_kv[0], k_rope[0], width))
            if ip is not None:
                full = len(picked)
                with jax.named_scope("indexer"):
                    q_i, k_i, w = _index_projections(cfg, ip, h, c_q,
                                                     positions)
                    index.write(full, k_i[0])
                    mask = _chunk_picks(
                        q_i[0], w[0],
                        index.rows[0].reshape(-1, page_len,
                                              cfg.index_head_dim),
                        full * index.per_layer + page_row, abs_pos,
                        context_len, cfg.index_topk)
                count = None
                if aux:
                    # counted before the mask is used: else the old mask
                    # lives on beside the next until its sum is taken
                    mask, count = jax.lax.optimization_barrier((
                        mask, latent_context_pairs(q_pos, context_len,
                                                   mask)))
                picked.append((mask, count))
            pairs.append(picked[-1][1])
            return latent_context_attention(
                ap, q_nope[0], q_rope[0],
                pool.rows[0].reshape(-1, page_len, width),
                layer * pool.per_layer + page_row, q_pos, context_len,
                kv_rank=cfg.kv_lora_rank,
                sm_scale=default_scale(cfg.qk_head_dim),
                allowed=picked[-1][0])[None]

        logits, stats = _layers(cfg, params, tokens, positions, valid, attend)
        out = (logits, *pool.arrays(), None, *index.arrays())
        if aux:
            out += (_aux(cfg, stats, context_rows=context_len * cfg.n_layer,
                         context_pairs=sum(pairs)),)
        return out


# -- the layer's parts ----------------------------------------------------

def _index_projections(cfg: GlmDsaConfig, ip, h, c_q, positions):
    """:func:`walked.index_projections` at this config's widths."""
    return index_projections(
        ip, h, c_q, positions, heads=cfg.index_n_heads,
        dim=cfg.index_head_dim, rot=cfg.qk_rope_head_dim,
        theta=cfg.rope_theta)


def _experts(cfg: GlmDsaConfig, ep, stacked, index: int, x, valid):
    """The expert layer on normed x [N, d]: this share's part of the
    routed sum (selection on score + ``router_bias``) and the shared
    expert whole.  ``stacked``: every layer's held experts flat."""
    with jax.named_scope("moe"):
        routed, st = routed_experts(
            x, ep["router_w"], ep["router_bias"], stacked, index,
            top_k=cfg.num_experts_per_tok, held=cfg.held, valid=valid,
            act="swiglu", scale=cfg.routed_scaling_factor,
            renormalize=cfg.norm_topk_prob)
    if cfg.n_shared_experts:
        routed = routed + shared_expert(ep, x)
    return routed, st


def _ffn(cfg: GlmDsaConfig, params, stacked, layer: int, x, valid, stats):
    """x [N, d] -> x + ffn(norm(x)); an expert layer's statistics are
    appended to ``stats``."""
    dense = cfg.mlp_layer_types[layer] == "dense"
    kind = "dense" if dense else "moe"
    i = _place(cfg.mlp_layer_types, layer)
    fp = at(params[kind], i)
    h = rms_norm(x, fp["ln2"], cfg.rms_norm_eps)
    if dense:
        return x + dense_ffn(fp, h)
    out, st = _experts(cfg, fp, stacked, i, h, valid)
    stats.append(st)
    return x + out


def _aux(cfg: GlmDsaConfig, stats, latent_kv_tokens=0, scored=0,
         selected=0, context_rows=0,
         context_pairs=0) -> Dict[str, jnp.ndarray]:
    """The call's counters: the expert layers' (of the HELD experts);
    ``latent_kv_tokens``: the LIVE rows of the slots' contexts summed
    over layers; ``index_scored_rows``: the keys the indexer scored,
    summed over the ``full`` layers; ``index_selected_rows``: the rows
    the attention attended (the picked ones, of the ``latent_kv_tokens``
    the tick's kernel read to attend them), summed over layers (all 0 in
    a prefill); and of a prefill (0 in a tick) ``latent_context_rows``:
    the context's rows ``ds_latent_context_attn`` walked, summed over
    layers, and ``latent_context_pairs``: the (query, key) pairs its masks
    let through a head (``walked.latent_context_pairs``), summed over
    layers, float32."""
    return {**held_expert_counters(stats, cfg.held[1]),
            "latent_kv_tokens": jnp.asarray(latent_kv_tokens, jnp.int32),
            "index_scored_rows": jnp.asarray(scored, jnp.int32),
            "index_selected_rows": jnp.asarray(selected, jnp.int32),
            "latent_context_rows": jnp.asarray(context_rows, jnp.int32),
            "latent_context_pairs": jnp.asarray(context_pairs, F32)}


def _layers(cfg: GlmDsaConfig, params, tokens, positions, valid, attend):
    """The forward over sequences tokens [B, T] at ``positions`` [B, T];
    ``attend(layer, ap, ip, h, latents)`` -> [B, H, T, v_head_dim] is the
    caller's form of the attention (it keeps what a cache keeps, and a
    ``full`` layer's picks for the ``shared`` ones after it); ``ip`` is
    the layer's indexer, None on a ``shared`` layer.  Returns (logits,
    the expert layers' statistics)."""
    B, T = tokens.shape
    stacked = stacked_experts(params) if cfg.count("moe") else None
    stats = []
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for layer in range(cfg.num_hidden_layers):
        with jax.named_scope("layer"):
            ap = at(params["attn"], layer)
            ip = at(params["indexer"], _place(cfg.indexer_types, layer)) \
                if cfg.indexer_types[layer] == "full" else None
            with jax.named_scope("attn"):
                h = rms_norm(x, ap["ln1"], cfg.rms_norm_eps)
                out = attend(layer, ap, ip, h, latent_projections(
                    ap, h, positions, heads=cfg.n_head,
                    nope=cfg.qk_nope_head_dim, kv_rank=cfg.kv_lora_rank,
                    eps=cfg.rms_norm_eps, theta=cfg.rope_theta))
                x = x + merge_heads(out) @ ap["o_w"].astype(x.dtype)
            x = _ffn(cfg, params, stacked, layer, x.reshape(B * T, -1),
                     valid, stats).reshape(x.shape)
    logits = lm_head(x, params["norm_f"], params["lm_head"],
                     cfg.rms_norm_eps)
    return logits, stats
