"""SK Telecom A.X-K1 (HF ``model_type: axk1``): a pre-norm decoder with
multi-head LATENT attention (MLA, DeepSeek-V2's) in every layer, a dense
SwiGLU feed-forward in the first ``first_k_dense_replace`` layers and, in
the others, sigmoid-routed SwiGLU experts beside a shared expert.

    x <- x + attn(RMSNorm(x)); x <- x + ffn(RMSNorm(x));
    logits = RMSNorm(x) W_head          (untied, no bias anywhere)

* attention, ``H = num_attention_heads`` heads: ``c_q = RMSNorm(x W_qa)``
  (``q_lora_rank``); ``q = c_q W_qb``, a head ``[q_nope (qk_nope_head_dim)
  ; q_rope (qk_rope_head_dim)]``; ``[c_kv (kv_lora_rank) ; k_r] = x
  W_kva``; ``c_kv <- RMSNorm(c_kv)``; ``k_rope = RoPE(k_r)``, ONE for all
  heads; ``q_rope <- RoPE(q_rope)``; a head's ``k_nope = c_kv W_UK_h``,
  ``v = c_kv W_UV_h``.  Scores ``(q_nope . k_nope_j + q_rope . k_rope_j)
  * s`` over ``j <= t``, softmax in float32, ``o = sum p v``, ``W_o`` from
  ``H * v_head_dim``.  RoPE is rotate-half over the rotated dims at
  YaRN's frequencies (:func:`yarn_inv_freq`); ``s = (qk_nope_head_dim +
  qk_rope_head_dim)**-0.5 * m**2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1`` (:func:`softmax_scale`).
* the ABSORBED form of the same attention (``W_UK`` into the query,
  ``W_UV`` after the sum): ``q_lat_h = q_nope_h W_UK_h^T``; score
  ``(q_lat_h . c_kv_j + q_rope_h . k_rope_j) * s``; ``o_lat_h = sum p
  c_kv_j``; ``o_h = o_lat_h W_UV_h``.  It reads ``[c_kv ; k_rope]`` and
  nothing a head owns.
* dense FFN: ``down(silu(gate x) * up x)`` at ``intermediate_size``.
* expert layer: ``s = sigmoid(x_f32 W_r)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` largest (``topk_method "none"``: no
  selection bias, no group limit); weights ``s[chosen] / sum *
  routed_scaling_factor``; SwiGLU experts at ``moe_intermediate_size``;
  plus ``n_shared_experts`` shared SwiGLU experts (one matrix triple at
  ``n_shared_experts * moe_intermediate_size``) on every token.
  ``experts_held=(first, count)`` is this chip's share
  (``moe/dropless.py``): the held experts' part of the routed sum, the
  shared expert whole.

Not built, refused at construction: group-limited routing (``topk_method``
other than ``"none"``), softmax scoring, biases, tied embeddings, a RoPE
scaling other than YaRN or none, ``mscale != mscale_all_dim``.

This file is the model's SERVING surface (``ServeEngine``'s protocol).
The two steps take DIFFERENT forms of the one attention:

* the prefill computes the EXPANDED form (``k_nope`` and ``v`` of every
  head, ``flash_attention_fwd`` at the two widths) and writes the LATENT
  rows to the pages; with a cached prefix (a prefix hit, a chunk) it
  reads the context's latent rows where they lie in the pages and expands
  them a block of pages at a time inside ``ds_latent_context_attn``
  (:func:`_paged_context_attention`);
* the decode tick computes the ABSORBED form over the pool
  (``ds_latent_decode_attn``).

The cache is ONE pool (``config.values_in_keys``): a layer keeps one row
a token, ``[c_kv ; k_rope]`` after the norm and the rotation, read by all
heads; the values are its first ``kv_lora_rank`` lanes, so the engine
allocates no second array.  A row at rest is ``latent_width`` wide:
``kv_lora_rank + qk_rope_head_dim`` rounded up to whole 128-lane tiles
(576 -> 640, the last 64 lanes zero), which is how the TPU's tiled layout
holds a 576-wide row anyway.  Nothing else is kept for a request: no
``serving_state``, so the prefix cache and chunked prefill work.

Parameter tree: ``wte``, ``lm_head`` [d, V], ``norm_f``; ``attn``
(``ln1``, ``q_a_w`` [d, q_lora_rank], ``q_a_norm``, ``q_b_w``
[q_lora_rank, H * (nope + rope)], ``kv_a_w`` [d, kv_lora_rank + rope],
``kv_a_norm``, ``k_b_w`` [H, nope, kv_lora_rank] (``W_UK``), ``v_b_w``
[H, kv_lora_rank, v_head_dim] (``W_UV``), ``o_w``); ``dense`` (``ln2``,
``gate_w``, ``up_w``, ``down_w``); ``moe`` (``ln2``, ``router_w`` [d, E],
``shared_gate_w`` / ``shared_up_w`` / ``shared_down_w``, and the routed
``gate_w`` / ``up_w`` [layers, held, d, f], ``down_w`` [layers, held, f,
d]).  Every matrix input-major.  The checkpoint's ``kv_b_proj`` [H *
(nope + v), kv_lora_rank] is ``k_b_w`` and ``v_b_w`` side by side a head
(a split and a transpose of the file, not of the mathematics).  The
layout follows ``models/mimo_v2.py``'s one rule: what a layer reads by
its own index is a leaf of its own (a tuple of one array a layer), the
experts alone stay stacked and reach their kernels whole.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .walked import (F32, PagePool, ServedConfig, WalkedModel, at,
                     decode_index, dense_ffn, draw_layers,
                     held_expert_counters, latent_context_attention,
                     latent_context_pairs, latent_projections, latent_rows,
                     latent_self_attention, lm_head, merge_heads,
                     prefill_index, rms_norm, routed_experts, shared_expert,
                     stacked_experts, whole_tiles)


@dataclasses.dataclass(frozen=True)
class AxK1Config(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432      # the dense FFN's
    moe_intermediate_size: int = 2048   # ONE expert's
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 64
    num_key_value_heads: int = 64       # MLA: every head has its own k, v
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8                    # not read: topk_method "none"
    topk_group: int = 4                 # not read: topk_method "none"
    topk_method: str = "none"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # the program's
    experts_held: Optional[Tuple[int, int]] = None    # (first, count)
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes

    def __post_init__(self):
        rs = self.rope_scaling or {}
        unbuilt = {
            f"topk_method {self.topk_method!r} (only 'none': group-limited "
            "routing and a selection bias are not built)":
                self.topk_method != "none",
            f"scoring_func {self.scoring_func!r} (only 'sigmoid')":
                self.scoring_func != "sigmoid",
            f"hidden_act {self.hidden_act!r} (only 'silu')":
                self.hidden_act != "silu",
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "moe_layer_freq != 1": self.moe_layer_freq != 1,
            "num_key_value_heads != num_attention_heads":
                self.num_key_value_heads != self.num_attention_heads,
            f"rope_scaling type {rs.get('type')!r} (only 'yarn' or none)":
                bool(rs) and rs.get("type") != "yarn",
            "rope_scaling mscale != mscale_all_dim (cos and sin scaled)":
                rs.get("mscale", 1) != rs.get("mscale_all_dim", 1),
        }
        self.check(unbuilt, self.n_routed_experts)
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace: 0 .. num_hidden_layers")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A cached row at rest (module docstring)."""
        return whole_tiles(self.kv_lora_rank + self.qk_rope_head_dim)

    def count(self, kind: str) -> int:
        """Layers of an FFN kind ('dense', 'moe')."""
        dense = self.first_k_dense_replace
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
        """The pool's row width."""
        return self.latent_width

    @property
    def d_head_v(self) -> int:
        """The values: the rows' first ``kv_lora_rank`` lanes."""
        return self.kv_lora_rank

    @property
    def values_in_keys(self) -> bool:
        """ONE pool (``PagedKVCacheSpec.values_in_keys``)."""
        return True


def yarn_inv_freq(cfg: AxK1Config) -> Optional[np.ndarray]:
    """A frequency a rotated pair [qk_rope_head_dim / 2] float32, YaRN's
    (Peng et al. 2023, as the DeepSeek family computes it): pair ``i`` of
    ``theta**(-2i/R)`` keeps its frequency where it turns more than
    ``beta_fast`` times in ``original_max_position_embeddings`` (pairs
    below ``low``), takes ``1 / factor`` of it where it turns fewer than
    ``beta_slow`` times (pairs above ``high``), and a linear ramp over
    the pair index between; ``low`` and ``high`` are the two bounds'
    pair indices rounded down and up.  None without ``rope_scaling``."""
    rs = cfg.rope_scaling
    if not rs:
        return None
    rot, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    orig = rs["original_max_position_embeddings"]

    def pair_of(turns):       # the pair that turns so often in ``orig``
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), rot - 1)
    i = np.arange(rot // 2, dtype=np.float64)
    own = base ** (-2.0 * i / rot)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return (own * (1.0 - ramp) + own / rs["factor"] * ramp).astype(
        np.float32)


def softmax_scale(cfg: AxK1Config) -> float:
    """``qk_head_dim**-0.5 * m**2``, ``m = 0.1 * mscale_all_dim *
    ln(factor) + 1`` (the family's convention under YaRN); without
    ``rope_scaling`` ``m = 1``."""
    rs = cfg.rope_scaling or {}
    m = 1.0
    if rs.get("mscale_all_dim") and rs.get("factor", 1) > 1:
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return float(np.float32(cfg.qk_head_dim ** -0.5 * m * m))


class AxK1Model(WalkedModel):
    #: ``serving_unsupported`` is the common one: arms these paged steps
    #: do not have (the prefix cache and chunked prefill they do: a
    #: request keeps pages and nothing else)
    serving_aux = WalkedModel.serving_aux + (
        "latent_kv_tokens", "latent_context_rows", "latent_context_pairs")
    #: ``query_projections`` stays empty: this tick's compiled text copies
    #: no ``q_b_w`` ([1536, 12288] at 192 rows: the compiler reads it where
    #: it lies), so a form at rest (``WalkedModel.serving_layouts``) has
    #: nothing to remove here, and declared it changed how the tick
    #: prefetches ``v_b_w`` (described v5e, PR 55)

    def serving_cache_layers(self) -> Dict[str, int]:
        """Layers by the kind of cache they keep."""
        return {"latent": self.config.num_hidden_layers}

    def init(self, rng) -> Dict[str, Any]:
        """Every matrix normal(0, initializer_range), norm weights 1,
        drawn a layer at a time in ``param_dtype`` (the experts a layer
        at a time too, so the largest temporary is one layer's)."""
        cfg = self.config
        d, dt = cfg.hidden_size, jnp.dtype(cfg.param_dtype)
        std = cfg.initializer_range
        H, nope, rot = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rq, rkv, dv = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.v_head_dim
        f, e, held = (cfg.moe_intermediate_size, cfg.n_routed_experts,
                      cfg.held[1])
        fs = f * cfg.n_shared_experts
        keys = jax.random.split(rng, 5)

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

        def dense(key):
            k = jax.random.split(key, 3)
            return {"gate_w": norm(k[0], (d, cfg.intermediate_size)),
                    "up_w": norm(k[1], (d, cfg.intermediate_size)),
                    "down_w": norm(k[2], (cfg.intermediate_size, d))}

        def moe(key):
            k = jax.random.split(key, 7)
            return {"router_w": norm(k[0], (d, e)),
                    "shared_gate_w": norm(k[1], (d, fs)),
                    "shared_up_w": norm(k[2], (d, fs)),
                    "shared_down_w": norm(k[3], (fs, d))}

        def experts(key):               # the layer's other three keys
            k = jax.random.split(key, 7)
            return {"gate_w": norm(k[4], (held, d, f)),
                    "up_w": norm(k[5], (held, d, f)),
                    "down_w": norm(k[6], (held, f, d))}

        ones = {"attn": {"ln1": d, "q_a_norm": rq, "kv_a_norm": rkv},
                "dense": {"ln2": d}, "moe": {"ln2": d}}
        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "lm_head": norm(keys[1], (d, cfg.vocab_size)),
               "norm_f": jnp.ones((d,), dt)}
        for name, layer, n, key, whole in (
                ("attn", attn, cfg.num_hidden_layers, keys[2], None),
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
        in the expanded form (no cache, every position live)."""
        cfg = self.config
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

        def attend(i, ap, q_nope, q_rope, c_kv, k_rope):
            return _self_attention(cfg, ap, q_nope, q_rope, c_kv, k_rope)

        logits, stats = _layers(cfg, params, tokens, positions, None, attend)
        return (logits, _aux(cfg, stats, 0)) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, impl: Optional[str] = None,
                          aux: bool = False, **unbuilt):
        """One decode tick of every slot in the ABSORBED form over the one
        pool ``k_pool`` ``[L, pages, 1, page_len, latent_width]``;
        ``gpt2_decode_step_paged``'s contract with None where a second
        pool would be.  Returns (logits [S, V], pool, None, new_lengths)
        and, with ``aux``, the tick's counters.  An inactive slot's pages
        are neither read nor written."""
        from ..ops.pallas.decode_attention import latent_decode_attention
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        page_len, width = k_pool.shape[3], k_pool.shape[4]
        scale = softmax_scale(cfg)
        lengths, positions, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, page_len, cfg.n_positions)
        pool = PagePool((k_pool,), page_ids, offs, active)

        def attend(layer, ap, q_nope, q_rope, c_kv, k_rope):
            pool.write(layer, _cached_rows(cfg, c_kv[:, 0], k_rope[:, 0]))
            with jax.named_scope("absorb"):
                q_lat = jnp.einsum("shn,hnc->shc", q_nope[:, :, 0],
                                   ap["k_b_w"].astype(q_nope.dtype))
            # a head's query in the rows' own layout: [q_lat ; q_rope ; 0]
            o_lat = latent_decode_attention(
                _cached_rows(cfg, q_lat, q_rope[:, :, 0]),
                pool.rows[0].reshape(-1, page_len, width),
                page_table + layer * pool.per_layer, att_len,
                cfg.kv_lora_rank, sm_scale=scale, impl=impl)
            with jax.named_scope("absorb"):
                out = jnp.einsum("shc,hcv->shv", o_lat,
                                 ap["v_b_w"].astype(o_lat.dtype))
            return out[:, :, None]

        logits, stats = _layers(cfg, params, tokens[:, None],
                                positions[:, None], active, attend)
        out = (logits[:, 0], *pool.arrays(), None,
               lengths + active.astype(jnp.int32))
        if aux:
            out += (_aux(cfg, stats, jnp.sum(att_len) * cfg.n_layer),)
        return out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool=None, *, aux: bool = False, **unbuilt):
        """Delta-aware prefill of one request in the EXPANDED form, its
        latent rows written to the one pool ``k_pool``;
        ``gpt2_prefill_paged``'s contract: tokens [1, Tq] are the prompt
        less its cached prefix, right-padded to the bucket; ``delta_len``,
        ``prefix_len`` and ``page_row`` [max_pages] are traced.  Returns
        (logits [1, Tq, V], pool, None); ``logits[0, delta_len - 1]``
        scores the first generated token.  Padding rows reach no expert
        and write no page."""
        self.refuse(unbuilt)
        cfg = self.config
        page_len, width = k_pool.shape[3], k_pool.shape[4]
        prefix_len = jnp.asarray(prefix_len, jnp.int32)
        delta_len = jnp.asarray(delta_len, jnp.int32)
        valid, page_ids, offs, abs_pos, positions = prefill_index(
            page_row, delta_len, tokens.shape[1], page_len, prefix_len,
            cfg.n_positions)
        pool = PagePool((k_pool,), page_ids, offs, valid)
        context_len = prefix_len + delta_len
        # a padding row sees no key: whole blocks of them are skipped
        q_pos = jnp.where(valid, abs_pos, -1)

        def attend(layer, ap, q_nope, q_rope, c_kv, k_rope):
            pool.write(layer, _cached_rows(cfg, c_kv[0], k_rope[0]))
            return jax.lax.cond(
                prefix_len == 0,
                lambda _: _self_attention(cfg, ap, q_nope, q_rope, c_kv,
                                          k_rope),
                lambda _: _paged_context_attention(
                    cfg, ap, q_nope[0], q_rope[0],
                    pool.rows[0].reshape(-1, page_len, width),
                    layer * pool.per_layer + page_row, q_pos,
                    context_len)[None],
                None)

        logits, stats = _layers(cfg, params, tokens, positions, valid, attend)
        out = (logits, *pool.arrays(), None)
        if aux:
            # the kernel runs where the context begins in the pages
            paged = jnp.where(prefix_len == 0, 0, cfg.n_layer)
            out += (_aux(cfg, stats, 0, paged * context_len,
                         paged * latent_context_pairs(q_pos, context_len)),)
        return out


# -- the layer's parts ----------------------------------------------------

def _latents(cfg: AxK1Config, ap, h, positions):
    """h [B, T, d] (normed), positions [B, T] -> q_nope [B, H, T, nope],
    q_rope [B, H, T, rot] (rotated), c_kv [B, T, kv_lora_rank] (normed),
    k_rope [B, T, rot] (rotated): what the cache keeps is the last two."""
    return latent_projections(
        ap, h, positions, heads=cfg.n_head, nope=cfg.qk_nope_head_dim,
        kv_rank=cfg.kv_lora_rank, eps=cfg.rms_norm_eps,
        theta=cfg.rope_theta, inv_freq=yarn_inv_freq(cfg))[1:]


def _cached_rows(cfg: AxK1Config, c_kv, k_rope):
    """The rows at rest ``[c_kv ; k_rope ; 0]``, ``latent_width`` wide."""
    return latent_rows(c_kv, k_rope, cfg.latent_width)


def _self_attention(cfg: AxK1Config, ap, q_nope, q_rope, c_kv, k_rope):
    """The expanded form over whole sequences from position 0
    (``walked.latent_self_attention``)."""
    return latent_self_attention(ap, q_nope, q_rope, c_kv, k_rope,
                                 flash=cfg.attn_impl == "flash",
                                 sm_scale=softmax_scale(cfg))


def _paged_context_attention(cfg: AxK1Config, ap, q_nope, q_rope, pool_pages,
                             page_ids, abs_pos, context_len):
    """The expanded form for a prefill whose context begins in the pages
    (``walked.latent_context_attention``, every key under the causal
    rule)."""
    return latent_context_attention(
        ap, q_nope, q_rope, pool_pages, page_ids, abs_pos, context_len,
        kv_rank=cfg.kv_lora_rank, sm_scale=softmax_scale(cfg))


def _experts(cfg: AxK1Config, ep, stacked, index: int, x, valid):
    """The expert layer on normed x [N, d]: this share's part of the
    routed sum and the shared expert whole.  ``stacked``: every layer's
    held experts flat."""
    with jax.named_scope("moe"):
        routed, st = routed_experts(
            x, ep["router_w"], jnp.zeros((cfg.n_routed_experts,), F32),
            stacked, index, top_k=cfg.num_experts_per_tok, held=cfg.held,
            valid=valid, act="swiglu", scale=cfg.routed_scaling_factor,
            renormalize=cfg.norm_topk_prob)
    if cfg.n_shared_experts:
        routed = routed + shared_expert(ep, x)
    return routed, st


def _ffn(cfg: AxK1Config, params, stacked, layer: int, x, valid, stats):
    """x [N, d] -> x + ffn(norm(x)); an expert layer's statistics are
    appended to ``stats``."""
    dense = cfg.first_k_dense_replace
    kind, i = ("dense", layer) if layer < dense else ("moe", layer - dense)
    fp = at(params[kind], i)
    h = rms_norm(x, fp["ln2"], cfg.rms_norm_eps)
    if kind == "dense":
        return x + dense_ffn(fp, h)
    out, st = _experts(cfg, fp, stacked, i, h, valid)
    stats.append(st)
    return x + out


def _aux(cfg: AxK1Config, stats, latent_kv_tokens, context_rows=0,
         context_pairs=0) -> Dict[str, jnp.ndarray]:
    """The call's counters: the expert layers' as ``NemotronHModel``'s
    (of the HELD experts); ``latent_kv_tokens``: the live rows the decode
    kernel read, summed over layers (0 in a prefill); and of a prefill
    whose context begins in the pages (0 elsewhere) ``latent_context_rows``:
    the context's rows ``ds_latent_context_attn`` walked, summed over
    layers, and ``latent_context_pairs``: the (query, key) pairs the causal
    rule let through a head (``walked.latent_context_pairs``), summed over
    layers, float32."""
    return {**held_expert_counters(stats, cfg.held[1]),
            "latent_kv_tokens": jnp.asarray(latent_kv_tokens, jnp.int32),
            "latent_context_rows": jnp.asarray(context_rows, jnp.int32),
            "latent_context_pairs": jnp.asarray(context_pairs, F32)}


def _layers(cfg: AxK1Config, params, tokens, positions, valid, attend):
    """The forward over sequences tokens [B, T] at ``positions`` [B, T];
    ``attend(layer, ap, q_nope, q_rope, c_kv, k_rope)`` -> [B, H, T,
    v_head_dim] is the caller's form of the attention (and keeps what a
    cache keeps); ``valid`` [B * T] bool leaves padding out of the expert
    layers.  Returns (logits, the expert layers' statistics)."""
    B, T = tokens.shape
    stacked = stacked_experts(params) if cfg.count("moe") else None
    stats = []
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for layer in range(cfg.num_hidden_layers):
        with jax.named_scope("layer"):
            ap = at(params["attn"], layer)
            with jax.named_scope("attn"):
                h = rms_norm(x, ap["ln1"], cfg.rms_norm_eps)
                out = attend(layer, ap, *_latents(cfg, ap, h, positions))
                x = x + merge_heads(out) @ ap["o_w"].astype(x.dtype)
            x = _ffn(cfg, params, stacked, layer, x.reshape(B * T, -1),
                     valid, stats).reshape(x.shape)
    logits = lm_head(x, params["norm_f"], params["lm_head"],
                     cfg.rms_norm_eps)
    return logits, stats
