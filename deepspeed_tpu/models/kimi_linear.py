"""Moonshot Kimi Linear (arXiv:2510.26692; HF ``model_type: kimi_linear``):
a pre-norm decoder whose mixers are of two kinds by two published lists
(``linear_attn_config.kda_layers`` / ``full_attn_layers``, layers numbered
from 1; three to one), a dense SwiGLU feed-forward in the first
``first_k_dense_replace`` layers and sigmoid-routed SwiGLU experts beside a
shared expert in the others.  No positional encoding anywhere.

    x <- x + mixer(RMSNorm(x)); x <- x + ffn(RMSNorm(x));
    logits = RMSNorm(x) W_head          (untied, no bias anywhere)

* a ``kda`` layer (Kimi Delta Attention; ``H`` heads, keys and values
  ``head_dim`` wide): ``[q~ | k~ | v] = SiLU(conv(h W_qkv))``, a depthwise
  causal convolution of ``short_conv_kernel_size`` over time, no bias; a
  head's ``q = q~ / |q~| * head_dim**-0.5``, ``k = k~ / |k~|``; the decay, a
  vector a head, ``g = -exp(A_log) * softplus(W_f2 (W_f1 h) + dt_bias)``,
  ``a = exp(g)``; the step ``b = sigmoid(W_b h)``, one a head; the state
  ``S`` ``[head_dim, head_dim]`` a head, float32: ``S' = a (rowwise) S``;
  ``u = b (v - S'^T k)``; ``S = S' + k u^T``; ``o = S^T q``
  (``ops/pallas/kda.py``).  Out: ``W_o [RMSNorm_head(o) * sigmoid(W_g2
  (W_g1 h))]``, the norm's weight one vector for all heads.
* an ``mla`` layer: latent attention (``models/axk1.py``'s) with two things
  taken out: the query comes straight from ``W_q h`` (``q_lora_rank``
  null), a head ``[q_nope ; q_pe]``, and NOTHING is rotated
  (``mla_use_nope``; ``rope_theta`` is in the source and rotates nothing).
  ``[c_kv ; k_pe] = h W_kva``, ``c_kv`` RMS-normed, ``k_pe`` ONE a token;
  a head's ``k = [c_kv W_UK ; k_pe]``, ``v = c_kv W_UV``; softmax in
  float32 at ``(qk_nope_head_dim + qk_rope_head_dim)**-0.5``.
* the FFN: dense SwiGLU at ``intermediate_size``, or sigmoid scores over
  all ``num_experts`` in float32, the ``num_experts_per_token`` largest of
  score + ``e_score_correction_bias`` (``num_expert_group`` 1: no group
  limit), weights = own scores / their sum (``moe_renormalize``) x
  ``routed_scaling_factor``, SwiGLU experts at ``moe_intermediate_size``,
  plus one shared SwiGLU expert on every token.  ``experts_held=(first,
  count)`` is this chip's share (``moe/dropless.py``).

Not built, refused at construction: ``rope_scaling``, a low-rank query,
the multi-token-prediction module, group-limited routing, a layer in
neither list or in both.

This file is the model's SERVING surface (``ServeEngine``'s protocol).  A
request keeps two kinds of thing.  By slot (``serving_state``), a ``kda``
layer's state ``[H, head_dim, head_dim]`` in float32 (``"kda"``) and the
last ``short_conv_kernel_size - 1`` rows of ``h W_qkv`` before the
convolution (``"kda_conv"``): their size does not grow with the context.
In the page pool, an ``mla`` layer's ONE row a token ``[c_kv ; k_pe]``,
kept ``latent_width`` wide (``config.values_in_keys``; ``config.n_layer``
counts the ``mla`` layers only).  The decode tick is the absorbed form over
the pool (``ds_latent_decode_attn``) and ``ds_kda_decode`` over the live
slots' states where they lie.  The prefill takes a CHUNK of a prompt
(``prefix_len`` > 0): a ``kda`` layer's scan starts from the slot's state
and its convolution from the slot's last rows, an ``mla`` layer writes the
chunk's rows and attends the request's pages
(``walked.latent_context_attention``); a request's first chunk starts from
zeros, whatever the slot's last occupant left there.  So
``serving_unsupported`` is the common one: chunked prefill is taken.

Parameter tree: ``wte``, ``lm_head`` [d, V], ``norm_f``; ``kda`` (``ln1``,
``qkv_w`` [d, 3 H head_dim] (``W_q | W_k | W_v``), ``conv_w`` [K, 3 H
head_dim], ``f_a_w`` / ``f_b_w``, ``A_log`` [H], ``dt_bias``, ``b_w``,
``g_a_w`` / ``g_b_w``, ``o_norm`` [head_dim], ``o_w``); ``mla`` (``ln1``,
``q_w``, ``kv_a_w``, ``kv_a_norm``, ``k_b_w`` [H, nope, rank] (``W_UK``),
``v_b_w`` [H, rank, v] (``W_UV``), ``o_w``); ``dense`` (``ln2``,
``gate_w``, ``up_w``, ``down_w``); ``moe`` (``ln2``, ``router_w`` [d, E],
``router_bias`` [E], ``shared_*_w`` and the routed ``gate_w`` / ``up_w``
[layers, held, d, f], ``down_w``).  Every matrix input-major; a leaf a
layer, the experts alone stacked (``models/mimo_v2.py``'s rule); the
form the ``mla`` layers' ``q_w`` rest in inside an engine is
``WalkedModel.serving_layouts``'s to say (PR 55).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.kda import kda_chunked, kda_decode
from .walked import (F32, PagePool, ServedConfig, WalkedModel, at,
                     decode_index, default_scale, dense_ffn, draw_layers,
                     held_expert_counters, l2_norm, latent_context_attention,
                     latent_projections, latent_rows, latent_self_attention,
                     lm_head, merge_heads, prefill_index, rms_norm,
                     routed_experts, shared_expert, shift_tail, silu_conv,
                     stacked_experts, whole_tiles, write_slot_state)


#: the range ``init`` draws a KDA channel's step from (log-uniform)
_TIME_STEP = (0.001, 0.1)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216       # the dense FFN's
    moe_intermediate_size: int = 1024   # ONE expert's
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 72                  # not read: hidden / heads
    linear_attn_config: Optional[Dict[str, Any]] = None
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    num_expert_group: int = 1
    topk_group: int = 1
    use_grouped_topk: bool = True       # one group: no limit
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0         # not read: mla_use_nope
    rope_scaling: Optional[Dict[str, Any]] = None
    model_max_length: int = 1048576
    num_nextn_predict_layers: int = 0
    initializer_range: float = 0.02
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    # the program's
    experts_held: Optional[Tuple[int, int]] = None    # (first, count)
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes
    state_dtype: str = "float32"        # the delta-rule state's

    def __post_init__(self):
        lin = self.linear_attn_config or {}
        kda = set(lin.get("kda_layers", ()))
        full = set(lin.get("full_attn_layers", ()))
        layers = set(range(1, self.num_hidden_layers + 1))
        unbuilt = {
            "rope_scaling": bool(self.rope_scaling),
            "q_lora_rank (a low-rank query)": self.q_lora_rank is not None,
            "mla_use_nope false (rotated latent attention)":
                not self.mla_use_nope,
            "num_nextn_predict_layers (the multi-token-prediction "
            "module)": self.num_nextn_predict_layers != 0,
            "num_expert_group / topk_group != 1 (group-limited routing)":
                (self.num_expert_group, self.topk_group) != (1, 1),
            f"moe_router_activation_func "
            f"{self.moe_router_activation_func!r} (only 'sigmoid')":
                self.moe_router_activation_func != "sigmoid",
            f"hidden_act {self.hidden_act!r} (only 'silu')":
                self.hidden_act != "silu",
            "num_shared_experts != 1": self.num_shared_experts != 1,
            "moe_layer_freq != 1": self.moe_layer_freq != 1,
            "tie_word_embeddings": self.tie_word_embeddings,
            "num_key_value_heads != num_attention_heads":
                self.num_key_value_heads != self.num_attention_heads,
            "a layer in neither of linear_attn_config's kda_layers and "
            "full_attn_layers, or in both":
                bool((layers - kda - full) | (kda & full & layers)),
        }
        self.check(unbuilt, self.num_experts)
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace: 0 .. num_hidden_layers")
        if self.num_experts_per_token > self.num_experts:
            raise ValueError("num_experts_per_token exceeds num_experts")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def mixers(self) -> Tuple[str, ...]:
        """Each layer's mixer kind, in order."""
        kda = set(self.linear_attn_config["kda_layers"])
        return tuple("kda" if layer in kda else "mla"
                     for layer in range(1, self.num_hidden_layers + 1))

    def count(self, kind: str) -> int:
        """Layers of a mixer kind ('kda', 'mla') or an FFN kind ('dense',
        'moe')."""
        if kind in ("kda", "mla"):
            return self.mixers.count(kind)
        dense = self.first_k_dense_replace
        return dense if kind == "dense" else self.num_hidden_layers - dense

    @property
    def kda_heads(self) -> int:
        return self.linear_attn_config["num_heads"]

    @property
    def kda_head_dim(self) -> int:
        """Keys and values of a ``kda`` head, and the gates' rank (the
        family's code sets both from ``linear_attn_config.head_dim``)."""
        return self.linear_attn_config["head_dim"]

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def conv_kernel(self) -> int:
        return self.linear_attn_config["short_conv_kernel_size"]

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A cached row at rest: ``[c_kv ; k_pe]`` in whole lane tiles
        (``models/axk1.py``: 576 -> 640)."""
        return whole_tiles(self.kv_lora_rank + self.qk_rope_head_dim)

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_positions(self) -> int:
        return self.model_max_length

    @property
    def n_layer(self) -> int:
        """Layers that keep a row a token: the pool's depth."""
        return self.count("mla")

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


class KimiLinearModel(WalkedModel):
    #: ``serving_unsupported`` is the common one: the prefill takes a chunk
    #: (module docstring); the engine refuses the prefix cache, KV tiering
    #: and speculation for any model with ``serving_state``
    serving_aux = WalkedModel.serving_aux + (
        "kda_slot_layers", "latent_kv_tokens", "kda_chunk_tokens",
        "latent_context_rows")
    query_projections = ("q_w",)      # walked.serving_layouts

    def serving_cache_layers(self) -> Dict[str, int]:
        """Layers by the kind of cache they keep."""
        return {"latent": self.config.count("mla"),
                "kda": self.config.count("kda")}

    def serving_state(self, slots: int) -> Dict[str, Any]:
        """What a request keeps beside its pages, by slot: name ->
        ``jax.ShapeDtypeStruct``; the slot is axis 1."""
        cfg = self.config
        lk, dk = cfg.count("kda"), cfg.kda_head_dim
        return {
            "kda": jax.ShapeDtypeStruct(
                (lk, slots, cfg.kda_heads, dk, dk),
                jnp.dtype(cfg.state_dtype)),
            "kda_conv": jax.ShapeDtypeStruct(
                (lk, slots, cfg.conv_kernel - 1, 3 * cfg.kda_width),
                jnp.dtype(cfg.param_dtype)),
        }

    def init(self, rng) -> Dict[str, Any]:
        """Every matrix normal(0, initializer_range), norm weights 1;
        ``A`` uniform in [1, 16] and ``dt`` log-uniform in [0.001, 0.1]
        (``_TIME_STEP``; ``dt_bias`` its inverse softplus), as
        ``models/nemotron_h.py`` draws Mamba-2's (that file's convention,
        not this model's); the depthwise conv torch's default for its
        fan-in; ``router_bias`` (``e_score_correction_bias``) 0.  Drawn a
        layer at a time in ``param_dtype``."""
        cfg = self.config
        d, dt = cfg.hidden_size, jnp.dtype(cfg.param_dtype)
        std = cfg.initializer_range
        H, nope, rot = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rkv, dv = cfg.kv_lora_rank, cfg.v_head_dim
        Hk, dk, C, K = (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width,
                        cfg.conv_kernel)
        f, e, held = (cfg.moe_intermediate_size, cfg.num_experts,
                      cfg.held[1])
        keys = jax.random.split(rng, 6)

        def norm(key, shape):
            return (jax.random.normal(key, shape, F32) * std).astype(dt)

        def kda(key):
            k = jax.random.split(key, 10)
            bound = 1.0 / math.sqrt(K)
            low, high = (math.log(t) for t in _TIME_STEP)
            step = jnp.exp(jax.random.uniform(k[3], (C,), F32)
                           * (high - low) + low)
            return {"qkv_w": norm(k[0], (d, 3 * C)),
                    "conv_w": jax.random.uniform(
                        k[1], (K, 3 * C), F32, -bound, bound).astype(dt),
                    "A_log": jnp.log(jax.random.uniform(
                        k[2], (Hk,), F32, 1.0, 16.0)).astype(dt),
                    "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                    "f_a_w": norm(k[4], (d, dk)),
                    "f_b_w": norm(k[5], (dk, C)),
                    "b_w": norm(k[6], (d, Hk)),
                    "g_a_w": norm(k[7], (d, dk)),
                    "g_b_w": norm(k[8], (dk, C)),
                    "o_w": norm(k[9], (C, d))}

        def mla(key):
            k = jax.random.split(key, 5)
            return {"q_w": norm(k[0], (d, H * (nope + rot))),
                    "kv_a_w": norm(k[1], (d, rkv + rot)),
                    "k_b_w": norm(k[2], (H, nope, rkv)),
                    "v_b_w": norm(k[3], (H, rkv, dv)),
                    "o_w": norm(k[4], (H * dv, d))}

        def dense(key):
            k = jax.random.split(key, 3)
            return {"gate_w": norm(k[0], (d, cfg.intermediate_size)),
                    "up_w": norm(k[1], (d, cfg.intermediate_size)),
                    "down_w": norm(k[2], (cfg.intermediate_size, d))}

        def moe(key):
            k = jax.random.split(key, 7)
            return {"router_w": norm(k[0], (d, e)),
                    "router_bias": jnp.zeros((e,), dt),
                    "shared_gate_w": norm(k[1], (d, f)),
                    "shared_up_w": norm(k[2], (d, f)),
                    "shared_down_w": norm(k[3], (f, d))}

        def experts(key):               # the layer's other three keys
            k = jax.random.split(key, 7)
            return {"gate_w": norm(k[4], (held, d, f)),
                    "up_w": norm(k[5], (held, d, f)),
                    "down_w": norm(k[6], (held, f, d))}

        ones = {"kda": {"ln1": d, "o_norm": dk},
                "mla": {"ln1": d, "kv_a_norm": rkv},
                "dense": {"ln2": d}, "moe": {"ln2": d}}
        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "lm_head": norm(keys[1], (d, cfg.vocab_size)),
               "norm_f": jnp.ones((d,), dt)}
        for name, layer, key, whole in (
                ("kda", kda, keys[2], None), ("mla", mla, keys[3], None),
                ("dense", dense, keys[4], None),
                ("moe", moe, keys[5], experts)):
            n = cfg.count(name)
            if not n:
                continue
            of = jax.random.split(key, n)
            out[name] = draw_layers(layer, of, ones[name], dt)
            if whole:
                out[name].update(jax.lax.map(whole, of))
        return out

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        from empty state (no cache, every position live)."""
        cfg = self.config
        B, T = tokens.shape
        K, C = cfg.conv_kernel, 3 * cfg.kda_width
        zeros = jnp.zeros((cfg.kda_heads,) + (cfg.kda_head_dim,) * 2, F32)

        def kda(i, kp, h):
            qkv = _kda_qkv(kp, h)
            padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
            q, k, v = _kda_heads(cfg, _kda_conv(
                kp, [padded[:, j:j + T] for j in range(K)]))
            g, b, gate = _kda_gates(cfg, kp, h)
            with jax.named_scope("kda_chunk"):
                o, _ = jax.vmap(lambda *t: kda_chunked(*t, zeros))(
                    q, k, v, g, b)
            return _kda_out(cfg, kp, o, gate, h.dtype)

        def mla(i, ap, q_nope, q_pe, c_kv, k_pe):
            return latent_self_attention(
                ap, q_nope, q_pe, c_kv, k_pe, flash=cfg.attn_impl == "flash",
                sm_scale=default_scale(cfg.qk_head_dim))

        logits, stats = _layers(cfg, params, tokens, None, kda, mla)
        return (logits, _aux(cfg, stats)) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, state,
                          impl: Optional[str] = None, aux: bool = False,
                          **unbuilt):
        """One decode tick of every slot: the absorbed form over the one
        pool ``k_pool`` ``[mla layers, pages, 1, page_len, latent_width]``
        and ``ds_kda_decode`` over the live slots' states;
        ``gpt2_decode_step_paged``'s contract with None where a second
        pool would be, plus the request state.  Returns (logits [S, V],
        pool, None, state, new_lengths) and, with ``aux``, the tick's
        counters.  An inactive slot's pages and state are neither read nor
        written."""
        from ..ops.pallas.decode_attention import latent_decode_attention
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        S = page_table.shape[0]
        page_len, width = k_pool.shape[3], k_pool.shape[4]
        scale = default_scale(cfg.qk_head_dim)
        lengths, _, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, page_len, cfg.n_positions)
        pool = PagePool((k_pool,), page_ids, offs, active)
        shape = state["kda"].shape
        # every layer's slots in one row, as ``kda_decode`` takes them;
        # the tails are read from the leaf as it came and written once,
        # stacked, at the end (``walked.shift_tail`` says why)
        new = {"kda": state["kda"].reshape((-1,) + shape[2:]),
               "kda_conv": []}

        def kda(i, kp, h):
            qkv = _kda_qkv(kp, h)                           # [S, 1, 3C]
            window, kept = shift_tail(state["kda_conv"][i], qkv, active)
            new["kda_conv"].append(kept)
            q, k, v = _kda_heads(cfg, _kda_conv(
                kp, [window[:, j] for j in range(cfg.conv_kernel)]))
            g, b, gate = _kda_gates(cfg, kp, h[:, 0])
            with jax.named_scope("kda_update"):
                new["kda"], o = kda_decode(new["kda"], jnp.exp(g), k, v, q,
                                           b, active, base=i * S)
            return _kda_out(cfg, kp, o[:, None], gate[:, None], h.dtype)

        def mla(i, ap, q_nope, q_pe, c_kv, k_pe):
            pool.write(i, latent_rows(c_kv[:, 0], k_pe[:, 0], width))
            with jax.named_scope("absorb"):
                q_lat = jnp.einsum("shn,hnc->shc", q_nope[:, :, 0],
                                   ap["k_b_w"].astype(q_nope.dtype))
            # a head's query in the rows' own layout: [q_lat ; q_pe ; 0]
            o_lat = latent_decode_attention(
                latent_rows(q_lat, q_pe[:, :, 0], width),
                pool.rows[0].reshape(-1, page_len, width),
                page_table + i * pool.per_layer, att_len,
                cfg.kv_lora_rank, sm_scale=scale, impl=impl)
            with jax.named_scope("absorb"):
                out = jnp.einsum("shc,hcv->shv", o_lat,
                                 ap["v_b_w"].astype(o_lat.dtype))
            return out[:, :, None]

        logits, stats = _layers(cfg, params, tokens[:, None], active, kda,
                                mla)
        new = {"kda": new["kda"].reshape(shape),
               "kda_conv": jnp.stack(new["kda_conv"])}
        out = (logits[:, 0], *pool.arrays(), None, new,
               lengths + active.astype(jnp.int32))
        if aux:
            live = jnp.sum(active.astype(jnp.int32))
            out += (_aux(cfg, stats, kda_slot_layers=live * cfg.count("kda"),
                         latent_kv_tokens=jnp.sum(att_len)
                         * cfg.count("mla")),)
        return out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool=None, *, state, slot,
                      aux: bool = False, **unbuilt):
        """Prefill of one request, or of one CHUNK of its prompt, into the
        one pool ``k_pool`` and into ``slot`` of the request state.
        tokens [1, Tq] are the prompt's tokens from ``prefix_len`` on,
        right-padded to the bucket; ``delta_len``, ``prefix_len``,
        ``page_row`` [max_pages] and ``slot`` traced.  With ``prefix_len``
        0 the ``kda`` layers start from a zero state and a zero
        convolution tail, whatever the slot holds; otherwise from what the
        chunk before left in the slot.  The ``mla`` layers write the
        chunk's rows and attend the request's pages up to the chunk's end.
        Returns (logits [1, Tq, V], pool, None, state);
        ``logits[0, delta_len - 1]`` scores the first generated token.
        The slot's state is OVERWRITTEN with the state at ``prefix_len +
        delta_len``: padding takes ``g = 0`` and ``b = 0`` and feeds
        nothing, the convolution's tail is read at the true end."""
        self.refuse(unbuilt)
        cfg = self.config
        Tq, K = tokens.shape[1], cfg.conv_kernel
        page_len, width = k_pool.shape[3], k_pool.shape[4]
        i32 = jnp.int32
        prefix_len = jnp.asarray(prefix_len, i32)
        delta_len = jnp.asarray(delta_len, i32)
        slot = jnp.asarray(slot, i32)
        valid, page_ids, offs, abs_pos, _ = prefill_index(
            page_row, delta_len, Tq, page_len, prefix_len, cfg.n_positions)
        pool = PagePool((k_pool,), page_ids, offs, valid)
        context_len = prefix_len + delta_len
        # a padding row sees no key: whole blocks of them are skipped
        q_pos = jnp.where(valid, abs_pos, -1)
        scale = default_scale(cfg.qk_head_dim)
        first = prefix_len == 0
        kept = {"kda": [], "kda_conv": []}

        def of_slot(leaf, i):
            # one slice of the leaf (``leaf[i]`` first would copy the
            # layer); zeros for a request's first chunk
            got = jax.lax.dynamic_slice(
                leaf, (i, slot) + (0,) * (leaf.ndim - 2),
                (1, 1) + leaf.shape[2:])[0, 0]
            return jnp.where(first, jnp.zeros_like(got), got)

        def kda(i, kp, h):
            qkv = _kda_qkv(kp, h)                           # [1, Tq, 3C]
            tail = of_slot(state["kda_conv"], i)            # [K - 1, 3C]
            padded = jnp.concatenate([tail.astype(qkv.dtype), qkv[0]])
            kept["kda_conv"].append(jax.lax.dynamic_slice_in_dim(
                padded, delta_len, K - 1, axis=0))
            q, k, v = _kda_heads(cfg, _kda_conv(
                kp, [padded[j:j + Tq] for j in range(K)]))
            g, b, gate = _kda_gates(cfg, kp, h[0])
            g = jnp.where(valid[:, None, None], g, 0.0)
            b = jnp.where(valid[:, None], b, 0.0)
            with jax.named_scope("kda_chunk"):
                o, final = kda_chunked(q, k, v, g, b,
                                       of_slot(state["kda"], i))
            kept["kda"].append(final)
            return _kda_out(cfg, kp, o[None], gate[None], h.dtype)

        def mla(i, ap, q_nope, q_pe, c_kv, k_pe):
            pool.write(i, latent_rows(c_kv[0], k_pe[0], width))
            return latent_context_attention(
                ap, q_nope[0], q_pe[0],
                pool.rows[0].reshape(-1, page_len, width),
                i * pool.per_layer + page_row, q_pos, context_len,
                kv_rank=cfg.kv_lora_rank, sm_scale=scale)[None]

        logits, stats = _layers(cfg, params, tokens, valid, kda, mla)
        out = (logits, *pool.arrays(), None,
               write_slot_state(state, kept, slot))
        if aux:
            out += (_aux(cfg, stats,
                         kda_chunk_tokens=delta_len * cfg.count("kda"),
                         latent_context_rows=context_len
                         * cfg.count("mla")),)
        return out


# -- the layer's parts ----------------------------------------------------

def _kda_qkv(kp, h):
    """h [..., d] (normed) -> ``[q~ | k~ | v]`` before the convolution
    [..., 3 H head_dim]."""
    with jax.named_scope("kda_proj"):
        return h @ kp["qkv_w"].astype(h.dtype)


def _kda_conv(kp, taps):
    """taps: the K rows under the filter, oldest first, each [..., 3C] ->
    silu(conv) in float32 [..., 3C]."""
    with jax.named_scope("kda_conv"):
        return silu_conv(kp["conv_w"], taps)


def _kda_heads(cfg: KimiLinearConfig, conv_out):
    """conv_out [..., 3C] float32 -> q, k [..., H, head_dim] (normalised,
    q scaled), v [..., H, head_dim]."""
    lead = conv_out.shape[:-1]
    q, k, v = (t.reshape(lead + (cfg.kda_heads, cfg.kda_head_dim))
               for t in jnp.split(conv_out, 3, axis=-1))
    return l2_norm(q) * cfg.kda_head_dim ** -0.5, l2_norm(k), v


def _kda_gates(cfg: KimiLinearConfig, kp, h):
    """h [..., d] (normed) -> the log-decay g [..., H, head_dim] (<= 0),
    the step b [..., H] and the output gate [..., H, head_dim], float32."""
    with jax.named_scope("kda_gates"):
        lead = h.shape[:-1]
        heads = lead + (cfg.kda_heads, cfg.kda_head_dim)

        def low_rank(a_w, b_w):
            return ((h @ kp[a_w].astype(h.dtype))
                    @ kp[b_w].astype(h.dtype)).astype(F32)

        f = low_rank("f_a_w", "f_b_w") + kp["dt_bias"].astype(F32)
        g = -jnp.exp(kp["A_log"].astype(F32))[:, None] \
            * jax.nn.softplus(f).reshape(heads)
        b = jax.nn.sigmoid((h @ kp["b_w"].astype(h.dtype)).astype(F32))
        gate = jax.nn.sigmoid(low_rank("g_a_w", "g_b_w")).reshape(heads)
    return g, b, gate


def _kda_out(cfg: KimiLinearConfig, kp, o, gate, dtype):
    """o, gate [..., H, head_dim] float32 -> the mixer's output [..., d]:
    RMSNorm a head, the gate, ``W_o``."""
    with jax.named_scope("kda_out"):
        y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = y * kp["o_norm"].astype(F32) * gate
        y = y.reshape(y.shape[:-2] + (cfg.kda_width,)).astype(dtype)
        return y @ kp["o_w"].astype(dtype)


def _experts(cfg: KimiLinearConfig, ep, stacked, index: int, x, valid):
    """The expert layer on normed x [N, d]: this share's part of the
    routed sum and the shared expert whole.  ``stacked``: every layer's
    held experts flat."""
    with jax.named_scope("moe"):
        routed, st = routed_experts(
            x, ep["router_w"], ep["router_bias"], stacked, index,
            top_k=cfg.num_experts_per_token, held=cfg.held, valid=valid,
            act="swiglu", scale=cfg.routed_scaling_factor,
            renormalize=cfg.moe_renormalize)
    return routed + shared_expert(ep, x), st


def _ffn(cfg: KimiLinearConfig, params, stacked, layer: int, x, valid,
         stats):
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


def _aux(cfg: KimiLinearConfig, stats, **counted) -> Dict[str, jnp.ndarray]:
    """The call's counters: the expert layers' (of the HELD experts);
    ``kda_slot_layers``: live slots x ``kda`` layers of a tick (what
    ``ds_kda_decode`` rewrote), ``latent_kv_tokens``: the live rows the
    latent decode kernel read, summed over layers; and of a prefill
    ``kda_chunk_tokens``: the call's tokens x ``kda`` layers,
    ``latent_context_rows``: the context's rows ``ds_latent_context_attn``
    walked, summed over layers.  0 where the call is of the other kind."""
    names = ("kda_slot_layers", "latent_kv_tokens", "kda_chunk_tokens",
             "latent_context_rows")
    return {**held_expert_counters(stats, cfg.held[1]),
            **{name: jnp.asarray(counted.get(name, 0), jnp.int32)
               for name in names}}


def _layers(cfg: KimiLinearConfig, params, tokens, valid, kda, mla):
    """The forward over sequences tokens [B, T]: ``kda(i, kp, h)`` -> [B,
    T, d] and ``mla(i, ap, q_nope, q_pe, c_kv, k_pe)`` -> [B, H, T,
    v_head_dim] are the caller's forms of the two mixers (``i``: the
    layer's index among its kind; they keep what a cache keeps);
    ``valid`` [B * T] bool leaves padding out of the expert layers.
    Returns (logits, the expert layers' statistics)."""
    B, T = tokens.shape
    stacked = stacked_experts(params) if cfg.count("moe") else None
    stats, seen = [], {"kda": 0, "mla": 0}
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for layer, kind in enumerate(cfg.mixers):
        i = seen[kind]
        seen[kind] += 1
        with jax.named_scope("layer"):
            mp = at(params[kind], i)
            h = rms_norm(x, mp["ln1"], cfg.rms_norm_eps)
            if kind == "kda":
                with jax.named_scope("kda"):
                    x = x + kda(i, mp, h)
            else:
                with jax.named_scope("attn"):
                    out = mla(i, mp, *latent_projections(
                        mp, h, None, heads=cfg.n_head,
                        nope=cfg.qk_nope_head_dim, kv_rank=cfg.kv_lora_rank,
                        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
                        low_rank_q=False, rotate=False)[1:])
                    x = x + merge_heads(out) @ mp["o_w"].astype(x.dtype)
            x = _ffn(cfg, params, stacked, layer, x.reshape(B * T, -1),
                     valid, stats).reshape(x.shape)
    logits = lm_head(x, params["norm_f"], params["lm_head"],
                     cfg.rms_norm_eps)
    return logits, stats
