"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; HF ``modeling_olmoe``):
a pre-norm decoder whose every feed-forward layer is a dropless top-k
mixture of SwiGLU experts.

Block: RMSNorm -> q/k/v projections without bias -> **RMSNorm over the
whole q and k projections, before the split into heads** (OLMoE's QK-norm)
-> rotate-half RoPE -> causal softmax attention -> o projection ->
residual; RMSNorm -> router (softmax over all experts in float32, top-k,
weights not renormalised unless ``norm_topk_prob``) -> the k experts'
``down(silu(gate(x)) * up(x))`` weighted and summed -> residual.  Final
RMSNorm, untied ``lm_head``.

This file is the model's SERVING surface (``ServeEngine``'s protocol,
``inference/engine.py``): ``init``, ``apply`` (whole-sequence forward) and
the two paged steps.  It is imported where it is used and by nothing in
``deepspeed_tpu/__init__`` or ``models/__init__``.  Not built here, and
refused by the engine at construction: the slot (unpaged) cache,
speculative verify, LoRA and int8 arms.  Training through ``initialize``
(auxiliary load-balancing loss) is a later issue.

Parameter tree (HF name -> here): ``embed_tokens`` -> ``wte`` (the name
the engine and the benchmark read the serving dtype from), ``norm`` ->
``norm_f``, ``lm_head`` [d, V]; per layer, stacked on a leading L under
``blocks``: ``input_layernorm`` -> ``ln1``, ``post_attention_layernorm``
-> ``ln2``, ``self_attn.{q,k,v,o}_proj`` -> ``{q,k,v,o}_w`` [d, d] (input
major), ``self_attn.{q,k}_norm``, ``mlp.gate`` -> ``router_w`` [d, E],
``mlp.experts.*.{gate,up}_proj`` -> ``gate_w`` / ``up_w`` [E, d, f],
``down_proj`` -> ``down_w`` [E, f, d].

The paged steps carry the pool whole through the layer scan and write it
in place; a layer is addressed by adding ``layer * pages`` to the page
ids, never by slicing the pool.  Inside a page this model keeps the keys
as ``[page_len, heads, head_dim]`` (a token's heads contiguous: what the
cache write and the decode kernel both want); the engine treats a page
as opaque bytes of the size ``PagedKVCacheSpec`` gives.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..moe.dropless import dropless_moe
from ..ops.attention import causal_attention
from .walked import (ServedConfig, WalkedModel, decode_index, default_scale,
                     lm_head, merge_heads, prefill_index, rms_norm, rope)


@dataclasses.dataclass(frozen=True)
class OlmoeConfig(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024       # width of ONE expert
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096
    initializer_range: float = 0.02
    attention_bias: bool = False
    clip_qkv: Optional[float] = None
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    # the program's
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes

    def __post_init__(self):
        unbuilt = {
            "num_key_value_heads != num_attention_heads (grouped keys)":
                self.num_key_value_heads != self.num_attention_heads,
            "clip_qkv": self.clip_qkv is not None,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            f"hidden_act {self.hidden_act!r} (only 'silu')":
                self.hidden_act != "silu",
        }
        self.check(unbuilt)
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def d_head(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_params(self) -> int:
        d, f, e = self.hidden_size, self.intermediate_size, self.num_experts
        per_layer = 4 * d * d + 4 * d + d * e + 3 * e * d * f
        return 2 * self.vocab_size * d + d + self.num_hidden_layers * per_layer


class OlmoeModel(WalkedModel):
    #: every expert is held here: no ``moe_rows_elsewhere``
    serving_aux = ("moe_experts_hit", "moe_load_imbalance", "moe_rows")
    refusal_note = " (int8 KV and LoRA are GPT2Model's)"

    def init(self, rng) -> Dict[str, Any]:
        """HF's init: every matrix normal(0, initializer_range), every
        norm weight 1.  The experts are drawn a layer at a time in
        ``param_dtype``, so the largest temporary is one layer's."""
        cfg = self.config
        d, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
        L, dt = cfg.num_hidden_layers, jnp.dtype(cfg.param_dtype)
        std = cfg.initializer_range
        keys = jax.random.split(rng, 3)

        def norm(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * std).astype(dt)

        def layer(key):
            k = jax.random.split(key, 8)
            return {"q_w": norm(k[0], (d, d)), "k_w": norm(k[1], (d, d)),
                    "v_w": norm(k[2], (d, d)), "o_w": norm(k[3], (d, d)),
                    "router_w": norm(k[4], (d, e)),
                    "gate_w": norm(k[5], (e, d, f)),
                    "up_w": norm(k[6], (e, d, f)),
                    "down_w": norm(k[7], (e, f, d))}

        blocks = jax.lax.map(layer, jax.random.split(keys[0], L))
        for name in ("ln1", "ln2", "q_norm", "k_norm"):
            blocks[name] = jnp.ones((L, d), dt)
        return {"wte": norm(keys[1], (cfg.vocab_size, d)),
                "lm_head": norm(keys[2], (d, cfg.vocab_size)),
                "norm_f": jnp.ones((d,), dt), "blocks": blocks}

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        (no cache)."""
        cfg = self.config
        B, T = tokens.shape
        if T > cfg.n_positions:
            raise ValueError(f"sequence length {T} exceeds "
                             f"max_position_embeddings={cfg.n_positions}")
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        small, stacked = _split_blocks(params["blocks"])
        with jax.named_scope("embed"):
            x = params["wte"][tokens]

        def body(x, xs):
            bp, layer = xs
            with jax.named_scope("layer"):
                with jax.named_scope("attn"):
                    h = rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
                    q, k, v = qkv_heads(cfg, bp, h, positions)
                    x = _attn_out(bp, x, _self_attention(cfg, q, k, v))
                return _experts(cfg, bp, stacked, layer, x)

        x, stats = jax.lax.scan(
            body, x, (small, jnp.arange(cfg.n_layer, dtype=jnp.int32)))
        logits = lm_head(x, params["norm_f"], params["lm_head"],
                         cfg.rms_norm_eps)
        return (logits, _aux(cfg, stats)) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, impl: Optional[str] = None,
                          aux: bool = False, **unbuilt):
        """One decode tick of every slot over the paged pool; the contract
        of ``gpt2_decode_step_paged`` (masked no-op for inactive slots,
        every operand traced).  Returns (logits [S, V], k_pool, v_pool,
        new_lengths) and, with ``aux``, the tick's expert counters."""
        from ..ops.pallas.decode_attention import decode_attention_paged
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        shape = k_pool.shape
        pages, page_len = shape[1], shape[3]
        lengths, positions, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, page_len, cfg.n_positions)
        small, stacked = _split_blocks(params["blocks"])
        with jax.named_scope("embed"):
            x = params["wte"][tokens][:, None, :]           # [S, 1, d]

        def body(carry, xs):
            x, kf, vf = carry
            bp, layer = xs
            base = layer * pages
            with jax.named_scope("layer"):
                with jax.named_scope("attn"):
                    h = rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
                    q, k, v = qkv_heads(cfg, bp, h, positions[:, None])
                    kf = _write_rows(kf, k[:, :, 0], base + page_ids, offs,
                                     active)
                    vf = _write_rows(vf, v[:, :, 0], base + page_ids, offs,
                                     active)
                    attn = decode_attention_paged(
                        q[:, :, 0], _kernel_view(kf), _kernel_view(vf),
                        page_table + base, att_len, impl=impl)
                    x = _attn_out(bp, x, attn[:, :, None, :])
                x, stats = _experts(cfg, bp, stacked, layer, x, valid=active)
            return (x, kf, vf), stats

        (x, kf, vf), stats = jax.lax.scan(
            body, (x, _flat_pool(k_pool), _flat_pool(v_pool)),
            (small, jnp.arange(cfg.n_layer, dtype=jnp.int32)))
        logits = lm_head(x, params["norm_f"], params["lm_head"],
                         cfg.rms_norm_eps)[:, 0]
        out = (logits, kf.reshape(shape), vf.reshape(shape),
               lengths + active.astype(jnp.int32))
        return out + (_aux(cfg, stats),) if aux else out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool, aux: bool = False, **unbuilt):
        """Delta-aware prefill into the paged pool; the contract of
        ``gpt2_prefill_paged``: tokens [1, Tq] are the prompt less its
        cached prefix, right-padded to the bucket; ``delta_len``,
        ``prefix_len`` and ``page_row`` [max_pages] are traced.  Returns
        (logits [1, Tq, V], k_pool, v_pool); ``logits[0, delta_len - 1]``
        scores the first generated token.  Padding rows reach no expert
        and write no page."""
        self.refuse(unbuilt)
        cfg = self.config
        B, Tq = tokens.shape
        if Tq > cfg.n_positions:
            raise ValueError(f"sequence length {Tq} exceeds "
                             f"max_position_embeddings={cfg.n_positions}")
        shape = k_pool.shape
        pages, page_len = shape[1], shape[3]
        cap = page_row.shape[0] * page_len
        prefix_len = jnp.asarray(prefix_len, jnp.int32)
        delta_len = jnp.asarray(delta_len, jnp.int32)
        valid, page_ids, offs, abs_pos, positions = prefill_index(
            page_row, delta_len, Tq, page_len, prefix_len, cfg.n_positions)
        small, stacked = _split_blocks(params["blocks"])
        with jax.named_scope("embed"):
            x = params["wte"][tokens]                       # [1, Tq, d]

        def body(carry, xs):
            x, kf, vf = carry
            bp, layer = xs
            base = layer * pages
            with jax.named_scope("layer"):
                with jax.named_scope("attn"):
                    h = rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
                    q, k, v = qkv_heads(cfg, bp, h, positions)
                    kf = _write_rows(kf, k[0].transpose(1, 0, 2),
                                     base + page_ids, offs, valid)
                    vf = _write_rows(vf, v[0].transpose(1, 0, 2),
                                     base + page_ids, offs, valid)

                    def cached_prefix(_):
                        # dense attention over the slot's pages: the
                        # cached prefix and the causal delta
                        rows = base + page_row
                        kg = kf[rows].reshape(cap, cfg.n_head, cfg.d_head)
                        vg = vf[rows].reshape(cap, cfg.n_head, cfg.d_head)
                        s = jnp.einsum("htd,shd->hts", q[0],
                                       kg.astype(q.dtype),
                                       preferred_element_type=jnp.float32)
                        s = s * default_scale(cfg.d_head)
                        ok = jnp.arange(cap)[None, :] <= abs_pos[:, None]
                        s = jnp.where(ok[None], s,
                                      jnp.finfo(jnp.float32).min)
                        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
                        return jnp.einsum("hts,shd->htd", p,
                                          vg.astype(q.dtype))[None]

                    attn = jax.lax.cond(
                        prefix_len == 0,
                        lambda _: _self_attention(cfg, q, k, v),
                        cached_prefix, operand=None)
                    x = _attn_out(bp, x, attn)
                x, stats = _experts(cfg, bp, stacked, layer, x, valid=valid)
            return (x, kf, vf), stats

        (x, kf, vf), stats = jax.lax.scan(
            body, (x, _flat_pool(k_pool), _flat_pool(v_pool)),
            (small, jnp.arange(cfg.n_layer, dtype=jnp.int32)))
        logits = lm_head(x, params["norm_f"], params["lm_head"],
                         cfg.rms_norm_eps)
        out = (logits, kf.reshape(shape), vf.reshape(shape))
        return out + (_aux(cfg, stats),) if aux else out


# -- the block's pieces ---------------------------------------------------

def qkv_heads(cfg: OlmoeConfig, bp, h, positions):
    """h [B, T, d] (normed) -> q, k, v [B, H, T, Dh], q and k QK-normed
    over the whole projection, then rotated."""
    B, T, _ = h.shape
    eps = cfg.rms_norm_eps

    def heads(t):
        return t.reshape(B, T, cfg.n_head, cfg.d_head).transpose(0, 2, 1, 3)

    q = heads(rms_norm(h @ bp["q_w"].astype(h.dtype), bp["q_norm"], eps))
    k = heads(rms_norm(h @ bp["k_w"].astype(h.dtype), bp["k_norm"], eps))
    v = heads(h @ bp["v_w"].astype(h.dtype))
    return (rope(q, positions, cfg.rope_theta),
            rope(k, positions, cfg.rope_theta), v)


def _attn_out(bp, x, attn):
    """attn [B, H, T, Dh] -> residual added."""
    return x + merge_heads(attn) @ bp["o_w"].astype(x.dtype)


def _experts(cfg: OlmoeConfig, bp, stacked, layer, x, valid=None):
    """The expert sub-layer on x [B, T, d] with its residual.  ``stacked``
    holds every layer's experts flat ([L*E, ...]); ``layer`` picks."""
    B, T, d = x.shape
    h = rms_norm(x, bp["ln2"], cfg.rms_norm_eps).reshape(B * T, d)
    y, stats = dropless_moe(
        h, bp["router_w"], stacked["gate_w"], stacked["up_w"],
        stacked["down_w"], cfg.num_experts_per_tok,
        expert_offset=layer * cfg.num_experts, valid=valid,
        renormalize=cfg.norm_topk_prob)
    return x + y.reshape(B, T, d), stats


def _split_blocks(blocks):
    """(per-layer leaves for the scan's xs, every layer's experts flat)."""
    big = ("gate_w", "up_w", "down_w")
    stacked = {k: blocks[k].reshape((-1,) + blocks[k].shape[2:])
               for k in big}
    small = {k: v for k, v in blocks.items() if k not in big}
    return small, stacked


def _aux(cfg: OlmoeConfig, stats) -> Dict[str, jnp.ndarray]:
    """Per-layer MoEStats [L] -> the call's counters: experts hit and
    rows summed over layers, and the busiest expert's rows over the mean
    rows an expert, largest over layers."""
    mean = jnp.maximum(stats.rows, 1).astype(jnp.float32) / cfg.num_experts
    return {"moe_experts_hit": jnp.sum(stats.experts_hit),
            "moe_rows": jnp.sum(stats.rows),
            "moe_load_imbalance": jnp.max(stats.max_rows / mean)}


def _self_attention(cfg: OlmoeConfig, q, k, v):
    if cfg.attn_impl == "flash":
        from ..parallel.attention import sharded_flash_attention
        return sharded_flash_attention(q, k, v, causal=True)
    return causal_attention(q, k, v)


# -- the paged steps' pool -----------------------------------------------

def _flat_pool(pool):
    """[L, P, H, page_len, Dh] as the engine holds it -> every layer's
    pages in one row, a page read as [page_len, H, Dh] (see the module
    docstring).  Same bytes: a reshape."""
    L, P, H, page_len, Dh = pool.shape
    return pool.reshape(L * P, page_len, H, Dh)


def _write_rows(flat, new, page_ids, offs, keep):
    """``flat[page_ids[i], offs[i]] = new[i]`` where ``keep[i]``; the
    others write their old value back (their ids name a scratch page).
    flat [X, page_len, H, Dh], new [N, H, Dh]."""
    old = flat[page_ids, offs]
    blended = jnp.where(keep[:, None, None], new.astype(flat.dtype), old)
    return flat.at[page_ids, offs].set(blended)


def _kernel_view(flat):
    """The pool as ``ops/pallas/decode_attention.py`` takes it,
    [X, H, page_len, Dh]: that file's own transpose to
    [X, page_len, H, Dh] then cancels this one and nothing moves."""
    return flat.transpose(0, 2, 1, 3)
