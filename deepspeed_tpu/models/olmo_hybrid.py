"""AllenAI Olmo Hybrid (HF ``model_type: olmo_hybrid``; the row
``Olmo-Hybrid-7B`` of ``model-configs/architectures.jsonl``): a decoder
whose mixers are of two kinds by a published list (``layer_types``: three
``linear_attention`` to one ``full_attention``), a dense SwiGLU in every
layer, and the Olmo 2 block: NO norm ahead of a sublayer, one on its
OUTPUT.  No positional encoding anywhere (``rope_parameters.rope_theta``
null, read literally: the recurrent layers carry order).

    x <- x + RMSNorm(mixer(x));  x <- x + RMSNorm(mlp(x))
    logits = RMSNorm(x) W_head           (untied, no bias anywhere)
    mlp(x) = W_down (SiLU(W_gate x) * W_up x)

* a ``linear_attention`` layer (Gated DeltaNet, arXiv:2412.06464; ``H``
  heads, keys ``dk`` wide, values ``dv``): ``[q~ | k~ | v~] = SiLU(conv(x
  W_qkv))``, three depthwise causal convolutions of
  ``linear_conv_kernel_dim`` over time, no bias; a head's ``q = q~ / |q~| *
  dk**-0.5``, ``k = k~ / |k~|``; ONE log-decay a head ``g = -exp(A_log) *
  softplus(W_a x + dt_bias)``; the step ``b = 2 sigmoid(W_b x)``
  (``linear_allow_neg_eigval``: in (0, 2); false: ``sigmoid``); the state
  ``S`` ``[dk, dv]`` a head, float32: ``S' = exp(g) S``; ``u = b (v - S'^T
  k)``; ``S = S' + k u^T``; ``o = S^T q`` (``ops/pallas/kda.py``, the
  scalar-decay forms).  Out: ``W_o [RMSNorm_head(o) * SiLU(W_g x)]``, the
  norm over a head's ``dv`` values with one weight vector.
* a ``full_attention`` layer: ``q, k = RMSNorm(W_q x), RMSNorm(W_k x)``
  over the WHOLE projection (the Olmo 2 / OLMoE QK-norm), ``H`` heads of
  ``head_dim`` on as many key heads, ``v = W_v x``, causal softmax in
  float32 at ``head_dim**-0.5``, nothing rotated, ``W_o``.

Not built, refused at construction: grouped keys, a ``rope_theta`` that is
not null, a ``layer_types`` entry of another kind, bias, tied embeddings,
value heads that are not the key heads.

This file is the model's SERVING surface (``ServeEngine``'s protocol).  A
request keeps two kinds of thing, as ``models/nemotron_h.py``'s does.  By
slot (``serving_state``): a ``linear_attention`` layer's state in float32
AT REST ``[dk, H dv]`` (``"gdn"``; ``ops/pallas/kda.py::gdn_rest``: every
head's values side by side on the lanes, so that 96 x 192 pads nothing) and
the last ``linear_conv_kernel_dim - 1`` rows of ``x W_qkv`` before the
convolution (``"gdn_conv"``).  In the two page pools: a ``full_attention``
layer's keys and values, ``[key heads, page_len, head_dim]`` a page
(``config.n_layer`` counts those layers only).  The decode tick is
``ds_gdn_decode`` over the live slots' states where they lie and
``ds_paged_decode_attn`` over the pools.  The prefill takes a CHUNK of a
prompt (``prefix_len`` > 0) as ``models/kimi_linear.py``'s does: a linear
layer's scan starts from the slot's state and its convolution from the
slot's last rows, a full layer writes the chunk's keys and attends the
request's pages gathered ahead of them (``walked.context_attention``); a
request's first chunk starts from zeros, whatever the slot's last occupant
left there.

Parameter tree: ``wte``, ``lm_head`` [d, V], ``norm_f``; ``gdn``
(``qkv_w`` [d, 2 H dk + H dv] (``W_q | W_k | W_v``), ``conv_w`` [K, the
same], ``a_w`` / ``b_w`` [d, H], ``A_log`` / ``dt_bias`` [H], ``g_w`` [d,
H dv], ``o_norm`` [dv], ``o_w`` [H dv, d], ``ln1`` [d]: the norm on the
mixer's output); ``full`` (``q_w``, ``k_w``, ``v_w``, ``o_w`` [d, d],
``q_norm`` / ``k_norm`` [d], ``ln1``); ``ffn`` (``gate_w``, ``up_w`` [d,
f], ``down_w``, ``ln2``: the norm on the MLP's output), one a layer.
Every matrix input-major, a leaf a layer (``models/mimo_v2.py``'s rule).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.kda import (GDN_CHUNK, gdn_chunked, gdn_decode, gdn_heads,
                              gdn_rest)
from .walked import (F32, PagePool, ServedConfig, WalkedModel, at,
                     causal_self_attention, context_attention, decode_index,
                     default_scale, dense_ffn, draw_layers, l2_norm, lm_head,
                     merge_heads, prefill_index, prefix_keys, rms_norm,
                     shift_tail, silu_conv, write_slot_state)

_KINDS = {"linear_attention": "gdn", "full_attention": "full"}
#: the range ``init`` draws a head's step from (log-uniform), and A's
_TIME_STEP, _A_RANGE = (0.001, 0.1), (1.0, 16.0)
#: the call's counters (``serving_aux``)
_COUNTERS = ("gdn_slot_layers", "gdn_chunk_tokens", "full_kv_tokens")


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    head_dim: Optional[int] = None      # null in the source: hidden / heads
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    layer_types: Tuple[str, ...] = ()   # (): three linear to one full
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: Optional[Dict[str, Any]] = None
    # the program's
    initializer_range: float = 0.02
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes
    state_dtype: str = "float32"        # the delta-rule state's

    def __post_init__(self):
        types = tuple(self.layer_types) or tuple(
            "full_attention" if layer % 4 == 3 else "linear_attention"
            for layer in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", types)
        unbuilt = {
            "num_key_value_heads != num_attention_heads (grouped keys)":
                self.num_key_value_heads != self.num_attention_heads,
            "rope_parameters.rope_theta (a rotation)":
                (self.rope_parameters or {}).get("rope_theta") is not None,
            "a layer_types entry that is neither 'linear_attention' nor "
            "'full_attention'": bool(set(types) - set(_KINDS)),
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "linear_num_value_heads != linear_num_key_heads":
                self.linear_num_value_heads != self.linear_num_key_heads,
            f"hidden_act {self.hidden_act!r} (only 'silu')":
                self.hidden_act != "silu",
        }
        self.check(unbuilt)
        if len(types) != self.num_hidden_layers:
            raise ValueError(f"layer_types: {self.num_hidden_layers} "
                             f"entries, one a layer; got {len(types)}")
        if self.head_dim is None and \
                self.hidden_size % self.num_attention_heads:
            raise ValueError("head_dim null: hidden_size must be whole "
                             "heads")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """'gdn' | 'full' of each layer, in order."""
        return tuple(_KINDS[t] for t in self.layer_types)

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)

    @property
    def gdn_heads(self) -> int:
        return self.linear_num_key_heads

    @property
    def key_width(self) -> int:
        return self.gdn_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.gdn_heads * self.linear_value_head_dim

    @property
    def conv_width(self) -> int:
        """``[q~ | k~ | v~]``: what the convolutions run over."""
        return 2 * self.key_width + self.value_width

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_layer(self) -> int:
        """Layers that keep every key: the page pools' depth."""
        return self.count("full")

    @property
    def d_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads


class OlmoHybridModel(WalkedModel):
    #: ``serving_unsupported`` is the common one: the prefill takes a chunk
    #: (module docstring); the engine refuses the prefix cache, KV tiering
    #: and speculation for any model with ``serving_state``
    serving_aux = _COUNTERS
    #: ``PagePool`` lays a page ``[H, page_len, head_dim]`` and the tick
    #: reads it so at a group of one (``decode_attention_paged(head_major
    #: =)``: 30 heads are no whole sublane tile); the engine's gauge of
    #: the kernel's body reads this
    pool_head_major = True

    def serving_cache_layers(self) -> Dict[str, int]:
        """Layers by the kind of cache they keep."""
        return {k: self.config.count(k) for k in ("full", "gdn")}

    def serving_state(self, slots: int) -> Dict[str, Any]:
        """What a request keeps beside its pages, by slot: name ->
        ``jax.ShapeDtypeStruct``; the slot is axis 1."""
        cfg = self.config
        lg = cfg.count("gdn")
        return {
            "gdn": jax.ShapeDtypeStruct(
                (lg, slots, cfg.linear_key_head_dim, cfg.value_width),
                jnp.dtype(cfg.state_dtype)),
            "gdn_conv": jax.ShapeDtypeStruct(
                (lg, slots, cfg.linear_conv_kernel_dim - 1, cfg.conv_width),
                jnp.dtype(cfg.param_dtype)),
        }

    def init(self, rng) -> Dict[str, Any]:
        """Every matrix normal(0, initializer_range), norm weights 1;
        ``A`` uniform in [1, 16] and a step log-uniform in [0.001, 0.1]
        (``dt_bias`` its inverse softplus), one a head, as
        ``models/kimi_linear.py`` draws KDA's; the depthwise convolutions
        torch's default for their fan-in.  Drawn a layer at a time in
        ``param_dtype``."""
        cfg = self.config
        d, dt = cfg.hidden_size, jnp.dtype(cfg.param_dtype)
        std, f = cfg.initializer_range, cfg.intermediate_size
        H, C, Cv, K = (cfg.gdn_heads, cfg.conv_width, cfg.value_width,
                       cfg.linear_conv_kernel_dim)
        keys = jax.random.split(rng, 5)

        def norm(key, shape):
            return (jax.random.normal(key, shape, F32) * std).astype(dt)

        def gdn(key):
            k = jax.random.split(key, 8)
            bound = 1.0 / math.sqrt(K)
            low, high = (math.log(t) for t in _TIME_STEP)
            step = jnp.exp(jax.random.uniform(k[3], (H,), F32)
                           * (high - low) + low)
            return {"qkv_w": norm(k[0], (d, C)),
                    "conv_w": jax.random.uniform(
                        k[1], (K, C), F32, -bound, bound).astype(dt),
                    "A_log": jnp.log(jax.random.uniform(
                        k[2], (H,), F32, *_A_RANGE)).astype(dt),
                    "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                    "a_w": norm(k[4], (d, H)),
                    "b_w": norm(k[5], (d, H)),
                    "g_w": norm(k[6], (d, Cv)),
                    "o_w": norm(k[7], (Cv, d))}

        def full(key):
            k = jax.random.split(key, 4)
            return {name: norm(k[j], (d, d))
                    for j, name in enumerate(("q_w", "k_w", "v_w", "o_w"))}

        def ffn(key):
            k = jax.random.split(key, 3)
            return {"gate_w": norm(k[0], (d, f)), "up_w": norm(k[1], (d, f)),
                    "down_w": norm(k[2], (f, d))}

        ones = {"gdn": {"ln1": d, "o_norm": cfg.linear_value_head_dim},
                "full": {"ln1": d, "q_norm": d, "k_norm": d},
                "ffn": {"ln2": d}}
        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "lm_head": norm(keys[1], (d, cfg.vocab_size)),
               "norm_f": jnp.ones((d,), dt)}
        for name, layer, key, n in (
                ("gdn", gdn, keys[2], cfg.count("gdn")),
                ("full", full, keys[3], cfg.count("full")),
                ("ffn", ffn, keys[4], cfg.num_hidden_layers)):
            if n:
                out[name] = draw_layers(layer, jax.random.split(key, n),
                                        ones[name], dt)
        return out

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        from empty state (no cache, every position live)."""
        cfg = self.config
        B, T = tokens.shape
        K = cfg.linear_conv_kernel_dim
        zeros = jnp.zeros((cfg.gdn_heads, cfg.linear_key_head_dim,
                           cfg.linear_value_head_dim), F32)

        def gdn(i, gp, x):
            qkv = _gdn_qkv(gp, x)
            padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
            q, k, v = _gdn_heads(cfg, _gdn_conv(
                gp, [padded[:, j:j + T] for j in range(K)]))
            g, b, gate = _gdn_gates(cfg, gp, x)
            with jax.named_scope("gdn_chunk"):
                o, _ = jax.vmap(lambda *t: gdn_chunked(*t, zeros))(
                    q, k, v, g, b)
            return _gdn_out(cfg, gp, o, gate, x.dtype)

        def full(i, q, k, v):
            return causal_self_attention(
                q, k, v, cfg.attn_impl == "flash",
                sm_scale=default_scale(cfg.d_head))

        logits = _layers(cfg, params, tokens, gdn, full)
        return (logits, _aux()) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, state,
                          impl: Optional[str] = None, aux: bool = False,
                          **unbuilt):
        """One decode tick of every slot: ``ds_gdn_decode`` over the live
        slots' states and ``ds_paged_decode_attn`` over the two pools
        ``[full layers, pages, H, page_len, head_dim]``;
        ``gpt2_decode_step_paged``'s contract plus the request state.
        Returns (logits [S, V], k_pool, v_pool, state, new_lengths) and,
        with ``aux``, the tick's counters.  An inactive slot's pages and
        state are neither read nor written."""
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        S = page_table.shape[0]
        lengths, _, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, k_pool.shape[3], cfg.n_positions)
        pool = PagePool((k_pool, v_pool), page_ids, offs, active)
        shape = state["gdn"].shape
        # every layer's slots in one row, as ``gdn_decode`` takes them; the
        # tails are read from the leaf as it came and written once,
        # stacked, at the end (``walked.shift_tail`` says why)
        new = {"gdn": state["gdn"].reshape((-1,) + shape[2:]),
               "gdn_conv": []}

        def gdn(i, gp, x):
            window, kept = shift_tail(state["gdn_conv"][i], _gdn_qkv(gp, x),
                                      active)
            new["gdn_conv"].append(kept)
            q, k, v = _gdn_heads(cfg, _gdn_conv(
                gp, [window[:, j] for j in range(cfg.linear_conv_kernel_dim)]))
            g, b, gate = _gdn_gates(cfg, gp, x[:, 0])
            with jax.named_scope("gdn_update"):
                new["gdn"], o = gdn_decode(new["gdn"], jnp.exp(g), k, v, q,
                                           b, active, base=i * S)
            return _gdn_out(cfg, gp, o[:, None], gate[:, None], x.dtype)

        def full(i, q, k, v):
            pool.write(i, k[:, :, 0], v[:, :, 0])
            return pool.attend(i, q[:, :, 0], page_table, att_len,
                               impl=impl)[:, :, None]

        logits = _layers(cfg, params, tokens[:, None], gdn, full)
        new = {"gdn": new["gdn"].reshape(shape),
               "gdn_conv": jnp.stack(new["gdn_conv"])}
        out = (logits[:, 0], *pool.arrays(), new,
               lengths + active.astype(jnp.int32))
        if aux:
            live = jnp.sum(active.astype(jnp.int32))
            out += (_aux(gdn_slot_layers=live * cfg.count("gdn"),
                         full_kv_tokens=jnp.sum(att_len)
                         * cfg.count("full")),)
        return out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool, *, state, slot, aux: bool = False,
                      **unbuilt):
        """Prefill of one request, or of one CHUNK of its prompt, into the
        pools and into ``slot`` of the request state.  tokens [1, Tq] are
        the prompt's tokens from ``prefix_len`` on, right-padded to the
        bucket; ``delta_len``, ``prefix_len``, ``page_row`` [max_pages] and
        ``slot`` traced.  With ``prefix_len`` 0 the linear layers start
        from a zero state and a zero convolution tail, whatever the slot
        holds, and the full layers read no page; otherwise from what the
        chunk before left in the slot and in the request's pages.  Returns
        (logits [1, Tq, V], k_pool, v_pool, state); ``logits[0, delta_len
        - 1]`` scores the first generated token.  The slot's state is
        OVERWRITTEN with the state at ``prefix_len + delta_len``: padding
        takes ``g = 0`` and ``b = 0`` and feeds nothing, the convolution's
        tail is read at the true end."""
        self.refuse(unbuilt)
        cfg = self.config
        Tq, K, H = tokens.shape[1], cfg.linear_conv_kernel_dim, cfg.gdn_heads
        page_len = k_pool.shape[3]
        cap = page_row.shape[0] * page_len
        i32 = jnp.int32
        prefix_len = jnp.asarray(prefix_len, i32)
        delta_len = jnp.asarray(delta_len, i32)
        slot = jnp.asarray(slot, i32)
        valid, page_ids, offs, _, _ = prefill_index(
            page_row, delta_len, Tq, page_len, prefix_len, cfg.n_positions)
        pool = PagePool((k_pool, v_pool), page_ids, offs, valid)
        flash, scale = cfg.attn_impl == "flash", default_scale(cfg.d_head)
        first = prefix_len == 0
        kept = {"gdn": [], "gdn_conv": []}

        def of_slot(leaf, i):
            # one slice of the leaf (``leaf[i]`` first would copy the
            # layer); zeros for a request's first chunk
            got = jax.lax.dynamic_slice(
                leaf, (i, slot) + (0,) * (leaf.ndim - 2),
                (1, 1) + leaf.shape[2:])[0, 0]
            return jnp.where(first, jnp.zeros_like(got), got)

        def gdn(i, gp, x):
            qkv = _gdn_qkv(gp, x)                           # [1, Tq, C]
            tail = of_slot(state["gdn_conv"], i)            # [K - 1, C]
            padded = jnp.concatenate([tail.astype(qkv.dtype), qkv[0]])
            kept["gdn_conv"].append(jax.lax.dynamic_slice_in_dim(
                padded, delta_len, K - 1, axis=0))
            q, k, v = _gdn_heads(cfg, _gdn_conv(
                gp, [padded[j:j + Tq] for j in range(K)]))
            g, b, gate = _gdn_gates(cfg, gp, x[0])
            g, b = (jnp.where(valid[:, None], t, 0.0) for t in (g, b))
            with jax.named_scope("gdn_chunk"):
                o, final = gdn_chunked(
                    q, k, v, g, b, gdn_heads(of_slot(state["gdn"], i), H))
            kept["gdn"].append(gdn_rest(final))
            return _gdn_out(cfg, gp, o[None], gate[None], x.dtype)

        def full(i, q, k, v):
            pool.write(i, k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))

            def context():
                with jax.named_scope("chunk_context"):
                    # the request's pages of this layer out of every
                    # layer's in one row (``t[i]`` first would copy the
                    # layer: 1.5 GB of temporaries at the cell's pool)
                    ctx_k, ctx_v = (
                        prefix_keys(t, i * pool.per_layer + page_row,
                                    prefix_len) for t in pool.flat())
                return context_attention(
                    q, k, v, ctx_k, ctx_v, jnp.minimum(prefix_len, cap),
                    flash, sm_scale=scale)

            return jax.lax.cond(
                first, lambda: causal_self_attention(q, k, v, flash,
                                                     sm_scale=scale),
                context)

        logits = _layers(cfg, params, tokens, gdn, full)
        out = (logits, *pool.arrays(), write_slot_state(state, kept, slot))
        if aux:
            whole = Tq + -Tq % GDN_CHUNK    # ``gdn_chunked``'s whole chunks
            out += (_aux(gdn_chunk_tokens=whole * cfg.count("gdn")),)
        return out


# -- the layer's parts ----------------------------------------------------

def _gdn_qkv(gp, x):
    """x [..., d] -> ``[q~ | k~ | v~]`` before the convolutions [..., 2 H
    dk + H dv]."""
    with jax.named_scope("gdn_proj"):
        return x @ gp["qkv_w"].astype(x.dtype)


def _gdn_conv(gp, taps):
    with jax.named_scope("gdn_conv"):
        return silu_conv(gp["conv_w"], taps)


def _gdn_heads(cfg: OlmoHybridConfig, conv_out):
    """conv_out [..., 2 H dk + H dv] float32 -> q, k [..., H, dk]
    (normalised, q scaled), v [..., H, dv]."""
    lead, H, kw = conv_out.shape[:-1], cfg.gdn_heads, cfg.key_width
    q, k, v = (t.reshape(lead + (H, -1)) for t in (
        conv_out[..., :kw], conv_out[..., kw:2 * kw], conv_out[..., 2 * kw:]))
    return l2_norm(q) * cfg.linear_key_head_dim ** -0.5, l2_norm(k), v


def _gdn_gates(cfg: OlmoHybridConfig, gp, x):
    """x [..., d] -> the log-decay g [..., H] (<= 0), the step b [..., H]
    and the output gate [..., H, dv], float32."""
    with jax.named_scope("gdn_gates"):
        def proj(name):
            return (x @ gp[name].astype(x.dtype)).astype(F32)

        g = -jnp.exp(gp["A_log"].astype(F32)) * jax.nn.softplus(
            proj("a_w") + gp["dt_bias"].astype(F32))
        b = jax.nn.sigmoid(proj("b_w"))
        if cfg.linear_allow_neg_eigval:
            b = 2.0 * b
        gate = jax.nn.silu(proj("g_w")).reshape(
            x.shape[:-1] + (cfg.gdn_heads, cfg.linear_value_head_dim))
    return g, b, gate


def _gdn_out(cfg: OlmoHybridConfig, gp, o, gate, dtype):
    """o, gate [..., H, dv] float32 -> the mixer's output [..., d]: RMSNorm
    a head, the gate, ``W_o``."""
    with jax.named_scope("gdn_out"):
        y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = y * gp["o_norm"].astype(F32) * gate
        y = y.reshape(y.shape[:-2] + (cfg.value_width,)).astype(dtype)
        return y @ gp["o_w"].astype(dtype)


def _full_qkv(cfg: OlmoHybridConfig, fp, x):
    """x [B, T, d] -> q, k, v [B, H, T, head_dim], q and k QK-normed over
    the whole projection (``models/olmoe.py::qkv_heads``'s, less the
    rotation: if the family's code turns out to rotate, ``walked.rope`` on
    q and k goes here and its like into the reference's ``_attention``)."""
    B, T, _ = x.shape
    eps = cfg.rms_norm_eps

    def heads(t):
        return t.reshape(B, T, cfg.n_head, cfg.d_head).transpose(0, 2, 1, 3)

    return (heads(rms_norm(x @ fp["q_w"].astype(x.dtype), fp["q_norm"], eps)),
            heads(rms_norm(x @ fp["k_w"].astype(x.dtype), fp["k_norm"], eps)),
            heads(x @ fp["v_w"].astype(x.dtype)))


def _aux(**counted) -> Dict[str, jnp.ndarray]:
    """The call's counters: ``gdn_slot_layers``: live slots x linear layers
    of a tick (what ``ds_gdn_decode`` rewrote); ``full_kv_tokens``: the
    live keys ``ds_paged_decode_attn`` read, summed over the full layers;
    and of a prefill ``gdn_chunk_tokens``: the tokens the chunked form ran,
    padding included, x linear layers.  0 where the call is of the other
    kind."""
    return {name: jnp.asarray(counted.get(name, 0), jnp.int32)
            for name in _COUNTERS}


def _layers(cfg: OlmoHybridConfig, params, tokens, gdn, full):
    """The forward over sequences tokens [B, T]: ``gdn(i, gp, x)`` -> [B,
    T, d] and ``full(i, q, k, v)`` -> [B, H, T, head_dim] are the caller's
    forms of the two mixers (``i``: the layer's index among its kind; they
    keep what a cache keeps).  Returns the logits."""
    eps = cfg.rms_norm_eps
    seen = {"gdn": 0, "full": 0}
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for layer, kind in enumerate(cfg.kinds):
        i = seen[kind]
        seen[kind] += 1
        with jax.named_scope("layer"):
            mp = at(params[kind], i)
            if kind == "gdn":
                with jax.named_scope("gdn"):
                    out = gdn(i, mp, x)
            else:
                with jax.named_scope("attn"), jax.named_scope("full_attn"):
                    out = merge_heads(full(i, *_full_qkv(cfg, mp, x))) \
                        @ mp["o_w"].astype(x.dtype)
            x = x + rms_norm(out, mp["ln1"], eps)
            fp = at(params["ffn"], layer)
            x = x + rms_norm(dense_ffn(fp, x), fp["ln2"], eps)
    return lm_head(x, params["norm_f"], params["lm_head"], eps)
