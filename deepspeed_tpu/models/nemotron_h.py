"""NVIDIA Nemotron-H (arXiv:2504.03624; HF ``modeling_nemotron_h``) as
Nemotron-3 Super configures it: a pre-norm residual stack of single-part
layers whose kinds follow a published string (``hybrid_override_pattern``):
``M`` a Mamba-2 mixer, ``E`` a layer of latent experts, ``*`` attention on
grouped keys.

    x <- x + part(RMSNorm(x))    for each character; final RMSNorm; untied head

* ``M``: ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv1d(xBC))`` (causal,
  depthwise, width ``conv_kernel``, with bias); ``xBC -> x_s [H, P], B, C
  [G, N]`` (head ``h`` reads group ``h // (H / G)``); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(dt A) h_{t-1} + dt x_s (outer)
  B``, ``y = h_t C + D x_s``; ``y = RMSNorm_by_group(y * silu(z)) * w``;
  ``out = y W_out``.  The recurrence is ``ops/pallas/ssm.py``.
* ``*``: q on ``num_attention_heads`` heads, k and v on
  ``num_key_value_heads``, no bias, query head ``h`` on key head ``h //
  (Hq / Hkv)``, causal softmax at ``1/sqrt(head_dim)``, ``W_o``.
  Nothing is rotated: the family's published forward applies no
  positional embedding (``NemotronHAttention``; the configuration's
  ``rope_theta`` is unused there).  ``_qkv`` says where a rotation would go.
* ``E``: ``s = sigmoid(x_f32 W_r)`` over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  weights ``s[chosen] / sum * routed_scaling_factor``; ``u = x W_down``
  (hidden -> latent); ``r = sum_k w_k relu(u U_e)**2 D_e``; ``out = r W_up
  + relu(x S_u)**2 S_d`` (the shared expert, at hidden width).
  ``experts_held=(first, count)`` is this chip's share of the routed
  experts (``moe/dropless.py``): the weights hold those only and the
  layer returns their part of the sum; everything every chip computes
  alike (router, latent projections, shared expert) is whole.

Not built: the multi-token-prediction module (``num_nextn_predict_layers``;
the base forward does not use it).

This file is the model's SERVING surface (``ServeEngine``'s protocol):
``init``, ``apply`` and the two paged steps, plus ``serving_state``: what
a request keeps beside its pages.  Per slot and ``M`` layer that is the
recurrent state ``[H, P, N]`` in float32 (it accumulates over thousands of
steps) and the last ``conv_kernel - 1`` rows of ``xBC``.  The engine holds
it by slot, hands it to both steps and takes it back; the prefill of a
request writes the slot it is admitted to, so nothing ever clears a slot.
``config.n_layer``, the page pool's depth, counts the ``*`` layers only.
Imported where it is used and by nothing in ``deepspeed_tpu/__init__`` or
``models/__init__``.

Parameter tree: ``wte``, ``lm_head`` [d, V], ``norm_f``; the layers stacked
by kind in the order they occur: ``mamba`` (``norm``, ``in_w``, ``conv_w``
[K, C], ``conv_b``, ``A_log``, ``D``, ``dt_bias``, ``gate_norm``,
``out_w``), ``moe`` (``norm``, ``router_w``, ``router_bias``,
``latent_down``, ``latent_up``, ``up_w`` [held, latent, f], ``down_w``,
``shared_up``, ``shared_down``), ``attn`` (``norm``, ``q_w``, ``k_w``,
``v_w``, ``o_w``); every matrix input-major.  In the pool a page of this
model is ``[key heads, page_len, head_dim]``, the engine's own layout.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import causal_attention
from ..ops.pallas.ssm import ssd_chunked, ssm_decode
from .walked import (F32, PagePool, ServedConfig, WalkedModel, decode_index,
                     held_expert_counters, lm_head, merge_heads, prefill_index,
                     project_heads, rms_norm, routed_experts, stacked_experts,
                     write_slot_state)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(ServedConfig):
    """The source's keys (HF ``config.json``), then the program's own."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    hybrid_override_pattern: str = "MEMEM*EMEME"
    num_hidden_layers: int = 11
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    n_group: int = 1
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 262144
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    initializer_range: float = 0.02
    mlp_hidden_act: str = "relu2"
    mamba_hidden_act: str = "silu"
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mlp_bias: bool = False
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    # the program's
    experts_held: Optional[Tuple[int, int]] = None    # (first, count)
    attn_impl: str = "flash"            # 'flash' (Pallas) | 'dense'
    param_dtype: str = "float32"        # what ``init`` makes
    state_dtype: str = "float32"        # the recurrent state's

    def __post_init__(self):
        unbuilt = {
            "n_group / topk_group != 1 (group-limited routing)":
                (self.n_group, self.topk_group) != (1, 1),
            "n_shared_experts != 1": self.n_shared_experts != 1,
            "a bias on a projection": self.mamba_proj_bias or self.mlp_bias
                or self.attention_bias,
            "use_conv_bias false": not self.use_conv_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            f"mlp_hidden_act {self.mlp_hidden_act!r} (only 'relu2')":
                self.mlp_hidden_act != "relu2",
            f"mamba_hidden_act {self.mamba_hidden_act!r} (only 'silu')":
                self.mamba_hidden_act != "silu",
            "num_nextn_predict_layers (the multi-token-prediction "
            "module)": self.num_nextn_predict_layers != 0,
        }
        self.check(unbuilt, self.n_routed_experts)
        pattern = self.hybrid_override_pattern
        if set(pattern) - set("ME*") or not pattern:
            raise ValueError(f"hybrid_override_pattern {pattern!r}: a "
                             "string of 'M', 'E' and '*'")
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers} is not the "
                f"length of hybrid_override_pattern ({len(pattern)})")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.mamba_num_heads % self.n_groups \
                or self.d_inner % self.n_groups:
            raise ValueError("mamba_num_heads and the mixer's width must "
                             "divide into n_groups")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def count(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)

    # -- what the serving engine reads of any model's config -------------
    @property
    def n_layer(self) -> int:
        """Layers that keep keys and values: the pool's depth."""
        return self.count("*")

    @property
    def d_head(self) -> int:
        return self.head_dim


class NemotronHModel(WalkedModel):
    #: the engine refuses the prefix cache, KV tiering and migration for
    #: any model with ``serving_state``; the rest are arms these paged
    #: steps do not have.  Chunked prefill among them, by THIS model's
    #: choice: a stateful model may prefill in chunks if its
    #: ``prefill_paged`` reads ``state=`` at ``slot=`` (``models/
    #: kimi_linear.py`` does); here ``ssd_chunked`` starts from a zero
    #: state and the prefill takes no prefix: this file's edit to make
    serving_unsupported = WalkedModel.serving_unsupported + (
        "prefill_chunk_len",)

    def serving_state(self, slots: int) -> Dict[str, Any]:
        """What a request keeps beside its pages, by slot: name ->
        ``jax.ShapeDtypeStruct``; the slot is axis 1."""
        cfg = self.config
        lm = cfg.count("M")
        return {
            "ssm": jax.ShapeDtypeStruct(
                (lm, slots, cfg.mamba_num_heads, cfg.mamba_head_dim,
                 cfg.ssm_state_size), jnp.dtype(cfg.state_dtype)),
            "conv": jax.ShapeDtypeStruct(
                (lm, slots, cfg.conv_kernel - 1, cfg.conv_dim),
                jnp.dtype(cfg.param_dtype)),
        }

    def init(self, rng) -> Dict[str, Any]:
        """As published: every projection normal(0, initializer_range),
        norm weights 1; ``A`` uniform in [1, 16], ``dt`` log-uniform in
        [time_step_min, time_step_max] floored at time_step_floor
        (``dt_bias`` its inverse softplus), ``D`` 1; the depthwise conv
        torch's default for its fan-in (uniform within 1/sqrt(width));
        ``router_bias`` (``e_score_correction_bias``) 0, as the source
        starts it (its training then balances the experts' loads with
        it).  Drawn a layer at a time in ``param_dtype``."""
        cfg = self.config
        d, dt = cfg.hidden_size, jnp.dtype(cfg.param_dtype)
        std = cfg.initializer_range
        H, K, C = cfg.mamba_num_heads, cfg.conv_kernel, cfg.conv_dim
        lat, f = cfg.moe_latent_size, cfg.moe_intermediate_size
        fs, E = cfg.moe_shared_expert_intermediate_size, cfg.n_routed_experts
        held = cfg.held[1]
        hq = cfg.n_head * cfg.d_head
        hkv = cfg.n_kv_head * cfg.d_head
        keys = jax.random.split(rng, 5)

        def norm(key, shape):
            return (jax.random.normal(key, shape, F32) * std).astype(dt)

        def ones(n, *shape):
            return jnp.ones((n,) + shape, dt)

        def mamba(key):
            k = jax.random.split(key, 6)
            bound = 1.0 / math.sqrt(K)
            step = jnp.exp(jax.random.uniform(k[4], (H,), F32) * (
                math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
                + math.log(cfg.time_step_min))
            step = jnp.maximum(step, cfg.time_step_floor)
            return {
                "in_w": norm(k[0], (d, cfg.d_inner + C + H)),
                "conv_w": jax.random.uniform(
                    k[1], (K, C), F32, -bound, bound).astype(dt),
                "conv_b": jax.random.uniform(
                    k[2], (C,), F32, -bound, bound).astype(dt),
                "A_log": jnp.log(jax.random.uniform(
                    k[3], (H,), F32, 1.0, 16.0)).astype(dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "out_w": norm(k[5], (cfg.d_inner, d))}

        def moe(key):
            k = jax.random.split(key, 8)
            return {"router_w": norm(k[0], (d, E)),
                    "router_bias": jnp.zeros((E,), dt),
                    "latent_down": norm(k[2], (d, lat)),
                    "latent_up": norm(k[3], (lat, d)),
                    "up_w": norm(k[4], (held, lat, f)),
                    "down_w": norm(k[5], (held, f, lat)),
                    "shared_up": norm(k[6], (d, fs)),
                    "shared_down": norm(k[7], (fs, d))}

        def attn(key):
            k = jax.random.split(key, 4)
            return {"q_w": norm(k[0], (d, hq)), "k_w": norm(k[1], (d, hkv)),
                    "v_w": norm(k[2], (d, hkv)), "o_w": norm(k[3], (hq, d))}

        out = {"wte": norm(keys[0], (cfg.vocab_size, d)),
               "lm_head": norm(keys[1], (d, cfg.vocab_size)),
               "norm_f": jnp.ones((d,), dt)}
        for name, kind, layer, key in (("mamba", "M", mamba, keys[2]),
                                       ("moe", "E", moe, keys[3]),
                                       ("attn", "*", attn, keys[4])):
            n = cfg.count(kind)
            if n:
                out[name] = jax.lax.map(layer, jax.random.split(key, n))
                out[name]["norm"] = ones(n, d)
        if "mamba" in out:
            lm = cfg.count("M")
            out["mamba"]["D"] = ones(lm, H)
            out["mamba"]["gate_norm"] = ones(lm, cfg.d_inner)
        return out

    def apply(self, params, tokens, aux: bool = False):
        """tokens [B, T] -> logits [B, T, V]: the whole-sequence forward
        (no cache, every position live)."""
        logits, _, stats = _sequence(self.config, params, tokens, None)
        return (logits, held_expert_counters(
            stats, self.config.held[1])) if aux else logits

    def decode_step_paged(self, params, tokens, k_pool, v_pool, page_table,
                          lengths, active, *, state,
                          impl: Optional[str] = None, aux: bool = False,
                          **unbuilt):
        """One decode tick of every slot; ``gpt2_decode_step_paged``'s
        contract plus the request state.  Returns (logits [S, V], k_pool,
        v_pool, state, new_lengths) and, with ``aux``, the tick's
        counters.  An inactive slot's state is neither read nor
        written."""
        self.refuse(unbuilt)
        cfg, impl = self.config, self.decode_impl(impl)
        S = page_table.shape[0]
        eps = cfg.layer_norm_epsilon
        lengths, _, att_len, page_ids, offs = decode_index(
            page_table, lengths, active, k_pool.shape[3], cfg.n_positions)
        pool = PagePool((k_pool, v_pool), page_ids, offs, active)
        stacked = stacked_experts(params, names=("up_w", "down_w")) \
            if cfg.count("E") else None
        ssm, conv = state["ssm"], state["conv"]
        ssm_flat = ssm.reshape((-1,) + ssm.shape[2:])
        stats = []
        with jax.named_scope("embed"):
            x = params["wte"][tokens]                       # [S, d]
        for kind, i in _layers(cfg):
            with jax.named_scope("layer"):
                if kind == "M":
                    mp = _at(params["mamba"], i)
                    with jax.named_scope("ssm"):
                        h = rms_norm(x, mp["norm"], eps)
                        z, xbc, dt_raw = _mamba_in(cfg, mp, h)
                        window = jnp.concatenate(
                            [conv[i], xbc[:, None].astype(conv.dtype)],
                            axis=1)
                        conv = conv.at[i].set(jnp.where(
                            active[:, None, None], window[:, 1:], conv[i]))
                        x_s, b, c, dt, a = _ssm_inputs(
                            cfg, mp, _conv(mp, [window[:, j] for j in range(
                                cfg.conv_kernel)]), dt_raw)
                        with jax.named_scope("scan"):
                            ssm_flat, y = ssm_decode(
                                ssm_flat, jnp.exp(dt * a),
                                dt[..., None] * x_s, b, c, active,
                                base=i * S)
                        x = x + _mamba_out(cfg, mp, y, x_s, z, x.dtype)
                elif kind == "*":
                    ap = _at(params["attn"], i)
                    with jax.named_scope("attn"):
                        h = rms_norm(x, ap["norm"], eps)
                        q, k, v = _qkv(cfg, ap, h[:, None])
                        pool.write(i, k[:, :, 0], v[:, :, 0])
                        attn = pool.attend(i, q[:, :, 0], page_table,
                                           att_len, impl=impl)
                        x = x + attn.reshape(S, -1) \
                            @ ap["o_w"].astype(x.dtype)
                else:
                    ep = _at(params["moe"], i)
                    out, st = _experts(cfg, ep, stacked, i,
                                       rms_norm(x, ep["norm"], eps), active)
                    stats.append(st)
                    x = x + out
        logits = lm_head(x, params["norm_f"], params["lm_head"], eps)
        new_state = {"ssm": ssm_flat.reshape(ssm.shape), "conv": conv}
        out = (logits, *pool.arrays(), new_state,
               lengths + active.astype(jnp.int32))
        return out + (held_expert_counters(stats, cfg.held[1]),) \
            if aux else out

    def prefill_paged(self, params, tokens, delta_len, prefix_len, page_row,
                      k_pool, v_pool, *, state, slot, aux: bool = False,
                      **unbuilt):
        """Prefill of one request into the pool and into ``slot`` of the
        request state.  tokens [1, Tq] right-padded to the bucket;
        ``delta_len``, ``page_row`` [max_pages] and ``slot`` traced.  No
        cached prefix (``prefix_len`` is not read): the engine refuses
        the prefix cache for this model, because a page of keys without
        the recurrent state at its boundary is no prefix.  Returns (logits
        [1, Tq, V], k_pool, v_pool, state); ``logits[0, delta_len - 1]``
        scores the first generated token.  The slot's state is
        OVERWRITTEN with the state at ``delta_len``: padding takes ``dt =
        0`` and feeds nothing, the conv window is read at the true
        end."""
        self.refuse(unbuilt)
        cfg = self.config
        delta_len = jnp.asarray(delta_len, jnp.int32)
        slot = jnp.asarray(slot, jnp.int32)
        valid, page_ids, offs, _, _ = prefill_index(
            page_row, delta_len, tokens.shape[1], k_pool.shape[3])
        pool = PagePool((k_pool, v_pool), page_ids, offs, valid)
        logits, kept, stats = _sequence(cfg, params, tokens, delta_len)
        for i, (k, v) in enumerate(kept.pop("kv")):         # [Hkv, Tq, Dh]
            pool.write(i, k.transpose(1, 0, 2), v.transpose(1, 0, 2))
        out = (logits, *pool.arrays(), write_slot_state(state, kept, slot))
        return out + (held_expert_counters(stats, cfg.held[1]),) \
            if aux else out


# -- the three parts ------------------------------------------------------

def _layers(cfg: NemotronHConfig):
    """(kind, index among its kind) for each layer, in order."""
    seen = {"M": 0, "E": 0, "*": 0}
    for kind in cfg.hybrid_override_pattern:
        yield kind, seen[kind]
        seen[kind] += 1


def _at(stacked, i: int):
    """Layer ``i`` of a kind's stacked leaves, but for the experts, which
    reach their kernels whole (``walked.stacked_experts``)."""
    return {k: v[i] for k, v in stacked.items()
            if k not in ("up_w", "down_w")}


def _mamba_in(cfg: NemotronHConfig, mp, x):
    """x [..., d] (normed) -> z [..., d_inner], xBC [..., C], dt [..., H]
    (raw)."""
    with jax.named_scope("in_proj"):
        zxbcdt = x @ mp["in_w"].astype(x.dtype)
    return jnp.split(zxbcdt, [cfg.d_inner, cfg.d_inner + cfg.conv_dim],
                     axis=-1)


def _conv(mp, taps):
    """taps: the K rows under the filter, oldest first, each [..., C] ->
    silu(conv) in float32 [..., C]."""
    with jax.named_scope("conv"):
        w = mp["conv_w"].astype(F32)
        y = sum(t.astype(F32) * w[j] for j, t in enumerate(taps)) \
            + mp["conv_b"].astype(F32)
        return jax.nn.silu(y)


def _ssm_inputs(cfg: NemotronHConfig, mp, conv_out, dt_raw):
    """conv_out [..., C] float32, dt_raw [..., H] -> x_s [..., H, P], B,
    C [..., G, N], dt [..., H] (float32, after softplus), A [H]."""
    lead = conv_out.shape[:-1]
    gn = cfg.n_groups * cfg.ssm_state_size
    x_s, b, c = jnp.split(conv_out, [cfg.d_inner, cfg.d_inner + gn], axis=-1)
    x_s = x_s.reshape(lead + (cfg.mamba_num_heads, cfg.mamba_head_dim))
    b = b.reshape(lead + (cfg.n_groups, cfg.ssm_state_size))
    c = c.reshape(lead + (cfg.n_groups, cfg.ssm_state_size))
    dt = jax.nn.softplus(dt_raw.astype(F32) + mp["dt_bias"].astype(F32))
    return x_s, b, c, dt, -jnp.exp(mp["A_log"].astype(F32))


def _mamba_out(cfg: NemotronHConfig, mp, y, x_s, z, dtype):
    """y, x_s [..., H, P] float32, z [..., d_inner] -> the mixer's
    output [..., d]: ``D`` skip, gate, RMSNorm by group, ``W_out``."""
    with jax.named_scope("gate_norm"):
        y = y + mp["D"].astype(F32)[:, None] * x_s
        lead = y.shape[:-2]
        y = y.reshape(lead + (cfg.d_inner,)) * jax.nn.silu(z.astype(F32))
        g = y.reshape(lead + (cfg.n_groups, cfg.d_inner // cfg.n_groups))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg.layer_norm_epsilon)
        y = g.reshape(lead + (cfg.d_inner,)).astype(dtype) \
            * mp["gate_norm"].astype(dtype)
    with jax.named_scope("out_proj"):
        return y @ mp["out_w"].astype(dtype)


def _experts(cfg: NemotronHConfig, ep, stacked, index: int, x, valid):
    """The ``E`` part on normed x [N, d]: this share's routed part + the
    shared expert.  ``stacked``: every layer's held experts flat."""
    with jax.named_scope("moe"):
        with jax.named_scope("latent_down"):
            u = x @ ep["latent_down"].astype(x.dtype)
        r, stats = routed_experts(
            x, ep["router_w"], ep["router_bias"], stacked, index, rows=u,
            top_k=cfg.num_experts_per_tok, held=cfg.held, valid=valid,
            act="relu2", scale=cfg.routed_scaling_factor,
            renormalize=cfg.norm_topk_prob)
        with jax.named_scope("latent_up"):
            out = r @ ep["latent_up"].astype(x.dtype)
        with jax.named_scope("shared_expert"):
            s = jnp.maximum(x @ ep["shared_up"].astype(x.dtype), 0)
            out = out + (s * s) @ ep["shared_down"].astype(x.dtype)
    return out, stats


def _qkv(cfg: NemotronHConfig, ap, h):
    """h [B, T, d] (normed) -> q [B, Hq, T, Dh], k, v [B, Hkv, T, Dh].
    Nothing is rotated (module docstring); if the source turns out to
    rotate, ``models/walked.py::rope(t, positions, cfg.rope_theta)`` on q
    and k goes here, and its like into the reference's ``_attention``."""
    return (project_heads(h, ap["q_w"], cfg.n_head),
            project_heads(h, ap["k_w"], cfg.n_kv_head),
            project_heads(h, ap["v_w"], cfg.n_kv_head))


def _self_attention(cfg: NemotronHConfig, q, k, v):
    """Causal attention of a whole sequence; each key head repeated for
    the query heads that read it."""
    rep = cfg.n_head // cfg.n_kv_head
    k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
    if cfg.attn_impl == "flash":
        from ..parallel.attention import sharded_flash_attention
        return sharded_flash_attention(q, k, v, causal=True)
    return causal_attention(q, k, v)


def _sequence(cfg: NemotronHConfig, params, tokens, delta_len):
    """The forward over whole sequences tokens [B, T] from empty state.
    ``delta_len`` (traced, B == 1) is the live length inside a padded
    bucket; None: every position is live.  Returns (logits, what a cache
    keeps: per ``*`` layer (k, v) and per ``M`` layer (final state, conv
    window) of sequence 0, the ``E`` layers' statistics)."""
    B, T = tokens.shape
    eps, K = cfg.layer_norm_epsilon, cfg.conv_kernel
    live = jnp.full((T,), True) if delta_len is None \
        else jnp.arange(T) < delta_len
    end = T if delta_len is None else delta_len
    pad = -T % cfg.chunk_size
    stacked = stacked_experts(params, names=("up_w", "down_w")) \
        if cfg.count("E") else None
    kept = {"kv": [], "ssm": [], "conv": []}
    stats = []
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
    for kind, i in _layers(cfg):
        with jax.named_scope("layer"):
            if kind == "M":
                mp = _at(params["mamba"], i)
                with jax.named_scope("ssm"):
                    h = rms_norm(x, mp["norm"], eps)
                    z, xbc, dt_raw = _mamba_in(cfg, mp, h)
                    # zeros before the sequence; the K taps as K shifted
                    # views, never stacked
                    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
                    x_s, b, c, dt, a = _ssm_inputs(
                        cfg, mp, _conv(mp, [padded[:, j:j + T]
                                            for j in range(K)]), dt_raw)
                    dt = jnp.where(live[None, :, None], dt, 0.0)
                    with jax.named_scope("scan"):
                        def one(x_s, dt, b, c):
                            grow = ((0, pad),) + ((0, 0),) * 2
                            y, fin = ssd_chunked(
                                jnp.pad(x_s, grow), jnp.pad(dt, grow[:2]),
                                a, jnp.pad(b, grow), jnp.pad(c, grow),
                                cfg.chunk_size)
                            return y[:T], fin
                        y, final = jax.vmap(one)(x_s, dt, b, c)
                    kept["ssm"].append(final[0])
                    kept["conv"].append(jax.lax.dynamic_slice_in_dim(
                        padded[0], end, K - 1, axis=0))
                    x = x + _mamba_out(cfg, mp, y, x_s, z, x.dtype)
            elif kind == "*":
                ap = _at(params["attn"], i)
                with jax.named_scope("attn"):
                    h = rms_norm(x, ap["norm"], eps)
                    q, k, v = _qkv(cfg, ap, h)
                    kept["kv"].append((k[0], v[0]))
                    attn = _self_attention(cfg, q, k, v)
                    x = x + merge_heads(attn) @ ap["o_w"].astype(x.dtype)
            else:
                ep = _at(params["moe"], i)
                h = rms_norm(x, ep["norm"], eps).reshape(B * T, -1)
                valid = None if delta_len is None else jnp.tile(live, B)
                out, st = _experts(cfg, ep, stacked, i, h, valid)
                stats.append(st)
                x = x + out.reshape(x.shape)
    logits = lm_head(x, params["norm_f"], params["lm_head"], eps)
    return logits, kept, stats
