"""What the served families whose layers are WALKED share: ``olmoe.py``
(the one that scans), ``nemotron_h.py``, ``mimo_v2.py``, ``axk1.py``,
``cohere2_moe.py``, ``glm_dsa.py``, ``kimi_linear.py``, ``dots3_note.py``,
``olmo_hybrid.py``, ``lfm2_moe.py``.  A family's file
holds what is its own: its config under the source's keys, ``init`` and
the parameter tree, its projections, latents and mixers, its list of layer
kinds, and two paged steps that read as that list walked over the pieces
here.  No family imports another; the
next one imports this module, ``ops`` and ``moe``.

The functions know no family and take VALUES, never a config (the configs
keep their sources' key names: ``rms_norm_eps``, ``layernorm_epsilon``,
``layer_norm_epsilon``, ``layer_norm_eps``): the mathematics two or more
families call; the index preludes of the two paged steps
(:func:`decode_index`, :func:`prefill_index`); the cache's bookkeeping as
two pairs of write and attend (:class:`PagePool`, :class:`Rings`), the
pool of grouped keys narrower than the lanes (:class:`PairedPagePool`) and
the ring of ONE array of latent rows (:class:`LatentRing`); the
routed-expert call (:func:`routed_experts`); a chunk's attention over keys
gathered from the pool ahead of it (:func:`context_attention`); the
delta-rule mixers' small parts (``kimi_linear.py``, ``olmo_hybrid.py``);
latent attention's
projections, rows at rest and expanded form, from nothing or over a paged
context (``axk1.py``, ``glm_dsa.py``, ``kimi_linear.py``,
``dots3_note.py``); the learned indexer's projections and picks
(``glm_dsa.py``, ``dots3_note.py``).  ONE rule for what is not
live: an inactive slot and a padded prompt row name page 0, the engine's
scratch page, are kept out of every write and attend over length 0.
:class:`ServedConfig` and :class:`WalkedModel` are what ``ServeEngine``
reads of every one of them alike.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..moe.dropless import dropless_moe, route_sigmoid_topk

F32 = jnp.float32


@jax.tree_util.register_pytree_node_class
class OutputMajor:
    """A matrix ``w [d, n]`` as it RESTS inside a ``ServeEngine`` where its
    model says so (:meth:`WalkedModel.serving_layouts`): ``t = w.T``, its
    second (output) axis major.  A pytree node around the one array, so a
    step function tells an engine's leaf from a caller's by its TYPE
    (:func:`project_heads`), not by a name or a shape."""
    __slots__ = ("t",)

    def __init__(self, t):
        self.t = t                      # [n, d]

    @classmethod
    def of(cls, w):
        """The leaf as a caller holds it -> as it rests (traceable)."""
        return cls(jnp.transpose(w))

    def tree_flatten(self):
        return (self.t,), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


class ServedConfig:
    """What ``ServeEngine`` reads of any model's config under the names
    every source shares (HF ``config.json``); ``n_layer`` (the pool's
    depth), ``d_head`` and the rest are the family's."""

    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_head(self) -> int:
        return self.num_key_value_heads

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    def check(self, unbuilt: Dict[str, bool],
              experts: Optional[int] = None) -> None:
        """Refuses what the source asks for and is not built (``unbuilt``:
        what -> asked), ``experts_held`` no range of ``experts``."""
        bad = [k for k, v in unbuilt.items() if v]
        if bad:
            raise ValueError(f"{type(self).__name__}: not built: "
                             + "; ".join(bad))
        if experts is not None:
            first, count = self.held
            if first < 0 or count < 1 or first + count > experts:
                raise ValueError(f"experts_held {self.experts_held}: not a "
                                 f"range of the {experts}")
        if self.attn_impl not in ("flash", "dense"):
            raise ValueError(f"attn_impl {self.attn_impl!r}: 'flash' or "
                             "'dense'")


class WalkedModel:
    """The part of ``ServeEngine``'s protocol the families spell alike
    (``docs/serving.md``, "Adding a served family")."""
    #: the serving features the paged steps do not have; the engine
    #: refuses a configuration that asks for one (``ServeEngine``)
    serving_unsupported = ("slot_cache", "speculate_k", "quantization",
                           "lora")
    #: the paged steps also return these per-call counters (``aux=True``):
    #: the engine keeps them per call (``ServeEngine.aux_log``)
    serving_aux = ("moe_experts_hit", "moe_load_imbalance", "moe_rows",
                   "moe_rows_elsewhere")
    refusal_note = ""                   # whose such an arm is, if anyone's
    #: the family's query projections by leaf name, ``[d, heads * width]``
    #: read through :func:`project_heads`: what :meth:`serving_layouts`
    #: declares; a family that names none is placed as ever
    query_projections: Tuple[str, ...] = ()

    def __init__(self, config):
        self.config = config

    def param_partition_specs(self, params):
        return None                     # one chip: everything replicated

    def serving_layouts(self, params):
        """How each leaf of ``params`` RESTS inside a ``ServeEngine``: a
        tree like ``params`` of None (as the caller holds it) or a form
        with ``.of(leaf)``.  ONE rule: a query projection rests
        :class:`OutputMajor`, which is how the TPU compiler wants the wide
        operand of ``h @ w`` at few rows: from ``[d, n]`` row-major it
        wrote the whole matrix to HBM transposed before every such matmul,
        once a layer, every tick (PERF.md section 6, PR 55).  The engine
        makes ITS copy of these leaves so once (``setup_params``) and its
        programs read them through :func:`project_heads`; the caller's
        tree, the leaf names and every program run on the caller's tree
        are as ever.  A family whose compiled tick says otherwise of its
        leaves names none (``models/axk1.py``)."""
        def rests(path, _):
            named = any(getattr(k, "key", None) in self.query_projections
                        for k in path)
            return OutputMajor if named else None
        return jax.tree_util.tree_map_with_path(rests, params)

    def decode_impl(self, impl: Optional[str]) -> str:
        """The decode kernels' arm where the engine names none."""
        return impl or (
            "pallas" if self.config.attn_impl == "flash" else "dense")

    def refuse(self, unbuilt: dict) -> None:
        """The keyword arms a paged step was handed and does not have."""
        asked = sorted(k for k, v in unbuilt.items() if v is not None)
        if asked:
            raise NotImplementedError(
                f"{type(self).__name__}'s paged steps have no "
                f"{', '.join(asked)} arm{self.refusal_note}")


# -- the leaf helpers -------------------------------------------------------

def rms_norm(x, weight, eps: float):
    """HF ``OlmoeRMSNorm``: normalise in float32, back to x's type, then
    the weight."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * weight.astype(x.dtype)


def rope(x, positions, theta: float, rotary_dim: Optional[int] = None,
         inv_freq=None):
    """Rotate-half RoPE.  x [B, H, T, Dh], positions [B, T] (absolute).
    Pair i is (x[i], x[i + R/2]), angle ``pos * theta**(-2i/R)``, over
    the first ``R = rotary_dim`` dims (None: the whole head); the others
    pass untouched.  ``inv_freq`` [R/2] float32: a frequency a pair of
    the caller's own (a scaled RoPE: ``models/axk1.py::yarn_inv_freq``)
    in place of ``theta``'s, which is then not read."""
    rot = x.shape[-1] if rotary_dim is None else rotary_dim
    half = rot // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, :, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            xf[..., rot:]], axis=-1).astype(x.dtype)


def project_heads(h, w, n: int):
    """h [B, T, d] @ w [d, n * width] -> [B, n, T, width]; ``w`` as a
    caller holds it, or :class:`OutputMajor` as an engine does."""
    B, T, _ = h.shape
    if isinstance(w, OutputMajor):
        y = jnp.einsum("btd,nd->btn", h, w.t.astype(h.dtype))
    else:
        y = h @ w.astype(h.dtype)
    return y.reshape(B, T, n, -1).transpose(0, 2, 1, 3)


def merge_heads(t):
    """[B, n, T, width] -> [B, T, n * width]."""
    B, _, T, _ = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B, T, -1)


def grouped_causal_attention(q, k, v, window=None, sink=None,
                             sm_scale=None):
    """The dense (XLA) arm of a whole sequence's attention: q [B, Hq, T,
    Dk] over k [B, Hkv, T, Dk], v [B, Hkv, T, Dv]; ``window``: the last
    so many keys, the query's own included; ``sink`` [Hq]: one more
    softmax column a head that gives no value."""
    B, Hq, T, _ = q.shape
    rep = Hq // k.shape[1]
    k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=F32) * sm_scale
    at = jnp.arange(T)
    ok = at[None, :] <= at[:, None]
    if window is not None:
        ok &= at[None, :] > at[:, None] - window
    s = jnp.where(ok[None, None], s, jnp.finfo(F32).min)
    if sink is not None:
        col = jnp.broadcast_to(sink.astype(F32)[None, :, None, None],
                               (B, Hq, T, 1))
        s = jnp.concatenate([s, col], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :T].astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def default_scale(head_dim: int) -> float:
    """``head_dim ** -0.5``, as the decode kernels round it."""
    from ..ops.pallas.decode_attention import _default_scale
    return _default_scale(head_dim)


def causal_self_attention(q, k, v, flash: bool, *, window=None, sink=None,
                          sm_scale=None, **blocks):
    """A whole sequence's attention from nothing ahead of it, on grouped
    keys: the flash forward kernel (``blocks``: its ``block_q`` /
    ``block_k``) or the dense arm."""
    if flash:
        from ..ops.pallas.flash_attention import flash_attention_fwd
        return flash_attention_fwd(q, k, v, window=window, sink=sink,
                                   sm_scale=sm_scale, **blocks)
    return grouped_causal_attention(q, k, v, window=window, sink=sink,
                                    sm_scale=sm_scale)


def context_attention(q, k, v, ctx_k, ctx_v, live, flash: bool, *,
                      window=None, sm_scale=None):
    """A chunk's attention with keys ahead of it: q [B, Hq, Tq, D], the
    chunk's own k, v [B, Hkv, Tq, D]; ``ctx_k`` / ``ctx_v`` [Hkv, Tc, D] of
    which the LAST ``live`` (traced) are the positions just before the
    chunk, in order (:func:`prefix_keys`).  The flash forward kernel over
    ``[context ; chunk]`` (``ctx_live=``) or the dense arm."""
    keys = jnp.concatenate([ctx_k[None].astype(k.dtype), k], axis=2)
    values = jnp.concatenate([ctx_v[None].astype(v.dtype), v], axis=2)
    if flash:
        from ..ops.pallas.flash_attention import flash_attention_fwd
        return flash_attention_fwd(q, keys, values, window=window,
                                   sm_scale=sm_scale, ctx_live=live)
    return _dense_context_attention(q, keys, values, live, window, sm_scale)


def _dense_context_attention(q, k, v, live, window, sm_scale):
    """The dense (XLA) arm of :func:`context_attention`."""
    Tq, Tk = q.shape[2], k.shape[2]
    Tc = Tk - Tq
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=F32) * sm_scale
    kk, qq = jnp.arange(Tk)[None, :], Tc + jnp.arange(Tq)[:, None]
    ok = (kk <= qq) & (kk >= Tc - live)
    if window is not None:
        ok &= kk > qq - window
    s = jnp.where(ok[None, None], s, jnp.finfo(F32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def prefix_keys(layer_pool, page_row, prefix_len):
    """A full layer's keys ahead of a chunk, for
    :func:`context_attention`: ``layer_pool`` [pages, Hkv, page_len, D]
    (one layer's, or every layer's in one row, :meth:`PagePool.flat`,
    with the layer's base in ``page_row``) -> [Hkv, cap, D], positions ``0
    .. prefix_len - 1`` at the END (the request's pages gathered in order,
    as ``models/olmoe.py`` gathers them, then rolled)."""
    got = layer_pool[page_row]                  # [max_pages, Hkv, pl, D]
    hkv, d = got.shape[1], got.shape[3]
    flat = got.transpose(1, 0, 2, 3).reshape(hkv, -1, d)
    return jnp.roll(flat, flat.shape[1] - prefix_len, axis=1)


# -- the delta-rule mixers' parts (``kimi_linear.py``, ``olmo_hybrid.py``) --

def l2_norm(x):
    """x / |x| over the last axis (flash-linear-attention's ``l2norm``,
    eps 1e-6 under the root)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def conv_taps(conv_w, taps):
    """A depthwise causal convolution as its taps: ``conv_w`` [K, C];
    ``taps``: the K rows under the filter, oldest first, each [..., C] ->
    the convolution in float32 [..., C], no activation
    (``models/lfm2_moe.py`` calls it bare)."""
    w = conv_w.astype(F32)
    return sum(t.astype(F32) * w[j] for j, t in enumerate(taps))


def silu_conv(conv_w, taps):
    """silu(:func:`conv_taps`): the delta-rule mixers' convolution."""
    return jax.nn.silu(conv_taps(conv_w, taps))


def shift_tail(tail, new, active):
    """A decode tick's convolution window: ``tail`` [S, K - 1, C] (the
    slots' last rows, one layer's, as the state's leaf holds them) and the
    tick's rows ``new`` [S, 1, C] -> (window [S, K, C], the tail to keep
    [S, K - 1, C]: shifted where ``active``).  The caller STACKS the kept
    tails of its layers and writes the leaf once at the end of the tick: a
    layer's ``.at[i].set`` of a shifted read of the same rows is an
    in-place update XLA rematerialised on the chip, and the second run read
    the first one's rows (PERF.md section 6, PR 52)."""
    window = jnp.concatenate([tail, new.astype(tail.dtype)], axis=1)
    return window, jnp.where(active[:, None, None], window[:, 1:], tail)


def shift_tail_lanes(tail, new, active):
    """:func:`shift_tail` for tails that rest with their rows SIDE BY SIDE
    on the lanes: ``tail`` [S, (K - 1) C] and the tick's rows ``new`` [S, C]
    -> (the K taps, oldest first, each [S, C]: aligned slices of the lanes;
    the tail to keep [S, (K - 1) C]).  A leaf ``[layers, slots, K - 1, C]``
    with ``K - 1`` = 2 rests in tiles of 2 sublanes, and the tick's one
    write of it was a copy into that layout the compiler priced at a twelfth
    of the tick (described-v5e compile, PR 65); ``[layers, slots, (K - 1)
    C]`` rests in whole tiles."""
    C = new.shape[-1]
    window = jnp.concatenate([tail, new.astype(tail.dtype)], axis=-1)
    taps = [window[:, j * C:(j + 1) * C] for j in range(window.shape[-1] // C)]
    return taps, jnp.where(active[:, None], window[:, C:], tail)


def swiglu(x, gate_w, up_w, down_w):
    """``down(silu(gate x) * up x)``, every matrix input-major."""
    g = x @ gate_w.astype(x.dtype)
    u = x @ up_w.astype(x.dtype)
    return (jax.nn.silu(g) * u) @ down_w.astype(x.dtype)


@jax.named_scope("dense_ffn")
def dense_ffn(fp, x):
    return swiglu(x, fp["gate_w"], fp["up_w"], fp["down_w"])


@jax.named_scope("lm_head")
def lm_head(x, norm_w, head_w, eps: float):
    """The final RMSNorm, then the untied head [d, V]."""
    x = rms_norm(x, norm_w, eps)
    return x @ head_w.astype(x.dtype)


@jax.named_scope("lm_head")
def tied_head(x, norm_w, wte, eps: float):
    """The final RMSNorm, then the head that IS the embedding ``wte`` [V,
    d], read where it lies: one ``dot_general`` over the last axes of both,
    no transpose of the table."""
    x = rms_norm(x, norm_w, eps)
    return jnp.einsum("...d,vd->...v", x, wte.astype(x.dtype))


# -- latent attention (MLA): what its families share --------------------------

_LANES = 128


def whole_tiles(width: int) -> int:
    """``width``, in whole lane tiles where it is wider than one."""
    return width if width <= _LANES else -(-width // _LANES) * _LANES


def latent_projections(ap, h, positions, *, heads: int, nope: int,
                       kv_rank: int, eps: float, theta: float,
                       inv_freq=None, low_rank_q: bool = True,
                       rotate: bool = True, q_scale=None, kv_scale=None):
    """h [B, T, d] (normed), positions [B, T] -> c_q [B, T, q_lora_rank]
    (normed), q_nope [B, H, T, nope], q_rope [B, H, T, rot] (rotated),
    c_kv [B, T, kv_rank] (normed), k_rope [B, T, rot] (rotated): what the
    cache keeps is the last two.  ``low_rank_q`` false: the query comes
    straight from ``ap["q_w"]`` and ``c_q`` is None; ``rotate`` false:
    nothing is rotated and ``positions`` is not read.  ``q_scale`` /
    ``kv_scale``: a factor on the normed latent (``c_q``, ``c_kv``) where
    the source rescales them (``models/dots3_note.py``); None: none."""
    def turned(t):
        return rope(t, positions, theta, inv_freq=inv_freq) if rotate else t

    with jax.named_scope("latent_q"):
        c_q = rms_norm(h @ ap["q_a_w"].astype(h.dtype), ap["q_a_norm"],
                       eps) if low_rank_q else None
        if q_scale is not None:
            c_q = c_q * jnp.asarray(q_scale, c_q.dtype)
        q = project_heads(c_q, ap["q_b_w"], heads) if low_rank_q \
            else project_heads(h, ap["q_w"], heads)
        q_nope = q[..., :nope]
        q_rope = turned(q[..., nope:])
    with jax.named_scope("latent_kv"):
        kv = h @ ap["kv_a_w"].astype(h.dtype)
        c_kv = rms_norm(kv[..., :kv_rank], ap["kv_a_norm"], eps)
        if kv_scale is not None:
            c_kv = c_kv * jnp.asarray(kv_scale, c_kv.dtype)
        k_rope = turned(kv[:, None, :, kv_rank:])[:, 0]
    return c_q, q_nope, q_rope, c_kv, k_rope


def latent_rows(c_kv, k_rope, width: int):
    """[..., kv_rank], [..., rot] -> the rows at rest [..., width]:
    ``[c_kv ; k_rope ; 0]``."""
    rows = jnp.concatenate([c_kv, k_rope], axis=-1)
    pad = width - rows.shape[-1]
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, pad),))


@jax.named_scope("expand")
def expand_latents(ap, c_kv, dtype):
    """c_kv [..., T, kv_rank] -> every head's k_nope [..., H, T, nope] and
    v [..., H, T, v_head_dim]."""
    c_kv = c_kv.astype(dtype)
    return (jnp.einsum("...tc,hnc->...htn", c_kv, ap["k_b_w"].astype(dtype)),
            jnp.einsum("...tc,hcv->...htv", c_kv, ap["v_b_w"].astype(dtype)))


def latent_self_attention(ap, q_nope, q_rope, c_kv, k_rope, *, flash: bool,
                          sm_scale: float, window=None):
    """The expanded form over whole sequences from position 0: q_* [B, H,
    T, .], c_kv [B, T, C], k_rope [B, T, rot] -> [B, H, T, v_head_dim];
    ``window``: the last so many keys, the query's own included."""
    k_nope, v = expand_latents(ap, c_kv, q_nope.dtype)
    k_rope = jnp.broadcast_to(k_rope[:, None], q_rope.shape)
    # the two widths at rest in whole lane tiles (192 -> 256), zeros in
    # the upper lanes: scores do not change, every matmul is aligned
    width = q_nope.shape[-1] + q_rope.shape[-1]
    pad = ((0, 0),) * 3 + ((0, whole_tiles(width) - width),)
    q = jnp.pad(jnp.concatenate([q_nope, q_rope], axis=-1), pad)
    k = jnp.pad(jnp.concatenate([k_nope, k_rope], axis=-1), pad)
    return causal_self_attention(q, k, v, flash, sm_scale=sm_scale,
                                 window=window)


def latent_context_attention(ap, q_nope, q_rope, pool_pages, page_ids,
                             abs_pos, context_len, *, kv_rank: int,
                             sm_scale: float, allowed=None):
    """The expanded form for a prefill whose context is in the pages (a
    prefix hit, a chunk; the delta's own rows already written): queries
    q_* [H, Tq, .] at absolute positions ``abs_pos`` [Tq] (below 0: a row
    that sees nothing) against the request's latent rows READ WHERE THEY
    LIE in the pool (``pool_pages`` [X, page_len, width]; ``page_ids``
    [max_pages] the request's pages of this layer) up to ``context_len``
    (traced).  ``allowed`` [Tq, max_pages * page_len] bool: the keys a
    query may see beside the causal rule (learned sparse attention); None:
    all.  One kernel, ``ds_latent_context_attn``
    (``ops/pallas/context_attention.py``): a block of whole pages at a
    time expanded to a group of heads' keys and values, float32 scores and
    an online softmax that stay in VMEM; nothing the size of the context
    is ever held.  Returns [H, Tq, v_head_dim]; a query with no key to see
    gives zeros."""
    from ..ops.pallas.context_attention import \
        latent_context_attention as attend
    H, _, nope = q_nope.shape
    rot, width, dt = q_rope.shape[-1], pool_pages.shape[-1], q_nope.dtype
    dq = whole_tiles(nope + rot)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, dq - nope - rot)))
    # a row at rest [c_kv ; k_rope ; 0] -> a head's key [k_nope ; k_rope ;
    # 0] in ONE matmul: W_UK over the latent's lanes, the identity over
    # the rotated ones (exact: each is one product by 1.0; the sum below
    # adds zeros, inside the pad's own pass).  Built when the
    # layer's queries are (the barrier), as the positions' column is: the
    # compiler otherwise lays out every layer's ahead of the first and
    # holds them all
    k_b_w, q, abs_pos = jax.lax.optimization_barrier(
        (ap["k_b_w"], q, abs_pos))
    passed = jnp.pad(jnp.eye(rot, dq, k=nope, dtype=dt),
                     ((kv_rank, width - kv_rank - rot), (0, 0)))
    k_w = passed + jnp.pad(jnp.swapaxes(k_b_w.astype(dt), 1, 2), (
        (0, 0), (0, width - kv_rank), (0, dq - nope)))
    return attend(q, k_w, ap["v_b_w"], pool_pages, page_ids, abs_pos,
                  context_len, sm_scale=sm_scale, allowed=allowed)


def latent_context_pairs(abs_pos, context_len, allowed=None):
    """The (query, key) pairs one call of :func:`latent_context_attention`
    lets through a head, float32 (a chunk of 2,048 over 16,384 keys passes
    2**24): a query at ``abs_pos`` sees the positions up to its own and
    below ``context_len``, of those ``allowed`` names where given."""
    if allowed is None:
        return jnp.sum(jnp.clip(jnp.minimum(abs_pos + 1, context_len), 0)
                       .astype(F32))
    at = jnp.arange(allowed.shape[1], dtype=jnp.int32)[None, :]
    return jnp.sum(allowed & (at <= abs_pos[:, None]) & (at < context_len),
                   dtype=F32)


# -- the learned indexer (DeepSeek sparse attention): what its families share --

#: cached keys a step of :func:`chunk_index_scores` scores (whole pages)
SCORE_BLOCK = 512
#: queries of a chunk whose picks :func:`chunk_picks` makes at a time
PICK_QUERIES = 512



def layer_norm_bias(x, weight, bias, eps: float = 1e-6):
    """LayerNorm in float32, back to x's type (the indexer key's)."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(F32) + bias.astype(F32)).astype(x.dtype)


@jax.named_scope("index_q")
def index_projections(ip, h, c_q, positions, *, heads: int, dim: int,
                      rot: int, theta: float):
    """h [B, T, d] (normed), c_q [B, T, q_lora_rank] -> the indexer's
    queries q_I [B, J, T, D] (rotated), its key k_I [B, T, D] (normed,
    rotated: what the cache keeps) and the heads' weights w [B, T, J]
    float32 with both scales folded in; ``heads`` J of ``dim`` D, the
    first ``rot`` dims rotated at ``theta``."""
    q_i = rope(project_heads(c_q, ip["wq_b_w"], heads), positions, theta,
               rotary_dim=rot)
    k_i = layer_norm_bias(h @ ip["wk_w"].astype(h.dtype), ip["k_norm_w"],
                          ip["k_norm_b"])
    k_i = rope(k_i[:, None], positions, theta, rotary_dim=rot)[:, 0]
    w = (h @ ip["weights_proj_w"].astype(h.dtype)).astype(F32) \
        * (heads ** -0.5 * dim ** -0.5)
    return q_i, k_i, w


def chunk_index_scores(q_i, w, index_pages, page_ids, abs_pos, context_len):
    """The indexer's scores of a chunk's queries q_i [J, Tq, D], w [Tq, J]
    at positions ``abs_pos`` [Tq] over the request's cached keys
    (``index_pages`` [X, page_len, D]; ``page_ids`` [max_pages] its pages
    of this layer, the chunk's own keys already written) up to
    ``context_len`` (traced), a block of whole pages at a time.  Returns
    [Tq, max_pages * page_len] float32, ``-inf`` at a key after the query
    and past the context."""
    J, Tq, D = q_i.shape
    page_len = index_pages.shape[1]
    ppb = max(1, SCORE_BLOCK // page_len)
    bk = ppb * page_len
    cap = page_ids.shape[0] * page_len
    ids = jnp.pad(page_ids, (0, (-page_ids.shape[0]) % ppb))
    wt = w.T[:, :, None]                                     # [J, Tq, 1]

    def block(j, scores):
        keys = index_pages[jax.lax.dynamic_slice_in_dim(
            ids, j * ppb, ppb)].reshape(bk, D)
        s = jnp.einsum("jtd,kd->jtk", q_i, keys.astype(q_i.dtype),
                       preferred_element_type=F32)
        s = jnp.sum(jnp.maximum(s, 0.0) * wt, axis=0)        # [Tq, bk]
        at_key = j * bk + jnp.arange(bk, dtype=jnp.int32)
        s = jnp.where(at_key[None, :] <= abs_pos[:, None], s, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(scores, s, j * bk, axis=1)

    scores = jax.lax.fori_loop(
        0, (context_len + bk - 1) // bk, block,
        jnp.full((Tq, ids.shape[0] * page_len), -jnp.inf, F32))
    return scores[:, :cap]


def chunk_picks(q_i, w, index_pages, page_ids, abs_pos, context_len,
                 k: int):
    """The picked sets of a chunk's queries as a mask [Tq, max_pages *
    page_len]: scores (:func:`chunk_index_scores`), then the ``k``
    largest of each query (:func:`pick_mask`), ``PICK_QUERIES`` queries
    at a time so that the float32 scores of the whole context and the
    selection's own temporaries are a block's."""
    J, Tq, D = q_i.shape
    bq = min(PICK_QUERIES, Tq)
    assert Tq % bq == 0, (Tq, bq)

    def block(args):
        q, wb, pos = args
        with jax.named_scope("index_score"):
            scores = chunk_index_scores(q, wb, index_pages, page_ids, pos,
                                         context_len)
        with jax.named_scope("index_topk"):
            return pick_mask(scores, k)

    masks = jax.lax.map(block, (
        q_i.reshape(J, Tq // bq, bq, D).transpose(1, 0, 2, 3),
        w.reshape(Tq // bq, bq, J), abs_pos.reshape(Tq // bq, bq)))
    return masks.reshape(Tq, -1)


def pick_mask(scores, k: int):
    """scores [..., N] float32 (``-inf``: not a candidate) -> bool [...,
    N]: the ``min(k, candidates)`` largest of a row, ties to the lower
    index.  Exact, and no sort: the ``k``-th largest value is found a bit
    at a time (32 counts over the row, on the floats' bits put in the
    floats' order), and of the entries equal to it the first so many as
    are still wanted; that last step costs a running count, taken only
    where some row has more equal entries than it wants.  (XLA's ``top_k``
    of 24,576 scores a query sorts them: 14.7 ms for 512 queries on a v5e
    against 0.6 here; my chip run, PR 49.)"""
    k = min(k, scores.shape[-1])
    live = scores > -jnp.inf
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.uint32)     # -0.0 is 0.0
    order = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, kth):
        trial = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(order >= trial[..., None], axis=-1) >= k
        return jnp.where(enough, trial, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1],
                                                  jnp.uint32))[..., None]
    above = order > kth
    tie = (order == kth) & live
    wanted = k - jnp.sum(above, axis=-1, keepdims=True)
    tie = jax.lax.cond(
        jnp.any(jnp.sum(tie, axis=-1, keepdims=True) > wanted),
        lambda: tie & (jnp.cumsum(tie, axis=-1) <= wanted), lambda: tie)
    return (above & live) | tie


@jax.named_scope("shared_expert")
def shared_expert(ep, x):
    """The SwiGLU expert every token takes (``shared_*_w``)."""
    return swiglu(x, ep["shared_gate_w"], ep["shared_up_w"],
                  ep["shared_down_w"])


def at(kind, i: int):
    """Layer ``i``'s own leaves of a kind (``models/mimo_v2.py``'s rule:
    each a leaf of the tree, nothing is sliced); not the experts, which
    reach their kernels whole (:func:`stacked_experts`)."""
    return {k: v[i] for k, v in kind.items() if isinstance(v, tuple)}


def draw_layers(layer, keys, ones: Dict[str, int], dtype):
    """A kind's per-layer leaves as :func:`at` reads them, name -> a TUPLE
    of one array a layer: ``layer(key)`` draws a layer's, ``ones`` (name
    -> width) are the norm weights beside them.  One XLA computation a
    layer, as the body of a scan over the keys is: the constants of a draw
    fold the same way called eagerly or inside a caller's jit (apart they
    round apart, an ulp in a quarter of the numbers)."""
    draw = jax.jit(layer)
    drawn = [{**draw(key), **{name: jnp.ones((width,), dtype)
                              for name, width in ones.items()}}
             for key in keys]
    return {leaf: tuple(one[leaf] for one in drawn) for leaf in drawn[0]}


def stacked_experts(params, kind: str = "moe",
                    names=("gate_w", "up_w", "down_w")):
    """Every expert layer's held experts flat, ``[layers * held, ...]``:
    a reshape of the leading axes of ``params[kind]``'s ``[layers, held,
    ...]`` arrays."""
    tree = params[kind]
    return {k: tree[k].reshape((-1,) + tree[k].shape[2:]) for k in names}


def held_expert_counters(stats, held: int) -> Dict[str, jnp.ndarray]:
    """The expert layers' HeldMoEStats -> the call's counters: experts
    hit and rows summed over layers (of the ``held`` HELD experts), the
    busiest held expert's rows over the mean rows a held expert (largest
    over layers), and the live assignments that went to experts held
    elsewhere."""
    zero = jnp.zeros((), jnp.int32)
    imb = [s.max_rows / (jnp.maximum(s.rows, 1).astype(F32) / held)
           for s in stats]
    return {"moe_experts_hit": sum((s.experts_hit for s in stats), zero),
            "moe_rows": sum((s.rows for s in stats), zero),
            "moe_load_imbalance": jnp.max(jnp.stack(imb)) if imb
            else jnp.zeros((), F32),
            "moe_rows_elsewhere": sum((s.rows_elsewhere for s in stats),
                                      zero)}


def routed_experts(x, router_w, router_bias, experts, index: int, *,
                   top_k: int, held, valid, act: str, scale: float = 1.0,
                   renormalize: bool = True, rows=None):
    """The routed part of expert layer ``index`` (among the expert layers)
    on normed x [N, d]: sigmoid routing (``route_sigmoid_topk``), then the
    ``held = (first, count)`` experts this chip holds on the rows routed
    to them, of ``rows`` [N, .] where the experts read another width than
    the router (a latent; default x).  ``experts``: every layer's held
    experts flat (:func:`stacked_experts`); the kernel finds this layer's
    at ``index * count``.  Returns (this share's part of the sum,
    HeldMoEStats).  The activation, the bias, the scale and what stands
    beside the sum (latent projections, shared experts) are the family's."""
    routing = route_sigmoid_topk(x, router_w, router_bias, top_k,
                                 scale=scale, renormalize=renormalize)
    return dropless_moe(
        x if rows is None else rows, router_w, experts.get("gate_w"),
        experts["up_w"], experts["down_w"], top_k,
        expert_offset=index * held[1], valid=valid, routing=routing,
        experts_held=held, act=act)


# -- the index preludes -----------------------------------------------------

def decode_index(page_table, lengths, active, page_len: int,
                 n_positions: int):
    """A decode tick's bookkeeping for slots [S]: -> (lengths as int32,
    positions [S] of the new token (clipped into the slot's pages and the
    model's positions), att_len [S] the keys a slot attends over, the new
    one included (0 where inactive), page_ids [S] and offs [S]: the page
    and the row of it the new key goes to (page 0 where inactive))."""
    cap = page_table.shape[1] * page_len
    lengths = lengths.astype(jnp.int32)
    positions = jnp.clip(lengths, 0, min(cap, n_positions) - 1)
    att_len = jnp.where(active, lengths + 1, 0).astype(jnp.int32)
    s_idx = jnp.arange(page_table.shape[0])
    page_ids = jnp.where(active, page_table[s_idx, positions // page_len], 0)
    return lengths, positions, att_len, page_ids, positions % page_len


def prefill_index(page_row, delta_len, Tq: int, page_len: int,
                  prefix_len=None, n_positions: Optional[int] = None):
    """A prefill's bookkeeping for the ``Tq`` rows of a bucket, the first
    ``delta_len`` (traced) the prompt's, at positions ``prefix_len ..``
    (traced; None: a whole prompt, from 0): -> (valid [Tq], page_ids [Tq]
    and offs [Tq]: the page of ``page_row`` [max_pages] and the row of it
    a position's key goes to (page 0 where not valid), abs_pos [Tq]
    unclipped, positions [1, Tq] clipped into the model's (None without
    ``prefix_len``))."""
    cap = page_row.shape[0] * page_len
    abs_pos = jnp.arange(Tq, dtype=jnp.int32)
    if prefix_len is not None:
        abs_pos = prefix_len + abs_pos
    valid = jnp.arange(Tq) < delta_len
    abs_clip = jnp.clip(abs_pos, 0, cap - 1)
    page_ids = jnp.where(valid, page_row[abs_clip // page_len], 0)
    offs = abs_clip % page_len
    positions = None if prefix_len is None \
        else jnp.clip(abs_pos, 0, n_positions - 1)[None]
    return valid, page_ids, offs, abs_pos, positions


# -- the cache's bookkeeping ------------------------------------------------

def rows_view(pool):
    """[L, P, Hkv, page_len, Dh] as the engine holds it -> every key row
    of every layer in one column [L*P*Hkv*page_len, Dh].  Same bytes."""
    return pool.reshape(-1, pool.shape[-1])


def write_rows(rows, new, index, keep):
    """``rows[index[i]] = new[i]`` where ``keep[i]``; the others write
    their old value back (their index names the scratch page)."""
    old = rows[index]
    return rows.at[index].set(
        jnp.where(keep[:, None], new.astype(rows.dtype), old))


def row_index(pages_flat, offs, kv_heads: int, page_len: int):
    """Row of key ``offs[i]`` of page ``pages_flat[i]`` (layer's base
    added) for each key head -> [n, Hkv] flattened."""
    g = jnp.arange(kv_heads, dtype=jnp.int32)
    return ((pages_flat[:, None] * kv_heads + g[None, :]) * page_len
            + offs[:, None]).reshape(-1)


class _LayerRows:
    """Arrays ``[layers, X, heads, rows, D]`` (one, or keys and values)
    held as flat rows while a step walks the layers.  ``units`` [n],
    ``offs`` [n]: the unit (of X) and the row each of a call's ``n`` new
    keys goes to; ``keep`` [n]: which are written."""

    def __init__(self, arrays, units, offs, keep):
        self.shapes = [a.shape for a in arrays]
        _, self.per_layer, self.heads, self.length, _ = self.shapes[0]
        self.rows = [rows_view(a) for a in arrays]
        self.units, self.offs = units, offs
        self.keep = keep if self.heads == 1 else jnp.repeat(keep, self.heads)

    def write(self, layer: int, *new):
        """``new``: one [n, heads, D] for each array."""
        index = row_index(layer * self.per_layer + self.units, self.offs,
                          self.heads, self.length)
        self.rows = [write_rows(r, t.reshape(-1, t.shape[-1]), index,
                                self.keep)
                     for r, t in zip(self.rows, new)]

    def flat(self):
        """Every layer's units in one row, ``[layers * X, heads, rows,
        D]``: what the decode kernels take, with the layer's base."""
        return [r.reshape((-1,) + s[2:])
                for r, s in zip(self.rows, self.shapes)]

    def arrays(self):
        """As they came."""
        return [r.reshape(s) for r, s in zip(self.rows, self.shapes)]


class PagePool(_LayerRows):
    """The POOL pair: the engine's pool(s) ``[L, pages, Hkv, page_len,
    D]`` of the layers that keep every key; ``page_ids``, ``offs``,
    ``keep``: a prelude's.  One pool (A.X-K1's latent rows) is the pair
    with one row set, and its kernel the family's."""

    def attend(self, layer: int, q, page_table, att_len, *, impl: str,
               sm_scale: Optional[float] = None):
        """q [S, Hq, D] over layer ``layer``'s pages of each slot, the
        tick's keys already written -> [S, Hq, Dv]."""
        from ..ops.pallas.decode_attention import decode_attention_paged
        k, v = self.flat()
        # a page rests [Hkv, page_len, D], as ``write`` lays it: said
        # for the family with as many key heads as query heads
        return decode_attention_paged(
            q, k, v, page_table + layer * self.per_layer, att_len,
            sm_scale=sm_scale, impl=impl, head_major=True)


def lane_pairs(kv_heads: int, head_dim: int) -> int:
    """Key heads that rest side by side in one row of the pool, for
    grouped keys whose head is narrower than the 128 lanes: a token's
    ``kv_heads x head_dim`` keys are contiguous, so ``pairs`` heads of
    ``head_dim`` ARE one head of ``pairs * head_dim`` (a reshape of ``x
    W_k``).  The most heads that fit the lanes and divide ``kv_heads``
    (8 heads of 64: 2); 1 where the head fills the lanes."""
    fit = max(_LANES // head_dim, 1)
    return max(p for p in range(1, fit + 1) if kv_heads % p == 0)


class PairedPagePool(PagePool):
    """The POOL pair of grouped keys at a head under 128 (:func:`lane_pairs`
    of them a row): the engine's pools are ``[L, pages, Hkv / pairs,
    page_len, pairs * D]``, the PUBLISHED bytes, and every copy the decode
    kernel makes of a page is whole lane tiles wide (Mosaic copies no
    window of an HBM array whose last dimension is under 128:
    ``ops/pallas/decode_attention.py``).  ``write`` and ``attend`` take and
    give the model's own heads; a query head's ``D`` values ride the lanes
    its key head owns with zeros in the others (the scores are the same
    sums; ``pairs`` times the multiply-adds of a kernel its bytes bound),
    and its output is those lanes of the paired row."""

    def __init__(self, arrays, page_ids, offs, keep, *, kv_heads: int):
        super().__init__(arrays, page_ids, offs, keep)
        assert kv_heads % self.heads == 0, (kv_heads, self.heads)
        self.kv_heads, self.pairs = kv_heads, kv_heads // self.heads

    def write(self, layer: int, *new):
        """``new``: one [n, Hkv, D] for each array."""
        super().write(layer, *(t.reshape(t.shape[0], self.heads, -1)
                               for t in new))

    def _own(self, q_heads: int, dtype):
        """For each of the row's ``pairs`` parts, [Hq, 1]: 1 where a query
        head's key head owns that part of the lanes."""
        part = jnp.arange(q_heads) // (q_heads // self.kv_heads) % self.pairs
        return [(part == j).astype(dtype)[:, None] for j in range(self.pairs)]

    def attend(self, layer: int, q, page_table, att_len, *, impl: str,
               sm_scale: float):
        """q [S, Hq, D] -> [S, Hq, D]; ``sm_scale`` is the caller's (the
        pool's rows are ``pairs`` heads wide)."""
        if self.pairs == 1:
            return super().attend(layer, q, page_table, att_len, impl=impl,
                                  sm_scale=sm_scale)
        D, own = q.shape[-1], self._own(q.shape[1], q.dtype)
        wide = jnp.concatenate([q * on for on in own], axis=-1)
        out = super().attend(layer, wide, page_table, att_len, impl=impl,
                             sm_scale=sm_scale)
        return sum(out[..., j * D:(j + 1) * D] * on
                   for j, on in enumerate(own))

    def unpaired(self, rows):
        """Rows out of the pool [Hkv / pairs, T, pairs * D] (a context
        gathered by :func:`prefix_keys`) -> the model's heads [Hkv, T,
        D]."""
        H, T, _ = rows.shape
        return rows.reshape(H, T, self.pairs, -1).transpose(
            0, 2, 1, 3).reshape(self.kv_heads, T, -1)


class Rings(_LayerRows):
    """The RING pair: the window layers' last ``W`` keys and values BY SLOT,
    ``[Lw, slots, Hkv, W, D]`` (request state), position ``p`` at ``p % W``."""

    def __init__(self, window_k, window_v, positions, active):
        slots, W = window_k.shape[1], window_k.shape[3]
        super().__init__([window_k, window_v],
                         jnp.arange(slots, dtype=jnp.int32), positions % W,
                         active)

    def attend(self, layer: int, q, att_len, sink, *, impl: str,
               sm_scale: Optional[float] = None):
        """q [S, Hq, D] over window layer ``layer``'s ring of each slot,
        the tick's keys already written; ``sink`` [Hq] or None -> [S, Hq,
        Dv]."""
        from ..ops.pallas.decode_attention import window_decode_attention
        k, v = self.flat()
        return window_decode_attention(
            q, k, v, att_len, sink, base=layer * self.per_layer,
            sm_scale=sm_scale, impl=impl)


class LatentRing(_LayerRows):
    """ONE array of latent rows kept as a ring BY SLOT, ``[Lw, slots, 1, R,
    width]`` (request state): a window layer's last ``window`` rows
    ``[c_kv ; k_rope ; 0]``, shared by every head, position ``p`` at row
    ``p % window``; the ``R - window`` rows beyond (``R``: whole granules
    of the kernel's copies) are never written nor read."""

    def __init__(self, ring, window: int, positions, active):
        slots = ring.shape[1]
        assert ring.shape[2] == 1 and window <= ring.shape[3], ring.shape
        self.window = window
        super().__init__([ring], jnp.arange(slots, dtype=jnp.int32),
                         positions % window, active)

    def attend(self, layer: int, q, att_len, value_dim: int, *, impl: str,
               sm_scale: float):
        """q [S, H, width] (absorbed: ``[q_lat ; q_rope ; 0]``) over
        window layer ``layer``'s ring of each slot, the tick's row already
        written -> [S, H, value_dim] (the latent's lanes)."""
        from ..ops.pallas.decode_attention import \
            window_latent_decode_attention
        ring, = self.flat()
        return window_latent_decode_attention(
            q, ring[:, 0], jnp.minimum(att_len, self.window), value_dim,
            base=layer * self.per_layer, sm_scale=sm_scale, impl=impl)


def ring_positions(end, W: int):
    """For each row ``r`` of a ring, the last position before ``end``
    (traced) that is ``r mod W`` [W]; negative where there is none yet."""
    last = end - 1
    return last - jnp.mod(last - jnp.arange(W, dtype=jnp.int32), W)


def write_slot_state(state, kept, slot):
    """What a prefill computed for its request (``kept``: name -> one
    array a layer; none: the leaf stays) OVERWRITES ``slot`` (traced) of
    the request state's leaves ``[layers, slots, ...]``."""
    new_state = dict(state)
    for name, new in kept.items():
        if new:
            leaf = state[name]
            new_state[name] = jax.lax.dynamic_update_slice(
                leaf, jnp.stack(new)[:, None].astype(leaf.dtype),
                (0, slot) + (0,) * (leaf.ndim - 2))
    return new_state
