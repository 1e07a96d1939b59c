"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): the gated
delta rule with a decay a key CHANNEL, for serving: the decode update as a
Mosaic kernel that rewrites the state where it lies, and the chunked (WY)
form for a prefill as XLA's matmuls, from a given state.

Per head (keys ``dk`` wide, values ``dv``; state ``S`` ``[dk, dv]``,
float32; ``a_t = exp(g_t)`` in (0, 1]^dk, ``b_t`` in (0, 1)):

    S' = a_t (rowwise) S_{t-1}
    u  = b_t (v_t - S'^T k_t)          (the delta rule: what the state
    S_t = S' + k_t u^T                  already holds for the key goes)
    o_t = S_t^T q_t

**Decode** (:func:`kda_decode`, kernel ``ds_kda_decode``).  The state of
every layer and slot is one float32 array ``[X, H, dk, dv]`` (``X = layers
* slots``; 2 MiB a layer and slot at 32 heads of 128 x 128), an operand
aliased to the first output, as ``ssm.py::ssm_decode`` holds Mamba-2's: a
grid step is one LIVE slot of one layer (a scalar-prefetched list, ``base +
slot``), so a slot that is not active is neither read nor written.  The
state's rows are the key channels, so everything that multiplies a row
rides in ONE operand with the channels on the sublanes, ``[S, dk, 4 H]``:
``a``, ``k``, ``b k`` and ``q``, a head's column of each a static lane
slice (128 lanes at 32 heads); ``b v`` rides as rows ``[S, H, dv]``, and
``o`` comes back the same way.  Inside, a head is four passes over its
``[dk, dv]`` tile on the VPU (decay, ``S'^T (b k)`` as a sum over the
sublanes, the rank-one update, ``S^T q``): 4 MiB moved for ~4 M
multiply-adds, so the HBM bounds it.

**Prefill** (:func:`kda_chunked`).  Within a chunk of 64 the WY
representation: with ``G`` the cumulative log-decay a channel,

    M_ij = sum_c k_ic k_jc exp(G_ic - G_jc)  (j < i)
    A = (I + Diag(b) strict_tril(M))^-1 Diag(b)
    W = A (k e^G),  U = A v,  v' = U - W S_0
    o = (q e^G) S_0 + tril(P) v',  P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)
    S_end = e^{G_end} S_0 + (k e^{G_end - G})^T v'

and across chunks a scan over the chunk states that starts at ``state0``.
No ``exp`` ever takes a positive argument (a strong decay over a chunk
would overflow ``e^-G``): ``M`` and ``P`` are built by sub-blocks of 16
rows, a block against the EARLIER blocks as a matmul with both sides taken
relative to the block's first row (``exp(G_i - G_ref) <= 1``, ``exp(G_ref -
G_j) <= 1``), a block against itself with the exponent of each (row,
column, channel) formed as a difference before ``exp``.  The inverse of the
unit lower-triangular ``I - N`` is exact as ``(I + N)(I + N^2)...(I +
N^32)`` (``N^64 = 0``): matmuls the MXU takes, where a triangular solve
would serialise.  A position with ``b = 0`` and ``g = 0`` leaves the state
as it is, which is how a padded rung ends on the state at the prompt's true
length.  float32 throughout, the products at full precision: the final
state is what thousands of decode ticks then build on.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import use_interpret

# Stable name of the Mosaic custom call (docs/observability.md "Kernel
# naming"): trace rows are ``ds_kda_decode.<n>``.
KDA_DECODE_KERNEL = "ds_kda_decode"

#: a slot's state block of one layer is 2 MiB at the published widths
#: (32 x 128 x 128 float32); in and out, double-buffered, are 8 MiB, and
#: the body's temporaries come on top of Mosaic's default 16 MiB
KDA_VMEM_LIMIT = 32 * 1024 * 1024

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _kda_decode_kernel(rows_ref, ids_ref, n_ref, s_ref, cols_ref, bv_ref,
                       new_ref, o_ref, *, heads: int):
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _():
        cols = cols_ref[0]                                  # [dk, 4 H]

        def col(part, h):
            at = part * heads + h
            return cols[:, at:at + 1]                       # [dk, 1]

        for h in range(heads):
            s = s_ref[0, h] * col(0, h)                     # [dk, dv]
            u = bv_ref[0, h:h + 1, :] - jnp.sum(
                s * col(2, h), axis=0, keepdims=True)       # [1, dv]
            s = s + col(1, h) * u
            new_ref[0, h] = s
            o_ref[0, h:h + 1, :] = jnp.sum(s * col(3, h), axis=0,
                                           keepdims=True)

    @pl.when((n_ref[0] == 0) & (pl.program_id(0) == 0))
    def _():
        # nothing is live: the one block every step names goes back as
        # it came
        new_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_decode(state, a, k, v, q, b, active, *, base=0,
               interpret: Optional[bool] = None):
    """One token of every ACTIVE slot through one layer's update.

    state [X, H, dk, dv] float32: every layer's slots in one row, this
    layer's from row ``base`` (traced).  a [S, H, dk] = ``exp(g)``, k and q
    [S, H, dk] (normalised, q scaled), v [S, H, dv], b [S, H], active [S]
    bool.  Returns (state, o [S, H, dv] float32); ``state`` is the operand,
    rewritten in place for the active slots and untouched for the others,
    whose ``o`` is 0."""
    X, H, dk, dv = state.shape
    S = a.shape[0]
    if interpret is None:
        interpret = use_interpret()
    i32 = jnp.int32
    # the live slots first, in order; the rest of the list repeats the
    # last live one
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(i32)
    n = jnp.sum(active).astype(i32)
    ids = order[jnp.minimum(jnp.arange(S, dtype=i32), jnp.maximum(n - 1, 0))]
    rows = ids + jnp.asarray(base, i32)
    a, k, v, q, b = (t.astype(F32) for t in (a, k, v, q, b))
    cols = jnp.concatenate([a, k, b[..., None] * k, q],
                           axis=1).transpose(0, 2, 1)       # [S, dk, 4 H]

    def small(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda s, rows, ids, n: (ids[s], 0, 0))

    def block():
        return pl.BlockSpec((1, H, dk, dv),
                            lambda s, rows, ids, n: (rows[s], 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[block(), small((dk, 4 * H)), small((H, dv))],
        out_specs=[block(), small((H, dv))],
    )
    new_state, o = pl.pallas_call(
        functools.partial(_kda_decode_kernel, heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((S, H, dv), F32)],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=KDA_VMEM_LIMIT),
        interpret=interpret, name=KDA_DECODE_KERNEL,
    )(rows, ids, jnp.reshape(n, (1,)), state, cols, b[..., None] * v)
    return new_state, jnp.where(active[:, None, None], o, 0.0)


def kda_decode_reference(state, a, k, v, q, b, active):
    """:func:`kda_decode` on one layer's own slots ``[S, H, dk, dv]`` in
    plain jax.numpy: the oracle of the kernel's tests."""
    a, k, v, q, b = (t.astype(F32) for t in (a, k, v, q, b))
    s = state * a[..., None]
    u = b[..., None] * (v - jnp.einsum("shkv,shk->shv", s, k,
                                       precision=HIGHEST))
    s = s + k[..., None] * u[..., None, :]
    o = jnp.einsum("shkv,shk->shv", s, q, precision=HIGHEST)
    keep = active[:, None, None]
    return jnp.where(keep[..., None], s, state), jnp.where(keep, o, 0.0)


def _masked_exp(x, mask):
    """``exp(x)`` where ``mask``, 0 elsewhere, with no ``exp`` of what the
    mask leaves out."""
    return jnp.where(mask, jnp.exp(jnp.where(mask, x, 0.0)), 0.0)


def kda_chunked(q, k, v, g, b, state0, chunk: int = 64, sub: int = 16):
    """The update over a whole sequence from ``state0``, chunked.

    q, k [T, H, dk] (normalised, q scaled), v [T, H, dv], g [T, H, dk]
    the log-decay (<= 0), b [T, H]; a position that must leave the state
    as it is has ``g = 0`` and ``b = 0``.  state0 [H, dk, dv].  Returns
    (o [T, H, dv] float32, final state [H, dk, dv] float32).  ``T`` is
    padded to whole chunks of ``chunk`` (a power of two, whole sub-blocks
    of ``sub``) with such positions."""
    T, H, dk = k.shape
    dv = v.shape[-1]
    if chunk % sub or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk}: a power of two in whole "
                         f"sub-blocks of {sub}")
    pad = -T % chunk
    nc, nb = (T + pad) // chunk, chunk // sub

    def chunks(t):
        """[T, H, w] -> [nc, H, chunk, w] float32, padded with zeros."""
        t = jnp.pad(t.astype(F32), ((0, pad), (0, 0), (0, 0)))
        return t.reshape(nc, chunk, H, -1).transpose(0, 2, 1, 3)

    def blocks(t):
        return t.reshape(nc, H, nb, sub, -1)

    def mm(eq, x, y):
        return jnp.einsum(eq, x, y, precision=HIGHEST)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    b = chunks(b[..., None])[..., 0]                        # [nc, H, C]
    G = jnp.cumsum(g, axis=2)                               # [nc, H, C, dk]
    Gb = blocks(G)
    ref = Gb[:, :, :, :1]                                   # a block's first
    own = jnp.exp(Gb - ref)                                 # <= 1
    kd, qd = blocks(k) * own, blocks(q) * own
    # a block's rows against the rows of the EARLIER blocks, both relative
    # to the block's first row: [nc, H, block, chunk, dk]
    earlier = (jnp.arange(chunk)[None, :] // sub
               < jnp.arange(nb)[:, None])[..., None]
    kj = k[:, :, None] * _masked_exp(ref - G[:, :, None], earlier)
    # a block against itself: the exponent as a difference, row >= column,
    # a chunk at a time (all at once [T, sub, H, dk] would be held)
    low = jnp.tril(jnp.ones((sub, sub), bool))[..., None]

    def own_blocks(xs):
        g_c, k_c, q_c = xs                                  # [H, nb, sub, dk]
        kk = k_c[:, :, None] * _masked_exp(
            g_c[:, :, :, None] - g_c[:, :, None], low)      # [.., i, j, dk]
        return (jnp.sum(k_c[:, :, :, None] * kk, axis=-1),
                jnp.sum(q_c[:, :, :, None] * kk, axis=-1))

    m_own, p_own = jax.lax.map(own_blocks, (Gb, blocks(k), blocks(q)))
    eye = jnp.eye(nb, dtype=F32)

    def whole(off, diag):
        """[.., block, sub, chunk] + the blocks' own [.., block, sub, sub]
        on the diagonal -> [nc, H, chunk, chunk]."""
        full = off.reshape(nc, H, nb, sub, nb, sub) \
            + diag[:, :, :, :, None, :] * eye[:, None, :, None]
        return full.reshape(nc, H, chunk, chunk)

    M = whole(mm("nhbid,nhbjd->nhbij", kd, kj), m_own)
    P = whole(mm("nhbid,nhbjd->nhbij", qd, kj), p_own)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    # (I - N)^-1 = (I + N)(I + N^2)(I + N^4)..., N strictly lower
    N = -b[..., None] * jnp.where(strict, M, 0.0)
    inv, power = jnp.eye(chunk, dtype=F32) + N, N
    for _ in range(chunk.bit_length() - 2):
        power = mm("nhij,nhjk->nhik", power, power)
        inv = inv + mm("nhij,nhjk->nhik", inv, power)
    A = inv * b[:, :, None, :]
    W = mm("nhij,nhjd->nhid", A, k * jnp.exp(G))
    U = mm("nhij,nhjv->nhiv", A, v)
    to_end = jnp.exp(G[:, :, -1:] - G)

    # across chunks: the state each chunk starts from, and its v'
    def step(state, xs):
        w, u, k_end, total = xs
        vp = u - mm("hid,hdv->hiv", w, state)
        return (state * total[..., None] + mm("hid,hiv->hdv", k_end, vp),
                (state, vp))

    final, (starts, vps) = jax.lax.scan(
        step, state0.astype(F32), (W, U, k * to_end, jnp.exp(G[:, :, -1])))
    o = mm("nhid,nhdv->nhiv", q * jnp.exp(G), starts) \
        + mm("nhij,nhjv->nhiv", P, vps)
    return o.transpose(0, 2, 1, 3).reshape(nc * chunk, H, dv)[:T], final
