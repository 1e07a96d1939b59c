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
unit lower-triangular ``I - N`` is ``(I + N)(I + N^2)...(I + N^32)``
(``N^64 = 0``; :func:`_product_inverse`, which says what it cannot take):
matmuls the MXU takes, where a triangular solve would serialise.  A position with ``b = 0`` and ``g = 0`` leaves the state
as it is, which is how a padded rung ends on the state at the prompt's true
length.  float32 throughout, the products at full precision: the final
state is what thousands of decode ticks then build on.

**One decay a head** (Gated DeltaNet, arXiv:2412.06464; Olmo Hybrid): ``g_t``
a scalar a head, ``b_t`` in (0, 2), ``dk != dv``.  The same mathematics with
``a`` constant over a head's channels, so the two forms below stand beside
KDA's and share what does not depend on it.

*Decode* (:func:`gdn_decode`, kernel ``ds_gdn_decode``).  The state rests
``[X, dk, H dv]``: the key channels on the sublanes and EVERY head's values
side by side on the lanes (:func:`gdn_rest`), so that widths that are no
lane tile (a head of 96 x 192: ``[.., 96, 192]`` would rest 256 lanes wide,
a third more bytes in the kernel the HBM bounds) pad nothing: 30 x 192 =
5,760 lanes are 45 tiles.  The price is heads that start in the middle of a
tile; the kernel therefore never slices a head: it walks the state a LANE
TILE at a time, ``[dk, 128]``, and what multiplies a row (``k``, ``q``: one
column a head in ``[S, dk, 2 H]``) is spread over the tile's lanes with a
select at the lane where the next head starts.  What multiplies a lane
(``a``, ``a b`` and ``b v``, a head's scalar repeated over its values)
rides as ``[S, 3, tiles, 128]``; ``S'^T k = a (S^T k)`` with the scalar
decay, so ``u = b v - a b (S^T k)`` and ``S = a S + k u^T``: the ``a``
columns of KDA's operand do not exist.  Grid, aliasing and the list of live
slots are ``ds_kda_decode``'s.

*Prefill* (:func:`gdn_chunked`).  ``exp(G_i - G_j)`` does not depend on the
channel, so ``M = (K K^T) * D`` and ``P = (Q K^T) * D`` with ONE ``[C, C]``
matrix ``D_ij = exp(G_i - G_j)`` (``i >= j``, never a positive exponent) a
head and chunk: two matmuls and a mask where KDA walks sub-blocks.  The
inverse, ``W``, ``U``, the scan over chunk states and the output are the
same code (:func:`_wy`) but for ONE step: the inverse.  ``b`` up to 2
doubles ``N``, and KDA's product of powers then loses float32's digits on
keys that merely resemble each other (the first chip runs' one wrong probe,
PERF.md section 6, PR 59); this form takes it by halves
(:func:`_halves_inverse`: as many matmuls, every factor of order 1).
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
GDN_DECODE_KERNEL = "ds_gdn_decode"

#: a slot's state block of one layer is 2 MiB at the published widths
#: (32 x 128 x 128 float32); in and out, double-buffered, are 8 MiB, and
#: the body's temporaries come on top of Mosaic's default 16 MiB
KDA_VMEM_LIMIT = 32 * 1024 * 1024

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
#: positions a chunk of :func:`gdn_chunked` holds (a prefill's rows are
#: padded to whole chunks of it)
GDN_CHUNK = 64


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


def _live_slots(active, base):
    """The grid's list for ``active`` [S]: the live slots first, in order;
    the rest of the list repeats the last live one.  -> (rows [S]: their
    rows of the state, ``base + slot``; ids [S]; n the live count)."""
    i32 = jnp.int32
    S = active.shape[0]
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(i32)
    n = jnp.sum(active).astype(i32)
    ids = order[jnp.minimum(jnp.arange(S, dtype=i32), jnp.maximum(n - 1, 0))]
    return ids + jnp.asarray(base, i32), ids, n


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
    rows, ids, n = _live_slots(active, base)
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


def gdn_rest(state):
    """A scalar-decay state by head ``[..., H, dk, dv]`` -> as it rests
    ``[..., dk, H dv]`` (module docstring)."""
    *lead, H, dk, dv = state.shape
    return jnp.swapaxes(state, -3, -2).reshape(*lead, dk, H * dv)


def gdn_heads(rest, heads: int):
    """:func:`gdn_rest`'s inverse: ``[..., dk, H dv]`` -> ``[..., H, dk,
    dv]``."""
    *lead, dk, lanes = rest.shape
    return jnp.swapaxes(rest.reshape(*lead, dk, heads, lanes // heads),
                        -3, -2)


def _gdn_decode_kernel(rows_ref, ids_ref, n_ref, s_ref, cols_ref, lanes_ref,
                       new_ref, o_ref, *, heads: int, dv: int):
    live = pl.program_id(0) < n_ref[0]
    dk = cols_ref.shape[1]
    tiles, tile = lanes_ref.shape[2:]

    @pl.when(live)
    def _():
        cols = cols_ref[0]                                  # [dk, 2 H]
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, tile), 1)
        spread = {}

        def col(part, h):
            """Head ``h``'s column of ``k`` (0) or ``q`` (1) over a tile's
            lanes [dk, tile], made once for the tiles the head covers."""
            if (part, h) not in spread:
                at = part * heads + h
                spread[part, h] = jnp.broadcast_to(cols[:, at:at + 1],
                                                   (dk, tile))
            return spread[part, h]

        for j in range(tiles):
            t0 = j * tile
            first, last = t0 // dv, (t0 + tile - 1) // dv

            def of(part):
                out = col(part, first)
                for h in range(first + 1, last + 1):
                    out = jnp.where(lane >= h * dv - t0, col(part, h), out)
                return out

            kk, qq = of(0), of(1)
            a, ab, bv = (lanes_ref[0, i, j:j + 1, :] for i in range(3))
            s = s_ref[0, :, t0:t0 + tile]                   # [dk, tile]
            u = bv - ab * jnp.sum(s * kk, axis=0, keepdims=True)
            s = a * s + kk * u
            new_ref[0, :, t0:t0 + tile] = s
            o_ref[0, j:j + 1, :] = jnp.sum(s * qq, axis=0, keepdims=True)

    @pl.when((n_ref[0] == 0) & (pl.program_id(0) == 0))
    def _():
        new_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def gdn_decode(state, a, k, v, q, b, active, *, base=0,
               interpret: Optional[bool] = None):
    """One token of every ACTIVE slot through one layer's update, one
    decay a head.

    state [X, dk, H dv] float32 (:func:`gdn_rest`): every layer's slots in
    one row, this layer's from row ``base`` (traced).  a [S, H] =
    ``exp(g)``, k and q [S, H, dk] (normalised, q scaled), v [S, H, dv], b
    [S, H] (any step: 2 sigmoid included), active [S] bool.  Returns
    (state, o [S, H, dv] float32) as :func:`kda_decode` does."""
    _, dk, lanes = state.shape
    S, H = a.shape
    dv = lanes // H
    if interpret is None:
        interpret = use_interpret()
    rows, ids, n = _live_slots(active, base)
    a, k, v, q, b = (t.astype(F32) for t in (a, k, v, q, b))
    tile = _LANES if lanes % _LANES == 0 else lanes
    tiles = lanes // tile
    cols = jnp.concatenate([k, q], axis=1).transpose(0, 2, 1)   # [S, dk, 2H]
    by_lane = jnp.stack([jnp.repeat(a, dv, axis=1),
                         jnp.repeat(a * b, dv, axis=1),
                         (b[..., None] * v).reshape(S, lanes)],
                        axis=1).reshape(S, 3, tiles, tile)

    def small(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda s, rows, ids, n: (ids[s],) + (0,) * len(
                                shape))

    def block():
        return pl.BlockSpec((1, dk, lanes),
                            lambda s, rows, ids, n: (rows[s], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[block(), small(dk, 2 * H), small(3, tiles, tile)],
        out_specs=[block(), small(tiles, tile)],
    )
    new_state, o = pl.pallas_call(
        functools.partial(_gdn_decode_kernel, heads=H, dv=dv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((S, tiles, tile), F32)],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=KDA_VMEM_LIMIT),
        interpret=interpret, name=GDN_DECODE_KERNEL,
    )(rows, ids, jnp.reshape(n, (1,)), state, cols, by_lane)
    return new_state, jnp.where(active[:, None, None],
                                o.reshape(S, H, dv), 0.0)


def gdn_decode_reference(state, a, k, v, q, b, active):
    """:func:`gdn_decode` on one layer's own slots BY HEAD ``[S, H, dk,
    dv]`` in plain jax.numpy (:func:`kda_decode_reference` with the decay
    spread over a head's channels): the oracle of the kernel's tests."""
    return kda_decode_reference(
        state, jnp.broadcast_to(a[..., None], k.shape), k, v, q, b, active)


def _masked_exp(x, mask):
    """``exp(x)`` where ``mask``, 0 elsewhere, with no ``exp`` of what the
    mask leaves out."""
    return jnp.where(mask, jnp.exp(jnp.where(mask, x, 0.0)), 0.0)


def _mm(eq, x, y):
    return jnp.einsum(eq, x, y, precision=HIGHEST)


def _chunker(T: int, H: int, chunk: int):
    """-> (chunks in ``T`` padded, ``[T, H, w] -> [nc, H, chunk, w]``
    float32 padded with zeros)."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk}: a power of two")
    pad = -T % chunk
    nc = (T + pad) // chunk

    def chunks(t):
        t = jnp.pad(t.astype(F32), ((0, pad), (0, 0), (0, 0)))
        return t.reshape(nc, chunk, H, -1).transpose(0, 2, 1, 3)

    return nc, chunks


def _product_inverse(N):
    """``(I - N)^-1 = (I + N)(I + N^2)(I + N^4)...`` for ``N`` [nc, H, C,
    C] strictly lower (``N^C = 0``): exact on paper, and KDA's since PR 52.
    In float32 its terms ``N^k`` grow like ``C(64, k) |N|^k`` and cancel:
    fine for keys that do not resemble each other, as a benchmark's random
    tokens give them; a run of one token inside a chunk (``M_ij`` near 1)
    gives ``inf`` (:func:`_halves_inverse` does not; what it costs the Kimi
    Linear cell is unread: PERF.md section 7)."""
    chunk = N.shape[-1]
    inv, power = jnp.eye(chunk, dtype=F32) + N, N
    for _ in range(chunk.bit_length() - 2):
        power = _mm("nhij,nhjk->nhik", power, power)
        inv = inv + _mm("nhij,nhjk->nhik", inv, power)
    return inv


def _halves_inverse(N):
    """``(I - N)^-1`` for ``N`` [nc, H, C, C] strictly lower, ``C`` a power
    of two, by halves: the inverse of ``[[A, 0], [-L, B]]`` is ``[[A^-1,
    0], [B^-1 L A^-1, B^-1]]``, so with ``X`` the inverse of the diagonal
    blocks of ``s`` (zeros elsewhere) and ``L`` the lower-left quarters of
    the diagonal blocks of ``2 s``, the next is ``X + X L X``: from ``X =
    I`` up, two whole ``[C, C]`` matmuls a level (as many as the product
    form's), every factor a block of the inverse itself, which stays of
    order 1 where the system's solution does.  At steps up to 2 the
    product form lost every digit on keys whose cosine is 0.5 (read 3e7
    times the recurrence's largest output) and gave ``inf`` on a run of
    one token; this reads the recurrence's to 1e-6 on both."""
    C = N.shape[-1]
    at = jnp.arange(C)
    inv, s = None, 1
    while s < C:
        row, col = at[:, None], at[None, :]
        quarter = (row // (2 * s) == col // (2 * s)) \
            & (row % (2 * s) >= s) & (col % (2 * s) < s)
        low = jnp.where(quarter, N, 0.0)
        inv = jnp.eye(C, dtype=F32) + low if inv is None else inv + _mm(
            "nhij,nhjk->nhik", _mm("nhij,nhjk->nhik", inv, low), inv)
        s *= 2
    return inv


def _wy(q, k, v, b, G, M, P, state0, T: int, inverse):
    """What the two chunked forms share, from ``M`` and ``P`` on (module
    docstring).  q, k [nc, H, C, dk], v [nc, H, C, dv], b [nc, H, C]; ``G``
    the cumulative log-decay [nc, H, C, dk] or, one decay a head, [nc, H,
    C, 1]; ``M``, ``P`` [nc, H, C, C], zero above the diagonal;
    ``inverse``: the form's way to ``(I - N)^-1``.  Returns (o [T, H, dv],
    final state [H, dk, dv])."""
    nc, H, chunk, _ = k.shape
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    N = -b[..., None] * jnp.where(strict, M, 0.0)
    A = inverse(N) * b[:, :, None, :]
    W = _mm("nhij,nhjd->nhid", A, k * jnp.exp(G))
    U = _mm("nhij,nhjv->nhiv", A, v)
    to_end = jnp.exp(G[:, :, -1:] - G)

    # across chunks: the state each chunk starts from, and its v'
    def step(state, xs):
        w, u, k_end, total = xs
        vp = u - _mm("hid,hdv->hiv", w, state)
        return (state * total[..., None] + _mm("hid,hiv->hdv", k_end, vp),
                (state, vp))

    final, (starts, vps) = jax.lax.scan(
        step, state0.astype(F32), (W, U, k * to_end, jnp.exp(G[:, :, -1])))
    o = _mm("nhid,nhdv->nhiv", q * jnp.exp(G), starts) \
        + _mm("nhij,nhjv->nhiv", P, vps)
    return o.transpose(0, 2, 1, 3).reshape(nc * chunk, H, -1)[:T], final


def kda_chunked(q, k, v, g, b, state0, chunk: int = 64, sub: int = 16):
    """The update over a whole sequence from ``state0``, chunked.

    q, k [T, H, dk] (normalised, q scaled), v [T, H, dv], g [T, H, dk]
    the log-decay (<= 0), b [T, H]; a position that must leave the state
    as it is has ``g = 0`` and ``b = 0``.  state0 [H, dk, dv].  Returns
    (o [T, H, dv] float32, final state [H, dk, dv] float32).  ``T`` is
    padded to whole chunks of ``chunk`` (a power of two, whole sub-blocks
    of ``sub``) with such positions."""
    T, H, dk = k.shape
    if chunk % sub:
        raise ValueError(f"chunk {chunk}: whole sub-blocks of {sub}")
    nc, chunks = _chunker(T, H, chunk)
    nb = chunk // sub

    def blocks(t):
        return t.reshape(nc, H, nb, sub, -1)

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

    M = whole(_mm("nhbid,nhbjd->nhbij", kd, kj), m_own)
    P = whole(_mm("nhbid,nhbjd->nhbij", qd, kj), p_own)
    return _wy(q, k, v, b, G, M, P, state0, T, _product_inverse)


@functools.partial(jax.jit, static_argnames=("chunk",))
def gdn_chunked(q, k, v, g, b, state0, chunk: int = GDN_CHUNK):
    """:func:`kda_chunked` with ONE log-decay a head: g [T, H] (<= 0), b
    [T, H] (any step, 2 sigmoid included); q, k [T, H, dk], v [T, H, dv],
    state0 [H, dk, dv] BY HEAD (:func:`gdn_heads` of a state at rest).
    Returns (o [T, H, dv] float32, final state [H, dk, dv] float32).  A
    ``jit`` of its own: a model that unrolls its layers traces it once a
    shape, not once a layer and a program (PERF.md section 7, "Since PR
    58")."""
    T, H, _ = k.shape
    nc, chunks = _chunker(T, H, chunk)
    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g[..., None])
    b = chunks(b[..., None])[..., 0]                        # [nc, H, C]
    G = jnp.cumsum(g, axis=2)                               # [nc, H, C, 1]
    # D_ij = exp(G_i - G_j), row >= column: the one matrix a head
    D = _masked_exp(G - jnp.swapaxes(G, 2, 3),
                    jnp.tril(jnp.ones((chunk, chunk), bool)))
    M = _mm("nhid,nhjd->nhij", k, k) * D
    P = _mm("nhid,nhjd->nhij", q, k) * D
    return _wy(q, k, v, b, G, M, P, state0, T, _halves_inverse)
