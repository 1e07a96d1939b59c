"""Mamba-2's recurrence (Dao & Gu 2024, arXiv:2405.21060) for serving:
the decode update as a Mosaic kernel that rewrites the state where it
lies, and the chunked prefill form as XLA's matmuls.

Per head ``h`` (``P`` channels, state width ``N``; head ``h`` reads the
``B`` and ``C`` of group ``h // (H / G)``):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t        [P, N]
    y_t = h_t C_t                                            [P]

(``D x_t`` is the caller's: it needs no state.)

**Decode** (:func:`ssm_decode`, kernel ``ds_ssm_decode``).  The state of
every layer and slot is one float32 array ``[X, H, P, N]`` (``X = layers
* slots``), 4 MiB a layer and slot at the published widths and 4 GB in a
full engine: it is an operand aliased to the first output
(``input_output_aliases``), so a tick reads each live slot's state once
and writes it once and the program holds no second copy.  A grid step is
one slot of one layer, all its heads; which slot is a scalar-prefetched
list of the LIVE slots (``base + slot``), so a slot that is not active is
neither read nor written, and the steps past the last live one name the
last live block again: the pipeline moves nothing for a block index that
does not change.  The small operands ride beside the state in layouts
that need no relayout in the kernel: ``exp(dt A)`` broadcast over the
lanes ``[S, H, N]``, ``dt x`` with the channels on the sublanes ``[S, P,
H]`` (a head's column is a static lane slice), ``B`` and ``C`` a row a
group ``[S, G, N]``; ``y`` comes back ``[S, P, H]``.

``y = h C`` is summed ACROSS heads, never over a head's own lanes.  A
head's tile ``[P, N]`` has ``N`` on the lanes, so a sum a head is one
cross-lane reduction a register, 1,024 a slot and layer at 128 heads of
64 channels, and was the only part of the body the block's DMA did not
hide (PERF.md section 6, PR 64: 14.6 ms a tick of this kernel alone with
it, 12.2 without it, which is what a plain copy through the same blocks
takes).  Instead the heads' products ``new * C`` (each head with its own
group's ``C``, so heads of any two groups pair) are merged two and two:
with ``low`` the lanes whose bit ``w`` is clear, ``where(low, a, b) +
roll(where(low, b, a), w)`` is one tile that holds both heads' partial
sums, ``a``'s on the ``low`` lanes and ``b``'s on the others.  Level by
level, ``w`` = 1, 2, 4, ... ``N / 2`` IN THAT ORDER (a lane keeps its low
bits through every later rotation; the falling order mixes two heads'
sums), ``N`` heads end as ONE tile with head ``h``'s sum at lane ``h``:
``y``'s block as it is stored, with no reduction, no select of a column
into ``y`` and nothing to put back in order afterwards.  A node of the
tree is a head's WHOLE tile (``P / 8`` registers): a merge's add waits
for its rotation to come back from the cross-lane unit, and what hides
that wait is the ``P / 8`` independent rotations a merge has in flight
(the same 1,016 rotations a step read 34.9 ms a tick with ONE register a
node, 12.3 with eight).  The pairs are formed as the heads are walked,
as a binary counter carries, so at most one node a level is live; fewer
heads than lanes finish with rotations of the tile on itself, more are a
tree every ``N`` heads.  Only the ORDER of a sum's ``N`` float32 adds
changes (a balanced tree: nearer the exact sum than a running one); the
state is stored before the product is taken, and its bits are what they
were.  The matrix unit at ``HIGHEST`` (``new`` against 128 rows of ``C``)
reads the same time at these widths and was not taken: its work grows
with the SQUARE of the heads.

**Prefill** (:func:`ssd_chunked`).  The chunked ("state-space duality")
form at the published chunk of 128: within a chunk the output is a masked
matrix product, across chunks a recurrence over a handful of chunk
states.  Written in jax.numpy: at a bucket of 1,024 tokens it is a few
batched matmuls XLA already places on the MXU, so no kernel of ours
(PERF.md section 5 has the trace that decided it).  A position whose
``dt`` is 0 neither decays the state nor feeds it, which is how a padded
bucket ends on the state at the prompt's true length.
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
# naming"): trace rows are ``ds_ssm_decode.<n>``.
SSM_DECODE_KERNEL = "ds_ssm_decode"

#: a slot's state block of one layer is 4 MiB at the published widths
#: (128 x 64 x 128 float32); in and out, double-buffered, are 16 MiB,
#: Mosaic's default limit for a whole kernel.  A v5e core has 128 MiB.
SSM_VMEM_LIMIT = 48 * 1024 * 1024

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _ssm_decode_kernel(rows_ref, ids_ref, n_ref, h_ref, da_ref, dtx_ref, b_ref, c_ref,
                       o_ref, y_ref, *, heads: int, group: int):
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _():
        dtx = dtx_ref[0]                                    # [P, H]
        P, N = dtx.shape[0], c_ref.shape[2]
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, N), 1)

        def merge(a, b, w):
            """Two nodes of ``w`` heads each -> one of ``2 w``: the
            lanes whose bit ``w`` is clear keep ``a``'s partial sums,
            the others ``b``'s, each with the half that lay ``w`` lanes
            below added."""
            low = (lane & w) == 0
            return jnp.where(low, a, b) + pltpu.roll(jnp.where(low, b, a),
                                                     w, 1)

        for h0 in range(0, heads, N):       # as many heads as lanes a tree
            h1 = min(h0 + N, heads)
            stack = []                      # [(heads merged, node [P, N])]
            for h in range(h0, h1):
                g = h // group
                new = (h_ref[0, h] * da_ref[0, h:h + 1, :]
                       + dtx[:, h:h + 1] * b_ref[0, g:g + 1, :])     # [P, N]
                o_ref[0, h] = new
                w, node = 1, new * c_ref[0, g:g + 1, :]
                # a binary counter: one node a level is live at most
                while stack and stack[-1][0] == w:
                    node = merge(stack.pop()[1], node, w)
                    w *= 2
                stack.append((w, node))
            (w, node), = stack
            while w < N:                    # fewer heads than lanes: a
                node = node + pltpu.roll(node, w, 1)    # tile on itself
                w *= 2
            y_ref[0, :, h0:h1] = node[:, :h1 - h0]

    @pl.when((n_ref[0] == 0) & (pl.program_id(0) == 0))
    def _():
        # nothing is live: the one block every step names goes back as
        # it came
        o_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_decode(state, decay, dtx, b, c, active, *, base=0,
               interpret: Optional[bool] = None):
    """One token of every ACTIVE slot through one layer's recurrence.

    state [X, H, P, N] float32: every layer's slots in one row, this
    layer's from row ``base`` (traced).  decay [S, H] = ``exp(dt A)``,
    dtx [S, H, P] = ``dt x``, b / c [S, G, N], active [S] bool.
    Returns (state, y [S, H, P] float32); ``state`` is the operand,
    rewritten in place for the active slots and untouched for the
    others, whose ``y`` is 0.

    Refused at trace time: a state width ``N`` that is no power of two
    (a lane keeps its head through the rotations only by its low bits),
    and a head count that is neither a power of two nor whole ``N``s
    (a tree of merges wants full pairs at every level)."""
    X, H, P, N = state.shape
    S, G = b.shape[0], b.shape[1]
    if N & (N - 1) or (H & (H - 1) and H % N):
        raise ValueError(
            f"{SSM_DECODE_KERNEL}: y = h C is merged across heads by lane "
            f"rotations, which takes a state width that is a power of two "
            f"and heads that are a power of two or a multiple of the width; "
            f"got N = {N}, H = {H}")
    if interpret is None:
        interpret = use_interpret()
    i32 = jnp.int32
    # the live slots first, in order; the rest of the list repeats the
    # last live one
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(i32)
    n = jnp.sum(active).astype(i32)
    ids = order[jnp.minimum(jnp.arange(S, dtype=i32), jnp.maximum(n - 1, 0))]
    rows = ids + jnp.asarray(base, i32)
    decay_l = jnp.broadcast_to(decay.astype(F32)[..., None], (S, H, N))
    dtx_t = dtx.astype(F32).transpose(0, 2, 1)              # [S, P, H]

    def small(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda s, rows, ids, n: (ids[s], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, H, P, N),
                               lambda s, rows, ids, n: (rows[s], 0, 0, 0)),
                  small((H, N)), small((P, H)), small((G, N)),
                  small((G, N))],
        out_specs=[pl.BlockSpec((1, H, P, N),
                                lambda s, rows, ids, n: (rows[s], 0, 0, 0)),
                   small((P, H))],
    )
    new_state, y = pl.pallas_call(
        functools.partial(_ssm_decode_kernel, heads=H, group=H // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((S, P, H), F32)],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SSM_VMEM_LIMIT),
        interpret=interpret, name=SSM_DECODE_KERNEL,
    )(rows, ids, jnp.reshape(n, (1,)), state, decay_l, dtx_t,
      b.astype(F32), c.astype(F32))
    y = jnp.where(active[:, None, None], y.transpose(0, 2, 1), 0.0)
    return new_state, y


def ssm_decode_reference(state, decay, dtx, b, c, active):
    """:func:`ssm_decode` on one layer's own slots ``[S, H, P, N]`` in
    plain jax.numpy: the oracle of the kernel's tests."""
    S, H, P, N = state.shape
    G = b.shape[1]
    bh = jnp.repeat(b.astype(F32), H // G, axis=1)          # [S, H, N]
    ch = jnp.repeat(c.astype(F32), H // G, axis=1)
    new = state * decay.astype(F32)[..., None, None] \
        + dtx.astype(F32)[..., None] * bh[:, :, None, :]
    y = jnp.sum(new * ch[:, :, None, :], axis=-1)
    keep = active[:, None, None]
    return (jnp.where(keep[..., None], new, state),
            jnp.where(keep, y, 0.0))


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The recurrence over a whole sequence from a zero state, chunked.

    x [T, H, P], dt [T, H] float32 (0 at a position that must leave the
    state as it is), a [H] (negative), b / c [T, G, N]; ``T`` a multiple
    of ``chunk``.  Returns (y [T, H, P] float32, final state [H, P, N]
    float32).  float32 throughout, the products at full precision: the
    final state is what thousands of decode ticks then build on."""
    T, H, P = x.shape
    G, N = b.shape[1], b.shape[2]
    if T % chunk:
        raise ValueError(f"sequence {T} is not a multiple of chunk {chunk}")
    nc, rep = T // chunk, H // G
    # heads as (group, head of the group): B and C are a group's, and
    # every product below contracts them per group, never repeated per head
    xf = (x.astype(F32) * dt[..., None]).reshape(nc, chunk, G, rep, P)
    bf = b.astype(F32).reshape(nc, chunk, G, N)
    cf = c.astype(F32).reshape(nc, chunk, G, N)
    la = (dt * a[None, :]).reshape(nc, chunk, H)            # log decay
    cum = jnp.cumsum(la, axis=1)                            # [nc, l, H]
    cum_h = cum.transpose(0, 2, 1).reshape(nc, G, rep, chunk)

    # within a chunk: y[l] = sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) dt_s x_s
    cb = jnp.einsum("clgn,csgn->cgls", cf, bf, precision=HIGHEST)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = cum_h[..., :, None] - cum_h[..., None, :]         # [nc, G, r, l, s]
    m = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0) \
        * cb[:, :, None]
    y = jnp.einsum("cgrls,csgrp->clgrp", m, xf, precision=HIGHEST)

    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:, :] - cum).reshape(nc, chunk, G, rep)
    own = jnp.einsum("csgrp,csgn->cgrpn", xf * to_end[..., None], bf,
                     precision=HIGHEST).reshape(nc, H, P, N)

    # across chunks: the state each chunk starts from
    def step(state, xs):
        own_c, total = xs
        return state * jnp.exp(total)[:, None, None] + own_c, state

    final, starts = jax.lax.scan(step, jnp.zeros((H, P, N), F32),
                                 (own, cum[:, -1, :]))
    carried = jnp.einsum("clgn,cgrpn->clgrp", cf,
                         starts.reshape(nc, G, rep, P, N), precision=HIGHEST)
    y = y + carried * jnp.exp(cum).reshape(nc, chunk, G, rep)[..., None]
    return y.reshape(T, H, P), final
