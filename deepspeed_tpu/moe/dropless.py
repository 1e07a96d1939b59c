"""Dropless top-k expert layer: no capacity, no dropped token, no padding
to a capacity (OLMoE, arXiv:2409.02060; MegaBlocks' formulation).

The third dispatch beside ``moe/layer.py``'s capacity paths (``einsum`` |
``scatter``), which stay as they are for ``models/gpt2_moe.py``.  One
function, :func:`dropless_moe`, serves a prefill (a thousand tokens, ~128
rows an expert: compute-bound) and a decode tick (64 tokens, ~8 rows an
expert: bound by reading the hit experts' weights):

1. route: ``softmax(x @ router_w)`` over ALL experts in float32, top-k,
   weights not renormalised unless asked;
2. sort the ``N * k`` assignments by expert and lay each expert's rows out
   from a row-tile boundary (``tm`` rows, 16 at decode, up to 128 at
   prefill), so that a tile belongs to one expert.  At most
   ``N*k // tm + E`` tiles whatever the routing: a static shape.
   :func:`routing_plan` builds the layout with whole-array operations
   only: an expert's count is a sum of compares over ``[E, N*k]``, a
   tile's expert one over ``[tiles, E]``; the stable sort by expert gives
   the assignments in row order, :func:`_spread` moves them apart by the
   padding ahead of their expert (a dozen selects against a shifted
   copy), and sorting the order back carries each row to its assignment.
   No index table is gathered and nothing is scattered, because the chip
   reads the indices of a gather or a scatter one at a time (7-13 ns
   each) and makes a ``while`` of ``searchsorted`` and of a gather of
   windows: as index arithmetic a row the plan was four fifths of what a
   call spent outside its kernels (``PERF.md`` section 6, PRs 57, 58);
3. two grouped matmuls over the tiles, each a Pallas kernel whose weight
   block is the tile's expert (scalar prefetch): ``ds_moe_gate_up``
   (``silu(x @ gate) * (x @ up)``) and ``ds_moe_down``.  An expert's
   matrices are one contiguous block, fetched once while its tiles follow
   each other and not at all for an expert no token chose; tiles past the
   last live one run nothing and fetch nothing new.  Where whole matrices
   in flight would not fit ``MOE_WEIGHT_VMEM_BUDGET`` (both up-projections
   at d 7,168, f 2,048) a kernel walks them in blocks of its OUTPUT width
   (:func:`weight_blocks`): every tile for the first block of columns,
   then every tile for the next, so a block is still fetched once an
   expert and the rows once a block;
4. combine: each token's k rows gathered back by the plan's row of each
   assignment, weighted, summed in float32.  This gather and the one of
   ``x`` into rows are the two the layer keeps: an index fetches a row
   of ``d`` values, which runs at the memory's bandwidth.

The stacked weights of ALL layers reach the kernels whole
(``[L*E, d, f]``) with the layer's offset added to the tile's expert, so a
layer scan slices (copies) no expert matrix.

Three things a caller may hand in instead of OLMoE's defaults (NVIDIA
Nemotron-3 Super's latent experts, ``models/nemotron_h.py``):

* ``routing=(weights, experts)``: the (token, expert) assignments of a
  router of the caller's own (:func:`route_sigmoid_topk`: sigmoid scores,
  a selection bias, renormalised and scaled); :func:`route_topk` stays
  OLMoE's;
* ``experts_held=(first, count)``: this chip's share of the layer's
  experts.  The router still ranges over ALL experts and picks ``top_k``
  of them; assignments to an expert outside ``[first, first + count)``
  are left out of the rows, of the statistics and of the sum, and are
  counted (``HeldMoEStats.rows_elsewhere``).  What comes back is this
  share's PART of the layer's result; adding the parts of all shares
  gives the whole layer (``tests/test_nemotron_h.py`` holds that).  No
  code stands in for the chips that hold the others or for the exchange
  with them.  The weights handed in are the held experts' only.  The
  static tile count ``N*k // tm + count`` is sound whatever share of the
  assignments lands here (every one of them may), the row tile is sized
  for the share that does on average, and the dead tiles are skipped;
* ``act="relu2"``: a two-matrix expert ``relu(x @ up)**2 @ down`` (no
  gate), kernel ``ds_moe_up_relu2`` beside ``ds_moe_down``.  The
  benchmark's ``moe_*`` metric files say "three matrices" and "two
  Mosaic kernels (ds_moe_gate_up, ds_moe_down)": their readers match
  ``^ds_moe_``, so for a relu2 layer read "two matrices" and
  ``ds_moe_up_relu2``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.pallas.runtime import use_interpret

# Stable names of the Mosaic custom calls (docs/observability.md "Kernel
# naming"): trace rows are ``ds_moe_gate_up.<n>`` / ``ds_moe_down.<n>``.
MOE_GATE_UP_KERNEL = "ds_moe_gate_up"
MOE_DOWN_KERNEL = "ds_moe_down"
MOE_UP_RELU2_KERNEL = "ds_moe_up_relu2"

#: both of an expert's up-projections in flight, double-buffered, are
#: 16 MiB at OLMoE's widths (2 x 2 x 2048 x 1024 bf16): over Mosaic's
#: default of 16 MiB a kernel, well inside a v5e core's 128 MiB
MOE_VMEM_LIMIT = 48 * 1024 * 1024
MAX_ROW_TILE = 128


#: what an expert's weight blocks in flight (double-buffered) may take of
#: a v5e core's 128 MiB.  Both up-projections at d 4096, f 2048 are 64 MiB
#: and stay whole; at d 7168, f 2048 they would be 112 MiB and are walked
#: in two blocks of 1024 columns (56 MiB); the down-projection there is
#: 56 MiB and stays whole
MOE_WEIGHT_VMEM_BUDGET = 80 * 1024 * 1024
_LANES = 128


def _in_flight(weights) -> int:
    """Bytes of one expert's matrices, double-buffered."""
    return 2 * sum(math.prod(w.shape[1:]) * w.dtype.itemsize
                   for w in weights)


def weight_blocks(weights, width: int) -> int:
    """Blocks of the output width a grouped matmul walks an expert's
    matrices in: 1 (whole) where they fit ``MOE_WEIGHT_VMEM_BUDGET``
    double-buffered, else the smallest power of two that does, each block
    whole lanes wide.  A function of the shapes alone."""
    nb = 1
    while (_in_flight(weights) // nb > MOE_WEIGHT_VMEM_BUDGET
           and width % (2 * nb * _LANES) == 0):
        nb *= 2
    return nb


def _vmem_limit(weights, nb: int = 1) -> int:
    """``MOE_VMEM_LIMIT``, or where an expert's blocks in flight need
    more (both up-projections at d 4096, f 2048 are 64 MiB
    double-buffered), those and 16 MiB for the rows, the result and the
    body's temporaries."""
    return max(MOE_VMEM_LIMIT, _in_flight(weights) // nb + 16 * 1024 * 1024)


class MoEStats(NamedTuple):
    """What one call routed (int32 scalars, for the serving counters)."""
    experts_hit: jnp.ndarray      # experts with at least one row
    max_rows: jnp.ndarray         # rows of the busiest expert
    rows: jnp.ndarray             # live assignments (valid tokens x k)


class HeldMoEStats(NamedTuple):
    """:class:`MoEStats` of a share of the experts (``experts_held``):
    the first three count the held experts' rows only."""
    experts_hit: jnp.ndarray
    max_rows: jnp.ndarray
    rows: jnp.ndarray
    rows_elsewhere: jnp.ndarray   # live assignments to experts not held


def _picked(table, index):
    """``take_along_axis(table, index, -1)`` (``table`` [..., E], ``index``
    [..., k]; 0 where an index is none of 0 .. E-1) as a compare over
    [..., k, E] and a sum, the same bits: for what the module docstring's
    step 2 says of a gather an index (the router's weights at 192 x top-22
    of 512: 39 us gathered, 0.8 so; at 4,096 x top-8 of 256: 341, 17)."""
    ids = jnp.arange(table.shape[-1], dtype=index.dtype)
    return jnp.sum(jnp.where(index[..., None] == ids, table[..., None, :], 0),
                   axis=-1)


def route_topk(x, router_w, top_k: int, renormalize: bool = False):
    """x [N, d], router_w [d, E] -> (weights [N, k] float32, experts
    [N, k] int32): softmax over all E in float32, then the k largest."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def route_sigmoid_topk(x, router_w, select_bias, top_k: int,
                       scale: float = 1.0, renormalize: bool = True,
                       eps: float = 0.0):
    """x [N, d], router_w [d, E], select_bias [E] -> (weights [N, k]
    float32, experts [N, k] int32).  Scores ``sigmoid(x @ router_w)`` in
    float32; the k experts with the largest ``score + select_bias`` are
    chosen (the bias steers the choice only); their weights are their
    own scores (:func:`_picked`), renormalised to sum 1 if asked (over
    ``sum + eps`` where the source guards the division: HF
    ``Lfm2MoeSparseMoeBlock``'s 1e-6), times ``scale``.  ``select_bias``
    None: the scores alone choose."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    chosen_by = scores if select_bias is None \
        else scores + select_bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(chosen_by, top_k)
    weights = _picked(scores, experts)
    if renormalize:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + eps if eps else total)
    return weights * scale, experts.astype(jnp.int32)


def row_tile(assignments: int, n_experts: int) -> int:
    """Rows a tile: the power of two at or above the mean rows an expert,
    from 16 (one bf16 tile of sublanes) to ``MAX_ROW_TILE``."""
    tm = 16
    while tm < min(assignments // n_experts, MAX_ROW_TILE):
        tm *= 2
    return tm


def _gate_up_kernel(te_ref, live_ref, x_ref, wg_ref, wu_ref, h_ref, *,
                    axis=0):
    live = pl.program_id(axis) < live_ref[0]

    @pl.when(live)
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(h_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)


def _up_relu2_kernel(te_ref, live_ref, x_ref, wu_ref, h_ref, *, axis=0):
    live = pl.program_id(axis) < live_ref[0]

    @pl.when(live)
    def _():
        u = jnp.maximum(jnp.dot(x_ref[...], wu_ref[0],
                                preferred_element_type=jnp.float32), 0.0)
        h_ref[...] = (u * u).astype(h_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)


def _down_kernel(te_ref, live_ref, h_ref, wd_ref, y_ref, *, axis=0):
    live = pl.program_id(axis) < live_ref[0]

    @pl.when(live)
    def _():
        y_ref[...] = jnp.dot(h_ref[...], wd_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(y_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _grouped(kernel, name, rows, weights, tile_expert, n_live, tm, width,
             interpret):
    """One grouped matmul: ``rows`` [T*tm, d_in] against the tile's
    expert of each of ``weights`` ([X, d_in, width]), whole or in
    :func:`weight_blocks` blocks of ``width`` (the tiles inside a block:
    the module docstring has the order and why)."""
    tiles = rows.shape[0] // tm
    d_in = rows.shape[1]
    nb = weight_blocks(weights, width)
    if nb == 1:
        grid, semantics = (tiles,), ("arbitrary",)
        row_map = out_map = lambda t, te, nl: (t, 0)
        w_map = lambda t, te, nl: (te[t], 0, 0)
    else:
        grid, semantics = (nb, tiles), ("arbitrary", "arbitrary")
        kernel = functools.partial(kernel, axis=1)
        row_map = lambda j, t, te, nl: (t, 0)
        w_map = lambda j, t, te, nl: (te[t], 0, j)
        out_map = lambda j, t, te, nl: (t, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[pl.BlockSpec((tm, d_in), row_map)]
        + [pl.BlockSpec((1, d_in, width // nb), w_map) for _ in weights],
        out_specs=pl.BlockSpec((tm, width // nb), out_map),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles * tm, width), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_vmem_limit(weights, nb)),
        interpret=interpret, name=name,
    )(tile_expert, n_live, rows, *weights)


def _moved(v, step: int, fill):
    """``v`` [M] moved ``step`` places up, ``fill`` coming in behind."""
    return jnp.pad(v, (step, 0), constant_values=fill)[:v.shape[0]]


def _spread(values, shift, size: int, hole):
    """``out[s + shift[s]] = values[s]`` where ``shift[s] >= 0``, ``hole``
    in the rest of ``out`` [size]: the sorted assignments moved apart to
    their rows.  The shifts never decrease along ``s`` (an expert's is the
    padding ahead of it), so moving by the shift's bits, the highest
    first, never lands two items on one place: ``log2(size - A)`` rounds
    of a select between an array and itself moved by a constant."""
    room = size - values.shape[0]
    out = jnp.pad(jnp.where(shift >= 0, values, hole), (0, room),
                  constant_values=hole)
    left = jnp.pad(jnp.maximum(shift, 0), (0, room))   # of the move, to go
    for bit in reversed(range(room.bit_length())):
        step = 1 << bit
        goes = (left & step) != 0
        came = _moved(goes, step, False)
        out = jnp.where(came, _moved(out, step, hole),
                        jnp.where(goes, hole, out))
        left = jnp.where(came, _moved(left, step, 0),
                         jnp.where(goes, 0, left))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def routing_plan(flat, n_experts: int, top_k: int, tm: int):
    """The module docstring's step 2.  ``flat`` [A] int32: each
    assignment's expert, ``n_experts`` for one that reaches no expert
    here (a padding token, an expert held elsewhere); assignment ``i`` is
    token ``i // top_k``'s.  Returns

    * ``tile_expert`` [tiles = A // tm + n_experts]: the expert of each
      tile of ``tm`` rows (a tile past the live ones repeats the last live
      expert: the pipeline fetches nothing for a block index that does
      not move);
    * ``n_live``: the tiles that hold a row;
    * ``token`` [tiles * tm]: the token of each row, ``A // top_k`` (one
      past the last) for a row that holds none;
    * ``row`` [A]: the row of each assignment, 0 for one that has none;
    * ``counts`` [n_experts]: the assignments of each expert.

    Compares and sums over [experts, A] and [tiles, experts], two sorts
    and :func:`_spread`: no loop, no scatter, and no gather.  Jitted by
    itself so that a program of several expert layers traces and lowers
    it once (its ~250 operations were 3 s of a 52 s set-up where five
    unrolled layers and five programs each traced their own: PR 58)."""
    a, e, i32 = flat.shape[0], n_experts, jnp.int32
    tiles = a // tm + e
    ids = jnp.arange(e, dtype=i32)
    counts = jnp.sum(flat[None, :] == ids[:, None], axis=1, dtype=i32)
    per = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(per)
    n_live = jnp.sum(per)
    last = jnp.minimum(jnp.arange(tiles, dtype=i32),
                       jnp.maximum(n_live - 1, 0))
    tile_expert = jnp.minimum(jnp.sum(
        tile_end[None, :] <= last[:, None], axis=1, dtype=i32), e - 1)

    # an expert's rows start where its sorted assignments do, moved on by
    # the padding that filled the last tile of each expert ahead of it
    spare = per * tm - counts
    ahead = jnp.cumsum(spare) - spare                           # [E]
    position = jnp.arange(a, dtype=i32)
    expert, order = jax.lax.sort((flat, position), num_keys=1,
                                 is_stable=True)                # [A], [A]
    shift = jnp.where(expert < e, _picked(ahead, expert), -1)
    token = _spread(order // top_k, shift, tiles * tm,
                    jnp.asarray(a // top_k, i32))
    # sorting the order back to 0 .. A-1 carries each row to its assignment
    row = jax.lax.sort((order, jnp.where(shift >= 0, position + shift, 0)),
                       num_keys=1)[1]
    return tile_expert, n_live, token, row, counts


def dropless_moe(x, router_w, gate_w, up_w, down_w, top_k: int, *,
                 expert_offset=0, valid=None, renormalize: bool = False,
                 interpret: Optional[bool] = None, routing=None,
                 experts_held=None, act: str = "swiglu"):
    """x [N, d] -> (y [N, d], :class:`MoEStats`).

    ``router_w`` [d, E] is this layer's; ``gate_w`` / ``up_w``
    [X, d, f] and ``down_w`` [X, f, d] hold this layer's E experts from
    row ``expert_offset`` (a traced scalar: ``layer * E`` into the
    stacked weights of every layer, 0 for one layer's own).  ``valid``
    [N] bool leaves padding rows out: they reach no expert, count in no
    statistic and get zeros.

    ``routing``, ``experts_held`` and ``act`` are the module docstring's
    three: with ``routing`` the router is the caller's (of ``router_w``
    only the shape is read: over how many experts it ranged); with
    ``experts_held`` the weights hold those experts only and the
    statistics are :class:`HeldMoEStats`; with ``act='relu2'`` ``gate_w``
    is None."""
    n, d = x.shape
    e = router_w.shape[-1]
    width = up_w.shape[-1]
    if act not in ("swiglu", "relu2"):
        raise ValueError(f"act {act!r}: 'swiglu' or 'relu2'")
    if interpret is None:
        interpret = use_interpret()
    with jax.named_scope("moe"):
        if routing is None:
            weights, experts = route_topk(x, router_w, top_k, renormalize)
        else:
            weights, experts = routing
        flat = experts.reshape(-1)                          # [A]
        a = n * top_k
        share = a
        if experts_held is not None:
            first, count = experts_held
            # the share of the assignments that lands here on average
            share, e = a * count // e, count
            flat = flat - first
            elsewhere = (flat < 0) | (flat >= e)
            flat = jnp.where(elsewhere, e, flat)
            if valid is not None:
                elsewhere = elsewhere & jnp.repeat(valid, top_k)
        if valid is not None:
            flat = jnp.where(jnp.repeat(valid, top_k), flat, e)
        tm = row_tile(share, e)
        i32 = jnp.int32

        tile_expert, n_live, token, row, counts = routing_plan(
            flat, e, top_k, tm)
        x_rows = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[token]

        te = tile_expert + jnp.asarray(expert_offset, i32)
        live = jnp.reshape(n_live, (1,)).astype(i32)
        if act == "swiglu":
            h = _grouped(_gate_up_kernel, MOE_GATE_UP_KERNEL, x_rows,
                         (gate_w, up_w), te, live, tm, width, interpret)
        else:
            h = _grouped(_up_relu2_kernel, MOE_UP_RELU2_KERNEL, x_rows,
                         (up_w,), te, live, tm, width, interpret)
        y_rows = _grouped(_down_kernel, MOE_DOWN_KERNEL, h, (down_w,),
                          te, live, tm, d, interpret)

        # the weighted sum of a token's k rows
        picked = y_rows[row].reshape(n, top_k, d).astype(jnp.float32)
        keep = (flat < e).reshape(n, top_k, 1)
        y = jnp.sum(jnp.where(keep, picked * weights[..., None], 0.0), axis=1)
        stats = MoEStats(experts_hit=jnp.sum(counts > 0).astype(i32),
                         max_rows=jnp.max(counts).astype(i32),
                         rows=jnp.sum(counts).astype(i32))
        if experts_held is not None:
            stats = HeldMoEStats(*stats, rows_elsewhere=jnp.sum(
                elsewhere).astype(i32))
        return y.astype(x.dtype), stats
