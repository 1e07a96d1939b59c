"""A prefill's latent attention (MLA, EXPANDED form) over a context that
lies in the pages: the kernel ``ds_latent_context_attn``.

The decode tick's kernel (``decode_attention.py::latent_decode_attention``)
is the absorbed form: one query a slot, every head against the latent rows
themselves.  A prefill has thousands of queries a head, and there the
expanded form is the cheaper: a block of cached rows becomes every head's
keys and values ONCE (``rows @ k_w[h]``, ``rows[:, :C] @ v_w[h]``), and all
the chunk's queries read them.  One grid step is one GROUP of heads against
one block of whole pages:

* the block's rows are copied from the pool where they lie, by the
  request's page ids, into one half of a double buffer while the block
  before computes (``_latent_decode_kernel``'s hand-written page copies);
  a page past the context is not copied, a block past it runs nothing and
  fetches nothing (its mask tile's index repeats the last live block's);
* the group's keys and values of the block are expanded into VMEM, then
  the chunk's query blocks are walked inside the step: float32 scores
  ``[block_q, block_k]``, the mask (causal by the queries' positions, the
  context's end, and the caller's ``allowed`` tile, one for all the heads
  of the step), the online softmax, ``p`` cast to the rows' type for ``p @
  v``.  Scores, running max, sum and accumulator never leave VMEM.  A query
  block whose every position lies before the key block is skipped;
* the last block of a group writes ``acc / l``, zeros where no key was
  allowed (``l == 0``).

The heads of a step, ``block_q`` and ``block_k`` follow from the shapes and
``CONTEXT_VMEM_BUDGET``; the latter two are arguments for the tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _LANES, _round_up
from .flash_attention import NEG_INF

LATENT_CONTEXT_ATTN_KERNEL = "ds_latent_context_attn"

#: what a grid step may keep in VMEM (its operands' double buffers, the
#: group's accumulators); the compiler's limit is this and the body's
#: temporaries
CONTEXT_VMEM_BUDGET = 56 * 1024 * 1024
_BODY_VMEM = 32 * 1024 * 1024


def _lanes(n: int) -> int:
    return _round_up(n, _LANES)


def context_vmem_bytes(hg: int, tq: int, dq: int, dv: int, width: int,
                       rank: int, bk: int, itemsize: int,
                       masked: bool) -> int:
    """VMEM one grid step of ``hg`` heads holds: the operands the pipeline
    double-buffers (queries, both weights, the output, the mask tile, the
    positions), the rows' double buffer, the expanded keys and values, and
    the float32 running max, sum and accumulator of every query."""
    piped = (hg * tq * (_lanes(dq) + _lanes(dv))
             + hg * (width * _lanes(dq) + rank * _lanes(dv))) * itemsize \
        + tq * _LANES * 4 + (tq * bk if masked else 0)
    held = 2 * bk * _lanes(width) * itemsize \
        + hg * bk * (_lanes(dq) + _lanes(dv)) * itemsize \
        + hg * tq * (2 * _LANES + _lanes(dv)) * 4
    return 2 * piped + held


def context_heads_per_step(heads: int, budget: int, *shape) -> int:
    """The most heads (a divisor of ``heads``, at most 8) whose step fits
    ``budget`` bytes; ``shape``: :func:`context_vmem_bytes`'s other
    arguments."""
    fits = [hg for hg in range(1, min(heads, 8) + 1) if heads % hg == 0
            and context_vmem_bytes(hg, *shape) <= budget]
    return max(fits, default=1)


def latent_context_reference(q, k_w, v_w, pool, page_ids, abs_pos,
                             context_len, sm_scale: float, allowed=None):
    """Dense jnp reference of :func:`latent_context_attention`: the
    request's pages gathered, every head's keys and values expanded over
    the whole capacity, one softmax a query in float32."""
    rows = pool[page_ids].reshape(-1, pool.shape[-1]).astype(q.dtype)
    k = jnp.einsum("kw,hwd->hkd", rows, k_w.astype(q.dtype))
    v = jnp.einsum("kc,hcv->hkv", rows[:, :v_w.shape[1]],
                   v_w.astype(q.dtype))
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    at = jnp.arange(rows.shape[0], dtype=jnp.int32)[None, :]
    ok = (at <= abs_pos[:, None]) & (at < context_len)
    if allowed is not None:
        ok &= allowed
    s = jnp.where(ok[None], s, jnp.finfo(jnp.float32).min)
    p = jnp.where(jnp.any(ok, axis=-1, keepdims=True)[None],
                  jax.nn.softmax(s, axis=-1), 0.0).astype(q.dtype)
    return jnp.einsum("hqk,hkv->hqv", p, v)


def _latent_context_kernel(pt_ref, ctx_ref, hi_ref, q_ref, pos_ref, *refs,
                           sm_scale: float, block_q: int, masked: bool):
    """One grid step = one group of heads, one block of ``ppb`` whole
    pages (module docstring).  ``pt_ref``: the request's page ids;
    ``ctx_ref[0]``: the context's length; ``hi_ref[i]``: the largest
    position of query block ``i``.  ``masked``: one more operand ahead of
    the weights, the ``[Tq, bk]`` int8 tile of the caller's mask."""
    allowed_ref = refs[0] if masked else None
    (kw_ref, vw_ref, kv_hbm, o_ref, buf, sems, state_ref, k_scr, v_scr,
     m_scr, l_scr, acc_scr) = refs[masked:]
    g, j = pl.program_id(0), pl.program_id(1)
    groups, nb = pl.num_programs(0), pl.num_programs(1)
    _, ppb, page_len, width = buf.shape
    hg, tq, _ = q_ref.shape
    rank = vw_ref.shape[1]
    bk, bq = ppb * page_len, block_q
    ctx = ctx_ref[0]

    def for_live_pages(blk, fn):
        left = ctx - blk * bk
        jax.lax.fori_loop(0, jnp.minimum(ppb, (left + page_len - 1)
                                         // page_len),
                          lambda i, _: fn(i), None)

    def fetch(blk, half):
        for_live_pages(blk, lambda i: pltpu.make_async_copy(
            kv_hbm.at[pt_ref[blk * ppb + i]], buf.at[half, i],
            sems.at[half]).start())

    def for_query_blocks(fn):
        jax.lax.fori_loop(
            0, tq // bq,
            lambda i, _: fn(i, pl.ds(pl.multiple_of(i * bq, bq), bq)), None)

    @pl.when((g == 0) & (j == 0))
    def _clear():
        # a partly live block's dead pages are never copied into: what
        # VMEM held there would reach the value matmul times 0
        buf[...] = jnp.zeros_like(buf)
        state_ref[0] = 0
        state_ref[1] = 0

    @pl.when(j == 0)
    def _init():
        def clear(i, qs):
            for h in range(hg):
                m_scr[h, qs, :] = jnp.full((bq, _LANES), NEG_INF, jnp.float32)
                l_scr[h, qs, :] = jnp.zeros((bq, _LANES), jnp.float32)
                acc_scr[h, qs, :] = jnp.zeros((bq, acc_scr.shape[-1]),
                                              jnp.float32)
        for_query_blocks(clear)

    @pl.when(j * bk < ctx)
    def _live():
        half = state_ref[0]
        state_ref[0] = 1 - half

        @pl.when(state_ref[1] == 0)
        def _first():
            fetch(j, half)
            state_ref[1] = 1

        # every group walks the same blocks: after a group's last live
        # one comes the next group's block 0
        more = (j + 1 < nb) & ((j + 1) * bk < ctx)

        @pl.when(more | (g + 1 < groups))
        def _ahead():
            fetch(jnp.where(more, j + 1, 0), 1 - half)

        # a wait takes one page's bytes off the semaphore: whose, is the
        # same to it
        for_live_pages(j, lambda i: pltpu.make_async_copy(
            kv_hbm.at[0], buf.at[half, i], sems.at[half]).wait())
        rows = buf[half].reshape(bk, width)
        for h in range(hg):
            k_scr[h] = jnp.dot(rows, kw_ref[h],
                               preferred_element_type=jnp.float32
                               ).astype(k_scr.dtype)
            v_scr[h] = jnp.dot(rows[:, :rank], vw_ref[h],
                               preferred_element_type=jnp.float32
                               ).astype(v_scr.dtype)
        at = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        in_ctx = at < ctx

        def attend(i, qs):
            # a query block wholly ahead of this key block sees none of it
            @pl.when(hi_ref[i] >= j * bk)
            def _():
                ok = (at <= pos_ref[qs, :]) & in_ctx          # [bq, bk]
                if masked:
                    ok &= allowed_ref[qs, :].astype(jnp.int32) != 0
                for h in range(hg):
                    s = jax.lax.dot_general(
                        q_ref[h, qs, :], k_scr[h], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
                    s = jnp.where(ok, s, NEG_INF)
                    m_prev = m_scr[h, qs, 0:1]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s, axis=1, keepdims=True))
                    # no key of a row may have scored yet: m_new is then
                    # the floor itself, and against 0 the floored keys'
                    # exp is still 0 (alpha is exp(0) = 1 over sums that
                    # are 0)
                    p = jnp.exp(s - jnp.where(m_new == NEG_INF, 0.0, m_new))
                    alpha = jnp.exp(m_prev - m_new)
                    l_scr[h, qs, :] = jnp.broadcast_to(
                        alpha * l_scr[h, qs, 0:1]
                        + jnp.sum(p, axis=1, keepdims=True), (bq, _LANES))
                    acc_scr[h, qs, :] = acc_scr[h, qs, :] * alpha + jnp.dot(
                        p.astype(v_scr.dtype), v_scr[h],
                        preferred_element_type=jnp.float32)
                    m_scr[h, qs, :] = jnp.broadcast_to(m_new, (bq, _LANES))
        for_query_blocks(attend)

    @pl.when(j == nb - 1)
    def _finalize():
        def write(i, qs):
            for h in range(hg):
                l = l_scr[h, qs, 0:1]
                # no key allowed (or no context): l == 0 over an
                # accumulator that is 0 -> exact zeros
                o_ref[h, qs, :] = (acc_scr[h, qs, :] / jnp.where(
                    l == 0.0, 1.0, l)).astype(o_ref.dtype)
        for_query_blocks(write)


def latent_context_attention(q: jnp.ndarray, k_w: jnp.ndarray,
                             v_w: jnp.ndarray, pool: jnp.ndarray,
                             page_ids: jnp.ndarray, abs_pos: jnp.ndarray,
                             context_len, *, sm_scale: float,
                             allowed: Optional[jnp.ndarray] = None,
                             block_q: int = 512, block_k: int = 1024,
                             interpret: Optional[bool] = None
                             ) -> jnp.ndarray:
    """Latent attention (MLA, expanded form) of one request's queries over
    its context in ONE paged pool, the kernel ``ds_latent_context_attn``.

    q: [H, Tq, Dq]: a head's queries as its keys are laid out.
    k_w: [H, W, Dq], v_w: [H, C, dv]: a cached row ``[W]`` is head ``h``'s
        key ``row @ k_w[h]`` and its value ``row[:C] @ v_w[h]`` (``C`` the
        latent's rank; a key's rotated part passes through ``k_w`` by an
        identity).
    pool: [P, page_len, W]: one row a token; the request's position ``p``
        is row ``p % page_len`` of page ``page_ids[p // page_len]``.
    page_ids [max_pages], abs_pos [Tq] (the queries' positions; below 0:
        a row that sees nothing), context_len: traced.
    allowed: None, or bool ``[Tq, max_pages * page_len]``: the positions a
        query may see beside the causal rule and the context's end.
    block_q, block_k: queries of an inner step and keys (whole pages) of a
        grid step, at most.

    Returns ``[H, Tq, dv]``; a query with no key to see gives exact zeros.
    Scores, softmax and accumulation are float32; the expanded keys and
    values and the probabilities under ``p @ v`` have ``q``'s type."""
    if interpret is None:
        from .runtime import use_interpret
        interpret = use_interpret()
    return _attend(q, k_w, v_w, pool, page_ids, abs_pos, context_len, allowed,
                   sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                   budget=CONTEXT_VMEM_BUDGET, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_q", "block_k", "budget", "interpret"))
def _attend(q, k_w, v_w, pool, page_ids, abs_pos, context_len, allowed, *,
            sm_scale: float, block_q: int, block_k: int, budget: int,
            interpret: bool):
    """:func:`latent_context_attention` as a program of its own: a model's
    layers call it at one set of shapes, and the program that holds them
    traces and lowers the kernel once, not once a layer."""
    H, Tq, Dq = q.shape
    P, page_len, W = pool.shape
    C, dv = v_w.shape[1:]
    max_pages = page_ids.shape[0]
    assert k_w.shape == (H, W, Dq) and v_w.shape[0] == H and C <= W, (
        q.shape, k_w.shape, v_w.shape, pool.shape)
    dt = q.dtype
    ppb = max(1, min(block_k // page_len, max_pages))
    bk, nb = ppb * page_len, -(-max_pages // ppb)
    # an int8 tile is 32 rows: a query block is whole ones
    bq = min(block_q, -(-Tq // 32) * 32)
    tq = -(-Tq // bq) * bq
    nq = tq // bq
    masked = allowed is not None
    hg = context_heads_per_step(H, budget, tq, Dq, dv, W, C, bk,
                                dt.itemsize, masked)
    pos = jnp.pad(abs_pos.astype(jnp.int32), (0, tq - Tq),
                  constant_values=-1)
    mask, mask_spec = [], []
    if masked:
        assert allowed.shape == (Tq, max_pages * page_len), allowed.shape
        last = lambda ctx: (jnp.maximum(ctx[0], 1) - 1) // bk
        mask = [jnp.pad(allowed.astype(jnp.int8),
                        ((0, tq - Tq), (0, nb * bk - allowed.shape[1])))]
        mask_spec = [pl.BlockSpec(
            (tq, bk), lambda g, j, pt, ctx, hi: (0, jnp.minimum(j,
                                                                last(ctx))))]
    group = lambda g, j, *_: (g, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(H // hg, nb),
        in_specs=[pl.BlockSpec((hg, tq, Dq), group),
                  pl.BlockSpec((tq, 1), lambda g, j, *_: (0, 0)),
                  *mask_spec,
                  pl.BlockSpec((hg, W, Dq), group),
                  pl.BlockSpec((hg, C, dv), group),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((hg, tq, dv), group),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_len, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((hg, bk, Dq), dt),
            pltpu.VMEM((hg, bk, dv), dt),
            pltpu.VMEM((hg, tq, _LANES), jnp.float32),
            pltpu.VMEM((hg, tq, _LANES), jnp.float32),
            pltpu.VMEM((hg, tq, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_context_kernel, sm_scale=sm_scale,
                          block_q=bq, masked=masked),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, tq, dv), dt),
        # the double buffer and its parity pass from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=context_vmem_bytes(
                hg, tq, Dq, dv, W, C, bk, dt.itemsize, masked) + _BODY_VMEM),
        interpret=interpret,
        name=LATENT_CONTEXT_ATTN_KERNEL,
    )(jnp.pad(page_ids.astype(jnp.int32), (0, nb * ppb - max_pages)),
      jnp.asarray(context_len, jnp.int32).reshape(1),
      jnp.max(pos.reshape(nq, bq), axis=1),
      jnp.pad(q, ((0, 0), (0, tq - Tq), (0, 0))), pos[:, None], *mask,
      k_w.astype(dt), v_w.astype(dt), pool)
    return out[:, :Tq]
