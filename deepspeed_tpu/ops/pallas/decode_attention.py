"""Single-query flash attention over a slot KV cache (the decode path).

The training kernel (flash_attention.py) masks with STATIC lengths; the
serving engine needs the opposite shape: one new query token per slot
against that slot's cached keys, with PER-SLOT live lengths that change
every tick and therefore must be TRACED — no static length may leak
into the program or the one-compiled-decode-program contract
(docs/serving.md, jaxlint JL005) is gone.

Layout: the slot cache is slot-major ``[S, H, T, Dh]`` and its kernel
(:func:`decode_attention_slots`) runs a ``(S, k_blocks)`` grid: a grid
step streams a block of cache rows of ALL of a slot's key heads through
VMEM, the block ``[H, bk, Dh]`` being rows ``(head, row)`` of one matmul
operand and the query heads the rows that read it, with the same
online-softmax accumulator as the training kernel; a precomputed position
table keeps every query head to its own key head's live rows (the
grouped paged body's trick), and the per-slot lengths ride as a
prefetched scalar.  Keys at or beyond a slot's live length are
hard-masked with the validity floor, and a slot with length 0 (a free
slot riding along in the static batch) outputs exact zeros — the
mis-masking discipline the training kernel's kv_length arm enforces,
here with traced lengths.  The same body, named
``ds_window_decode_attn``, serves a sliding-window layer's ring of its
last keys by slot (:func:`window_decode_attention`): fewer key heads
than query heads, values narrower than keys, a traced base into the
slots of every layer, and a learned sink a head that joins the softmax's
denominator and no value.

Compute for blocks entirely beyond a slot's length is skipped
(``pl.when``), but their HBM->VMEM streaming is not: block index maps
are grid-index functions and cannot read traced lengths, so a short
slot still pays full-cache bandwidth.  The PAGED kernels below
(:func:`decode_attention_paged`) close that gap with the
scalar-prefetch grid of PagedAttention (PAPERS.md): the per-slot page
table rides as a ``PrefetchScalarGridSpec`` operand, block index maps
read it to gather the slot's pages, and a slot streams only the pages
it owns — the KV layout becomes ``[P, H, page_len, Dh]`` (a flat pool)
instead of one ``max_seq_len`` stride per slot.

The fp single-query paged arm (``ds_paged_decode_attn``, the one a
serving engine decodes with) takes a grid step for ALL HEADS of a
BLOCK OF PAGES: grid ``(S, max_pages / ppb)``, 256 steps a layer at
GPT-2 XL's serving shapes where a step for each (slot, head, page) was
51,200.  Each of the block's ``ppb`` pages is an operand of its own,
its block index the page's table entry: the pipeline fetches a block's
pages while the block before computes (the next slot's first block at
a slot's end) and fetches nothing while the index repeats, as it does
over dead table entries, which all name the scratch page.  A live step
packs its pages into one ``[rows, 128]`` buffer a pool (two 64-wide
keys side by side in the lanes) and attends every head in two matmuls:
the queries ride the sublane rows (25 heads -> 32), the scores of all
(query row, buffer row) pairs come out of one product, a precomputed
position table keeps each head's own live keys, and the masked
probabilities are already the block-diagonal operand of the value
matmul.  fp32 scores, softmax and accumulation, the same validity
floor, exact zeros for a slot of length 0; a block wholly beyond the
slot's length runs nothing.  ``ppb`` follows from the pool's shape and
``PAGED_KV_VMEM_BUDGET`` (:func:`paged_pages_per_block`), never from
configuration.  Two things the chip's compiler decided (PERF.md,
PR 27): Mosaic copies no window of an HBM array whose last dimension
is under 128, so the pages cannot be gathered by hand-written DMA
while a head is 64 wide; and the pool reaches the kernel as
``[P, page_len, H, Dh]`` because that is the layout XLA gives it for
the cache write just before — any other shape costs copies of the
whole pool, every layer of every tick.  The int8 and multi-query paged
arms keep the grid this one left: a step for each (slot, head, page).

That body, the PACKED one, re-lays every page it has fetched.  Where a
page at rest, ``[page_len, H, Dh]``, already is rows ``(r, h)`` of the
buffer it would be copied into (``Dh`` a multiple of the 128 lanes, the
heads a whole number of sublane tiles: 16 heads of 128),
:func:`paged_decode_arm` chooses the DIRECT body from the pool's shape
alone: the pools stay in HBM, and a live step copies the live pages of
the next live block by the prefetched page table into the other half of
a double buffer, then runs the same two matmuls on its own pages where
they landed.  No page operands, no repack, and a dead table entry is
neither fetched nor waited for.  On the chip the pipeline's fetch of 32
page operands a step was 91 % of the packed body's time at that shape,
the repack 4 % (PERF.md, PR 33): the hand-written copies, which head 64
forbids, are the gain.

``impl='dense'`` is the interpretable reference fallback on both
entry points: the same masking semantics in plain jnp (the paged arm
gathers with ``jnp.take``), the differential-test oracle and the
serving engine's CPU path.

MULTI-QUERY arm (speculative decoding, docs/serving.md): the verify
half of draft-verify speculation scores ``W = k+1`` new tokens per
slot in ONE pass, so both entry points grow a ``*_multi`` twin taking
``W`` query rows and PER-QUERY live lengths ``[S, W]`` — query ``i``
(absolute position ``base + i``) attends every key below
``lengths[s, i]`` = ``base + i + 1``.  The kernels reuse the sublane
dimension the single query only broadcast into: up to 8 query rows
ride one tile (W padded up to a sublane multiple), each with its own
length mask, same grid, same streaming.  The dense multi reference is
DEFINED as W stacked single-query calls — fp32-bitwise against
sequential decode ticks by construction, the parity anchor the
widened program is verified against (tests/test_spec_decode.py).

FUSED-DEQUANT arms (``serving.quantization.kv='int8'``, docs/
serving.md "quantized serving"): both paged entry points accept the
int8 pool's per-row scale sidecars ``k_scale``/``v_scale``
[P, H, page_len].  Because the scale is per KEY ROW, dequant folds
into the score/prob columns — ``q·(k8·sk) == (q·k8)·sk`` and
``p·(v8·sv) == (p·sv)·v8`` — so the kernel streams int8 pages from
HBM (the bandwidth halving) and never materializes an fp page.  The
scale rows ride the same page-table indirection as the blocks they
scale; ``impl='dense'`` dequantizes the gathered view
(:func:`dequantize_paged`) — the interpretable definition of the
quantize→dequant semantics the fused arms are verified against
(tests/test_quant_serve.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _pad_seq


def _use_interpret() -> bool:
    from .runtime import use_interpret
    return use_interpret()


def decode_attention_reference(q, k, v, lengths, sm_scale=None):
    """Dense jnp reference: q [S, H, Dh] against k/v [S, H, T, Dh]
    masked to per-slot ``lengths`` [S] (int32).  Rows with length 0
    return exact zeros.  Deliberately mirrors ``ops.attention.
    causal_attention`` op for op (finfo.min mask fill, jax.nn.softmax,
    probs cast to q.dtype before the value matmul) so a dense-path
    decode step is fp32-BITWISE against the training forward — the
    parity bar of tests/test_inference.py."""
    S, H, T, Dh = k.shape
    scale = _default_scale(Dh) if sm_scale is None else sm_scale
    s = jnp.einsum("shd,shtd->sht", q, k,
                   preferred_element_type=jnp.float32) * scale
    valid = (jnp.arange(T, dtype=jnp.int32)[None, None, :]
             < lengths.astype(jnp.int32)[:, None, None])
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
    s = jnp.where(valid, s, neg)
    probs = jax.nn.softmax(s, axis=-1)
    # all-masked rows (free slots): softmax renormalizes over masked
    # keys — hard-zero them instead of silently attending
    probs = jnp.where(lengths[:, None, None] > 0, probs, 0.0)
    probs = probs.astype(q.dtype)
    return jnp.einsum("sht,shtd->shd", probs, v)


def _default_scale(d: int) -> float:
    """1/sqrt(d) computed in fp32 — the exact constant
    ``causal_attention`` uses, so dense decode vs training forward stays
    bitwise (the python-float ``d ** -0.5`` can differ by 1 ulp)."""
    import numpy as np
    return float(np.float32(1.0) / np.sqrt(np.float32(d)))


# Stable names of the Mosaic custom calls (``pl.pallas_call(name=...)``):
# the HLO instruction, and so the device-trace row, is ``<name>.<n>``.
# Every kernel of the repo shares the ``ds_`` prefix, and the
# benchmark's per-kernel shares (benchmark/metrics/*_share.*.json)
# match on these strings — moving a call keeps its constant.
DECODE_ATTN_KERNEL = "ds_decode_attn"
#: the same body over a ring of the last ``window`` keys a slot (a
#: sliding-window layer's request state), with the sink in the softmax
WINDOW_DECODE_ATTN_KERNEL = "ds_window_decode_attn"


def slot_decode_reference(q, k, v, lengths, sink=None, sm_scale=None):
    """Dense jnp reference of :func:`decode_attention_slots`: q
    [S, Hq, Dk] against k [S, Hkv, T, Dk], v [S, Hkv, T, Dv] (query head
    ``h`` reads key head ``h // (Hq // Hkv)``), rows ``>= lengths[s]``
    masked, and with ``sink`` [Hq] one more softmax column a head that
    takes weight and gives no value:
    ``p_j = exp(s_j - m) / (sum_j exp(s_j - m) + exp(sink - m))``."""
    S, Hkv, T, Dk = k.shape
    Hq = q.shape[1]
    scale = _default_scale(Dk) if sm_scale is None else sm_scale
    if Hq != Hkv:
        k, v = (jnp.repeat(t, Hq // Hkv, axis=1) for t in (k, v))
    s = jnp.einsum("shd,shtd->sht", q, k,
                   preferred_element_type=jnp.float32) * scale
    valid = (jnp.arange(T, dtype=jnp.int32)[None, None, :]
             < lengths.astype(jnp.int32)[:, None, None])
    s = jnp.where(valid, s, jnp.finfo(jnp.float32).min)
    if sink is not None:
        col = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None],
                               (S, Hq, 1))
        s = jnp.concatenate([s, col], axis=-1)
    probs = jax.nn.softmax(s, axis=-1)[..., :T]
    probs = jnp.where(lengths[:, None, None] > 0, probs, 0.0)
    return jnp.einsum("sht,shtd->shd", probs.astype(q.dtype), v)


def _slot_decode_kernel(len_ref, base_ref, q_ref, pos_ref, k_ref, v_ref,
                        *rest, sm_scale: float, block_k: int,
                        with_sink: bool):
    """One grid step = one slot, ALL heads, ``block_k`` cache rows of
    every key head: the block ``[Hkv, bk, Dk]`` is rows ``(key head,
    row)`` of the matmul operand, the query heads are the rows that read
    it, and ``pos_ref`` keeps every query head to its own key head's
    live rows (the grouped paged body's trick).  A sink joins the
    denominator at the end."""
    if with_sink:
        sink_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    jk = pl.program_id(1)
    nk = pl.num_programs(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(jk == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # whole k block at or beyond the live length: nothing to do
    @pl.when(jk * block_k < length)
    def _compute():
        hkv, bk, dk = k_ref.shape[1:]
        k = k_ref[0].reshape(hkv * bk, dk)
        v = v_ref[0].reshape(hkv * bk, v_ref.shape[-1])
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Hq, Hkv*bk]
        s = jnp.where(pos_ref[...] < length - jk * block_k, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # row jk*bk of every head is live (the pl.when guard), so m_new
        # is a real score and the masked keys' exp underflows to 0
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(jk == nk - 1)
    def _finalize():
        l, acc = l_scr[:, 0:1], acc_scr[:]
        if with_sink:
            # one more column of the softmax, in no value
            m = m_scr[:, 0:1]
            b = sink_ref[:, 0:1]
            m_all = jnp.maximum(m, b)
            keep = jnp.exp(m - m_all)
            acc = acc * keep
            l_all = l * keep + jnp.exp(b - m_all)
        else:
            l_all = jnp.where(l == 0.0, 1.0, l)
        # length 0 -> no block ran -> l == 0 -> exact zeros (free slots)
        o_ref[0] = jnp.where(l == 0.0, 0.0, acc / l_all).astype(o_ref.dtype)


def _slot_decode_pallas(q, k, v, lengths, sink, base, *, sm_scale, block_k,
                        interpret, name):
    X, Hkv, T, Dk = k.shape
    Dv = v.shape[-1]
    S, Hq, _ = q.shape
    block_k = min(block_k, max(T, 8))
    kf, vf = _pad_seq(k, block_k, 2), _pad_seq(v, block_k, 2)
    nk = kf.shape[2] // block_k
    # the heads ride the sublane rows, a whole number of tiles
    hp = _round_up(Hq, 8 * 4 // q.dtype.itemsize)
    qf = jnp.pad(q, ((0, 0), (0, hp - Hq), (0, 0)))
    import numpy as np
    pos = np.full((hp, Hkv * block_k), 2 ** 30, np.int32)
    pos[:Hq] = _grouped_block_positions(Hq, Hkv, block_k, 1)
    operands = [qf, jnp.asarray(pos), kf, vf]

    def kv_im(s, j, ln, b):
        # a block past the live rows repeats the last live one: it is
        # neither computed nor fetched
        return (b[0] + s, 0,
                jnp.minimum(j, jnp.maximum(ln[s] - 1, 0) // block_k), 0)

    in_specs = [
        pl.BlockSpec((1, hp, Dk), lambda s, j, *_: (s, 0, 0)),
        pl.BlockSpec(pos.shape, lambda s, j, *_: (0, 0)),
        pl.BlockSpec((1, Hkv, block_k, Dk), kv_im),
        pl.BlockSpec((1, Hkv, block_k, Dv), kv_im),
    ]
    if sink is not None:
        tile = jnp.pad(sink.astype(jnp.float32), (0, hp - Hq))
        operands.append(jnp.broadcast_to(tile[:, None], (hp, 128)))
        in_specs.append(pl.BlockSpec((hp, 128), lambda s, j, *_: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hp, Dv), lambda s, j, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hp, 128), jnp.float32),
            pltpu.VMEM((hp, 128), jnp.float32),
            pltpu.VMEM((hp, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_slot_decode_kernel, sm_scale=sm_scale,
                          block_k=block_k, with_sink=sink is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, hp, Dv), q.dtype),
        interpret=interpret,
        name=name,
    )(lengths, jnp.reshape(base, (1,)).astype(jnp.int32), *operands)
    return out[:, :Hq]


def decode_attention_slots(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           lengths: jnp.ndarray, *,
                           sink: Optional[jnp.ndarray] = None, base=0,
                           sm_scale: Optional[float] = None,
                           block_k: int = 256, impl: str = "pallas",
                           interpret: Optional[bool] = None,
                           name: str = DECODE_ATTN_KERNEL) -> jnp.ndarray:
    """Single-query attention over keys kept BY SLOT (not
    differentiable): the slot cache of :func:`decode_attention`, and a
    sliding-window layer's ring of its last ``T`` keys.

    q: [S, Hq, Dk], one new query a slot.
    k: [X, Hkv, T, Dk], v: [X, Hkv, T, Dv]: slot ``s`` reads
        ``k[base + s]`` (``base`` traced: a layer's offset into the
        slots of every layer, so no layer is sliced out); ``Hq`` a
        multiple of ``Hkv``, query head ``h`` on key head
        ``h // (Hq // Hkv)``; ``Dv`` need not be ``Dk``.
    lengths: [S] int32, TRACED: rows ``0 .. lengths[s] - 1`` are live
        (a ring that has wrapped: all ``T``; softmax does not care in
        which order the keys lie).  0 = free slot -> exact zeros.
    sink: [Hq] or None: a learned logit a head that joins the softmax's
        denominator and no value.
    """
    X, Hkv, T, Dk = k.shape
    S, Hq, _ = q.shape
    assert q.shape == (S, Hq, Dk) and Hq % Hkv == 0, (q.shape, k.shape)
    assert v.shape[:3] == k.shape[:3], (k.shape, v.shape)
    if sm_scale is None:
        sm_scale = _default_scale(Dk)
    lengths = lengths.astype(jnp.int32)
    if impl == "dense":
        at = jnp.asarray(base, jnp.int32) + jnp.arange(S, dtype=jnp.int32)
        return slot_decode_reference(q, k[at], v[at], lengths, sink=sink,
                                     sm_scale=sm_scale)
    if impl != "pallas":
        raise ValueError(
            f"decode attention impl={impl!r}: expected 'pallas' or "
            "'dense'")
    if interpret is None:
        interpret = _use_interpret()
    return _slot_decode_pallas(
        q, k, v, lengths, sink, jnp.asarray(base, jnp.int32),
        sm_scale=sm_scale, block_k=block_k, interpret=interpret, name=name)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray,
                     sm_scale: Optional[float] = None,
                     block_k: int = 256,
                     impl: str = "pallas",
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Single-query attention over a slot KV cache (not differentiable —
    the decode path never backprops).

    q: [S, H, Dh] — one new query token per slot.
    k, v: [S, H, T, Dh] — the slot cache; positions >= lengths[s] are
        garbage (evicted requests, uninitialized tail) and are
        hard-masked.
    lengths: [S] int32, TRACED — per-slot live KV length including the
        position this query's K/V was just written to.  0 = free slot →
        exact-zero output.

    ``impl``: 'pallas' (:func:`decode_attention_slots`' kernel;
    interpret mode off-TPU) or 'dense' (the jnp reference — the serving
    engine's CPU fallback and the test oracle).
    """
    assert q.ndim == 3 and k.ndim == 4, (q.shape, k.shape)
    S, H, T, Dh = k.shape
    assert q.shape == (S, H, Dh), (q.shape, k.shape)
    if sm_scale is None:
        sm_scale = _default_scale(Dh)
    if impl == "dense":
        return decode_attention_reference(q, k, v, lengths,
                                          sm_scale=sm_scale)
    return decode_attention_slots(q, k, v, lengths, sm_scale=sm_scale,
                                  block_k=block_k, impl=impl,
                                  interpret=interpret)


def window_ring_block(kv_heads: int, rows: int, width: int,
                      itemsize: int) -> int:
    """Rows of every key head a grid step of
    :func:`window_decode_attention` takes: the whole ring where its key
    and value blocks, double-buffered, fit ``PAGED_KV_VMEM_BUDGET`` (128
    keys on 8 heads of 256 + 128: 1.5 MiB); else the largest power-of-two
    share of it that does (4,096 keys on 8 heads of 128 + 128 would be
    32 MiB: 512 rows, 4 MiB)."""
    block = rows
    while (2 * kv_heads * block * width * itemsize > PAGED_KV_VMEM_BUDGET
           and block % 2 == 0):
        block //= 2
    return block


def window_decode_attention(q, k_ring, v_ring, lengths, sink, *, base=0,
                            sm_scale: Optional[float] = None,
                            impl: str = "pallas",
                            interpret: Optional[bool] = None):
    """Single-query attention of a sliding-window layer over the ring of
    each slot's last ``T`` keys (``k_ring`` [X, Hkv, T, Dk], ``v_ring``
    [X, Hkv, T, Dv]; position ``p`` at row ``p % T``), the new key
    already written.  ``lengths`` [S] counts the keys so far, itself
    included: ``min(lengths, T)`` rows are live.  ``sink`` [Hq] or None.

    :func:`decode_attention_slots`' body under the window kernel's name:
    a whole ring a grid step where that fits, else the ring walked in
    blocks of :func:`window_ring_block` rows with the online softmax, the
    blocks past a ring's live rows (one that has not wrapped yet) neither
    computed nor fetched."""
    X, Hkv, T, Dk = k_ring.shape
    return decode_attention_slots(
        q, k_ring, v_ring, jnp.minimum(lengths.astype(jnp.int32), T),
        sink=sink, base=base, sm_scale=sm_scale,
        block_k=window_ring_block(Hkv, T, Dk + v_ring.shape[-1],
                                  k_ring.dtype.itemsize),
        impl=impl, interpret=interpret, name=WINDOW_DECODE_ATTN_KERNEL)


# ---------------------------------------------------------------------------
# paged decode attention: page-table indirection over a flat pool
# ---------------------------------------------------------------------------


def paged_gather(pool: jnp.ndarray,
                 page_table: jnp.ndarray) -> jnp.ndarray:
    """Materialize a slot-major dense view of the page pool:
    ``pool [P, H, page_len, Dh]`` gathered through
    ``page_table [S, max_pages]`` -> ``[S, H, max_pages*page_len, Dh]``.

    Position ``p`` of slot ``s`` is row ``p % page_len`` of page
    ``page_table[s, p // page_len]`` — the layout contract every paged
    consumer (kernel, reference, prefill) shares.  ``jnp.take`` keeps
    the page table traced, so this is recompilation-free."""
    g = jnp.take(pool, page_table, axis=0)  # [S, M, H, page_len, Dh]
    S, M, H, L, Dh = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(S, H, M * L, Dh)


def paged_gather_scales(scales: jnp.ndarray,
                        page_table: jnp.ndarray) -> jnp.ndarray:
    """The scale-sidecar twin of :func:`paged_gather`:
    ``scales [P, H, page_len]`` -> ``[S, H, max_pages*page_len]`` —
    row ``p`` of the gathered view carries the scale its int8 K/V row
    was quantized with."""
    g = jnp.take(scales, page_table, axis=0)  # [S, M, H, page_len]
    S, M, H, L = g.shape
    return g.transpose(0, 2, 1, 3).reshape(S, H, M * L)


def dequantize_paged(pool: jnp.ndarray, scales: jnp.ndarray,
                     page_table: jnp.ndarray) -> jnp.ndarray:
    """Gather + dequantize an int8 pool dense: the interpretable
    definition of what a quantized page MEANS (``stored value = int8 *
    its row scale``) — the semantics anchor the fused kernels are
    verified against (tests/test_quant_serve.py)."""
    from ...inference.quantize import dequantize_rows
    return dequantize_rows(paged_gather(pool, page_table),
                           paged_gather_scales(scales, page_table))


def _scale_tile(scales: jnp.ndarray) -> jnp.ndarray:
    """Scale sidecar ``[P, H, page_len]`` as a lane-packed VMEM
    operand ``[P, H, 8, 128]``: lane ``r`` of every sublane holds row
    ``r``'s scale (page_len <= 128 — enforced eagerly by the serving
    config, re-checked here for direct kernel users).  The same
    broadcast-tile idiom the kernels already use for traced lengths —
    the fused arms read one (1, 1, 8, 128) block per (page, head)
    through the scalar-prefetch page table, exactly like the int8 data
    block it scales.

    COST NOTE: this operand is rebuilt inside every compiled call (a
    pad + sublane broadcast over the whole pool, 2·P·H·4KiB per layer
    per tick) — transient bandwidth, not HBM capacity; the sidecar the
    cache STORES stays the compact ``[P, H, page_len]`` (storing the
    kernel layout would cost 8-128x the sidecar bytes and eat the
    capacity win this arm exists for).  The hardware refinement
    (docs/serving.md) is to pack the scale row into a spare lane of
    the int8 page so it streams with the data it scales."""
    Pp, Hh, pl = scales.shape
    if pl > 128:
        raise ValueError(
            f"quantized pages support page_len <= 128 (one scale lane "
            f"per row), got page_len={pl}")
    lanes = jnp.pad(scales.astype(jnp.float32),
                    ((0, 0), (0, 0), (0, 128 - pl)))
    return jnp.broadcast_to(lanes[:, :, None, :], (Pp, Hh, 8, 128))


PAGED_DECODE_ATTN_KERNEL = "ds_paged_decode_attn"
#: the fused-dequant arm is another kernel body (two more operands)
PAGED_DECODE_ATTN_INT8_KERNEL = PAGED_DECODE_ATTN_KERNEL + "_int8"

#: VMEM the fp paged kernel spends on the K and V pages of a block: the
#: page blocks in flight (two pools, double-buffered by the pipeline)
#: and the two buffers they are packed into.  Three eighths of the
#: 16 MiB a Mosaic kernel may use by default on a v5e; the scores and
#: probabilities of a block, as wide as those buffers are long, take
#: about a third as much again.
PAGED_KV_VMEM_BUDGET = 6 * 1024 * 1024
_LANES = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _paged_block_layout(heads, page_len, head_dim, itemsize):
    """How a page sits in the kernel's packed buffer: ``fold`` keys side
    by side in a row's lanes (two 64-wide keys fill the 128 lanes the
    MXU contracts over), the heads of one key group on ``head_rows``
    sublane rows (a whole number of VMEM tiles, so every store is
    aligned).  Returns (fold, head_rows, rows a page, lanes a row)."""
    fold = _LANES // head_dim if head_dim < _LANES else 1
    if page_len % fold:
        fold = 1
    head_rows = _round_up(heads, 8 * 4 // itemsize)
    return fold, head_rows, page_len // fold * head_rows, fold * head_dim


def _pages_by_head(heads: int, q_heads: Optional[int],
                   head_major: bool) -> bool:
    """Whether a page rests ``[H, page_len, Dh]``, as the pool's shape
    says: always under grouped keys; with as many key heads as query
    heads where the caller says so (``head_major``)."""
    return head_major or q_heads not in (None, heads)


def paged_decode_arm(heads: int, page_len: int, head_dim: int,
                     itemsize: int, q_heads: Optional[int] = None,
                     head_major: bool = False) -> str:
    """Which body of the fp paged kernel a pool of this shape runs.
    ``'direct'`` where a page at rest, ``[page_len, H, Dh]``, already is
    the rows of the packed buffer (no fold, no padded head rows, whole
    lanes): the fetched page is the matmul operand.  ``'packed'``
    everywhere else.  A function of the pool's shape alone.  Grouped
    keys (``q_heads`` query heads on ``heads`` key heads) have the
    direct body only: their page at rest is ``[H, page_len, Dh]``; so has
    a pool that rests that way at a group of one (``head_major``)."""
    if _pages_by_head(heads, q_heads, head_major):
        return "direct"
    fold, head_rows, _, _ = _paged_block_layout(
        heads, page_len, head_dim, itemsize)
    direct = fold == 1 and head_rows == heads and head_dim % _LANES == 0
    return "direct" if direct else "packed"


def paged_page_vmem_bytes(heads: int, page_len: int, head_dim: int,
                          itemsize: int) -> int:
    """VMEM one page of a block costs the fp paged kernel: its K and V
    blocks in flight, double-buffered, and (packed arm only) its rows of
    the two packed buffers (lanes padded to 128 in both)."""
    _, head_rows, rows, width = _paged_block_layout(
        heads, page_len, head_dim, itemsize)
    in_flight = page_len * head_rows * _round_up(head_dim, _LANES)
    direct = paged_decode_arm(heads, page_len, head_dim, itemsize) == "direct"
    packed = 0 if direct else rows * _round_up(width, _LANES)
    return (4 * in_flight + 2 * packed) * itemsize


def paged_pages_per_block(heads: int, page_len: int, head_dim: int,
                          itemsize: int, max_pages: int,
                          q_heads: Optional[int] = None,
                          v_head_dim: Optional[int] = None,
                          head_major: bool = False) -> int:
    """Pages one grid step of the fp paged kernel attends: the largest
    power of two that fits ``PAGED_KV_VMEM_BUDGET``, at most
    ``max_pages``.  A function of the pool's shape alone.  With grouped
    keys (or ``head_major``) a page in flight is its own bytes (K and V,
    double-buffered): ``[H, page_len, Dh]`` pads nothing; there the
    values may be ``v_head_dim`` wide where the keys are ``head_dim``."""
    if _pages_by_head(heads, q_heads, head_major):
        page_bytes = 2 * heads * page_len * itemsize * (
            head_dim + (v_head_dim or head_dim))
    else:
        page_bytes = paged_page_vmem_bytes(heads, page_len, head_dim,
                                           itemsize)
    fit = max(1, min(PAGED_KV_VMEM_BUDGET // page_bytes, max_pages))
    return 1 << (fit.bit_length() - 1)


@functools.lru_cache(maxsize=None)
def _paged_block_positions(heads, page_len, ppb, fold, head_rows):
    """``[fold*head_rows, ppb*rows]`` int32: where in its block (0 ..
    ppb*page_len-1) the key sits whose score a (query row, buffer row)
    pair is.  Query row ``a*head_rows + h`` carries head ``h``'s query
    in lane group ``a`` and zeros elsewhere; buffer row ``(i, r, h')``
    holds head ``h'``'s keys ``r*fold .. r*fold+fold-1`` of page ``i``,
    one a lane group.  So the pair is key ``r*fold + a`` of page ``i`` where
    ``h' == h``; every other pair, the padding heads and the padding
    rows get a position no length reaches."""
    import numpy as np
    groups = page_len // fold
    pos = np.full((fold, head_rows, ppb, groups, head_rows), 2 ** 30,
                  np.int32)
    h = np.arange(heads)
    for a in range(fold):
        for i in range(ppb):
            for r in range(groups):
                pos[a, h, i, r, h] = i * page_len + r * fold + a
    return pos.reshape(fold * head_rows, -1)


def _decode_paged_kernel(pt_ref, len_ref, q_ref, pos_ref, *refs,
                         sm_scale: float, ppb: int):
    """One grid step = one slot, ALL heads, ``ppb`` pages, each page an
    operand of its own whose block index the page table gives: the
    pipeline fetches a block's pages while the block before computes,
    and fetches nothing while the index repeats (dead table entries all
    name the scratch page).  A block wholly beyond the slot's length
    runs nothing."""
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, k_buf, v_buf, m_scr, l_scr, acc_scr = refs[2 * ppb:]
    s, j = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    _, hp, head_dim = o_ref.shape   # the heads, padded to whole tiles
    fold = q_ref.shape[1] // hp
    _, page_len, heads, _ = k_refs[0].shape
    groups = page_len // fold
    length = len_ref[s]

    @pl.when((s == 0) & (j == 0))
    def _clear():
        # no page is ever packed into the rows between the heads of two
        # key groups: whatever VMEM held there would reach the value
        # matmul times 0
        v_buf[:] = jnp.zeros_like(v_buf)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * ppb * page_len < length)
    def _live():
        # a page arrives as [page_len, H, Dh]; ``fold`` keys of a head
        # go side by side into one row of the packed buffer
        for refs_, buf in ((k_refs, k_buf), (v_refs, v_buf)):
            for i, ref in enumerate(refs_):
                for r in range(groups):
                    at = (i * groups + r) * hp
                    buf[at:at + heads, :] = jnp.concatenate(
                        [ref[0, r * fold + a] for a in range(fold)], axis=1)

        # Every head in two matmuls.  Scores of all (query row, buffer
        # row) pairs, of which pos_ref keeps a head's own live keys;
        # the masked probabilities are then already the block-diagonal
        # operand of the value matmul.  The MXU's spare rows cost
        # nothing; 2*H single-row matmuls would.
        sc = jax.lax.dot_general(
            q_ref[0], k_buf[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        sc = jnp.where(pos_ref[...] < length - j * ppb * page_len,
                       sc, NEG_INF)                     # [fold*hp, C]

        def per_head(x, op):
            """Combine a head's ``fold`` query rows; back on every row."""
            parts = [x[a * hp:(a + 1) * hp] for a in range(fold)]
            return jnp.concatenate(
                [functools.reduce(op, parts)] * fold, axis=0)

        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, per_head(
            jnp.max(sc, axis=1, keepdims=True), jnp.maximum))
        # key j*bk of every head is live (the pl.when guard), so m_new
        # is a real score and the masked keys' exp underflows to 0
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, 0:1] + per_head(
                jnp.sum(p, axis=1, keepdims=True), jnp.add),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_buf.dtype), v_buf[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nb - 1)
    def _finalize():
        # query row a*hp + h gathered its keys' values in lane group a
        acc = functools.reduce(jnp.add, [
            acc_scr[a * hp:(a + 1) * hp, a * head_dim:(a + 1) * head_dim]
            for a in range(fold)])
        l = l_scr[0:hp, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # length 0 -> no block ran -> l == 0 -> exact zeros (free slots)
        o_ref[0] = jnp.where(l == 0.0, 0.0, acc / l_safe).astype(o_ref.dtype)


def _decode_paged_pallas(q, k_pages, v_pages, page_table, lengths, *,
                         sm_scale, interpret):
    P, H, page_len, Dh = k_pages.shape
    S, max_pages = page_table.shape
    itemsize = k_pages.dtype.itemsize
    fold, hp, rows, width = _paged_block_layout(H, page_len, Dh, itemsize)
    ppb = paged_pages_per_block(H, page_len, Dh, itemsize, max_pages)
    nb = -(-max_pages // ppb)
    # dead table entries name the scratch page 0, and so does the padding
    pt_flat = jnp.pad(page_table,
                      ((0, 0), (0, nb * ppb - max_pages))).reshape(-1)
    # the heads ride the sublane rows (25 -> 32), once for each of the
    # ``fold`` keys a buffer row holds, the query in that key's lanes
    qf = jnp.pad(q, ((0, 0), (0, hp - H), (0, 0)))
    qf = jnp.einsum("shd,ab->sahbd", qf, jnp.eye(fold, dtype=q.dtype))
    qf = qf.reshape(S, fold * hp, width)
    pos = jnp.asarray(_paged_block_positions(H, page_len, ppb, fold, hp))

    def page_spec(i):
        return pl.BlockSpec(
            (1, page_len, H, Dh),
            lambda s, j, pt, ln: (pt[(s * nb + j) * ppb + i], 0, 0, 0))

    pages = [page_spec(i) for i in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nb),
        in_specs=[pl.BlockSpec((1, fold * hp, width),
                               lambda s, j, *_: (s, 0, 0)),
                  pl.BlockSpec(pos.shape, lambda s, j, *_: (0, 0)),
                  *pages, *pages],
        out_specs=pl.BlockSpec((1, hp, Dh), lambda s, j, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((ppb * rows, width), k_pages.dtype),
            pltpu.VMEM((ppb * rows, width), v_pages.dtype),
            pltpu.VMEM((fold * hp, 128), jnp.float32),
            pltpu.VMEM((fold * hp, 128), jnp.float32),
            pltpu.VMEM((fold * hp, width), jnp.float32),
        ],
    )
    # [P, page_len, H, Dh]: the layout XLA gives the pool for the cache
    # write just before, so the kernel's operand costs no copy of it
    kt, vt = (x.transpose(0, 2, 1, 3) for x in (k_pages, v_pages))
    out = pl.pallas_call(
        functools.partial(_decode_paged_kernel, sm_scale=sm_scale, ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, hp, Dh), q.dtype),
        interpret=interpret,
        name=PAGED_DECODE_ATTN_KERNEL,
    )(pt_flat, lengths, qf, pos, *[kt] * ppb, *[vt] * ppb)
    return out[:, :H]


def _decode_paged_direct_kernel(pt_ref, len_ref, q_ref, pos_ref,
                                k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
                                state_ref, m_scr, l_scr, acc_scr,
                                *, sm_scale: float,
                                page_len: Optional[int] = None):
    """The direct arm: the same grid step (one slot, all heads, ``ppb``
    pages), for pools whose page at rest, ``[page_len, H, Dh]``, already
    is rows ``(r, h)`` of the buffer the matmuls read.  The pools stay in
    HBM; a live step copies the LIVE pages of the next live block (the
    next slot's first at a slot's end) by the page table into the other
    half of a double buffer, then waits for its own pages and attends
    them where they landed.  A dead step copies and waits for nothing.
    ``state_ref`` passes from step to step which half the next live step
    reads, and whether any step has started a copy yet."""
    s, j = pl.program_id(0), pl.program_id(1)
    slots, nb = pl.num_programs(0), pl.num_programs(1)
    ppb = k_buf.shape[1]
    if page_len is None:
        page_len = k_buf.shape[2]       # a page [page_len, H, Dh]
    bk = ppb * page_len
    length = len_ref[s]

    def for_live_pages(slot, blk, fn):
        """``fn(i)`` for each page of the block that holds a live key."""
        left = len_ref[slot] - blk * bk
        jax.lax.fori_loop(0, jnp.minimum(ppb, (left + page_len - 1)
                                         // page_len),
                          lambda i, _: fn(i), None)

    pools = ((k_hbm, k_buf), (v_hbm, v_buf))

    def fetch(slot, blk, half):
        def start(i):
            page = pt_ref[(slot * nb + blk) * ppb + i]
            for which, (pool, buf) in enumerate(pools):
                pltpu.make_async_copy(pool.at[page], buf.at[half, i],
                                      sems.at[which, half]).start()
        for_live_pages(slot, blk, start)

    def wait(which, half):
        # a wait takes one page's bytes off the semaphore: whose, is the
        # same to it
        pool, buf = pools[which]
        for_live_pages(s, j, lambda i: pltpu.make_async_copy(
            pool.at[0], buf.at[half, i], sems.at[which, half]).wait())

    @pl.when((s == 0) & (j == 0))
    def _clear():
        # the rows of a partly live block's dead pages are never copied
        # into: whatever VMEM held there would reach the value matmul
        # times 0
        v_buf[...] = jnp.zeros_like(v_buf)
        state_ref[0] = 0
        state_ref[1] = 0

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * bk < length)
    def _live():
        half = state_ref[0]
        state_ref[0] = 1 - half

        @pl.when(state_ref[1] == 0)
        def _first():
            # the grid's first live step: nobody fetched ahead for it
            fetch(s, j, half)
            state_ref[1] = 1

        more = (j + 1 < nb) & ((j + 1) * bk < length)
        # the slot's next block, or the first block of the next slot
        # that holds a key (free slots ride along in the batch)
        next_s = jax.lax.while_loop(
            lambda t: (t < slots) & (len_ref[jnp.minimum(t, slots - 1)] == 0),
            lambda t: t + 1, jnp.where(more, s, s + 1))

        @pl.when(next_s < slots)
        def _ahead():
            fetch(next_s, jnp.where(more, j + 1, 0), 1 - half)

        rows = k_buf.shape[1] * k_buf.shape[2] * k_buf.shape[3]
        wait(0, half)
        sc = jax.lax.dot_general(
            q_ref[0], k_buf[half].reshape(rows, k_buf.shape[-1]),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        # pos_ref keeps a head's own keys; a dead page's rows hold
        # whatever an earlier block left there
        sc = jnp.where(pos_ref[...] < length - j * bk, sc, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        # key j*bk of every head is live (the pl.when guard), so m_new
        # is a real score and the masked keys' exp underflows to 0
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        wait(1, half)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_buf.dtype), v_buf[half].reshape(rows, v_buf.shape[-1]),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # length 0 -> no block ran -> l == 0 -> exact zeros (free slots)
        o_ref[0] = jnp.where(l == 0.0, 0.0,
                             acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_paged_direct_pallas(q, k_pages, v_pages, page_table, lengths, *,
                                sm_scale, interpret):
    P, H, page_len, Dh = k_pages.shape
    S, max_pages = page_table.shape
    ppb = paged_pages_per_block(H, page_len, Dh, k_pages.dtype.itemsize,
                                max_pages)
    nb = -(-max_pages // ppb)
    pt_flat = jnp.pad(page_table,
                      ((0, 0), (0, nb * ppb - max_pages))).reshape(-1)
    pos = jnp.asarray(_paged_block_positions(H, page_len, ppb, 1, H))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nb),
        in_specs=[pl.BlockSpec((1, H, Dh), lambda s, j, *_: (s, 0, 0)),
                  pl.BlockSpec(pos.shape, lambda s, j, *_: (0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, Dh), lambda s, j, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_len, H, Dh), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_len, H, Dh), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    # [P, page_len, H, Dh], as in the packed arm: no copy of the pool
    kt, vt = (x.transpose(0, 2, 1, 3) for x in (k_pages, v_pages))
    return pl.pallas_call(
        functools.partial(_decode_paged_direct_kernel, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, Dh), q.dtype),
        # the double buffer and its parity pass from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=PAGED_DECODE_ATTN_KERNEL,
    )(pt_flat, lengths, q, pos, kt, vt)


@functools.lru_cache(maxsize=None)
def _grouped_block_positions(q_heads, kv_heads, page_len, ppb):
    """:func:`_paged_block_positions` for grouped keys: ``[q_heads,
    ppb*kv_heads*page_len]``.  Buffer row ``(i, g, r)`` holds key ``r``
    of page ``i`` of key head ``g``; query head ``h`` reads key head
    ``h // (q_heads // kv_heads)`` and no other."""
    import numpy as np
    pos = np.full((q_heads, ppb, kv_heads, page_len), 2 ** 30, np.int32)
    own = np.arange(q_heads) // (q_heads // kv_heads)
    at = np.arange(ppb)[:, None] * page_len + np.arange(page_len)[None, :]
    pos[np.arange(q_heads), :, own, :] = at[None]
    return pos.reshape(q_heads, -1)


def _decode_paged_grouped_pallas(q, k_pages, v_pages, page_table, lengths, *,
                                 sm_scale, interpret):
    """The direct body for ``Hq`` query heads on ``H`` key heads.  A page
    at rest is ``[H, page_len, Dh]`` (the pool as the engine holds it:
    no transpose), so a block's pages are rows ``(page, key head, key)``
    of the matmul operand and the ``Hq / H`` query heads of a key head
    are the rows that read it; the position table keeps every query head
    to its own key head's rows.  The values may be narrower than the
    keys (``v_pages [P, H, page_len, Dv]``): each pool has its own
    buffer, and the output is ``Dv`` wide."""
    P, H, page_len, Dh = k_pages.shape
    Dv = v_pages.shape[-1]
    S, max_pages = page_table.shape
    Hq = q.shape[1]
    ppb = paged_pages_per_block(H, page_len, Dh, k_pages.dtype.itemsize,
                                max_pages, v_head_dim=Dv, head_major=True)
    nb = -(-max_pages // ppb)
    pt_flat = jnp.pad(page_table,
                      ((0, 0), (0, nb * ppb - max_pages))).reshape(-1)
    pos = jnp.asarray(_grouped_block_positions(Hq, H, page_len, ppb))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nb),
        in_specs=[pl.BlockSpec((1, Hq, Dh), lambda s, j, *_: (s, 0, 0)),
                  pl.BlockSpec(pos.shape, lambda s, j, *_: (0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, Hq, Dv), lambda s, j, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, H, page_len, Dh), k_pages.dtype),
            pltpu.VMEM((2, ppb, H, page_len, Dv), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_paged_direct_kernel, sm_scale=sm_scale,
                          page_len=page_len),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=PAGED_DECODE_ATTN_KERNEL,
    )(pt_flat, lengths, q, pos, k_pages, v_pages)


def _decode_paged_int8_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref,
                              ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr,
                              *, sm_scale: float, page_len: int, heads: int):
    """The fused-dequant arm keeps the grid the fp arm left behind: one
    step for each (slot, head, page), the query an 8-row broadcast."""
    jk = pl.program_id(1)
    nk = pl.num_programs(1)
    slot = pl.program_id(0) // heads
    length = len_ref[slot]

    @pl.when(jk == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # whole page at or beyond the live length: nothing to do (its table
    # entry points at the scratch page — valid storage, dead data)
    @pl.when(jk * page_len < length)
    def _compute():
        q = q_ref[0]                                    # [8, d] broadcast
        # dequant folds into the score/prob columns: the scale is per
        # KEY ROW, so q·(k8*sk) == (q·k8)*sk and p·(v8*sv) == (p*sv)·v8
        # — the int8 page never materializes in fp
        k = k_ref[0, 0].astype(jnp.float32)             # [page_len, d]
        v = v_ref[0, 0].astype(jnp.float32)
        ks_row = ks_ref[0, 0][0:1, :page_len]           # [1, page_len]
        vs_row = vs_ref[0, 0][0:1, :page_len]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = s * ks_row
        k_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + jk * page_len
        s = jnp.where(k_ids < length, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            (p * vs_row).astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = jnp.where(l == 0.0, 0.0,
                             acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_paged_int8_pallas(q, k_pages, v_pages, page_table, lengths, *,
                              sm_scale, interpret, k_scale, v_scale):
    P, H, page_len, Dh = k_pages.shape
    S, max_pages = page_table.shape
    qf = jnp.broadcast_to(q.reshape(S * H, 1, Dh), (S * H, 8, Dh))
    pt_flat = page_table.reshape(-1)

    def page_block(g, j, pt, ln, H=H, M=max_pages):
        # the block for grid cell (g, j) is whatever page the slot's
        # table names; the scale rows ride the SAME indirection as the
        # int8 blocks they dequantize, as lane-packed (8, 128) tiles
        return (pt[(g // H) * M + j], g % H, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S * H, max_pages),
        in_specs=[
            pl.BlockSpec((1, 8, Dh), lambda g, j, pt, ln: (g, 0, 0)),
            pl.BlockSpec((1, 1, page_len, Dh), page_block),
            pl.BlockSpec((1, 1, page_len, Dh), page_block),
            pl.BlockSpec((1, 1, 8, 128), page_block),
            pl.BlockSpec((1, 1, 8, 128), page_block),
        ],
        out_specs=pl.BlockSpec((1, 8, Dh), lambda g, j, pt, ln: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_paged_int8_kernel, sm_scale=sm_scale,
                          page_len=page_len, heads=H),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S * H, 8, Dh), jnp.float32),
        interpret=interpret,
        name=PAGED_DECODE_ATTN_INT8_KERNEL,
    )(pt_flat, lengths, qf, k_pages, v_pages,
      _scale_tile(k_scale), _scale_tile(v_scale))
    return out[:, 0, :].reshape(S, H, Dh).astype(q.dtype)


def _check_quant_args(k_pages, k_scale, v_scale, what: str):
    """The fused-dequant contract both paged entry points share: the
    two scale sidecars come together and only over an int8 pool."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            f"{what}: k_scale and v_scale must be passed together "
            "(the fused-dequant arm scales both pools)")
    if k_scale is not None and k_pages.dtype != jnp.int8:
        raise ValueError(
            f"{what}: scale operands imply an int8 page pool, got "
            f"dtype {k_pages.dtype}")


def decode_attention_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray,
                           page_table: jnp.ndarray,
                           lengths: jnp.ndarray,
                           sm_scale: Optional[float] = None,
                           impl: str = "pallas",
                           interpret: Optional[bool] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None,
                           head_major: bool = False) -> jnp.ndarray:
    """Single-query attention over a PAGED KV pool (docs/serving.md).

    q: [S, H, Dh] — one new query token per slot; ``[S, Hq, Dh]``
        with ``Hq`` a multiple of the pool's ``H`` for grouped keys
        (query head ``h`` reads key head ``h // (Hq // H)``; fp pool
        only).
    k_pages, v_pages: [P, H, page_len, Dh] — the flat page pool; a
        slot's position ``p`` lives at row ``p % page_len`` of page
        ``page_table[s, p // page_len]``.  Under grouped keys the
        values may be narrower than the keys (``v_pages [..., Dv]``,
        output ``[S, Hq, Dv]``); ``sm_scale`` then defaults to the
        keys' stored width, so a caller whose keys are padded at rest
        passes its own.
    page_table: [S, max_pages] int32, TRACED — dead entries must hold a
        valid page id (the engine fills them with the scratch page 0);
        their data is masked, their streaming is a no-op read.
    lengths: [S] int32, TRACED — per-slot live KV length including the
        position this query's K/V was just written to.  0 = free slot
        -> exact-zero output.
    k_scale, v_scale: [P, H, page_len] fp32, TRACED — the quantized
        pool's per-row scale sidecars (serving.quantization.kv='int8';
        the pool is then int8 and dequant fuses into the kernel).
        None = the fp pool, byte-identical to the pre-quant programs.
    head_major: with ``Hq == H``, the pool RESTS as its shape says,
        ``[P, H, page_len, Dh]`` (``models/walked.py::PagePool`` writes it
        so), and the body that reads a page that way runs (grouped keys'
        own, at a group of one: nothing is transposed, and a head count
        that is no whole sublane tile pads no row).  Without it the pool
        of an ungrouped model is taken to rest ``[P, page_len, H, Dh]``
        under this shape (``models/olmoe.py``, GPT-2), and the transpose
        below cancels the caller's.  Grouped keys always rest by head.

    ``impl='dense'`` gathers the pool dense with ``jnp.take`` and runs
    :func:`decode_attention_reference` — values identical to the
    pre-page slot layout, the CPU-bitwise parity anchor; on the quant
    arm it dequantizes the gathered view first
    (:func:`dequantize_paged` — the semantics the fused kernel is
    verified against).  ``'pallas'`` is the scalar-prefetch kernel
    (interpret mode off-TPU)."""
    assert q.ndim == 3 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    P, H, page_len, Dh = k_pages.shape
    S, max_pages = page_table.shape
    Hq = q.shape[1]
    assert q.shape == (S, Hq, Dh) and Hq % H == 0, (q.shape, k_pages.shape)
    if v_pages.shape != k_pages.shape and (
            Hq == H or v_pages.shape[:3] != k_pages.shape[:3]):
        raise NotImplementedError(
            f"decode_attention_paged: keys {k_pages.shape} over values "
            f"{v_pages.shape}: two widths only under grouped keys")
    _check_quant_args(k_pages, k_scale, v_scale, "decode_attention_paged")
    if sm_scale is None:
        sm_scale = _default_scale(Dh)
    if Hq != H or head_major:
        if k_scale is not None:
            raise NotImplementedError(
                "decode_attention_paged: grouped keys have no int8 arm")
        if impl == "dense":
            kg, vg = (jnp.repeat(paged_gather(x, page_table), Hq // H,
                                 axis=1) for x in (k_pages, v_pages))
            return decode_attention_reference(q, kg, vg, lengths,
                                              sm_scale=sm_scale)
        if impl != "pallas":
            raise ValueError(
                f"decode_attention_paged impl={impl!r}: expected 'pallas' "
                "or 'dense'")
        return _decode_paged_grouped_pallas(
            q, k_pages, v_pages, page_table.astype(jnp.int32),
            lengths.astype(jnp.int32), sm_scale=sm_scale,
            interpret=_use_interpret() if interpret is None else interpret)
    if impl == "dense":
        if k_scale is not None:
            kg = dequantize_paged(k_pages, k_scale, page_table)
            vg = dequantize_paged(v_pages, v_scale, page_table)
        else:
            kg = paged_gather(k_pages, page_table)
            vg = paged_gather(v_pages, page_table)
        return decode_attention_reference(q, kg, vg, lengths,
                                          sm_scale=sm_scale)
    if impl != "pallas":
        raise ValueError(
            f"decode_attention_paged impl={impl!r}: expected 'pallas' "
            "or 'dense'")
    if interpret is None:
        interpret = _use_interpret()
    page_table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    if k_scale is not None:
        return _decode_paged_int8_pallas(
            q, k_pages, v_pages, page_table, lengths, sm_scale=sm_scale,
            interpret=interpret, k_scale=k_scale, v_scale=v_scale)
    arm = {"direct": _decode_paged_direct_pallas,
           "packed": _decode_paged_pallas}[
               paged_decode_arm(H, page_len, Dh, k_pages.dtype.itemsize)]
    return arm(q, k_pages, v_pages, page_table, lengths, sm_scale=sm_scale,
               interpret=interpret)


# ---------------------------------------------------------------------------
# latent attention (MLA) in absorbed form: ONE pool of latent rows
# ---------------------------------------------------------------------------

LATENT_DECODE_ATTN_KERNEL = "ds_latent_decode_attn"


def latent_pages_per_block(page_len: int, width: int, itemsize: int,
                           max_pages: int) -> int:
    """Pages one grid step of the latent kernel attends: the largest
    power of two whose double buffer fits ``PAGED_KV_VMEM_BUDGET``, at
    most ``max_pages``.  A page in flight is its own bytes, once: the
    rows that score are the rows that are summed."""
    fit = max(1, min(PAGED_KV_VMEM_BUDGET // (2 * page_len * width
                                              * itemsize), max_pages))
    return 1 << (fit.bit_length() - 1)


def latent_decode_reference(q, pool, page_table, lengths, value_dim: int,
                            sm_scale: float, allowed=None):
    """Dense jnp reference of :func:`latent_decode_attention`: the slot's
    pages gathered, every head against the same rows, the values their
    first ``value_dim`` lanes; with ``allowed`` [S, cap] only the positions
    it names.  A slot with no key to read gives exact zeros."""
    S, max_pages = page_table.shape
    rows = pool[page_table].reshape(S, -1, pool.shape[-1])   # [S, cap, W]
    s = jnp.einsum("shw,stw->sht", q, rows.astype(q.dtype),
                   preferred_element_type=jnp.float32) * sm_scale
    live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] \
        < lengths.astype(jnp.int32)[:, None]
    if allowed is not None:
        live &= allowed
    live = live[:, None, :]
    s = jnp.where(live, s, jnp.finfo(jnp.float32).min)
    p = jnp.where(jnp.any(live, axis=-1, keepdims=True),
                  jax.nn.softmax(s, axis=-1), 0.0).astype(q.dtype)
    return jnp.einsum("sht,stv->shv", p,
                      rows[..., :value_dim].astype(q.dtype))


def _latent_decode_kernel(pt_ref, len_ref, q_ref, *refs, sm_scale: float,
                          value_dim: int, masked: bool):
    """One grid step = one slot, ALL heads, ``ppb`` pages of the ONE pool
    (the direct paged body's double buffer and hand-written page copies:
    ``_decode_paged_direct_kernel`` says how the halves pass from step to
    step).  A page ``[page_len, W]`` lands once and is read twice where
    it lies: all ``W`` lanes of its rows against every head's ``[q_lat ;
    q_rope]`` for the scores, their first ``value_dim`` lanes under the
    probabilities for the output.  A key's position is its row's place
    in the block: no head owns a row, so no position table.  ``masked``:
    one more operand ahead of the pool, the block's ``[1, bk]`` lanes of
    the caller's mask over positions (nonzero = this key may score)."""
    allowed_ref = refs[0] if masked else None
    kv_hbm, o_ref, buf, sems, state_ref, m_scr, l_scr, acc_scr = \
        refs[masked:]
    s, j = pl.program_id(0), pl.program_id(1)
    slots, nb = pl.num_programs(0), pl.num_programs(1)
    _, ppb, page_len, width = buf.shape
    bk = ppb * page_len
    length = len_ref[s]

    def for_live_pages(slot, blk, fn):
        left = len_ref[slot] - blk * bk
        jax.lax.fori_loop(0, jnp.minimum(ppb, (left + page_len - 1)
                                         // page_len),
                          lambda i, _: fn(i), None)

    def fetch(slot, blk, half):
        for_live_pages(slot, blk, lambda i: pltpu.make_async_copy(
            kv_hbm.at[pt_ref[(slot * nb + blk) * ppb + i]],
            buf.at[half, i], sems.at[half]).start())

    @pl.when((s == 0) & (j == 0))
    def _clear():
        # a partly live block's dead pages are never copied into: what
        # VMEM held there would reach the value matmul times 0
        buf[...] = jnp.zeros_like(buf)
        state_ref[0] = 0
        state_ref[1] = 0

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * bk < length)
    def _live():
        half = state_ref[0]
        state_ref[0] = 1 - half

        @pl.when(state_ref[1] == 0)
        def _first():
            fetch(s, j, half)
            state_ref[1] = 1

        more = (j + 1 < nb) & ((j + 1) * bk < length)
        next_s = jax.lax.while_loop(
            lambda t: (t < slots) & (len_ref[jnp.minimum(t, slots - 1)] == 0),
            lambda t: t + 1, jnp.where(more, s, s + 1))

        @pl.when(next_s < slots)
        def _ahead():
            fetch(next_s, jnp.where(more, j + 1, 0), 1 - half)

        # a wait takes one page's bytes off the semaphore: whose, is the
        # same to it
        for_live_pages(s, j, lambda i: pltpu.make_async_copy(
            kv_hbm.at[0], buf.at[half, i], sems.at[half]).wait())
        rows = buf[half].reshape(bk, width)
        sc = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            at = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            sc = jnp.where((at < length - j * bk) & (allowed_ref[0] != 0),
                           sc, NEG_INF)
        else:
            at = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(at < length - j * bk, sc, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        if masked:
            # no key of the slot may have scored yet: m_new is then the
            # floor itself, and against 0 the floored keys' exp is still
            # 0 (alpha is exp(0) = 1 over sums that are 0)
            p = jnp.exp(sc - jnp.where(m_new == NEG_INF, 0.0, m_new))
        else:
            # key j*bk is live (the pl.when guard), so m_new is a real
            # score and the masked keys' exp underflows to 0
            p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_dim],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # length 0 -> no block ran -> l == 0 -> exact zeros (free slots)
        o_ref[0] = jnp.where(l == 0.0, 0.0,
                             acc_scr[:] / l_safe).astype(o_ref.dtype)


def latent_decode_attention(q: jnp.ndarray, pool: jnp.ndarray,
                            page_table: jnp.ndarray, lengths: jnp.ndarray,
                            value_dim: int, *, sm_scale: float,
                            impl: str = "pallas",
                            interpret: Optional[bool] = None,
                            name: str = LATENT_DECODE_ATTN_KERNEL,
                            allowed: Optional[jnp.ndarray] = None,
                            pages_per_block: Optional[int] = None
                            ) -> jnp.ndarray:
    """Single-query latent attention (MLA, absorbed form) over ONE paged
    pool, the kernel ``ds_latent_decode_attn`` (or ``name``, for a caller
    whose pool is not the cache itself and whose time is read apart).

    q: [S, H, W]: a head's ``[q_lat ; q_rope]``, zeros in the lanes the
        rows pad.
    pool: [P, page_len, W]: one row a token, ``[c_kv ; k_rope]`` after
        the norm and the rotation, shared by every head; a slot's
        position ``p`` is row ``p % page_len`` of page ``page_table[s,
        p // page_len]``.  The values are the rows' first ``value_dim``
        lanes.
    page_table [S, max_pages], lengths [S]: traced, as
        :func:`decode_attention_paged` takes them.  ``sm_scale`` is the
        caller's: the stored width says nothing of the head it came from.
    allowed: None, or bool ``[S, max_pages * page_len]`` over a slot's
        POSITIONS: a key scores only where it is set (and under the
        slot's length); the others have weight 0, a block or a slot with
        none set included.  The kernel then takes one more operand, a
        block's lanes of the mask a grid step; without it the call is the
        one it always was.
    pages_per_block: pages a grid step attends; None:
        :func:`latent_pages_per_block`'s (a power of two).

    Returns ``[S, H, value_dim]``; a slot with no key to read (length 0,
    nothing allowed) gives exact zeros.  ``impl='dense'`` is
    :func:`latent_decode_reference`."""
    assert q.ndim == 3 and pool.ndim == 3, (q.shape, pool.shape)
    P, page_len, W = pool.shape
    S, max_pages = page_table.shape
    H = q.shape[1]
    assert q.shape == (S, H, W) and value_dim <= W, (q.shape, pool.shape)
    if impl == "dense":
        return latent_decode_reference(q, pool, page_table, lengths,
                                       value_dim, sm_scale, allowed)
    if impl != "pallas":
        raise ValueError(f"latent_decode_attention impl={impl!r}: expected "
                         "'pallas' or 'dense'")
    if interpret is None:
        interpret = _use_interpret()
    ppb = pages_per_block or latent_pages_per_block(
        page_len, W, pool.dtype.itemsize, max_pages)
    nb = -(-max_pages // ppb)
    bk = ppb * page_len
    pt_flat = jnp.pad(page_table.astype(jnp.int32),
                      ((0, 0), (0, nb * ppb - max_pages))).reshape(-1)
    mask, mask_spec = [], []
    if allowed is not None:
        assert allowed.shape == (S, max_pages * page_len), allowed.shape
        # a grid step's lanes: block j of slot s is row s * nb + j; a block
        # past the slot's live rows names the last live one, so nothing is
        # fetched for it
        mask = [jnp.pad(allowed.astype(jnp.int32),
                        ((0, 0), (0, nb * bk - allowed.shape[1])))
                .reshape(S * nb, 1, bk)]
        mask_spec = [pl.BlockSpec((1, 1, bk), lambda s, j, pt, ln: (
            s * nb + jnp.minimum(j, jnp.maximum(ln[s] - 1, 0) // bk), 0, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nb),
        in_specs=[pl.BlockSpec((1, H, W), lambda s, j, *_: (s, 0, 0)),
                  *mask_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, value_dim), lambda s, j, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_len, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, value_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, sm_scale=sm_scale,
                          value_dim=value_dim, masked=bool(mask)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, value_dim), q.dtype),
        # the double buffer and its parity pass from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(pt_flat, lengths.astype(jnp.int32), q, *mask, pool)


# ---------------------------------------------------------------------------
# latent attention inside a window: a RING of latent rows by slot
# ---------------------------------------------------------------------------

WINDOW_LATENT_DECODE_ATTN_KERNEL = "ds_window_latent_decode_attn"


def ring_granule(rows: int) -> int:
    """Rows one copy of :func:`window_latent_decode_attention` brings in:
    the largest of 64, 32, 16, 8 that divides the ring's ``rows`` at rest
    (64 at a ring of 576: four bfloat16 tiles)."""
    for g in (64, 32, 16, 8):
        if rows % g == 0:
            return g
    raise ValueError(f"a latent ring of {rows} rows at rest: not whole "
                     "granules of 8")


def window_latent_decode_attention(
        q: jnp.ndarray, rings: jnp.ndarray, lengths: jnp.ndarray,
        value_dim: int, *, base=0, sm_scale: float, impl: str = "pallas",
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Single-query latent attention (absorbed form) over each slot's own
    RING of latent rows, the kernel ``ds_window_latent_decode_attn``.

    q: [S, H, W] a head's ``[q_lat ; q_rope]``, zeros in the lanes the
        rows pad.
    rings: [X, R, W]: slot ``s`` reads ring ``base + s`` (``base``: a
        layer's place in the stacked rings), whose rows ``0 ..
        lengths[s] - 1`` are live: one ``[c_kv ; k_rope]`` row a
        position, the window's positions in ANY order (a softmax does
        not ask which: position ``p`` lies at row ``p % window``, so a
        ring that has wrapped is live in all its ``window`` rows and one
        that has not in its first ``length``).  ``R`` is whole granules
        (:func:`ring_granule`).
    lengths: [S] int32, at most the window; 0: the slot is not read.

    It is :func:`latent_decode_attention`'s body under another name, the
    ring presented as the slot's own pages of one granule each, ALL of
    them one grid step (a ring of 576 rows of 1,152 lanes is 1.33 MB in
    bfloat16, 2.65 MB the double buffer: inside ``PAGED_KV_VMEM_BUDGET``;
    a ring that is not walks in blocks of a power of two of them): float32
    scores and softmax, the granules past a slot's length neither copied
    nor (their block) computed, the next live slot's ring in flight while
    this one's is attended.  Returns ``[S, H, value_dim]``; a slot of
    length 0 gives exact zeros."""
    X, R, W = rings.shape
    S = q.shape[0]
    g = ring_granule(R)
    per = R // g
    table = (base + jnp.arange(S, dtype=jnp.int32))[:, None] * per \
        + jnp.arange(per, dtype=jnp.int32)[None, :]
    fits = 2 * R * W * rings.dtype.itemsize <= PAGED_KV_VMEM_BUDGET
    return latent_decode_attention(
        q, rings.reshape(X * per, g, W), table, lengths, value_dim,
        sm_scale=sm_scale, impl=impl, interpret=interpret,
        name=WINDOW_LATENT_DECODE_ATTN_KERNEL,
        pages_per_block=per if fits else None)


# ---------------------------------------------------------------------------
# learned sparse attention (DeepSeek sparse attention over latent rows): an
# indexer scores a slot's whole context, the largest ``k`` scores name the
# rows the real attention reads
# ---------------------------------------------------------------------------

INDEX_SCORE_KERNEL = "ds_index_score"
SPARSE_LATENT_DECODE_ATTN_KERNEL = "ds_sparse_latent_decode_attn"


def index_score_reference(q, w, pool, page_table, lengths):
    """Dense jnp reference of :func:`index_score`."""
    S = page_table.shape[0]
    keys = pool[page_table].reshape(S, -1, pool.shape[-1])   # [S, cap, D]
    s = jnp.einsum("sjd,std->sjt", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    score = jnp.sum(jnp.maximum(s, 0.0)
                    * w.astype(jnp.float32)[:, :, None], axis=1)
    live = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :] \
        < lengths.astype(jnp.int32)[:, None]
    return jnp.where(live, score, -jnp.inf)


def _index_score_kernel(pt_ref, len_ref, q_ref, w_ref, k_hbm, o_ref, buf,
                        sems, state_ref):
    """One grid step = one slot, ALL indexer heads, ``ppb`` pages of the
    indexer keys (``_latent_decode_kernel``'s double buffer and
    hand-written page copies).  A page ``[page_len, D]`` lands once:
    every head's query against its rows, ReLU, the heads' weighted sum,
    one float32 score a row.  Rows past the slot's length score -inf,
    whatever the buffer held there."""
    s, j = pl.program_id(0), pl.program_id(1)
    slots, nb = pl.num_programs(0), pl.num_programs(1)
    _, ppb, page_len, width = buf.shape
    bk = ppb * page_len
    length = len_ref[s]

    def for_live_pages(slot, blk, fn):
        left = len_ref[slot] - blk * bk
        jax.lax.fori_loop(0, jnp.minimum(ppb, (left + page_len - 1)
                                         // page_len),
                          lambda i, _: fn(i), None)

    def fetch(slot, blk, half):
        for_live_pages(slot, blk, lambda i: pltpu.make_async_copy(
            k_hbm.at[pt_ref[(slot * nb + blk) * ppb + i]],
            buf.at[half, i], sems.at[half]).start())

    @pl.when((s == 0) & (j == 0))
    def _clear():
        state_ref[0] = 0
        state_ref[1] = 0

    o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)

    @pl.when(j * bk < length)
    def _live():
        half = state_ref[0]
        state_ref[0] = 1 - half

        @pl.when(state_ref[1] == 0)
        def _first():
            fetch(s, j, half)
            state_ref[1] = 1

        more = (j + 1 < nb) & ((j + 1) * bk < length)
        next_s = jax.lax.while_loop(
            lambda t: (t < slots) & (len_ref[jnp.minimum(t, slots - 1)] == 0),
            lambda t: t + 1, jnp.where(more, s, s + 1))

        @pl.when(next_s < slots)
        def _ahead():
            fetch(next_s, jnp.where(more, j + 1, 0), 1 - half)

        for_live_pages(s, j, lambda i: pltpu.make_async_copy(
            k_hbm.at[0], buf.at[half, i], sems.at[half]).wait())
        rows = buf[half].reshape(bk, width)
        sc = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [heads, bk]
        score = jnp.sum(jnp.maximum(sc, 0.0) * w_ref[0], axis=0,
                        keepdims=True)                       # [1, bk]
        at = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
        o_ref[0] = jnp.where(at < length - j * bk, score, -jnp.inf)


def index_score(q: jnp.ndarray, w: jnp.ndarray, pool: jnp.ndarray,
                page_table: jnp.ndarray, lengths: jnp.ndarray, *,
                impl: str = "pallas",
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """The indexer's score of every cached position of every slot, the
    kernel ``ds_index_score``: ``I[s, t] = sum_j w[s, j] * relu(q[s, j] .
    key[s, t])`` in float32.

    q: [S, J, D] the slot's indexer queries (rotated); w: [S, J] float32,
        the heads' weights with every scale folded in.
    pool: [P, page_len, D]: one indexer key a token (normed, rotated), a
        slot's position ``p`` at row ``p % page_len`` of page
        ``page_table[s, p // page_len]``.
    page_table [S, max_pages], lengths [S]: traced.

    Returns ``[S, max_pages * page_len]`` float32, ``-inf`` at and past a
    slot's length.  ``impl='dense'`` is :func:`index_score_reference`."""
    P, page_len, D = pool.shape
    S, max_pages = page_table.shape
    J = q.shape[1]
    assert q.shape == (S, J, D) and w.shape == (S, J), (q.shape, w.shape)
    if impl == "dense":
        return index_score_reference(q, w, pool, page_table, lengths)
    if impl != "pallas":
        raise ValueError(f"index_score impl={impl!r}: expected 'pallas' or "
                         "'dense'")
    if interpret is None:
        interpret = _use_interpret()
    ppb = latent_pages_per_block(page_len, D, pool.dtype.itemsize, max_pages)
    nb = -(-max_pages // ppb)
    bk = ppb * page_len
    pt_flat = jnp.pad(page_table.astype(jnp.int32),
                      ((0, 0), (0, nb * ppb - max_pages))).reshape(-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nb),
        in_specs=[pl.BlockSpec((1, J, D), lambda s, j, *_: (s, 0, 0)),
                  pl.BlockSpec((1, J, 1), lambda s, j, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, bk), lambda s, j, *_: (s, 0, j)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_len, D), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        _index_score_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, nb * bk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=INDEX_SCORE_KERNEL,
    )(pt_flat, lengths.astype(jnp.int32), q,
      w.astype(jnp.float32)[:, :, None], pool)
    return out[:, 0, :max_pages * page_len]


def sparse_latent_decode_attention(
        q: jnp.ndarray, pool: jnp.ndarray, page_table: jnp.ndarray,
        lengths: jnp.ndarray, allowed: jnp.ndarray, value_dim: int, *,
        sm_scale: float, impl: str = "pallas",
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Single-query latent attention (absorbed form) over the rows an
    indexer PICKED, read where they lie: :func:`latent_decode_attention`
    under the name ``ds_sparse_latent_decode_attn`` walks each slot's whole
    context in the pool (``pool``, ``page_table``, ``lengths`` as it takes
    them) under the picks as a mask over positions (``allowed`` bool ``[S,
    max_pages * page_len]``, e.g. ``models/glm_dsa.py::_pick_mask`` of the
    indexer's scores).  A row that was not picked is fetched and has weight
    0: the softmax is over the picked rows alone, in float32.  Returns
    ``[S, H, value_dim]``; a slot with nothing picked gives exact zeros.

    The cost is the LIVE rows', not the picks'.  Fetching the picked rows
    by index first is XLA's gather (Mosaic takes no copy of one row out of
    the pool: a slice of an HBM array along its rows "must be aligned to
    tiling (8)", and a bfloat16 tile is 16 rows; compiled for a described
    v5e, PR 49), which moves 65,536 rows of 1,280 B in 1.04 ms, a tenth of
    the HBM peak, and needs a sort to say which; this kernel reads 32
    slots' whole contexts of ~7,700 rows in 0.49 ms (my chip runs, PR 49
    and PR 50).  Up to the 14,300 rows a slot that 32 slots of GLM-5.2's
    pool can hold, reading all of them is the cheaper (``PERF.md`` section
    7 has the length past which it is not, and the cell that would need
    the gather back)."""
    return latent_decode_attention(
        q, pool, page_table, lengths, value_dim, sm_scale=sm_scale,
        impl=impl, interpret=interpret, allowed=allowed,
        name=SPARSE_LATENT_DECODE_ATTN_KERNEL)


# ---------------------------------------------------------------------------
# multi-query decode attention: the speculative verify arm
# ---------------------------------------------------------------------------


def decode_attention_multi_reference(q, k, v, lengths, sm_scale=None):
    """W stacked single-query references: ``q [S, H, W, Dh]`` against
    ``k/v [S, H, T, Dh]`` with PER-QUERY lengths ``[S, W]`` — query
    ``i`` is exactly ``decode_attention_reference(q[:, :, i], ...,
    lengths[:, i])``, so a verify pass is fp32-BITWISE against the W
    sequential decode ticks it replaces (the parity anchor of
    tests/test_spec_decode.py).  W is small and static (k+1 <= 9), so
    the unrolled loop stays one trace."""
    W = q.shape[2]
    outs = [decode_attention_reference(q[:, :, i], k, v, lengths[:, i],
                                       sm_scale=sm_scale)
            for i in range(W)]
    return jnp.stack(outs, axis=2)                      # [S, H, W, Dh]


def _rows_pad(w: int) -> int:
    """Query rows padded to the TPU sublane multiple (min one tile)."""
    return max(8, -(-w // 8) * 8)


def _multi_len_op(lengths: jnp.ndarray, wp: int) -> jnp.ndarray:
    """Per-query lengths [S, W] as a broadcast [S, Wp, 128] int32 tile
    (padding rows get length 0 -> exact-zero outputs, sliced away)."""
    S, W = lengths.shape
    lens = jnp.zeros((S, wp), jnp.int32)
    lens = lens.at[:, :W].set(lengths.astype(jnp.int32))
    return jnp.broadcast_to(lens[:, :, None], (S, wp, 128))


def _pad_queries(q: jnp.ndarray, wp: int) -> jnp.ndarray:
    """[S, H, W, Dh] -> [S*H, Wp, Dh] with zero padding rows."""
    S, H, W, Dh = q.shape
    qf = q.reshape(S * H, W, Dh)
    if wp > W:
        qf = jnp.pad(qf, ((0, 0), (0, wp - W), (0, 0)))
    return qf


DECODE_ATTN_MULTI_KERNEL = "ds_decode_attn_multi"


def _decode_multi_kernel(q_ref, len_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr,
                         *, sm_scale: float, block_k: int):
    jk = pl.program_id(1)
    nk = pl.num_programs(1)
    row_lens = len_ref[0][:, 0:1]                       # [Wp, 1]

    @pl.when(jk == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # the block computes when ANY row still has live keys in it; rows
    # whose own length ends earlier mask themselves below
    @pl.when(jk * block_k < jnp.max(row_lens))
    def _compute():
        q = q_ref[0]                                    # [Wp, d]
        k = k_ref[0]                                    # [bk, d]
        v = v_ref[0]                                    # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Wp, bk]
        k_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + jk * block_k
        mask = k_ids < row_lens
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # unlike the single-query kernel, a ROW can be fully masked in
        # a block another row keeps live: its m_new stays NEG_INF and
        # exp(NEG_INF - NEG_INF) would be 1, so p is masked explicitly
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # length-0 rows (inactive slots, padding rows) -> exact zeros
        o_ref[0] = jnp.where(l == 0.0, 0.0,
                             acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_multi_pallas(q, k, v, lengths, *, sm_scale, block_k,
                         interpret):
    S, H, T, Dh = k.shape
    W = q.shape[2]
    wp = _rows_pad(W)
    block_k = min(block_k, max(T, 8))
    kf = _pad_seq(k.reshape(S * H, T, Dh), block_k, 1)
    vf = _pad_seq(v.reshape(S * H, T, Dh), block_k, 1)
    nk = kf.shape[1] // block_k
    qf = _pad_queries(q, wp)
    len_op = _multi_len_op(lengths, wp)
    out = pl.pallas_call(
        functools.partial(_decode_multi_kernel, sm_scale=sm_scale,
                          block_k=block_k),
        grid=(S * H, nk),
        in_specs=[
            pl.BlockSpec((1, wp, Dh), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((1, wp, 128), lambda g, j, H=H: (g // H, 0, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda g, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, wp, Dh), lambda g, j: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S * H, wp, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((wp, 128), jnp.float32),
            pltpu.VMEM((wp, 128), jnp.float32),
            pltpu.VMEM((wp, Dh), jnp.float32),
        ],
        interpret=interpret,
        name=DECODE_ATTN_MULTI_KERNEL,
    )(qf, len_op, kf, vf)
    return out[:, :W, :].reshape(S, H, W, Dh)


def decode_attention_multi(q: jnp.ndarray, k: jnp.ndarray,
                           v: jnp.ndarray, lengths: jnp.ndarray,
                           sm_scale: Optional[float] = None,
                           block_k: int = 256,
                           impl: str = "pallas",
                           interpret: Optional[bool] = None
                           ) -> jnp.ndarray:
    """Multi-query attention over the slot KV cache — the speculative
    ``verify_step``'s widened decode (docs/serving.md).

    q: [S, H, W, Dh] — W new query tokens per slot (the pending token
        + its k draft proposals; W = k+1).
    k, v: [S, H, T, Dh] — the slot cache with ALL W new rows already
        written (write-then-attend, exactly the decode contract).
    lengths: [S, W] int32, TRACED — per-QUERY live length including the
        query's own position (row ``i`` of an active slot at base
        length L is ``L + i + 1``); 0 = masked row -> exact zeros.

    ``impl='dense'`` is W stacked single-query references (bitwise the
    sequential ticks being replaced); ``'pallas'`` packs the W rows
    into the sublane dimension of the single-query kernel's tiles."""
    assert q.ndim == 4 and k.ndim == 4, (q.shape, k.shape)
    S, H, T, Dh = k.shape
    W = q.shape[2]
    assert q.shape == (S, H, W, Dh), (q.shape, k.shape)
    assert lengths.shape == (S, W), (lengths.shape, q.shape)
    if sm_scale is None:
        sm_scale = _default_scale(Dh)
    if impl == "dense":
        return decode_attention_multi_reference(q, k, v, lengths,
                                                sm_scale=sm_scale)
    if impl != "pallas":
        raise ValueError(
            f"decode_attention_multi impl={impl!r}: expected 'pallas' "
            "or 'dense'")
    if interpret is None:
        interpret = _use_interpret()
    return _decode_multi_pallas(q, k, v, lengths.astype(jnp.int32),
                                sm_scale=sm_scale, block_k=block_k,
                                interpret=interpret)


PAGED_DECODE_ATTN_MULTI_KERNEL = "ds_paged_decode_attn_multi"
PAGED_DECODE_ATTN_MULTI_INT8_KERNEL = PAGED_DECODE_ATTN_MULTI_KERNEL + "_int8"


def _decode_paged_multi_kernel(pt_ref, q_ref, len_ref, k_ref, v_ref,
                               *rest, sm_scale: float, page_len: int):
    # fused-dequant arm: see _decode_paged_kernel — same two scale-tile
    # refs, same python-level branch keeping the fp trace unchanged
    quant = len(rest) > 4
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    jk = pl.program_id(1)
    nk = pl.num_programs(1)
    row_lens = len_ref[0][:, 0:1]                       # [Wp, 1]

    @pl.when(jk == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(jk * page_len < jnp.max(row_lens))
    def _compute():
        q = q_ref[0]                                    # [Wp, d]
        k = k_ref[0, 0]                                 # [page_len, d]
        v = v_ref[0, 0]
        if quant:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
            ks_row = ks_ref[0, 0][0:1, :page_len]       # [1, page_len]
            vs_row = vs_ref[0, 0][0:1, :page_len]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if quant:
            s = s * ks_row
        k_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + jk * page_len
        mask = k_ids < row_lens
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        pv = (p * vs_row) if quant else p
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = jnp.where(l == 0.0, 0.0,
                             acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_paged_multi_pallas(q, k_pages, v_pages, page_table, lengths,
                               *, sm_scale, interpret, k_scale=None,
                               v_scale=None):
    P, H, page_len, Dh = k_pages.shape
    S, max_pages = page_table.shape
    W = q.shape[2]
    wp = _rows_pad(W)
    quant = k_scale is not None
    qf = _pad_queries(q, wp)
    len_op = _multi_len_op(lengths, wp)
    pt_flat = page_table.astype(jnp.int32).reshape(-1)

    def page_block(g, j, pt, H=H, M=max_pages):
        return (pt[(g // H) * M + j], g % H, 0, 0)

    # only the page table needs scalar prefetch (it feeds the index
    # maps); the per-query lengths ride as an ordinary VMEM tile
    in_specs = [
        pl.BlockSpec((1, wp, Dh), lambda g, j, pt: (g, 0, 0)),
        pl.BlockSpec((1, wp, 128),
                     lambda g, j, pt, H=H: (g // H, 0, 0)),
        pl.BlockSpec((1, 1, page_len, Dh), page_block),
        pl.BlockSpec((1, 1, page_len, Dh), page_block),
    ]
    operands = [qf, len_op, k_pages, v_pages]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 8, 128), page_block),
                     pl.BlockSpec((1, 1, 8, 128), page_block)]
        operands += [_scale_tile(k_scale), _scale_tile(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S * H, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, wp, Dh), lambda g, j, pt: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((wp, 128), jnp.float32),
            pltpu.VMEM((wp, 128), jnp.float32),
            pltpu.VMEM((wp, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_paged_multi_kernel, sm_scale=sm_scale,
                          page_len=page_len),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S * H, wp, Dh),
                                       jnp.float32 if quant else q.dtype),
        interpret=interpret,
        name=(PAGED_DECODE_ATTN_MULTI_INT8_KERNEL if quant
              else PAGED_DECODE_ATTN_MULTI_KERNEL),
    )(pt_flat, *operands)
    return out[:, :W, :].reshape(S, H, W, Dh).astype(q.dtype)


def decode_attention_paged_multi(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray,
                                 page_table: jnp.ndarray,
                                 lengths: jnp.ndarray,
                                 sm_scale: Optional[float] = None,
                                 impl: str = "pallas",
                                 interpret: Optional[bool] = None,
                                 k_scale: Optional[jnp.ndarray] = None,
                                 v_scale: Optional[jnp.ndarray] = None
                                 ) -> jnp.ndarray:
    """Multi-query attention over the PAGED KV pool — the paged twin of
    :func:`decode_attention_multi` (same per-query ``lengths [S, W]``
    contract) with the page pool/table layout of
    :func:`decode_attention_paged`, including its fused-dequant arm
    (``k_scale``/``v_scale`` [P, H, page_len] over an int8 pool).
    ``impl='dense'`` gathers the pool with ``jnp.take`` (dequantizing
    on the quant arm) then runs the stacked single-query reference —
    values identical to the unpaged multi arm on the same logical
    cache; ``'pallas'`` is the scalar-prefetch kernel with W query
    rows per tile (interpret mode off-TPU)."""
    assert q.ndim == 4 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    P, H, page_len, Dh = k_pages.shape
    S, max_pages = page_table.shape
    W = q.shape[2]
    assert q.shape == (S, H, W, Dh), (q.shape, k_pages.shape)
    assert lengths.shape == (S, W), (lengths.shape, q.shape)
    _check_quant_args(k_pages, k_scale, v_scale,
                      "decode_attention_paged_multi")
    if sm_scale is None:
        sm_scale = _default_scale(Dh)
    if impl == "dense":
        if k_scale is not None:
            kg = dequantize_paged(k_pages, k_scale, page_table)
            vg = dequantize_paged(v_pages, v_scale, page_table)
        else:
            kg = paged_gather(k_pages, page_table)
            vg = paged_gather(v_pages, page_table)
        return decode_attention_multi_reference(q, kg, vg, lengths,
                                                sm_scale=sm_scale)
    if impl != "pallas":
        raise ValueError(
            f"decode_attention_paged_multi impl={impl!r}: expected "
            "'pallas' or 'dense'")
    if interpret is None:
        interpret = _use_interpret()
    return _decode_paged_multi_pallas(q, k_pages, v_pages,
                                      page_table.astype(jnp.int32),
                                      lengths.astype(jnp.int32),
                                      sm_scale=sm_scale,
                                      interpret=interpret,
                                      k_scale=k_scale, v_scale=v_scale)
