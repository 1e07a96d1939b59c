"""Flash attention as a Pallas TPU kernel (forward + backward).

This is the TPU-native replacement for the reference's fused attention
path inside the CUDA transformer layer (reference:
csrc/transformer/softmax_kernels.cu + strided-batch GEMMs composed in
csrc/transformer/ds_transformer_cuda.cpp:99-121, whose fused softmax is
capped at seq 1024 — ds_transformer_cuda.cpp:124).  The Pallas kernel has
no sequence cap: scores are never materialised in HBM; an online-softmax
accumulator streams over key blocks in VMEM, so memory is O(T·D) instead
of O(T²), and both matmuls per block hit the MXU.

Layout: grid = (batch·heads, q_blocks, k_blocks) with the k axis
innermost; VMEM scratch (running max `m`, normaliser `l`, output
accumulator) persists across the k iterations of one q block.  The
backward pass recomputes probabilities per block from the saved
log-sum-exp (classic flash-attention-2 style) in two kernels: one
accumulating dQ over k blocks, one accumulating dK/dV over q blocks.

Numerics: softmax statistics and all accumulators are fp32 regardless of
input dtype (matching the reference kernel's fp32 softmax accumulation
for fp16 inputs).

Attention-probability dropout runs INSIDE the kernel (the reference
fuses dropout into its CUDA attention the same way,
csrc/transformer/dropout_kernels.cu composed at
ds_transformer_cuda.cpp:99-121): the keep mask is a counter-based hash
of (batch·head, q position, k position, seed), so the backward kernels
regenerate bit-identical masks from the same coordinates instead of
storing an O(T²) mask — dropout costs no extra HBM.  The same hash,
evaluated in plain jnp over full index grids, is the differential-test
oracle (tests compare kernel fwd+grads against a dense reference using
the exact same mask).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dropout import fmix32, keep_threshold

NEG_INF = -1e30
# additive-mask drop value for boolean key masks: large enough that the
# dropped probability underflows to 0 after the lse subtraction, finite
# so masked-out score arithmetic never produces inf - inf = nan
NEG_MASK = -1e9
# a row whose running max never rose above this had NO genuinely valid
# key (real scores are O(|q||k|/sqrt(d)) — nowhere near -5e8): every key
# was dropped by the additive mask (<= NEG_MASK) or the validity floor
# (NEG_INF).  Such rows are HARD-ZEROED at finalize instead of silently
# renormalizing over masked keys (the mis-masking hazard: an all-masked
# key_mask row, or kv_length=0, previously attended to the max-scoring
# MASKED key / the mean of V).  Their lse is set to +DEAD_LSE so the
# backward kernels' p = exp(s - lse) underflows to exactly 0 — zero
# gradients, consistent with the zero output.
DEAD_ROW_THRESH = NEG_MASK * 0.5
DEAD_LSE = 1e30


def _use_interpret() -> bool:
    from .runtime import use_interpret
    return use_interpret()


def _pad_seq(x, block, axis):
    t = x.shape[axis]
    pad = (-t) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def dropout_keep_mask(q_ids, k_ids, bh, seed, rate: float):
    """Counter-based keep mask: u32 hash of (bh, q position, k position,
    seed) compared against rate.  Pure jnp on index arrays, so the SAME
    function serves the forward kernel, both backward kernels (bit-equal
    regeneration — no stored mask), and the dense test oracle.  All of
    q_ids/k_ids/bh broadcast; returns bool of the broadcast shape."""
    x = (q_ids.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + k_ids.astype(jnp.uint32))
    x = x ^ (jnp.uint32(bh) * jnp.uint32(0x85EBCA6B))
    return fmix32(x ^ jnp.uint32(seed)) >= keep_threshold(rate)


def dense_keep_mask(B, H, Tq, Tk, seed, rate: float, bh_ids=None):
    """Full-array keep mask [B, H, Tq, Tk] — the dense-layout evaluation
    of the kernel's hash (single source of the broadcast recipe, used by
    the model's dense fallback, Ulysses' dense debug path, and the test
    oracle).  ``bh_ids``: optional [B·H] global batch·head ids."""
    if bh_ids is None:
        bh_ids = jnp.arange(B * H, dtype=jnp.uint32)
    return dropout_keep_mask(
        jnp.arange(Tq, dtype=jnp.uint32)[None, None, :, None],
        jnp.arange(Tk, dtype=jnp.uint32)[None, None, None, :],
        jnp.asarray(bh_ids, jnp.uint32).reshape(B, H, 1, 1),
        seed, rate)


def _block_keep(iq, ik, b, seed, *, rate, block_q, block_k):
    """Keep mask for one (q-block, k-block) tile, from global positions."""
    q_ids = jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 0) \
        + jnp.uint32(iq * block_q)
    k_ids = jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_k), 1) \
        + jnp.uint32(ik * block_k)
    return dropout_keep_mask(q_ids, k_ids, b, seed, rate)



def _grid_bh(bh_ref, period: int, stride: int):
    """Global batch-head id of this grid row:
    ``base + (g // period) * stride + (g % period)`` with g the bh grid
    index.  The affine form (one traced (1,1) scalar base + two STATIC
    ints) replaces a per-row id array operand: TPU lowering rejects
    sub-(8,128) blocked operands outright, and an SMEM array read
    indexed by program_id does not lower in interpret mode — while a
    (1,1) scalar operand works everywhere (same mechanics as the seed).
    Every caller's ids are affine: default contiguous arange(B*H) is
    (0, B*H, 0); Ulysses' global ids b*H + idx*Hn + j are
    (idx*Hn, Hn, H) — see parallel/sequence.py."""
    g = pl.program_id(0)
    return (bh_ref[0, 0] + jnp.uint32(g // period) * jnp.uint32(stride)
            + jnp.uint32(g % period))


def _masked_scores(q, k, iq, ik, *, sm_scale, causal, block_q, block_k,
                   seq_len, kmask=None, window=None):
    """Scaled q·kᵀ for one (q-block, k-block) tile with padding + causal
    masking — the single source of the mask math shared by the forward
    and both backward kernels (they must stay bit-identical or forward
    and backward silently disagree).  ``kmask``: optional [1, block_k]
    fp32 additive key mask (0 keep / large-negative drop — the HF
    convention), applied before the validity floor.  ``window`` (static,
    with ``causal``): a query sees its last ``window`` keys, itself
    included."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale      # [bq, bk]
    if kmask is not None:
        s = s + kmask
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    k_global = k_ids + ik * block_k
    valid = k_global < seq_len
    if causal:
        q_ids = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        q_global = q_ids + iq * block_q
        valid = jnp.logical_and(valid, k_global <= q_global)
        if window is not None:
            valid = jnp.logical_and(valid, k_global > q_global - window)
    return jnp.where(valid, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


# Stable names of the Mosaic custom calls (``pl.pallas_call(name=...)``):
# the HLO instruction, and so the device-trace row, is ``<name>.<n>``
# whatever JAX construct (checkpoint, scan, shard_map, cond) wraps the
# call.  The benchmark's per-kernel shares match on these strings.
FLASH_FWD_KERNEL = "ds_flash_fwd"
# ``checkpoint_name``s of the forward's two results where they become the
# backward's residuals (``_flash_fwd``): a remat policy that saves both
# (runtime/activation_checkpointing/block_remat.py) keeps the backward
# from running the forward kernel again.  Under a ``jax.checkpoint`` with
# no such policy, and outside one, a name is the identity.
FLASH_OUT = "flash_out"     # [batch * heads, seq, head size]
FLASH_LSE = "flash_lse"     # [batch * heads, seq], one float32 a row


def _band_first_block(iq, block_q, block_k, window):
    """First key block a query block of a ``window`` layer reads."""
    return jnp.maximum(iq * block_q - (window - 1), 0) // block_k


def _fwd_kernel(q_ref, k_ref, v_ref, seed_ref, bh_ref, kmask_ref, *rest,
                sm_scale: float, causal: bool, block_q: int,
                block_k: int, seq_len: int, dropout_rate: float,
                bh_period: int, bh_stride: int, use_kmask: bool,
                window: Optional[int] = None, with_sink: bool = False):
    if with_sink:
        sink_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    at_first, at_last = ik == 0, ik == nk - 1
    if window is not None:
        # the grid's key axis spans the band only: step ``ik`` is key
        # block ``first + ik`` (see _fwd)
        ik = ik + _band_first_block(iq, block_q, block_k, window)
    # program_id must be read OUTSIDE pl.when branches: interpret-mode
    # lowering only rewrites it in the top-level kernel body (closures
    # capture the value fine) — same reason iq/ik live up here.
    bh_row = _grid_bh(bh_ref, bh_period, bh_stride)

    @pl.when(at_first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Whole k block strictly above the causal diagonal → nothing to do.
    run = True
    if causal:
        run = (ik * block_k) <= (iq * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                   # [bq, d]
        k = k_ref[0]                                   # [bk, d]
        v = v_ref[0]                                   # [bk, dv]
        # row 0 of the 8-row sublane-broadcast mask tile (see _kmask_args)
        km = kmask_ref[0][0:1, :] if use_kmask else None
        s = _masked_scores(q, k, iq, ik, sm_scale=sm_scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           seq_len=seq_len, kmask=km, window=window)

        m_prev = m_scr[:, 0:1]                          # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                 # [bq, 1]
        # dropout scales probabilities AFTER normalisation; since the
        # final o = acc/l is linear in acc, masking p here (and keeping
        # the normaliser l on the UNdropped p) is exactly
        # dropout(softmax(s)) @ v
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        pd = p
        if dropout_rate > 0.0:
            keep = _block_keep(iq, ik, bh_row, seed_ref[0, 0],
                               rate=dropout_rate, block_q=block_q,
                               block_k=block_k)
            pd = p * keep.astype(p.dtype) / (1.0 - dropout_rate)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pd.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(at_last)
    def _finalize():
        l, acc = l_scr[:, 0:1], acc_scr[:]
        if with_sink:
            # the head's sink: one more column of the softmax, in the
            # denominator and in no value
            m = m_scr[:, 0:1]
            b = sink_ref[0][0:1, 0:1]
            m_all = jnp.maximum(m, b)
            keep = jnp.exp(m - m_all)
            acc = acc * keep
            l = l * keep + jnp.exp(b - m_all)
            m_scr[:] = jnp.broadcast_to(m_all, m_scr.shape)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # dead rows (every key masked — all-masked key_mask row, or all
        # keys beyond kv_length) hard-zero instead of renormalizing over
        # masked keys; their lse goes to +DEAD_LSE so backward p
        # underflows to 0 and the gradients are zero too
        dead = m_scr[:, 0:1] <= DEAD_ROW_THRESH         # [bq, 1]
        o_ref[0] = jnp.where(dead, 0.0, acc / l_safe).astype(o_ref.dtype)
        # lse output is q-blocked with a sublane-padded layout
        # [bh, nq, 8, block_q]: every store is a whole (8, block_q) tile at
        # lane offset 0.  Mosaic rejects dynamic lane offsets that are not
        # provably 128-aligned (iq*block_q is not, for block_q < 128), and
        # TPU block shapes need their last two dims (sublane, lane) to be
        # (8k, 128k) or the full array dims — the 8-row broadcast buys both.
        lse = jnp.where(dead[:, 0], DEAD_LSE,
                        m_scr[:, 0] + jnp.log(l_safe[:, 0]))  # [bq]
        lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, block_q))


def _seed_arr(seed):
    """Seed as a (1, 1) uint32 operand (traced — a new step's seed does
    not recompile); every grid step maps to the same block."""
    return jnp.asarray(seed, jnp.uint32).reshape(1, 1)


# Scalar operands ((1,1) uint32 seed / bh base) live in SMEM as FULL
# arrays: the TPU lowering's (8,128)/equal-dims tile rule applies to any
# blocked spec, so per-row blocked id arrays are rejected on real TPUs
# even in SMEM (found on hardware, round 3 — interpret mode accepts
# them, which is why tests never caught it).  Batch-head ids therefore
# travel as ONE scalar base + static affine params (see _grid_bh).
_SEED_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)
_BH_SPEC = _SEED_SPEC


def _kmask_args(kmask, bh, tk_p, block_k, k_block_of):
    """(operand, spec) for the additive key-mask input.

    TPU blocked operands need their last two dims to satisfy the
    (8, 128)-tile rule, so a per-key mask row travels as an 8-row
    sublane broadcast [bh, 8, tk_p] with block (1, 8, block_k) — the
    same layout trick as the lse output (see _fwd_kernel._finalize).
    ``k_block_of(b, i, j)`` maps grid indices to the k-block index
    (shared with the K/V specs so causal revisit elision applies).
    When no mask is used a single zero tile with a constant index map is
    passed: it is fetched once and never refetched, and the kernel's
    static use_kmask flag skips the math entirely."""
    if kmask is None:
        op = jnp.zeros((1, 8, block_k), jnp.float32)
        spec = pl.BlockSpec((1, 8, block_k), lambda b, i, j: (0, 0, 0))
        return op, spec, False
    km = _pad_seq(kmask.astype(jnp.float32), block_k, 1)       # [bh, tk_p]
    op = jnp.broadcast_to(km[:, None, :], (bh, 8, km.shape[1]))
    spec = pl.BlockSpec(
        (1, 8, block_k), lambda b, i, j: (b, 0, k_block_of(b, i, j)))
    return op, spec, True


def _fwd(q, k, v, seed, bh_base, kmask, *, sm_scale, causal, block_q,
         block_k, dropout_rate, bh_period, bh_stride, interpret,
         kv_length=None, window=None, sink=None, heads=None):
    """``window`` / ``sink`` / ``heads`` are the serving prefill's
    (:func:`flash_attention_fwd`); left None, the call is the training
    forward's, operand for operand.  ``heads = (Hq, Hkv)``: k and v hold
    ``Hkv`` heads a sequence under q's ``Hq``, and query head ``h`` reads
    key head ``h // (Hq // Hkv)``: the index map picks it, nothing is
    repeated.  ``v`` may be narrower than ``k``."""
    bh, t, d = q.shape
    dv = v.shape[-1]
    tk = k.shape[1]
    # live-KV clamp: keys >= kv_length are hard-masked via the validity
    # floor (the KV-cache decode hazard — a cache tail past the live
    # length must never be attended); rows left with no valid key zero
    seq_len = tk if kv_length is None else int(kv_length)
    block_q = min(block_q, max(t, 8))
    block_k = min(block_k, max(tk, 8))
    qp = _pad_seq(q, block_q, 1)
    kp = _pad_seq(k, block_k, 1)
    vp = _pad_seq(v, block_k, 1)
    tq_p, tk_p = qp.shape[1], kp.shape[1]
    nq, nk = tq_p // block_q, tk_p // block_k

    if window is not None:
        assert causal, "a window is a band under the causal diagonal"
        # the key axis of the grid spans the band's blocks only: blocks
        # outside it are neither fetched nor stepped over
        last = [(i * block_q + block_q - 1) // block_k for i in range(nq)]
        first = [max(i * block_q - (window - 1), 0) // block_k
                 for i in range(nq)]
        nk = max(b - a for a, b in zip(first, last)) + 1

        def k_block_of(b, i, j):
            return jnp.minimum(
                _band_first_block(i, block_q, block_k, window) + j,
                (i * block_q + block_q - 1) // block_k)
    elif causal:
        def k_block_of(b, i, j):
            return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
    else:
        def k_block_of(b, i, j):
            return j
    kmask_op, kmask_spec, use_kmask = _kmask_args(
        kmask, bh, tk_p, block_k, k_block_of)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_len=seq_len,
        dropout_rate=dropout_rate, bh_period=bh_period,
        bh_stride=bh_stride, use_kmask=use_kmask, window=window,
        with_sink=sink is not None)
    # clamp the K/V block index at the causal diagonal: skipped
    # (fully-masked) grid steps revisit the previous block, and Pallas
    # elides the HBM→VMEM copy for revisited blocks — without this the
    # pipeline streams every K/V block even though pl.when skips the
    # compute (≈2× attention HBM traffic at long T)
    if heads is None:
        def kv_im(b, i, j):
            return (b, k_block_of(b, i, j), 0)
    else:
        hq, hkv = heads

        def kv_im(b, i, j):
            return ((b // hq) * hkv + (b % hq) // (hq // hkv),
                    k_block_of(b, i, j), 0)
    operands = [qp, kp, vp, _seed_arr(seed), _seed_arr(bh_base), kmask_op]
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_im),
        pl.BlockSpec((1, block_k, dv), kv_im),
        _SEED_SPEC,
        _BH_SPEC,
        kmask_spec,
    ]
    if sink is not None:
        # a head's sink as one (8, 128) tile, every lane the same
        hs = sink.shape[0]
        operands.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (hs, 8, 128)))
        in_specs.append(
            pl.BlockSpec((1, 8, 128), lambda b, i, j: (b % hs, 0, 0)))
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, nq, 8, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD_KERNEL,
    )(*operands)
    return out[:, :t], lse[:, :, 0, :].reshape(bh, tq_p)[:, :t]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


FLASH_BWD_DQ_KERNEL = "ds_flash_bwd_dq"


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   seed_ref, bh_ref, kmask_ref, dq_ref, dq_scr,
                   *, sm_scale, causal, block_q, block_k, seq_len,
                   dropout_rate, bh_period, bh_stride, use_kmask):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    bh_row = _grid_bh(bh_ref, bh_period, bh_stride)  # see _fwd_kernel

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = (ik * block_k) <= (iq * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = jnp.transpose(lse_ref[0, 0, 0:1, :])      # [bq, 1]
        delta = jnp.transpose(delta_ref[0, 0, 0:1, :])  # [bq, 1]

        km = kmask_ref[0][0:1, :] if use_kmask else None
        s = _masked_scores(q, k, iq, ik, sm_scale=sm_scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           seq_len=seq_len, kmask=km)
        p = jnp.exp(s - lse)                            # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [bq, bk]
        if dropout_rate > 0.0:
            # dS = P ∘ (mask/(1-r) ∘ (dO·Vᵀ) − Δ); Δ = rowsum(dO ∘ O)
            # already absorbs the dropped terms (O was built from the
            # dropped probabilities)
            keep = _block_keep(iq, ik, bh_row, seed_ref[0, 0],
                               rate=dropout_rate, block_q=block_q,
                               block_k=block_k)
            dp = dp * keep.astype(dp.dtype) / (1.0 - dropout_rate)
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


FLASH_BWD_DKV_KERNEL = "ds_flash_bwd_dkv"


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    seed_ref, bh_ref, kmask_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr,
                    *, sm_scale, causal, block_q, block_k, seq_len,
                    dropout_rate, bh_period, bh_stride, use_kmask):
    ik, iq = pl.program_id(1), pl.program_id(2)
    bh_row = _grid_bh(bh_ref, bh_period, bh_stride)  # see _fwd_kernel
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = (ik * block_k) <= (iq * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = jnp.transpose(lse_ref[0, 0, 0:1, :])      # [bq, 1]
        delta = jnp.transpose(delta_ref[0, 0, 0:1, :])  # [bq, 1]

        km = kmask_ref[0][0:1, :] if use_kmask else None
        s = _masked_scores(q, k, iq, ik, sm_scale=sm_scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           seq_len=seq_len, kmask=km)
        p = jnp.exp(s - lse)                            # [bq, bk]
        pd = p
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _block_keep(iq, ik, bh_row, seed_ref[0, 0],
                               rate=dropout_rate, block_q=block_q,
                               block_k=block_k)
            scale = keep.astype(p.dtype) / (1.0 - dropout_rate)
            pd = p * scale      # dropped probabilities (forward's P̃)
            dp = dp * scale
        # dV += P̃ᵀ · dO
        dv_scr[:] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale                # [bq, bk]
        # dK += dSᵀ · Q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, seed, bh_base, kmask, *, sm_scale,
         causal, block_q, block_k, dropout_rate, bh_period, bh_stride,
         interpret, kv_length=None):
    bh, t, d = q.shape
    tk = k.shape[1]
    seq_len = tk if kv_length is None else int(kv_length)  # see _fwd
    block_q = min(block_q, max(t, 8))
    block_k = min(block_k, max(tk, 8))
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                            # [bh, t]

    qp = _pad_seq(q, block_q, 1)
    dop = _pad_seq(do, block_q, 1)
    kp = _pad_seq(k, block_k, 1)
    vp = _pad_seq(v, block_k, 1)
    tq_p, tk_p = qp.shape[1], kp.shape[1]
    nq, nk = tq_p // block_q, tk_p // block_k
    # q-blocked, sublane-padded row statistics ([bh, nq, 8, block_q]):
    # all kernel accesses are whole tiles at lane offset 0 (no dynamic
    # lane slicing, valid TPU block shape — see _fwd_kernel._finalize)
    def _rows(x):
        r = _pad_seq(x, block_q, 1).reshape(bh, nq, 1, block_q)
        return jnp.broadcast_to(r, (bh, nq, 8, block_q))

    lsep = _rows(lse)
    deltap = _rows(delta)

    q_spec_i = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    if causal:  # same revisit trick as the forward (see _fwd)
        def k_block_dq(b, i, j):
            return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
    else:
        def k_block_dq(b, i, j):
            return j

    def kv_im_j(b, i, j):
        return (b, k_block_dq(b, i, j), 0)
    kv_spec_j = pl.BlockSpec((1, block_k, d), kv_im_j)
    row_spec = pl.BlockSpec((1, 1, 8, block_q),
                            lambda b, i, j: (b, i, 0, 0))
    kmask_op, kmask_spec_dq, use_kmask = _kmask_args(
        kmask, bh, tk_p, block_k, k_block_dq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=seq_len,
                          dropout_rate=dropout_rate,
                          bh_period=bh_period, bh_stride=bh_stride,
                          use_kmask=use_kmask),
        grid=(bh, nq, nk),
        in_specs=[q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, row_spec,
                  row_spec, _SEED_SPEC, _BH_SPEC, kmask_spec_dq],
        out_specs=q_spec_i,
        out_shape=jax.ShapeDtypeStruct((bh, tq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=FLASH_BWD_DQ_KERNEL,
    )(qp, kp, vp, dop, lsep, deltap, _seed_arr(seed), _seed_arr(bh_base),
      kmask_op)

    # dK/dV: k blocks outer, q blocks inner.
    if causal:
        # the first useful q block for k block i starts at the diagonal:
        # clamp below it so masked steps revisit (no fetch)
        def q_im_j(b, i, j):
            return (b, jnp.maximum(j, (i * block_k) // block_q), 0)

        def row_im_j(b, i, j):
            return (b, jnp.maximum(j, (i * block_k) // block_q), 0, 0)
    else:
        def q_im_j(b, i, j):
            return (b, j, 0)

        def row_im_j(b, i, j):
            return (b, j, 0, 0)
    q_spec_j = pl.BlockSpec((1, block_q, d), q_im_j)
    kv_spec_i = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    row_spec_j = pl.BlockSpec((1, 1, 8, block_q), row_im_j)
    # k blocks ride the SECOND grid axis here (i), not the third
    _, kmask_spec_i, _ = _kmask_args(
        kmask, bh, tk_p, block_k, lambda b, i, j: i)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=seq_len,
                          dropout_rate=dropout_rate,
                          bh_period=bh_period, bh_stride=bh_stride,
                          use_kmask=use_kmask),
        grid=(bh, nk, nq),
        in_specs=[q_spec_j, kv_spec_i, kv_spec_i, q_spec_j, row_spec_j,
                  row_spec_j, _SEED_SPEC, _BH_SPEC, kmask_spec_i],
        out_specs=[kv_spec_i, kv_spec_i],
        out_shape=[jax.ShapeDtypeStruct((bh, tk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, tk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name=FLASH_BWD_DKV_KERNEL,
    )(qp, kp, vp, dop, lsep, deltap, _seed_arr(seed), _seed_arr(bh_base),
      kmask_op)
    return dq[:, :t], dk[:, :tk], dv[:, :tk]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13, 14))
def _flash(q, k, v, seed, bh_base, kmask, sm_scale, causal, block_q,
           block_k, dropout_rate, bh_period, bh_stride, interpret,
           kv_length):
    out, _ = _fwd(q, k, v, seed, bh_base, kmask, sm_scale=sm_scale,
                  causal=causal, block_q=block_q, block_k=block_k,
                  dropout_rate=dropout_rate, bh_period=bh_period,
                  bh_stride=bh_stride, interpret=interpret,
                  kv_length=kv_length)
    return out


def _flash_fwd(q, k, v, seed, bh_base, kmask, sm_scale, causal, block_q,
               block_k, dropout_rate, bh_period, bh_stride, interpret,
               kv_length):
    out, lse = _fwd(q, k, v, seed, bh_base, kmask, sm_scale=sm_scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    dropout_rate=dropout_rate, bh_period=bh_period,
                    bh_stride=bh_stride, interpret=interpret,
                    kv_length=kv_length)
    # with both saved the recomputed forward's kernel has no consumer left
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, seed, bh_base, kmask, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, dropout_rate,
               bh_period, bh_stride, interpret, kv_length, res, do):
    q, k, v, seed, bh_base, kmask, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse, do, seed, bh_base, kmask,
                      sm_scale=sm_scale, causal=causal, block_q=block_q,
                      block_k=block_k, dropout_rate=dropout_rate,
                      bh_period=bh_period, bh_stride=bh_stride,
                      interpret=interpret, kv_length=kv_length)
    # integer-dtype primals (seed, bh base) take float0 cotangents
    dseed = np.zeros(np.shape(seed), jax.dtypes.float0)
    dbh = np.zeros(np.shape(bh_base), jax.dtypes.float0)
    # the key mask is a constant (0 / -1e9) in every caller; its true
    # gradient is never consumed, so it is treated as non-differentiable
    dkmask = None if kmask is None else jnp.zeros_like(kmask)
    return dq, dk, dv, dseed, dbh, dkmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512,
                    block_k: int = 512,
                    dropout_rate: float = 0.0,
                    dropout_rng=None,
                    dropout_seed=None,
                    bh_affine=None,
                    key_mask=None,
                    kv_length: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention over [B, H, T, Dh] inputs (differentiable).

    Attention-probability dropout runs inside the kernel when
    ``dropout_rate > 0``: the keep mask is hashed from positions + a
    seed (``dropout_seed`` uint32 scalar, or derived from
    ``dropout_rng``), regenerated bit-identically in the backward
    kernels.  ``bh_affine`` = (base, period, stride) overrides the
    batch·head ids the hash sees: row g of the flattened [B·H] grid maps
    to ``base + (g // period) * stride + g % period`` (base may be a
    traced uint32 scalar; period/stride are static ints).  Sharded
    callers (Ulysses) pass their GLOBAL head mapping so the realization
    matches the unsharded layout — see _grid_bh.

    ``key_mask``: optional per-key mask for padding (the BERT/HF case —
    the reference's fused softmax applies the same additive mask,
    csrc/transformer/softmax_kernels.cu).  Shape [B, Tk] (broadcast over
    heads) or [B·H, Tk]; boolean (True = attend) or additive float (0
    keep / large-negative drop).  Applied identically in forward and
    both backward kernels; the mask rides as an 8-row sublane-broadcast
    operand so the TPU tile rules accept it (see _kmask_args).

    ``kv_length``: static live length of the key/value tensors.  Keys at
    positions >= kv_length are HARD-masked (validity floor) in forward
    and both backward kernels — a KV buffer whose tail holds garbage
    (the KV-cache decode case) is never silently attended.  Out-of-range
    values raise.  Rows left with no valid key at all (kv_length=0, or a
    key_mask dropping every key of a row) output exact zeros with zero
    gradients instead of renormalizing over masked keys.  For PER-ROW
    traced lengths use ``ops.pallas.decode_attention`` (the single-query
    serving kernel).
    """
    assert q.ndim == 4, f"expected [B, H, T, D], got {q.shape}"
    b, h, t, d = q.shape
    tk = k.shape[2]
    # The causal mask is top-left-anchored (k_pos <= q_pos); with t != tk
    # that silently mis-masks (e.g. a KV-cache decode step would attend to
    # key 0 only).  Cross-length callers must use causal=False (and bound
    # the live keys with kv_length when the KV tail is not real data).
    assert not causal or t == tk, (
        f"causal flash attention requires equal q/k lengths, got {t} vs "
        f"{tk}; pass causal=False for cross-attention")
    if kv_length is not None:
        kv_length = int(kv_length)
        if not 0 <= kv_length <= tk:
            raise ValueError(
                f"kv_length={kv_length} is out of range for key length "
                f"{tk}: the mask would silently cover the wrong keys "
                f"(want 0 <= kv_length <= {tk})")
    if sm_scale is None:
        sm_scale = float(d) ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    dropout_rate = float(dropout_rate)
    assert 0.0 <= dropout_rate < 1.0, f"bad dropout_rate {dropout_rate}"
    if dropout_rate > 0.0:
        if dropout_seed is not None:
            seed = jnp.asarray(dropout_seed, jnp.uint32)
        else:
            assert dropout_rng is not None, \
                "dropout_rate > 0 requires dropout_rng or dropout_seed"
            seed = jax.random.bits(dropout_rng, (), jnp.uint32)
    else:
        seed = jnp.zeros((), jnp.uint32)
    if bh_affine is None:
        bh_affine = (0, b * h, 0)
    bh_base, bh_period, bh_stride = bh_affine
    kmask = None
    if key_mask is not None:
        km = jnp.asarray(key_mask)
        if km.dtype == jnp.bool_:
            km = jnp.where(km, 0.0, NEG_MASK).astype(jnp.float32)
        else:
            km = km.astype(jnp.float32)
        if km.shape == (b, tk):
            km = jnp.broadcast_to(km[:, None, :], (b, h, tk))
        elif km.shape != (b * h, tk):
            raise ValueError(
                f"key_mask shape {km.shape} must be [B, Tk]={b, tk} or "
                f"[B*H, Tk]={b * h, tk}")
        kmask = km.reshape(b * h, tk)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    out = _flash(qf, kf, vf, seed, jnp.asarray(bh_base, jnp.uint32),
                 kmask, sm_scale, causal, block_q, block_k,
                 dropout_rate, int(bh_period), int(bh_stride), interpret,
                 kv_length)
    return out.reshape(b, h, t, d)



FLASH_FWD_CTX_KERNEL = "ds_flash_fwd_ctx"


def _ctx_first_block(iq, live, *, block_q, block_k, ctx, window):
    """First key block a query block reads of keys ``[ctx + Tq]``: the
    later of the first live context block and the band's first."""
    first = (ctx - live) // block_k
    if window is not None:
        first = jnp.maximum(
            first, jnp.maximum(ctx + iq * block_q - (window - 1), 0)
            // block_k)
    return first


def _fwd_ctx_kernel(live_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                    acc_scr, *, sm_scale: float, block_q: int, block_k: int,
                    ctx: int, seq_len: int, window: Optional[int]):
    """The serving forward over keys that lie BEFORE the queries: key
    index ``kk`` of ``[ctx + Tq]``, query row ``t`` at key index ``ctx +
    t``.  Of the ``ctx`` context keys the LAST ``live_ref[0]`` are live
    (right-aligned, so that index is position up to a constant and the
    causal and band masks are :func:`_masked_scores`' shifted by ``ctx``).
    The grid's key axis starts at the first block the masks let through
    (:func:`_ctx_first_block`); steps past the causal diagonal run
    nothing and, their block index clamped, fetch nothing."""
    iq, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    live = live_ref[0]
    ik = _ctx_first_block(iq, live, block_q=block_q, block_k=block_k,
                          ctx=ctx, window=window) + j
    q_last = ctx + iq * block_q + block_q - 1

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ik * block_k <= q_last)
    def _compute():
        v = v_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        shape = (block_q, block_k)
        kk = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + ik * block_k
        qq = jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
            + (ctx + iq * block_q)
        valid = (kk <= qq) & (kk >= ctx - live) & (kk < seq_len)
        if window is not None:
            valid &= kk > qq - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        # a query sees its own key at least: l > 0 on every real row
        l = l_scr[:, 0:1]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def _fwd_ctx(q, k, v, ctx_live, *, heads, ctx, sm_scale, block_q, block_k,
             window, interpret):
    """q [B*Hq, Tq, D] over k [B*Hkv, ctx + Tq, D], v [.., Dv]."""
    bh, t, d = q.shape
    dv = v.shape[-1]
    hq, hkv = heads
    block_q = min(block_q, max(t, 8))
    block_k = min(block_k, ctx)
    assert ctx % block_k == 0, (ctx, block_k)
    qp, kp, vp = _pad_seq(q, block_q, 1), _pad_seq(k, block_k, 1), \
        _pad_seq(v, block_k, 1)
    nq = qp.shape[1] // block_q
    last = [(ctx + i * block_q + block_q - 1) // block_k for i in range(nq)]
    band = [max(ctx + i * block_q - (window - 1), 0) // block_k
            if window is not None else 0 for i in range(nq)]
    nk = max(b - a for a, b in zip(band, last)) + 1
    geometry = dict(block_q=block_q, block_k=block_k, ctx=ctx, window=window)

    def kv_im(b, i, j, live):
        block = jnp.minimum(
            _ctx_first_block(i, live[0], **geometry) + j,
            (ctx + i * block_q + block_q - 1) // block_k)
        return ((b // hq) * hkv + (b % hq) // (hq // hkv), block, 0)

    out = pl.pallas_call(
        functools.partial(_fwd_ctx_kernel, sm_scale=sm_scale,
                          seq_len=ctx + t, **geometry),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j, live: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), kv_im),
                pl.BlockSpec((1, block_k, dv), kv_im),
            ],
            out_specs=pl.BlockSpec((1, block_q, dv),
                                   lambda b, i, j, live: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((bh, qp.shape[1], dv), q.dtype),
        interpret=interpret,
        name=FLASH_FWD_CTX_KERNEL,
    )(jnp.reshape(ctx_live, (1,)).astype(jnp.int32), qp, kp, vp)
    return out[:, :t]


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        window: Optional[int] = None,
                        sink: Optional[jnp.ndarray] = None,
                        sm_scale: Optional[float] = None,
                        block_q: int = 512, block_k: int = 512,
                        interpret: Optional[bool] = None,
                        ctx_live=None) -> jnp.ndarray:
    """The causal forward kernel for a serving prefill (no backward: a
    prefill never backpropagates), with what the training call has not:

    * grouped keys: q ``[B, Hq, T, Dk]`` over k ``[B, Hkv, T, Dk]``, v
      ``[B, Hkv, T, Dv]``; query head ``h`` reads key head
      ``h // (Hq // Hkv)`` through the block index, nothing is repeated;
    * ``Dv != Dk``: the output is ``[B, Hq, T, Dv]``;
    * ``window``: query ``t`` sees keys ``t - window < j <= t``; the
      grid's key axis spans the band's blocks only;
    * ``sink`` ``[Hq]``: a head's learned logit, one more column of the
      softmax that takes weight and gives no value;
    * context keys (a chunk of a prompt after its first): k ``[B, Hkv,
      Tc + T, Dk]``, v alike, the queries at key positions ``Tc ..``;
      ``ctx_live`` (traced) of the ``Tc`` keys ahead are live, the LAST
      so many (right-aligned: a key's index is its position less a
      constant), and the causal and band masks are shifted by ``Tc``;
      blocks wholly outside them are not visited.  ``Tc`` a multiple of
      ``block_k``; no sink.  ``Tc == 0`` is the call without.
    """
    b, hq, t, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    ctx = k.shape[2] - t
    assert k.shape == (b, hkv, ctx + t, d) and ctx >= 0 \
        and v.shape == (b, hkv, ctx + t, dv), (q.shape, k.shape, v.shape)
    assert hq % hkv == 0, (hq, hkv)
    if sm_scale is None:
        sm_scale = float(d) ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    if ctx:
        assert sink is None and ctx_live is not None
        out = _fwd_ctx(q.reshape(b * hq, t, d),
                       k.reshape(b * hkv, ctx + t, d),
                       v.reshape(b * hkv, ctx + t, dv), ctx_live,
                       heads=(hq, hkv), ctx=ctx, sm_scale=sm_scale,
                       block_q=block_q, block_k=block_k, window=window,
                       interpret=interpret)
        return out.reshape(b, hq, t, dv)
    zero = jnp.zeros((), jnp.uint32)
    out, _ = _fwd(q.reshape(b * hq, t, d), k.reshape(b * hkv, t, d),
                  v.reshape(b * hkv, t, dv), zero, zero, None,
                  sm_scale=sm_scale, causal=True, block_q=block_q,
                  block_k=block_k, dropout_rate=0.0, bh_period=b * hq,
                  bh_stride=0, interpret=interpret, window=window,
                  sink=sink, heads=None if hq == hkv else (hq, hkv))
    return out.reshape(b, hq, t, dv)
