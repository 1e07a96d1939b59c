"""Block-sparse attention as a Pallas TPU kernel (forward + backward).

The reference implements block-sparse attention as three Triton kernels —
sdd/dsd matmuls and a block-sparse softmax — driven by lookup tables built
natively (reference: deepspeed/ops/sparse_attention/matmul.py:16,
trsrc/matmul.tr:1, trsrc/softmax_fwd.tr:1, csrc/sparse_attention/
utils.cpp:14).  The TPU equivalent is ONE fused kernel per pass: for each
query-block row the grid walks that row's active key blocks via a
scalar-prefetched LUT (SMEM-resident, read inside the BlockSpec index maps
— the Pallas analogue of the Triton kernels' pointer tables), maintaining
an online-softmax accumulator in VMEM exactly like the flash kernel.
Scores never touch HBM; compute and HBM traffic are O(T · W · block)
where W is the row-max active-block count.

LUT padding repeats each row's LAST valid column instead of zero: padded
grid steps revisit the block already in VMEM, so Pallas elides the
HBM→VMEM copy and padding costs no bandwidth (same trick as the causal
clamp in flash_attention._fwd).

Backward follows flash-attention-2: probabilities are recomputed per
block from the saved log-sum-exp; dQ walks the row LUT, dK/dV walk the
TRANSPOSED LUT (for each key block, the query rows attending to it).

Granularity note: sparsity is block-granular (an active block attends
fully), matching the reference kernels — intra-block causal/padding
masking arrives via attn_mask/key_padding_mask, which the gather-einsum
path (sparse_self_attention.py) handles; SparseSelfAttention dispatches
there when masks/rpe are present.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _use_interpret() -> bool:
    from .runtime import use_interpret
    return use_interpret()


def build_kernel_luts(layout: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray,
                                 np.ndarray, np.ndarray]:
    """Layout [H, nb, nb] → (cols, nvalid, rows_t, nvalid_t).

    ``cols[h, r]`` lists query-row r's active key blocks, padded by
    REPEATING the last valid entry (revisit ⇒ no refetch); ``nvalid[h, r]``
    is the true count.  ``rows_t``/``nvalid_t`` are the transposed LUT
    (per key block, the query rows attending to it) for the dK/dV pass.
    Rows/cols with no active blocks get one self-referential padding entry
    with nvalid 0.  Trace-time numpy, like the reference's native
    segment_blocks build (csrc/sparse_attention/utils.cpp:14).

    Head dedup: the LUTs ride in SMEM (scalar prefetch, ~1 MB on v5e), and
    at long seq a per-head LUT overflows it — e.g. bigbird seq 16k/block 64
    is 12x256x~170 int32 ≈ 2 MB, the exact AOT failure this guard exists
    for.  Every stock SparsityConfig is head-uniform unless
    ``different_layout_per_head`` is set, so identical head planes collapse
    to one and the kernels index plane ``h % lut_heads``.
    """
    if layout.shape[0] > 1 and bool((layout == layout[:1]).all()):
        layout = layout[:1]
    H, nb, _ = layout.shape
    W = max(int(layout.sum(-1).max()), 1)
    Wt = max(int(layout.sum(-2).max()), 1)
    cols = np.zeros((H, nb, W), np.int32)
    nvalid = np.zeros((H, nb), np.int32)
    rows_t = np.zeros((H, nb, Wt), np.int32)
    nvalid_t = np.zeros((H, nb), np.int32)
    for h in range(H):
        for r in range(nb):
            (active,) = np.nonzero(layout[h, r])
            n = len(active)
            nvalid[h, r] = n
            if n:
                cols[h, r, :n] = active
                cols[h, r, n:] = active[-1]
            else:
                cols[h, r, :] = r  # harmless self block, compute skipped
        for c in range(nb):
            (active,) = np.nonzero(layout[h, :, c])
            n = len(active)
            nvalid_t[h, c] = n
            if n:
                rows_t[h, c, :n] = active
                rows_t[h, c, n:] = active[-1]
            else:
                rows_t[h, c, :] = c
    return cols, nvalid, rows_t, nvalid_t


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


# stable Mosaic custom-call names (see flash_attention.FLASH_FWD_KERNEL)
SPARSE_FWD_KERNEL = "ds_sparse_fwd"


def _fwd_kernel(cols_ref, nvalid_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, heads, lut_heads,
                block, width):
    bh, iq, w = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    h = (bh % heads) if lut_heads > 1 else 0

    @pl.when(w == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(w < nvalid_ref[h, iq])
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
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

    @pl.when(w == width - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # rows with zero active blocks output zeros (acc is zeros), same
        # as the gather path's fully-masked-row guard
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0] + jnp.log(l_safe[:, 0])
        lse = jnp.where(l[:, 0] == 0.0, NEG_INF, lse)
        lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, block))


def _sparse_fwd(q, k, v, cols, nvalid, *, sm_scale, heads, block,
                interpret):
    bh, t, d = q.shape
    nb = t // block
    width = cols.shape[-1]
    lut_h = cols.shape[0]

    def q_im(b, i, w, cols_ref, nv_ref):
        return (b, i, 0)

    def kv_im(b, i, w, cols_ref, nv_ref):
        h = (b % heads) if lut_h > 1 else 0
        return (b, cols_ref[h, i, w], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nb, width),
        in_specs=[
            pl.BlockSpec((1, block, d), q_im),
            pl.BlockSpec((1, block, d), kv_im),
            pl.BlockSpec((1, block, d), kv_im),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), q_im),
            pl.BlockSpec((1, 1, 8, block),
                         lambda b, i, w, *_: (b, i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, heads=heads,
                          lut_heads=lut_h, block=block, width=width),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, nb, 8, block), jnp.float32),
        ],
        interpret=interpret,
        name=SPARSE_FWD_KERNEL,
    )(cols, nvalid, q, k, v)
    return out, lse[:, :, 0, :].reshape(bh, t)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


SPARSE_BWD_DQ_KERNEL = "ds_sparse_bwd_dq"


def _bwd_dq_kernel(cols_ref, nvalid_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_scr,
                   *, sm_scale, heads, lut_heads, block, width):
    bh, iq, w = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    h = (bh % heads) if lut_heads > 1 else 0

    @pl.when(w == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(w < nvalid_ref[h, iq])
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = jnp.transpose(lse_ref[0, 0, 0:1, :])
        delta = jnp.transpose(delta_ref[0, 0, 0:1, :])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(w == width - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


SPARSE_BWD_DKV_KERNEL = "ds_sparse_bwd_dkv"


def _bwd_dkv_kernel(rows_ref, nvalid_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale, heads, lut_heads, block, width):
    bh, ic, w = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    h = (bh % heads) if lut_heads > 1 else 0

    @pl.when(w == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(w < nvalid_ref[h, ic])
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = jnp.transpose(lse_ref[0, 0, 0:1, :])
        delta = jnp.transpose(delta_ref[0, 0, 0:1, :])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(w == width - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _sparse_bwd(q, k, v, out, lse, do, cols, nvalid, rows_t, nvalid_t,
                *, sm_scale, heads, block, interpret):
    bh, t, d = q.shape
    nb = t // block
    width = cols.shape[-1]
    width_t = rows_t.shape[-1]
    lut_h = cols.shape[0]
    lut_ht = rows_t.shape[0]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def _rows(x):
        r = x.reshape(bh, nb, 1, block)
        return jnp.broadcast_to(r, (bh, nb, 8, block))

    lsep = _rows(lse)
    deltap = _rows(delta)

    def q_im(b, i, w, *_):
        return (b, i, 0)

    def kv_im(b, i, w, cols_ref, nv_ref):
        h = (b % heads) if lut_h > 1 else 0
        return (b, cols_ref[h, i, w], 0)

    def row_im(b, i, w, *_):
        return (b, i, 0, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, heads=heads,
                          lut_heads=lut_h, block=block, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nb, width),
            in_specs=[
                pl.BlockSpec((1, block, d), q_im),
                pl.BlockSpec((1, block, d), kv_im),
                pl.BlockSpec((1, block, d), kv_im),
                pl.BlockSpec((1, block, d), q_im),
                pl.BlockSpec((1, 1, 8, block), row_im),
                pl.BlockSpec((1, 1, 8, block), row_im),
            ],
            out_specs=pl.BlockSpec((1, block, d), q_im),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
        name=SPARSE_BWD_DQ_KERNEL,
    )(cols, nvalid, q, k, v, do, lsep, deltap)

    # dK/dV: walk the transposed LUT — q/do/lse/delta blocks come from the
    # query rows attending to key block ic
    def qrow_im(b, i, w, rows_ref, nv_ref):
        h = (b % heads) if lut_ht > 1 else 0
        return (b, rows_ref[h, i, w], 0)

    def qrow_stat_im(b, i, w, rows_ref, nv_ref):
        h = (b % heads) if lut_ht > 1 else 0
        return (b, rows_ref[h, i, w], 0, 0)

    def kvself_im(b, i, w, *_):
        return (b, i, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, heads=heads,
                          lut_heads=lut_ht, block=block, width=width_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nb, width_t),
            in_specs=[
                pl.BlockSpec((1, block, d), qrow_im),
                pl.BlockSpec((1, block, d), kvself_im),
                pl.BlockSpec((1, block, d), kvself_im),
                pl.BlockSpec((1, block, d), qrow_im),
                pl.BlockSpec((1, 1, 8, block), qrow_stat_im),
                pl.BlockSpec((1, 1, 8, block), qrow_stat_im),
            ],
            out_specs=[pl.BlockSpec((1, block, d), kvself_im),
                       pl.BlockSpec((1, block, d), kvself_im)],
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), v.dtype)],
        interpret=interpret,
        name=SPARSE_BWD_DKV_KERNEL,
    )(rows_t, nvalid_t, q, k, v, do, lsep, deltap)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _sparse(q, k, v, cols, nvalid, rows_t, nvalid_t, sm_scale, heads,
            block, interpret):
    out, _ = _sparse_fwd(q, k, v, cols, nvalid, sm_scale=sm_scale,
                         heads=heads, block=block, interpret=interpret)
    return out


def _sparse_vjp_fwd(q, k, v, cols, nvalid, rows_t, nvalid_t, sm_scale,
                    heads, block, interpret):
    out, lse = _sparse_fwd(q, k, v, cols, nvalid, sm_scale=sm_scale,
                           heads=heads, block=block, interpret=interpret)
    return out, (q, k, v, out, lse, cols, nvalid, rows_t, nvalid_t)


def _sparse_vjp_bwd(sm_scale, heads, block, interpret, res, do):
    q, k, v, out, lse, cols, nvalid, rows_t, nvalid_t = res
    dq, dk, dv = _sparse_bwd(
        q, k, v, out, lse, do, cols, nvalid, rows_t, nvalid_t,
        sm_scale=sm_scale, heads=heads, block=block, interpret=interpret)
    return dq, dk, dv, None, None, None, None


_sparse.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)


def block_sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           layout: np.ndarray, block: int,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           luts: Optional[Tuple] = None) -> jnp.ndarray:
    """Block-sparse attention over [B, H, T, Dh] with a [H, nb, nb] 0/1
    layout (differentiable).  T must be a multiple of ``block`` (use the
    reference's pad-to-block model surgery otherwise,
    sparse_attention_utils.py there).  ``luts`` optionally supplies
    prebuilt ``build_kernel_luts(layout)`` output (callers in a hot loop
    should cache it — SparseSelfAttention does)."""
    B, H, T, D = q.shape
    if T % block:
        raise ValueError(f"seq len {T} not a multiple of block {block}")
    nb = T // block
    if layout.shape != (H, nb, nb):
        raise ValueError(
            f"layout {layout.shape} != (H={H}, nb={nb}, nb={nb})")
    if sm_scale is None:
        sm_scale = float(D) ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    if luts is None:
        luts = build_kernel_luts(np.asarray(layout))
    cols, nvalid, rows_t, nvalid_t = (jnp.asarray(a) for a in luts)
    # The LUTs are scalar-prefetched into SMEM (~1 MB/core on v5e); an
    # oversized LUT fails AOT compile with an opaque allocator error, so
    # reject it here with the actual remedies.  Reachable only with
    # different_layout_per_head at very long seq (head-uniform layouts
    # dedup to one plane in build_kernel_luts).
    smem_need = max(cols.nbytes + nvalid.nbytes,
                    rows_t.nbytes + nvalid_t.nbytes)
    if not interpret and smem_need > 900_000:
        raise ValueError(
            f"block-sparse LUT needs {smem_need} B of SMEM (~1 MB budget "
            f"per TPU core): layout [{layout.shape[0]} heads x {nb} x {nb} "
            f"blocks]. Use a larger sparsity block, a head-uniform layout "
            f"(different_layout_per_head=False), or flash attention.")
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    out = _sparse(qf, kf, vf, cols, nvalid, rows_t, nvalid_t,
                  sm_scale, H, block, interpret)
    return out.reshape(B, H, T, D)
