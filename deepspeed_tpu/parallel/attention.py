"""The Pallas flash kernel on the engine's mesh.

``ops/pallas`` knows no mesh; the layout rule lives here, once: q/k/v are
[B, H, T, Dh] with batch rows over ``data`` (ZeRO, data parallelism) and
heads over ``model`` (Megatron TP), which is how GSPMD already lays them
out.  Rows are independent, so each device runs the same kernel on the
rows it holds (``manual_over_mesh``, parallel/mesh.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas.flash_attention import flash_attention
from .mesh import DATA_AXIS, MODEL_AXIS, auto_axis, manual_over_mesh


def sharded_flash_attention(q, k, v, *, key_mask=None,
                            dropout_rate: float = 0.0, dropout_rng=None,
                            dropout_seed=None, **kwargs):
    """``flash_attention`` (its arguments, but for ``bh_affine``) wherever
    the models trace it: manual over the mesh in scope; bare with no
    mesh, on one device, or inside a caller's own shard_map (the
    pipeline's stages).  The dropout hash sees the same global batch·head
    ids 0..B·H-1 however the rows are split."""
    b, h = q.shape[:2]
    if dropout_seed is None and dropout_rate > 0.0:
        assert dropout_rng is not None, \
            "dropout_rate > 0 requires dropout_rng or dropout_seed"
        dropout_seed = jax.random.bits(dropout_rng, (), jnp.uint32)
    seed = jnp.asarray(0 if dropout_seed is None else dropout_seed,
                       jnp.uint32)
    bax, hax = auto_axis(DATA_AXIS, b), auto_axis(MODEL_AXIS, h)
    masks, mask_specs = (), ()
    if key_mask is not None:
        km = jnp.asarray(key_mask)
        if h > 1 and km.shape[:1] == (b * h,):      # [B·H, Tk] rows
            km = km.reshape(b, h, -1)
        masks = (km,)
        mask_specs = (P(bax, hax, None) if km.ndim == 3 else P(bax, None),)

    def local(q, k, v, seed, brank, hrank, *mask):
        bl, hl = q.shape[:2]
        # global id of local row (b', j): (brank·bl + b')·h + hrank·hl + j
        # — the kernel's affine form (see _grid_bh there)
        first = (brank[0] * (bl * h) + hrank[0] * hl).astype(jnp.uint32)
        km = mask[0] if mask else None
        if km is not None and km.ndim == 3:
            km = km.reshape(bl * hl, -1)
        return flash_attention(q, k, v, bh_affine=(first, hl, h),
                               key_mask=km, dropout_rate=dropout_rate,
                               dropout_seed=seed, **kwargs)

    def ranks(axis):
        n = 1 if axis is None else jax.sharding.get_abstract_mesh().shape[axis]
        return jnp.arange(n, dtype=jnp.int32)

    rows = P(bax, hax, None, None)
    return manual_over_mesh(
        local,
        in_specs=(rows, rows, rows, P(), P(bax), P(hax)) + mask_specs,
        out_specs=rows,
    )(q, k, v, seed, ranks(bax), ranks(hax), *masks)
