"""Runtime numeric utilities (norms, clipping).

(reference: deepspeed/runtime/utils.py:154-275 — grad/weight norms with
model-parallel dedup.  Under SPMD-by-sharding there is nothing to dedup:
gradients are unique per logical tensor, so the norms are plain reductions
which XLA fuses into the step.)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.asarray(0.0, jnp.float32)
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    return jnp.sqrt(sq)


def clip_by_global_norm(tree, max_norm: float, norm=None):
    """Scale the tree so its global L2 norm is <= max_norm
    (reference: runtime/utils.py clip_grad_norm_ semantics)."""
    if norm is None:
        norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda x: x * scale.astype(x.dtype), tree), norm


def weight_norm(tree) -> jnp.ndarray:
    return global_norm(tree)


def device_bytes(tree) -> int:
    """Bytes one device holds of a placed tree of arrays, from each
    leaf's own sharding."""
    return sum(math.prod(x.sharding.shard_shape(x.shape))
               * x.dtype.itemsize for x in jax.tree.leaves(tree))


def see_memory_usage(message: str = "", force: bool = False) -> str:
    """Device + host memory snapshot (reference: runtime/utils.py:489-553
    see_memory_usage/memory_status — CUDA allocator stats there, per-device
    ``memory_stats()`` + RSS here)."""
    return memory_status(message)


def collect_memory_stats() -> dict:
    """Structured device + host memory snapshot — the ONE collection
    path shared by the ``memory_status`` log line and the telemetry
    gauges (``telemetry.memory.MemorySampler``), so neither re-parses
    the other's formatting.

    Returns ``{"devices": [{"id", "platform", "bytes_in_use",
    "peak_bytes_in_use", "bytes_limit"}, ...], "host_rss_bytes": int |
    None}``.  Reads PJRT allocator bookkeeping (``memory_stats()``) and
    ``/proc/self/status`` only — never drains the device, so it is safe
    to call at the engine's sync cadence without adding a sync."""
    import jax

    devices = []
    for d in jax.local_devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # backend without allocator stats (CPU)
            pass
        if stats:
            devices.append({
                "id": d.id,
                "platform": getattr(d, "platform", None),
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            })
    rss = None
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    rss = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    return {"devices": devices, "host_rss_bytes": rss}


def format_memory_status(stats: dict, message: str = "") -> str:
    """Render ``collect_memory_stats()`` output the way ``memory_status``
    always has (first 8 devices, GiB with peaks, host RSS)."""
    parts = []
    for dev in stats.get("devices", [])[:8]:
        used = (dev.get("bytes_in_use") or 0) / 2 ** 30
        peak = (dev.get("peak_bytes_in_use") or 0) / 2 ** 30
        lim = (dev.get("bytes_limit") or 0) / 2 ** 30
        parts.append(f"{dev['id']}: {used:.2f}/{lim:.2f}GB peak {peak:.2f}")
    rss = stats.get("host_rss_bytes")
    if rss is not None:
        parts.append(f"host RSS {rss / 2 ** 30:.2f}GB")
    return (f"MEMORY {message}: " if message else "MEMORY: ") + \
        ("; ".join(parts) if parts else "no stats available")


def memory_status(message: str = "") -> str:
    report = format_memory_status(collect_memory_stats(), message)
    from ..utils.logging import log_dist
    log_dist(report, ranks=[0])
    return report


class PartitionedTensor:
    """A tensor uniformly partitioned along a named mesh axis, with the
    reference's meta encoding (reference: runtime/utils.py:379-482 —
    used by the pipeline engine to ship tensor-parallel activations as
    per-rank slices and reconstruct with an all-gather).

    Inside ``shard_map`` over ``axis_name``, ``local_data`` is this
    shard's flat slice and ``full()`` reconstructs the original tensor
    with one ``all_gather``.  The meta vector follows the reference's
    field order (``[ndims, *shape, num_parts, rank, 0, *cumparts]``) but
    the partitioning itself is equal-ceil slices (padded), NOT the
    reference's base+remainder split — static slice shapes are what make
    the single fused all_gather possible; ``from_meta`` validates the
    layout so mixed-layout interop fails loudly rather than corrupting.
    """

    @staticmethod
    def _row_ptr(numel: int, parts: int):
        # equal ceil-sized slices (padded) — static shapes for the gather;
        # the rowptr is clamped to numel so meta matches the logical tensor
        per = -(-numel // parts)
        return [min(i * per, numel) for i in range(parts + 1)], per

    def __init__(self, tensor, axis_name: str, _local=None, _shape=None):
        self.axis_name = axis_name
        self.num_parts = jax.lax.axis_size(axis_name)
        self.rank = jax.lax.axis_index(axis_name)
        if _local is not None:
            self.local_data, self.orig_shape = _local, tuple(_shape)
            self.partition, _ = self._row_ptr(
                int(np.prod(self.orig_shape)), self.num_parts)
            return
        self.orig_shape = tuple(tensor.shape)
        numel = int(np.prod(self.orig_shape))
        self.partition, per = self._row_ptr(numel, self.num_parts)
        flat = jnp.pad(tensor.reshape(-1),
                       (0, per * self.num_parts - numel))
        self.local_data = jax.lax.dynamic_slice_in_dim(
            flat, self.rank * per, per)

    def to_meta(self) -> np.ndarray:
        """Meta vector in the reference's encoding (int32):
        ``[ndims, *shape, num_parts, rank, 0, *row_ptr[1:]]``.

        Returns CONCRETE numpy even under jit — every field is static at
        trace time (shapes, axis size, row pointers); the rank slot is -1
        because the receiver's own ``axis_index`` is the authoritative
        rank (the reference's assert rank==meta[1] compares pipe peers at
        the same coordinate, runtime/utils.py:411 there)."""
        shape = list(self.orig_shape)
        return np.asarray(
            [len(shape)] + shape + [self.num_parts, -1, 0]
            + list(self.partition)[1:], np.int32)

    @classmethod
    def from_meta(cls, meta, local_part, axis_name: str):
        meta = np.asarray(meta)
        nd = int(meta[0])
        shape = tuple(int(x) for x in meta[1:1 + nd])
        num_parts = int(meta[1 + nd])
        obj = cls(None, axis_name, _local=local_part, _shape=shape)
        if num_parts != obj.num_parts:
            raise ValueError(
                f"meta was produced over {num_parts} parts but axis "
                f"{axis_name!r} has {obj.num_parts}")
        _, per = obj._row_ptr(int(np.prod(shape)), obj.num_parts)
        if int(local_part.shape[0]) != per:
            raise ValueError(
                f"local slice has {local_part.shape[0]} elements; layout "
                f"expects {per}")
        return obj

    def full_size(self):
        return self.orig_shape

    def full(self) -> jnp.ndarray:
        flat = jax.lax.all_gather(self.local_data, self.axis_name,
                                  tiled=True)
        numel = int(np.prod(self.orig_shape))
        return flat[:numel].reshape(self.orig_shape)
