"""Device-mesh construction — the TPU replacement for the reference's
process-group zoo (reference: deepspeed/runtime/pipe/topology.py:252-455 and
the NCCL init at runtime/engine.py:125-145).

One ``jax.sharding.Mesh`` with named axes replaces all NCCL communicators:
  - ``data``  axis ↔ DP groups (gradient psum / ZeRO reduce-scatter)
  - ``model`` axis ↔ Megatron slice groups (TP collectives)
  - ``pipe``  axis ↔ stage p2p pair groups (ppermute)
Axis order places ``pipe`` outermost (slow links OK — p2p is latency-bound,
low volume) and ``model`` innermost (fastest ICI — TP collectives are in the
critical path of every matmul), matching the scaling-book recipe and the
reference's own axis-ordering rationale (topology.py:235-243).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
DEFAULT_AXES: Tuple[str, str, str, str] = (
    PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS)


def build_mesh(pp: int = 1,
               dp: Optional[int] = None,
               tp: int = 1,
               sp: int = 1,
               devices: Optional[Sequence] = None) -> Mesh:
    """Build a (pipe, data, seq, model) mesh over the available devices.

    ``dp=None`` absorbs whatever device count remains after pp×sp×tp.
    ``sp`` is the sequence/context-parallel axis consumed by
    parallel/sequence.py (ring / Ulysses attention); it sits between data
    (slow OK) and model (fastest ICI) because ring rotations are
    bandwidth-hungry but latency-tolerant.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if dp is None:
        if n % (pp * tp * sp) != 0:
            raise ValueError(
                f"device count {n} not divisible by pp*sp*tp="
                f"{pp * sp * tp}")
        dp = n // (pp * tp * sp)
    if pp * dp * sp * tp != n:
        raise ValueError(
            f"pp*dp*sp*tp = {pp}*{dp}*{sp}*{tp} != device count {n}")
    dev_array = np.asarray(devices).reshape(pp, dp, sp, tp)
    return Mesh(dev_array, DEFAULT_AXES)


def single_device_mesh() -> Mesh:
    return build_mesh(pp=1, dp=1, tp=1, devices=jax.devices()[:1])


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh, rank: int = 1, batch_dim: int = 0) -> NamedSharding:
    """Batch sharding over the data axis for an array of given rank."""
    spec = [None] * rank
    spec[batch_dim] = DATA_AXIS
    return NamedSharding(mesh, P(*spec))




def _kernel_mesh():
    """The abstract mesh a kernel call may go manual over, or None: no
    mesh, one device, or already inside somebody's shard_map.  A nested
    call stays bare for two reasons.  The TPU lowering refuses a Mosaic
    kernel under a partial-manual shard_map nested in another even when
    the two together cover the mesh, so nesting buys nothing on chips.
    And in the pipeline's stage bodies, which run divergent branches, the
    extra boundary moved GSPMD to put resharding collective-permutes
    inside a branch one stage alone executes (BERT pp2 x dp4 deadlocked
    on the CPU runtime)."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.manual_axes or am.size == 1 or am.empty else am


def auto_axis(name: str, dim: int) -> Optional[str]:
    """``name`` if ``manual_over_mesh`` would split over that axis: it is
    larger than one and divides ``dim``; else None — the spec entry that
    leaves ``dim`` unsplit."""
    am = _kernel_mesh()
    n = 1 if am is None else am.shape.get(name, 1)
    return name if n > 1 and dim % n == 0 else None


def manual_over_mesh(fn, in_specs, out_specs):
    """``fn`` under a shard_map that is manual over every axis of the mesh
    in scope.

    A Mosaic kernel cannot be partitioned by GSPMD: on a mesh of several
    real chips the compiler refuses a bare call whose operands are
    sharded (interpret mode on the CPU mesh hides this).  The callers of
    the Pallas kernels therefore run them here, with specs built from
    ``auto_axis``.  Returns ``fn`` itself where ``_kernel_mesh`` finds
    nothing to go manual over.
    """
    if _kernel_mesh() is None:
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)
