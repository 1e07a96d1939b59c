"""ZeRO as GSPMD sharding policy.

The reference implements ZeRO imperatively: ~1000 lines of sub-partitioning +
reduce-scatter for stage 1 (reference: deepspeed/runtime/zero/stage1.py) and
~1850 lines of autograd-hook bucketing, dedicated CUDA streams, and sharded
all-gathers for stage 2 (reference: deepspeed/runtime/zero/stage2.py).  On
TPU the identical memory/communication semantics are *placement decisions on
a compiled graph*:

  stage 0 — master params, grads, optimizer state replicated over ``data``.
  stage 1 — optimizer state (incl. fp32 master copy) sharded over ``data``;
            grads still fully reduced (psum); params all-gathered by XLA
            where consumed.  ≡ reference stage1.py sub-partitioning.
  stage 2 — + gradients sharded over ``data``: the sharding constraint on
            the grad tree turns XLA's grad all-reduce into reduce-scatter
            (≡ the IPG bucket + reduce-to-owner machinery, stage2.py:613-738)
            and the latency-hiding scheduler overlaps it with the backward
            (≡ ``overlap_comm``'s reduction stream, stage2.py:283-287).
  stage 3 — + parameters themselves stored sharded; XLA all-gathers each
            layer's params just before use and discards after (the reference
            *defines* stage 3 but raises NotImplementedError, engine.py:692;
            here it falls out of the same mechanism).
  offload — optimizer state placed in host memory (``pinned_host`` memory
            kind); see runtime/offload.py.

Which dim: the first unassigned one the data-axis size divides — but
never the layer axis a model scans over.  A scan body takes one slice of
its stacked ``[L, ...]`` leaves per iteration, and a slice along a
partitioned dimension is something the partitioner can only do by
replicating the operand first: with the shard on the layer axis every
iteration all-gathered the WHOLE stack to use one layer of it (GPT-2 XL at
dp=4: 283 GB gathered a step where 3.1 GB holds every parameter once;
PERF.md, PR 31).  So a leaf the model declares as stacked
(``TrainModule.stacked_param_spec``) is sharded from dim 1 on — a feature
axis — and an iteration gathers only its own layer (``gather_layer``).

Leaves whose dims don't divide the data-axis size stay replicated — the
analogue of the reference's alignment padding (stage2.py:218-278), chosen
instead of padding because XLA requires static per-shard shapes.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS
from . import precision


def _leaf_shape(leaf) -> tuple:
    return tuple(getattr(leaf, "shape", ()))


def _uses_axis(entry, axis_name: str) -> bool:
    """Whether one PartitionSpec entry (None, a name, a tuple of names)
    shards its dim over ``axis_name``."""
    return (axis_name in entry if isinstance(entry, tuple)
            else entry == axis_name)


def shard_spec_for_leaf(shape: tuple,
                        axis_size: int,
                        axis_name: str = DATA_AXIS,
                        base_spec: Optional[P] = None,
                        first_dim: int = 0) -> P:
    """Extend ``base_spec`` (e.g. a tensor-parallel spec) by sharding the
    first unassigned dim divisible by ``axis_size`` over ``axis_name``,
    looking from ``first_dim`` on (1 for a leaf stacked over a scanned
    layer axis: dim 0 is never cut)."""
    base = list(base_spec) if base_spec is not None else []
    base += [None] * (len(shape) - len(base))
    if axis_size <= 1:
        return P(*base)
    # A base spec may already consume the axis (expert-parallel weights
    # shard their expert dim over ``data``); a mesh axis can appear at most
    # once in a PartitionSpec, so ZeRO then has nothing to add.
    if any(_uses_axis(e, axis_name) for e in base):
        return P(*base)
    for i, d in enumerate(shape):
        if i >= first_dim and base[i] is None and d % axis_size == 0 \
                and d > 0:
            base[i] = axis_name
            return P(*base)
    return P(*base)  # too small / indivisible: replicate (no padding on TPU)


def sanitize_base_spec(spec: Optional[P], shape: tuple, mesh: Mesh) -> \
        Optional[P]:
    """Drop base-spec axis assignments whose leaf dim is not divisible by
    the mesh-axis size (product, for tuple entries) — the leaf falls back
    to replication on that dim, the same no-padding rule ZeRO applies to
    its own ``data``-axis sharding above.  Concretely: a model declaring
    expert-parallel ``P('data', ...)`` on a 4-expert weight keeps training
    on a dp=8 mesh instead of failing NamedSharding validation."""
    if spec is None:
        return None
    if len(spec) > len(shape):
        raise ValueError(
            f"partition spec {spec} has more entries than array rank "
            f"{len(shape)} (shape {shape}) — model param_partition_specs "
            "and param tree disagree")
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for i, e in enumerate(entries):
        if e is None:
            out.append(None)
            continue
        names = e if isinstance(e, tuple) else (e,)
        # Greedy major-to-minor retention: keep each sub-axis while the
        # running product still divides the dim, so a tuple entry like
        # ('data', 'model') on a dim divisible by dp but not dp*tp keeps
        # the 'data' sharding instead of replicating wholesale.
        kept, prod = [], 1
        for n in names:
            s = int(mesh.shape.get(n, 1))
            if shape[i] % (prod * s) == 0:
                kept.append(n)
                prod *= s
        if not kept:
            out.append(None)
        elif not isinstance(e, tuple):
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


class ZeroShardingPlan:
    """Per-stage placement rules for the train-state pytree."""

    def __init__(self, stage: int, mesh: Mesh,
                 base_param_specs: Optional[Any] = None,
                 offload: bool = False,
                 params: Optional[Any] = None,
                 stacked: Optional[Any] = None):
        if not 0 <= stage <= 3:
            raise ValueError(f"ZeRO stage must be 0..3, got {stage}")
        self.stage = stage
        self.mesh = mesh
        self.offload = offload
        self.dp = mesh.shape.get(DATA_AXIS, 1)
        # the model's ``stacked_param_spec``: per leaf, whether dim 0 is
        # a layer axis the forward scans over (never sharded, see the
        # module docstring).  None: no leaf is.
        self.scanned = (None if stacked is None
                        else [bool(b) for b in jax.tree.leaves(stacked)])
        # base specs carry tensor/expert-parallel placement decided by the
        # model; ZeRO composes the 'data' axis on top.  Sanitized ONCE here
        # (indivisible dims → replicated); ``params`` supplies leaf shapes.
        if base_param_specs is not None and params is not None:
            spec_def = jax.tree.structure(
                base_param_specs, is_leaf=lambda x: isinstance(x, P))
            param_def = jax.tree.structure(params)
            if spec_def != param_def:
                raise ValueError(
                    "param_partition_specs tree structure does not match "
                    "the param tree — every param leaf needs exactly one "
                    "PartitionSpec at the same position (a silent "
                    "mismatch would drop ALL tensor-parallel placement "
                    "and replicate every leaf).\n"
                    f"  specs tree:  {spec_def}\n"
                    f"  params tree: {param_def}")
            base_param_specs = jax.tree.map(
                lambda s, l: sanitize_base_spec(
                    s, _leaf_shape(l), mesh),
                base_param_specs, params,
                is_leaf=lambda x: isinstance(x, P))
        self.base_param_specs = base_param_specs

    # -- helpers --------------------------------------------------------
    def _specs(self, tree, sharded: bool):
        leaves, treedef = jax.tree.flatten(tree)
        base_leaves = (None if self.base_param_specs is None
                       else jax.tree.leaves(self.base_param_specs))
        if base_leaves is not None and len(base_leaves) != len(leaves):
            raise ValueError(
                "param_partition_specs leaf count does not match the "
                f"tree being placed: {len(base_leaves)} specs vs "
                f"{len(leaves)} leaves — positional matching would "
                "mis-assign tensor-parallel placement.\n"
                f"  specs tree: "
                f"{jax.tree.structure(self.base_param_specs)}\n"
                f"  placed tree: {treedef}")
        if self.scanned is not None and len(self.scanned) != len(leaves):
            raise ValueError(
                "stacked_param_spec leaf count does not match the tree "
                f"being placed: {len(self.scanned)} marks vs "
                f"{len(leaves)} leaves")
        specs = []
        for i, leaf in enumerate(leaves):
            base = None if base_leaves is None else base_leaves[i]
            if sharded:
                specs.append(shard_spec_for_leaf(
                    _leaf_shape(leaf), self.dp, DATA_AXIS, base,
                    first_dim=int(self.is_scanned(i))))
            else:
                specs.append(base if base is not None else P())
        return jax.tree.unflatten(treedef, specs)

    def is_scanned(self, i: int) -> bool:
        """Whether dim 0 of the i-th leaf is a scanned layer axis."""
        return self.scanned is not None and self.scanned[i]

    def _sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # -- public placement queries --------------------------------------
    def master_param_specs(self, params):
        """fp32 master copy: sharded from stage >= 1."""
        return self._specs(params, sharded=self.stage >= 1)

    def compute_param_specs(self, params):
        """Params as consumed by the forward pass: sharded only at stage 3."""
        return self._specs(params, sharded=self.stage >= 3)

    def grad_specs(self, params):
        """Gradients: sharded (reduce-scattered) from stage >= 2."""
        return self._specs(params, sharded=self.stage >= 2)

    def opt_state_specs(self, opt_state, params):
        """Optimizer moments mirror the master-param placement; scalar
        counters stay replicated."""
        master = self.master_param_specs(params)
        master_leaves = jax.tree.leaves(master)
        # Build spec tree by structural matching: any sub-tree of opt_state
        # with the same structure as params gets master specs; scalars get P().
        params_def = jax.tree.structure(params)

        param_shapes = [_leaf_shape(l) for l in jax.tree.leaves(params)]

        def match(subtree):
            """Same structure AND same leaf shapes as params.  The shape
            check matters: optimizer states may carry param-structured trees
            whose leaves are NOT param-shaped (e.g. 1-bit Adam's flat error
            buffers), and assigning them master specs would be wrong."""
            try:
                if (jax.tree.structure(subtree) == params_def
                        and [_leaf_shape(l) for l in
                             jax.tree.leaves(subtree)] == param_shapes):
                    return jax.tree.unflatten(params_def, master_leaves)
            except Exception:
                pass
            return None

        sharded = self.stage >= 1

        def recurse(node):
            m = match(node)
            if m is not None:
                return m
            if isinstance(node, (list, tuple)):
                out = [recurse(c) for c in node]
                return type(node)(out) if not hasattr(node, "_fields") else type(node)(*out)
            if isinstance(node, dict):
                return {k: recurse(v) for k, v in node.items()}
            # non-param-shaped state (e.g. 1-bit Adam's flat error buffers):
            # shard over data when divisible — replicating a full-param-size
            # fp32 buffer per device would undo the ZeRO memory win.  Scalar
            # counters have no divisible dim and stay replicated.
            shape = _leaf_shape(node)
            if sharded and shape:
                return shard_spec_for_leaf(shape, self.dp, DATA_AXIS)
            return P()

        return recurse(opt_state)

    def placement_summary(self, params):
        """Where the sharded placement (master, and with it moments and
        stage>=2 gradients) puts the ``data`` axis, leaf by leaf:
        ``({"scanned": n, "other": n, "replicated": n}, names)``.
        ``scanned``: on dim 0 of a leaf the model scans over — the
        placement that gathers a whole stack per scan iteration; only a
        model's own base spec can still put it there.  ``replicated``:
        no dim left that ``data`` divides; ``names`` are those leaves'
        key paths with their shapes."""
        specs = jax.tree.leaves(self._specs(params, sharded=True),
                                is_leaf=lambda x: isinstance(x, P))
        counts = {"scanned": 0, "other": 0, "replicated": 0}
        names = []
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        for i, ((path, leaf), spec) in enumerate(zip(flat, specs)):
            dims = [d for d, e in enumerate(spec)
                    if _uses_axis(e, DATA_AXIS)]
            if not dims:
                counts["replicated"] += 1
                names.append(f"{jax.tree_util.keystr(path)}"
                             f"{list(_leaf_shape(leaf))}")
            elif dims[0] == 0 and self.is_scanned(i):
                counts["scanned"] += 1
            else:
                counts["other"] += 1
        return counts, names

    def master_shardings(self, params):
        """Master params stay in device HBM even when offloading: they feed
        the forward cast every micro-step.  Offload targets the optimizer
        moments only (the reference's host-resident state is the fp32
        partitions consumed *only* at step time, stage2.py:743-900; our
        equivalent of that working set is the moments — see
        runtime/offload.py for the full host-Adam tier)."""
        return jax.tree.map(self._sharding, self.master_param_specs(params),
                            is_leaf=lambda x: isinstance(x, P))

    def opt_state_shardings(self, opt_state, params):
        # Only the non-offload engine path consumes this (both offload
        # tiers build their own flat host staging; see runtime/engine.py),
        # so placement is plain device memory.
        return jax.tree.map(self._sharding,
                            self.opt_state_specs(opt_state, params),
                            is_leaf=lambda x: isinstance(x, P))


# The two places where a ZeRO placement turns into a collective inside
# the jitted step each sit under a ``jax.named_scope``; the scope lands
# in the ``op_name`` of the operations the partitioner derives from them
# (xprof's op profile groups by it; docs/observability.md).

def cast_for_compute(master, compute_dtype, plan=None):
    """The step's read of the master copy (data-sharded from stage 1): the
    cast to compute dtype, which is what XLA all-gathers for the forward
    — scope ``zero_gather``.

    With ``plan``, the compute copy of a leaf the model scans over is
    pinned to the master's own placement: the cast is local to a shard
    (2 bytes a value leave the chip, not 4), the stack never exists
    gathered, and each scan iteration gathers its own layer
    (``gather_layer``).  Every other leaf is the partitioner's, as
    before."""
    with jax.named_scope("zero_gather"):
        out = precision.cast_to_compute(master, compute_dtype)
        if plan is None or plan.dp <= 1 or plan.stage < 1 \
                or plan.scanned is None:
            return out
        specs = jax.tree.leaves(plan.master_param_specs(master),
                                is_leaf=lambda x: isinstance(x, P))
        leaves, treedef = jax.tree.flatten(out)
        leaves = [
            jax.lax.with_sharding_constraint(
                x, NamedSharding(plan.mesh, spec))
            if plan.is_scanned(i) else x
            for i, (x, spec) in enumerate(zip(leaves, specs))]
        return jax.tree.unflatten(treedef, leaves)


def gather_layer(layer, stack_specs, keep_leading: bool = False):
    """The scan body's read of ONE slice of the model's stacked leaves:
    pin it to the model's own (tensor-parallel) placement, replicated over
    ``data``, so that the partitioner all-gathers this layer — and not the
    stack, and not the activations for a tensor-parallel execution over
    the batch's own axis.  Its transpose is the backward's: the layer's
    gradient leaves the iteration reduced, into a stack sharded on a
    feature axis.  Scope ``zero_gather``.

    ``layer``: the slice the scan handed the body; ``stack_specs``: the
    model's ``param_partition_specs`` of the STACKED leaves, same
    structure (dim 0, the scanned axis, is dropped here; kept as an
    unsharded dim with ``keep_leading`` for a body that takes several
    rows a tick).  Called by every model that declares
    ``stacked_param_spec``; a no-op where there is nothing to gather: no
    ambient mesh (eager use, serving), a ``data`` axis of one, or a
    manual (``shard_map``) region, whose operands are already local."""
    am = jax.sharding.get_abstract_mesh()
    if am.shape.get(DATA_AXIS, 1) <= 1 or am.manual_axes:
        return layer

    def one(x, spec):
        entries = tuple(spec)[1:]
        if keep_leading:
            entries = (None,) + entries
        spec = sanitize_base_spec(P(*entries), _leaf_shape(x), am)
        return jax.lax.with_sharding_constraint(x, spec)

    with jax.named_scope("zero_gather"):
        return jax.tree.map(one, layer, stack_specs)


def constrain_grads(grads, plan: ZeroShardingPlan):
    """Apply the stage>=2 reduce-scatter constraint inside the jitted
    step — scope ``zero_scatter``."""
    if plan.stage < 2 or plan.dp <= 1:
        return grads
    specs = plan.grad_specs(grads)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    grad_leaves, treedef = jax.tree.flatten(grads)
    with jax.named_scope("zero_scatter"):
        out = [jax.lax.with_sharding_constraint(
                   g, NamedSharding(plan.mesh, s))
               for g, s in zip(grad_leaves, spec_leaves)]
    return jax.tree.unflatten(treedef, out)
