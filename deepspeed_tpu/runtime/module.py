"""Model protocol consumed by the engine.

The reference wraps an ``nn.Module`` whose forward returns the loss
(reference: tests/unit/simple_model.py:9-25 and engine.py:779).  The JAX
equivalent is a pair (init, loss_fn) over an immutable param pytree:

    class MyModel(TrainModule):
        def init(self, rng) -> params
        def loss_fn(self, params, batch, rng, train=True) -> scalar loss

Adapters are provided for Flax linen modules and bare (init_fn, loss_fn)
pairs.  ``param_partition_specs`` optionally returns a pytree of
PartitionSpecs carrying the model's own tensor-parallel placement (the
analogue of the user-supplied Megatron ``mpu`` object, reference
deepspeed/__init__.py:76-77) which ZeRO composes with the data axis.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax


def mark_subtrees(params: dict, keys) -> dict:
    """A pytree of bools aligned with ``params``: True for every leaf
    under one of the top-level ``keys`` — the form ``stacked_param_spec``
    takes for a model that keeps its layer stacks under a few keys."""
    return {k: jax.tree.map(lambda _: k in keys, v)
            for k, v in params.items()}


class TrainModule:
    """Duck-typed protocol; subclass or just match the surface."""

    def init(self, rng) -> Any:
        raise NotImplementedError

    def loss_fn(self, params, batch, rng, train: bool = True):
        raise NotImplementedError

    def param_partition_specs(self, params) -> Optional[Any]:
        return None

    def stacked_param_spec(self, params) -> Optional[Any]:
        """Optional: a pytree of bools aligned with ``params`` marking the
        leaves that are stacked over a leading layer axis which the
        forward pass SCANS over (True = dim 0 is the scanned axis and the
        model consumes one slice of it per scan tick).  The one
        declaration of that fact: ZeRO never shards a marked leaf's dim 0
        (a slice along a partitioned dimension makes the partitioner
        replicate the whole stack inside the loop; runtime/zero.py), and
        ``streaming_param_spec`` is derived from it.  Return None when
        nothing is scanned (unrolled layers, or a model without a layer
        stack): every leaf then keeps the first-divisible-dim rule."""
        return None

    def streaming_param_spec(self, params) -> Optional[Any]:
        """Optional: the ``stacked_param_spec`` marks, returned only when
        the model also FETCHES each scan tick's slice itself (True =
        streamable).  With
        ``zero_optimization.param_streaming`` the engine keeps those
        leaves' compute copies in HOST memory, so device-resident
        parameter bytes ~ one layer — ZeRO-Infinity-style parameter
        offload (the capacity feature the reference implements as CPU/
        NVMe param partitions, deepspeed/runtime/zero/stage2.py's fp16
        partition machinery generalized by the ZeRO-Infinity paper).
        Return None when nothing is streamable (streaming becomes a
        config error rather than a silent no-op)."""
        return None

    def labelled_rows(self, batch) -> Optional[int]:
        """Optional: rows of a HOST batch (numpy leaves, as
        ``train_batch`` receives it) that carry a label, for a model
        whose head reads the labelled rows alone (models/bert.py).  With
        telemetry on the engine sets ``train_head_rows{kind="labelled"}``
        from it; None (a batch already on the device, or a head that
        reads every row) sets nothing."""
        return None

    def sparse_grad_tokens(self, batch) -> dict:
        """Optional: declare embedding-style params whose gradient rows are
        only the batch's token rows.  Returns {param keystr: token-id
        array}, where keystr is ``jax.tree_util.keystr`` of the param's
        path and the tokens come from ``batch`` (called inside the traced
        step with the per-worker batch ``[grad_acc, local_micro, ...]``).
        With ``sparse_gradients`` enabled the engine exchanges these
        params' grads as (indices, values) instead of dense — the
        reference's nn.Embedding CSR allreduce (engine.py:177-183,
        1153-1209)."""
        return {}


class FunctionalModule(TrainModule):
    """Wrap bare (init_fn, loss_fn) callables."""

    def __init__(self, init_fn: Callable, loss_fn: Callable,
                 partition_spec_fn: Optional[Callable] = None):
        self._init = init_fn
        self._loss = loss_fn
        self._specs = partition_spec_fn

    def init(self, rng):
        return self._init(rng)

    def loss_fn(self, params, batch, rng, train: bool = True):
        return self._loss(params, batch, rng, train)

    def param_partition_specs(self, params):
        return self._specs(params) if self._specs else None


class FlaxModule(TrainModule):
    """Adapter for a Flax linen module + a loss callable.

    ``loss_fn(apply_fn, variables, batch, rng, train) -> loss``.
    ``example_batch`` supplies shapes for lazy init.
    """

    def __init__(self, module, loss_fn: Callable, example_batch,
                 partition_spec_fn: Optional[Callable] = None):
        self.module = module
        self._loss = loss_fn
        self._example_batch = example_batch
        self._specs = partition_spec_fn

    def init(self, rng):
        return self.module.init(rng, self._example_batch)

    def loss_fn(self, params, batch, rng, train: bool = True):
        return self._loss(self.module.apply, params, batch, rng, train)

    def param_partition_specs(self, params):
        return self._specs(params) if self._specs else None
