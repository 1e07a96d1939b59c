"""What a model's remat'd block keeps across its boundary.

``remat="block"`` recomputes a layer in the backward pass.  A bare
``jax.checkpoint`` keeps the layer's input and nothing else, so the
backward runs the flash kernel's forward a second time only to hand its
output and log-sum-exp to the backward kernels.  ``checkpoint_block``
gives that ``jax.checkpoint`` a policy that saves the two
(``flash_out`` and ``flash_lse``, named in the kernel's ``custom_vjp``,
ops/pallas/flash_attention.py) where the device holds them: the
recomputed forward's kernel then has no consumer and is removed, and the
backward recomputes the projections and the head split, not the kernel.
The saved values are the ones the recomputation would produce (same
kernel, same seed, same hash), so the step computes what it computed.

The engine owns the memory and the model spends it: the train step is
traced inside ``remat_budget_scope(RematBudget(...))``, as a kernel's
interpret mode is handed over by ``engine._pallas_scope()``.  The choice
is analytic, made once while the step is traced, from the device's
``bytes_limit``, the bytes the device holds and the shapes known in the
body; nothing is compiled to find out.  With no budget in scope (no
engine, a backend that states no limit, an offload tier) nothing is
saved: the program of before.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from contextvars import ContextVar
from typing import Callable, Dict, Optional

import jax
import numpy as np

from ...ops.pallas.flash_attention import FLASH_LSE, FLASH_OUT
from ...parallel.mesh import DATA_AXIS, MODEL_AXIS

SAVED = (FLASH_OUT, FLASH_LSE)      # kept together or not at all

# Share of ``bytes_limit`` nothing is planned into: the allocator's own
# needs, and what the reckoning below leaves out.
HOLD_BACK = 0.10
LANES = 128     # a row of an array in HBM takes whole 128-lane tile rows


@dataclasses.dataclass
class RematBudget:
    """One device's memory as the engine knows it when the step is traced.

    ``resident_bytes``: what the device holds all step long (the train
    state, exact from the placed tree, or the allocator's count where that
    is larger).  ``copy_bytes``: the compute-dtype copy of the parameters
    a device holds through the step.  ``grad_bytes``: a gradient tree of
    them, in compute dtype as the backward writes it and in float32 as the
    optimizer reads it.  ``report`` is called with the choice (``{name:
    bytes a device keeps over the whole stack}``, 0 for a name that is
    recomputed) and a line for the log, each time a model is traced."""
    bytes_limit: int
    resident_bytes: int
    copy_bytes: int = 0
    grad_bytes: int = 0
    report: Optional[Callable[[Dict[str, int], str], None]] = None


_budget: ContextVar[Optional[RematBudget]] = ContextVar(
    "remat_budget", default=None)


@contextlib.contextmanager
def remat_budget_scope(budget: Optional[RematBudget]):
    """``budget`` for the blocks traced within the scope (None: none)."""
    token = _budget.set(budget)
    try:
        yield
    finally:
        _budget.reset(token)


def _axis_split(axis: str, dim: int) -> int:
    """Devices the mesh in scope cuts ``dim`` over along ``axis``."""
    n = jax.sharding.get_abstract_mesh().shape.get(axis, 1)
    return n if dim % n == 0 else 1


def _padded(width: int) -> int:
    return -(-width // LANES) * LANES


def saved_bytes(carry, *, trips: int, heads: int,
                attn_sites: int = 1) -> Dict[str, int]:
    """Bytes one device keeps for each saved name over ``trips`` bodies of
    ``attn_sites`` flash calls each, from the carry ``[batch, seq,
    width]``: batch rows cut over ``data``, heads over ``model``.  As they
    lie in HBM: the output is ``[batch * heads, seq, head size]``, a head
    of 64 in rows of 128 lanes (twice its values); the log-sum-exp is
    ``[batch * heads, seq]`` float32."""
    b, t, d = carry.shape
    local = b // _axis_split(DATA_AXIS, b) \
        * heads // _axis_split(MODEL_AXIS, heads) * trips * attn_sites
    return {
        FLASH_OUT: local * t * _padded(d // heads)
        * np.dtype(carry.dtype).itemsize,
        FLASH_LSE: local * _padded(t) * 4,
    }


def activation_room(budget: RematBudget, carry, *, trips: int,
                    ffn_width: int, head_width: int, attn_sites: int = 1,
                    ffn_sites: int = 1,
                    head_rows: Optional[int] = None) -> int:
    """Bytes of one device left for saved names, after what the step
    holds besides them.  Reckoned from shapes, the large ones only:

    * what is resident and the compute copy of the parameters, all step
      long;
    * the stack of layer inputs remat keeps (``trips`` carries);
    * the larger of the two phases that never overlap: the head (float32
      logits ``[rows, head_width]`` and their gradient; for a head that
      walks its rows ``head_rows`` at a time, models/mlm_head.py, the
      logits of one block, their gradient and the decoder's float32
      gradient ``[head_width, width]``) and the backward of one body (its
      working set, taken as twice what its forward writes: eight
      carry-wide and two FFN-wide values a layer) beside the gradient
      tree.
    """
    b, t, d = carry.shape
    item = np.dtype(carry.dtype).itemsize
    rows = b * t // _axis_split(DATA_AXIS, b)
    carry_bytes = rows * d * item
    ffn_bytes = rows * ffn_width * item // _axis_split(MODEL_AXIS, ffn_width)
    if head_rows is None:
        head = 2 * rows * head_width * 4
    else:
        head = (2 * min(head_rows, rows) + d) * head_width * 4
    body = 2 * (attn_sites * 8 * carry_bytes + ffn_sites * 2 * ffn_bytes)
    held = (budget.resident_bytes + budget.copy_bytes + trips * carry_bytes
            + max(head, body + budget.grad_bytes))
    return int(budget.bytes_limit * (1.0 - HOLD_BACK)) - held


def checkpoint_block(carry, *, trips: int, heads: int, ffn_width: int,
                     head_width: int, attn_sites: int = 1,
                     ffn_sites: int = 1, head_rows: Optional[int] = None):
    """The ``jax.checkpoint`` of a model's layer bodies: a decorator for
    the body (or bodies) run ``trips`` times in all, by a ``lax.scan`` or
    a Python loop, over ``carry`` ``[batch, seq, width]``, saving the
    flash kernel's output and log-sum-exp where the budget in scope has
    room for them.  One trip holds ``attn_sites`` flash calls and
    ``ffn_sites`` dense FFNs of ``ffn_width``; ``head_width`` is the width
    of the float32 logits the model's head writes for every row, or for
    ``head_rows`` of a device's rows at a time.  Bodies
    whose attention is not the flash kernel (``attn_sites=0``) have
    nothing to save."""
    budget = _budget.get()
    if budget is None or not attn_sites:
        return jax.checkpoint
    costs = saved_bytes(carry, trips=trips, heads=heads,
                        attn_sites=attn_sites)
    room = activation_room(budget, carry, trips=trips, ffn_width=ffn_width,
                           head_width=head_width, attn_sites=attn_sites,
                           ffn_sites=ffn_sites, head_rows=head_rows)
    fits = sum(costs.values()) <= room
    if budget.report is not None:
        kept = " + ".join(f"{n} {costs[n] / 1e9:.3f} GB" for n in SAVED)
        budget.report(
            {n: costs[n] if fits else 0 for n in SAVED},
            f"remat: device limit {budget.bytes_limit / 1e9:.2f} GB "
            f"({HOLD_BACK:.0%} held back), resident "
            f"{budget.resident_bytes / 1e9:.2f} GB, room for saved "
            f"activations {room / 1e9:.2f} GB: a block keeps its input"
            + (f" + {kept}" if fits else f"; recomputed: {kept}"))
    if not fits:
        return jax.checkpoint
    return functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED))
