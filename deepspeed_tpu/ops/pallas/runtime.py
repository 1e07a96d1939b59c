"""Pallas execution-mode plumbing.

Mosaic kernels run only on a TPU; anywhere else (the CPU test meshes)
they are interpreted.  Each engine decides from the devices of *its*
mesh and declares that around the calls that trace its compiled steps
(``_pallas_scope`` in runtime/engine.py and inference/engine.py);
kernels consult it at trace time.  A scoped setting, not a set-once
global, keeps several engines on different meshes in one process honest.
With no engine scope, the default backend decides.

A chip run must never interpret unnoticed: ``chip_smoke.py`` asserts
``engine._pallas_interpret is False`` and finds the kernel
(``tpu_custom_call``) in the compiled train, prefill and decode programs.
"""
from __future__ import annotations

import contextlib
from contextvars import ContextVar
from typing import Optional

import jax

_interpret_override: ContextVar[Optional[bool]] = ContextVar(
    "pallas_interpret", default=None)


@contextlib.contextmanager
def interpret_scope(value: Optional[bool]):
    """Force interpret mode (or None = auto) within the scope."""
    token = _interpret_override.set(value)
    try:
        yield
    finally:
        _interpret_override.reset(token)


def mesh_wants_interpret(mesh) -> bool:
    """True when the mesh's devices are not real TPU chips."""
    return mesh.devices.flat[0].platform != "tpu"


def use_interpret() -> bool:
    override = _interpret_override.get()
    if override is not None:
        return override
    return jax.default_backend() != "tpu"
