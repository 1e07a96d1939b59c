"""Int8 quantization for the serving plane (docs/serving.md,
"quantized serving").

Two independent arms behind ``serving.quantization``:

**Weights** (LLM.int8, Dettmers et al. 2022 — PAPERS.md): one-shot
post-load symmetric per-OUTPUT-CHANNEL absmax quantization of the
GPT-2 matmul weights (attn qkv/out, MLP fc/proj).  ``scale[c] =
absmax(w[:, c]) / 127`` over the contraction (input-feature) axis, so
dequant fuses into the serving matmuls as ``(x · w_int8) * scale`` —
one multiply per output element, never a dequantized weight matrix in
HBM.  The fp master copy stays on the host; device memory holds int8
weights + fp32 scale rows, so params HBM ~ halves vs fp16 (~quarters
vs the CPU oracle's fp32).  Embeddings, layer norms and biases stay in
the master dtype: they are gather/elementwise consumers, small, and
the tied-embedding logits matmul wants the full-precision table.

**KV rows** (KVQuant / KIVI per-head scaling, PAPERS.md): the paged
pool stores int8 K/V rows with a per-(page, head, row) fp32 scale —
``quantize_rows`` at write time inside the compiled programs,
dequantized fused into the decode kernels.  Per-ROW (per stored token,
per head) rather than one scalar per (page, head): decode appends one
row at a time into a live page, and a page-scalar scale would either
clip rows hotter than the page's first write or re-quantize the whole
page per append (unbounded double-rounding drift).  Per-row keeps
every write's error bounded by ``scale/2`` forever — the numeric-
bounds contract tests/test_quant_serve.py pins.

Everything here is pure jnp: ``quantize_rows`` runs on-trace inside
the serving programs; the weight path runs once at engine build.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

#: the GPT-2 block matmul weights the int8 arm covers; every one
#: stores input-features on axis 1 (after the stacked layer axis), so
#: the per-output-channel absmax always reduces axis 1.
QUANT_WEIGHT_KEYS = ("qkv_w", "out_w", "fc_w", "proj_w")
_CONTRACT_AXIS = 1
SCALE_SUFFIX = "_scale"


def quantize_channels(w: jnp.ndarray,
                      axis: int = _CONTRACT_AXIS
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-output-channel int8: reduce ``axis`` (the
    contraction axis, keepdims so the scale broadcasts back), scale =
    absmax/127 (all-zero channels get scale 1.0 — a harmless identity),
    values round-to-nearest into [-127, 127].  ``|q*scale - w| <=
    scale/2`` exactly: the absmax itself maps to ±127 with no clip."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis,
                     keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_channels(q: jnp.ndarray,
                        scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def quantize_rows(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row (last-axis) symmetric int8 for KV rows: ``x [..., Dh]``
    -> ``(q int8 [..., Dh], scale fp32 [...])``.  On-trace (called
    inside the compiled write paths); all-zero rows get scale 1.0 so
    the scratch page stays exact zeros."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_rows(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_rows`: ``q [..., Dh] * scale [...]``
    broadcast over the row — the ONE dequant rule every consumer (the
    dense reference, the fused kernels, the prefill gather arm)
    shares."""
    return q.astype(jnp.float32) * scale[..., None]


class NotGPT2ParamsError(TypeError):
    """A GPT-2-only serving arm (int8 weights, the speculative draft) was
    handed a model or a parameter tree of another family."""


def quantize_gpt2_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """One-shot post-load quantization of a GPT-2 param tree: each
    block matmul weight becomes int8 with an ``<name>_scale`` fp32
    sibling (keepdims over the contraction axis, so the serving
    matmuls multiply it straight onto their output).  Input tree is
    never mutated; non-covered leaves pass through unchanged.  Works
    on any GPT-2-family tree whose ``blocks`` stack layers on axis 0
    (the target and the speculative draft alike)."""
    blocks = params.get("blocks") if isinstance(params, dict) else None
    missing = [k for k in QUANT_WEIGHT_KEYS
               if not isinstance(blocks, dict) or k not in blocks]
    if missing:
        raise NotGPT2ParamsError(
            "int8 weight quantization covers GPT-2's block matmuls "
            f"{QUANT_WEIGHT_KEYS}; this tree has no blocks/{missing[0]}")
    blocks = dict(blocks)
    for name in QUANT_WEIGHT_KEYS:
        q, scale = quantize_channels(blocks[name])
        blocks[name] = q
        blocks[name + SCALE_SUFFIX] = scale
    out = dict(params)
    out["blocks"] = blocks
    return out


def quantized_partition_specs(pspecs: Dict[str, Any]) -> Dict[str, Any]:
    """Partition specs matching :func:`quantize_gpt2_params`: each
    scale inherits its weight's spec with the contracted (now size-1)
    axis unsharded — the output-channel shard stays aligned with the
    Megatron column split, so a TP shard holds exactly the scales of
    the channels it computes."""
    blocks = dict(pspecs["blocks"])
    for name in QUANT_WEIGHT_KEYS:
        axes = list(tuple(blocks[name]))
        while len(axes) <= _CONTRACT_AXIS:
            axes.append(None)
        axes[_CONTRACT_AXIS] = None
        blocks[name + SCALE_SUFFIX] = P(*axes)
    out = dict(pspecs)
    out["blocks"] = blocks
    return out


def param_nbytes(tree) -> int:
    """Total bytes of every leaf — the ``serve_param_bytes`` source
    (device-resident logical bytes: int8 leaves count 1 byte/elem, the
    whole point of the weights arm)."""
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))
