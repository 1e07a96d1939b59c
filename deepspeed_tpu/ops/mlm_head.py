"""BERT's masked-LM loss from the labelled rows alone.

A pre-training batch labels about one position in seven
(``masked_lm_labels`` is -100 elsewhere), and the loss of an unlabelled
row is an exact zero: its transform, its 30,522 logits, their float32
softmax and both backward matmuls of the tied decoder multiply into
nothing.  ``masked_lm_loss`` orders a device's rows labelled-first, walks
that order a block of ``HEAD_BLOCK_ROWS`` rows at a time, and stops after
the last block that holds a labelled row: the trip count of the walk is a
device value, the label count, so shapes stay static at the row count and
no capacity is configured.  A batch with every row labelled walks every
block; a batch with none walks no block and its term is 0.  The source
project's BERT recipe selects the labelled rows before its decoder too
(``masked_token_indexes`` of the ``bing_bert`` prediction head).

A loop of a traced trip count has no transpose, so the walk takes the
gradient as it goes, under a ``custom_vjp``: a block's ``softmax -
onehot`` is turned into ``dlogits @ E`` and ``dlogits^T @ h`` while its
logits exist, the decoder's gradient summed in float32 over the blocks.
Nothing is recomputed and no more than ``[HEAD_BLOCK_ROWS, vocab]``
float32 is held, forward or backward.  The decoder matmul accumulates
and returns float32 (the dense head rounds its logits to the compute
dtype first).

On a mesh the rows are compacted within a data shard (``shard_map`` over
``data`` alone: each shard walks its own count, no collective is added;
the shards' gradients of the head are summed outside it, as the dense
head's were); ``word_embeddings`` stays cut over ``model`` and the
log-sum-exp crosses that axis where the partitioner puts it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .transformer.transformer import _layer_norm
from ..parallel.mesh import DATA_AXIS, auto_axis

# Rows of one block of the walk: [HEAD_BLOCK_ROWS, vocab] float32 is what
# the head holds at once.  Chosen on the chip (PERF.md section 6, PR 47).
HEAD_BLOCK_ROWS = 512

# The parameters the head reads, by their names in ``BertModel``'s tree.
HEAD_LEAVES = ("mlm_transform_w", "mlm_transform_b", "mlm_ln_scale",
               "mlm_ln_bias", "word_embeddings", "mlm_bias")

# The walk differentiates ``_SHIFT`` times its sum and the backward rule
# divides it out again: a probability of 3e-5 (one of 30,522) is then no
# float16 subnormal when ``softmax - onehot`` is rounded for the MXU.  A
# power of two: exact in every dtype.
_SHIFT = 4096.0

_SCOPE = "head_block_rows"   # the gather of a block, by name:
#                              ``traced_head_rows``


def mlm_transform(x, head):
    """dense + GELU + LayerNorm ahead of the tied decoder."""
    h = x @ head["mlm_transform_w"].astype(x.dtype) \
        + head["mlm_transform_b"].astype(x.dtype)
    h = jax.nn.gelu(h, approximate=False)
    return _layer_norm(h, head["mlm_ln_scale"], head["mlm_ln_bias"])


def _ordered(seq, labels, block_rows: int):
    """One shard's ``seq`` ``[b, t, d]`` and ``labels`` ``[b, t]`` as rows,
    and the order of the walk: (rows ``[n, d]``; labels ``[n]``; rows of a
    block; row numbers labelled-first, padded to whole blocks with a
    number past the last row; how many rows carry a label; how many
    blocks hold one)."""
    rows, labels = seq.reshape(-1, seq.shape[-1]), labels.reshape(-1)
    n = rows.shape[0]
    block = min(block_rows, n)
    unlabelled = labels < 0
    order = jnp.argsort(unlabelled, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, -n % block), constant_values=n)
    count = n - jnp.sum(unlabelled, dtype=jnp.int32)
    return rows, labels, block, order, count, (count + block - 1) // block


def _block_rows(i, rows, labels, order, count, block: int):
    """Block ``i`` of the order: its row numbers, rows, labels (0 for a
    row without one) and which of its rows carry a label."""
    idx = jax.lax.dynamic_slice(order, (i * block,), (block,))
    with jax.named_scope(_SCOPE):
        x = jnp.take(rows, idx, axis=0, mode="clip")
    lab = jnp.maximum(jnp.take(labels, idx, mode="clip"), 0)
    live = i * block + jnp.arange(block, dtype=jnp.int32) < count
    return idx, x, lab, live


def _block_logits(h, head):
    """float32 logits of a block out of the MXU's accumulator."""
    return jax.lax.dot_general(
        h, head["word_embeddings"].astype(h.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) \
        + head["mlm_bias"].astype(jnp.float32)


def _block_nll(logits, lab, live):
    """(summed negative log-likelihood of the block's labelled rows, the
    rows' log-sum-exp)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(live, lse - picked, 0.0)), lse


def _walk(seq, labels, weight, head, block_rows: int):
    """One shard's rows ``seq`` ``[b, t, d]``: ``weight`` times the summed
    negative log-likelihood of its labelled rows, as ``[1]``."""
    rows, labels, block, order, count, live_blocks = _ordered(
        seq, labels, block_rows)

    def body(carry):
        i, total = carry
        _, x, lab, live = _block_rows(i, rows, labels, order, count, block)
        nll, _ = _block_nll(_block_logits(mlm_transform(x, head), head),
                            lab, live)
        return i + 1, total + nll

    _, total = jax.lax.while_loop(
        lambda carry: carry[0] < live_blocks, body,
        (jnp.int32(0), jnp.float32(0.0)))
    return (total * weight)[None]


def _walk_with_grad(seq, labels, weight, head, block_rows: int):
    """The same walk, taking each block's gradient while its logits
    exist: (the sum as ``[1]``; the gradient of ``_SHIFT`` x the sum, by
    the rows ``[b, t, d]`` and, in float32 under a leading axis of 1, by
    the head's leaves)."""
    rows, labels, block, order, count, live_blocks = _ordered(
        seq, labels, block_rows)
    rest = {k: head[k] for k in HEAD_LEAVES[:4]}      # the transform's

    def body(carry):
        i, total, d_rows, d_head = carry
        idx, x, lab, live = _block_rows(i, rows, labels, order, count, block)
        h, pull = jax.vjp(mlm_transform, x, rest)
        logits = _block_logits(h, head)
        nll, lse = _block_nll(logits, lab, live)
        onehot = lab[:, None] == jnp.arange(logits.shape[1], dtype=jnp.int32)
        d_logits = (jnp.exp(logits - lse[:, None]) - onehot) \
            * jnp.where(live, weight * _SHIFT, 0.0)[:, None]
        low = d_logits.astype(h.dtype)
        d_h = jax.lax.dot_general(
            low, head["word_embeddings"].astype(h.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        d_x, d_rest = pull(d_h.astype(h.dtype))
        d_head = {
            **{k: d_head[k] + d_rest[k].astype(jnp.float32) for k in rest},
            "word_embeddings": d_head["word_embeddings"]
            + jax.lax.dot_general(low, h, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32),
            "mlm_bias": d_head["mlm_bias"] + jnp.sum(d_logits, axis=0),
        }
        # a row stands once in the order; padding drops out of range
        d_rows = d_rows.at[idx].add(d_x, mode="drop")
        return i + 1, total + nll, d_rows, d_head

    _, total, d_rows, d_head = jax.lax.while_loop(
        lambda carry: carry[0] < live_blocks, body,
        (jnp.int32(0), jnp.float32(0.0), jnp.zeros_like(rows),
         {k: jnp.zeros(head[k].shape, jnp.float32) for k in HEAD_LEAVES}))
    return ((total * weight)[None], d_rows.reshape(seq.shape),
            {k: v[None] for k, v in d_head.items()})


def _per_shard(walk, seq, labels, head, block_rows: int):
    """``walk`` over each data shard's own rows, its results stacked over
    the shards.  Manual over ``data`` alone, so that each shard walks its
    own count (under ``vmap`` or the partitioner every shard would wait
    for the longest) and no collective is added; bare with no mesh, on
    one device or inside a caller's own ``shard_map``."""
    # a position's weight: the mean is over the labels of the whole batch
    weight = 1.0 / jnp.maximum(
        jnp.sum(labels >= 0, dtype=jnp.int32), 1).astype(jnp.float32)
    walk = functools.partial(walk, block_rows=block_rows)
    data = auto_axis(DATA_AXIS, seq.shape[0])
    if data is not None:
        walk = jax.shard_map(
            walk, in_specs=(P(data), P(data), P(), P()), out_specs=P(data),
            axis_names={data}, check_vma=False)
    return walk(seq, labels, weight, head)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def masked_lm_loss(seq, labels, head, block_rows: int):
    """Mean negative log-likelihood of the labelled positions of ``seq``
    ``[B, T, d]`` (``labels`` ``[B, T]``, below 0 = no label; 0 when no
    position carries one) under the head ``{name: leaf for name in
    HEAD_LEAVES}``, from the labelled rows alone, ``block_rows`` of a
    device's rows at a time."""
    return jnp.sum(_per_shard(_walk, seq, labels, head, block_rows))


def _loss_fwd(seq, labels, head, block_rows: int):
    sums, d_seq, d_head = _per_shard(_walk_with_grad, seq, labels, head,
                                     block_rows)
    return jnp.sum(sums), (d_seq, d_head, head)


def _loss_bwd(block_rows: int, res, g):
    d_seq, d_head, head = res
    scale = g.astype(jnp.float32) / _SHIFT
    # rounded to the leaf's dtype before the shards are summed, as the
    # partitioner sums the dense head's partial gradients
    return ((d_seq.astype(jnp.float32) * scale).astype(d_seq.dtype),
            np.zeros(d_seq.shape[:2], jax.dtypes.float0),     # labels
            {k: jnp.sum((d_head[k] * scale).astype(head[k].dtype), axis=0)
             for k in d_head})


masked_lm_loss.defvjp(_loss_fwd, _loss_bwd)


def traced_head_rows(jaxpr):
    """``{"all": rows one device's walk orders, "block": rows of a block}``
    of the first walk in a traced FORWARD program
    (``jax.make_jaxpr(...)(...).jaxpr``), None where it holds none."""
    for eqn in jaxpr.eqns:
        if str(eqn.source_info.name_stack).endswith(_SCOPE):
            return {"all": eqn.invars[0].aval.shape[0],
                    "block": eqn.outvars[0].aval.shape[0]}
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found = traced_head_rows(inner)
            if found is not None:
                return found
    return None
