"""The dropless expert layer's routing plan (``moe/dropless.py``, steps 2
and 4 of its docstring) against a plain NumPy walk of the same semantics,
bit for bit, and the shape of what it lowers to.

The walk sorts the assignments by expert (stably), lays each expert's
rows out from a tile boundary, multiplies tile by tile and sums a token's
k rows in float32.  Only a tile's two matmuls and its activation are
taken through ``jnp`` (on a tile's own operands, as the kernel body does),
so that their rounding is the kernel's; the plan, the layout and the sum
are NumPy loops over assignments.  CPU, kernels interpreted, tiny widths.
"""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import dropless

F32 = jnp.float32


def _tile_product(tile, gate, up, down, act):
    """One tile through one expert, with the kernel bodies' own
    operations in their order."""
    if act == "swiglu":
        g = jnp.dot(tile, gate, preferred_element_type=F32)
        u = jnp.dot(tile, up, preferred_element_type=F32)
        h = (g * jax.nn.sigmoid(g) * u).astype(tile.dtype)
    else:
        u = jnp.maximum(jnp.dot(tile, up, preferred_element_type=F32), 0.0)
        h = (u * u).astype(tile.dtype)
    return np.asarray(jnp.dot(h, down, preferred_element_type=F32
                              ).astype(tile.dtype))


def walk(x, weights, experts, gate, up, down, *, n_experts, tm, held, valid,
         act, offset):
    """-> (y [N, d], statistics, the plan as the walk lays it out)."""
    n, k = experts.shape
    a = n * k
    first, count = held if held is not None else (0, n_experts)
    local = experts.reshape(-1).astype(np.int64) - first
    real = np.repeat(valid, k) if valid is not None else np.ones(a, bool)
    here = real & (local >= 0) & (local < count)
    by_expert = [[i for i in range(a) if here[i] and local[i] == ex]
                 for ex in range(count)]        # ascending i: the stable sort
    tiles = a // tm + count
    token = np.full(tiles * tm, n, np.int64)
    row = np.zeros(a, np.int64)
    tile_expert, r = [], 0
    for ex, mine in enumerate(by_expert):
        for j, i in enumerate(mine):
            token[r + j], row[i] = i // k, r + j
        full = -(-len(mine) // tm)
        tile_expert += [ex] * full
        r += full * tm
    n_live = len(tile_expert)
    x_ext = np.concatenate([x, np.zeros((1, x.shape[1]), x.dtype)])
    x_rows = x_ext[token]
    y_rows = np.zeros((tiles * tm, down.shape[-1]), x.dtype)
    for t in range(n_live):
        w = offset + tile_expert[t]
        y_rows[t * tm:(t + 1) * tm] = _tile_product(
            x_rows[t * tm:(t + 1) * tm], None if gate is None else gate[w],
            up[w], down[w], act)
    y = np.zeros((n, down.shape[-1]), np.float32)
    for i in range(a):
        if here[i]:
            y[i // k] += (y_rows[row[i]].astype(np.float32)
                          * np.float32(weights[i // k, i % k]))
    counts = [len(mine) for mine in by_expert]
    stats = [sum(c > 0 for c in counts), max(counts), sum(counts)]
    if held is not None:
        stats.append(int((real & ~here).sum()))
    # a tile past the live ones repeats the last live expert (the last
    # expert where none is live: nothing runs, the block index stands)
    tile_expert += [tile_expert[-1] if tile_expert else count - 1] \
        * (tiles - n_live)
    plan = (np.array(tile_expert), n_live, token, row, np.array(counts))
    return y.astype(x.dtype), stats, plan


#: name -> (tokens, top_k, experts routed over, held (first, count) or
#: None, act, layers of stacked weights, this layer, what the routing is)
CASES = {
    "whole_layer": (12, 2, 8, None, "swiglu", 1, 0, "random"),
    "held_share": (12, 4, 16, (4, 8), "swiglu", 1, 0, "random"),
    "held_share_relu2": (12, 4, 16, (4, 8), "relu2", 1, 0, "random"),
    "valid_mask": (12, 2, 8, None, "swiglu", 1, 0, "masked"),
    "held_and_valid_mask": (12, 4, 16, (8, 8), "relu2", 1, 0, "masked"),
    "every_assignment_elsewhere": (8, 2, 16, (12, 4), "swiglu", 1, 0,
                                   "elsewhere"),
    "no_token_valid": (8, 2, 8, None, "relu2", 1, 0, "none_valid"),
    "one_expert_takes_every_row": (20, 2, 8, (2, 4), "swiglu", 1, 0,
                                   "one_expert"),
    "assignments_no_multiple_of_the_tile": (7, 3, 4, None, "relu2", 1, 0,
                                            "random"),
    "fewer_experts_than_the_tiles_remainder": (13, 2, 2, None, "swiglu", 1,
                                               0, "random"),
    "top_8_of_64": (64, 8, 64, None, "swiglu", 1, 0, "random"),
    "tile_of_128": (96, 4, 4, None, "swiglu", 1, 0, "random"),
    "tile_of_128_held_relu2": (160, 4, 8, (2, 4), "relu2", 1, 0, "random"),
    "second_layer_of_stacked_experts": (12, 2, 8, None, "swiglu", 3, 1,
                                        "random"),
    "third_layer_held_relu2": (12, 4, 16, (0, 8), "relu2", 3, 2, "masked"),
}


#: the cases whose row tile is not the smallest
ROW_TILES = {"tile_of_128": 128, "tile_of_128_held_relu2": 128}


def _case(name):
    n, k, e, held, act, layers, layer, kind = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    d, f = 16, 32
    count = held[1] if held else e
    x = rng.standard_normal((n, d)).astype(np.float32)
    experts = np.stack([rng.permutation(e)[:k] for _ in range(n)])
    valid = None
    if kind == "masked":
        valid = rng.random(n) < 0.6
    elif kind == "none_valid":
        valid = np.zeros(n, bool)
    elif kind == "elsewhere":
        experts = experts % held[0]
    elif kind == "one_expert":
        experts[:, 0], experts[:, 1] = held[0] + 1, 0
    weights = rng.random((n, k)).astype(np.float32)
    mats = lambda *s: (rng.standard_normal(s) / 4).astype(np.float32)
    gate = mats(layers * count, d, f) if act == "swiglu" else None
    up, down = mats(layers * count, d, f), mats(layers * count, f, d)
    return (x, weights, experts.astype(np.int32), gate, up, down, e, held,
            valid, act, layer * count, k)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_layer_equals_a_plain_walk_bit_for_bit(name):
    (x, weights, experts, gate, up, down, e, held, valid, act, offset,
     k) = _case(name)
    a = experts.size
    count = held[1] if held else e
    tm = dropless.row_tile(a * count // e, count)
    assert tm == ROW_TILES.get(name, 16)
    want_y, want_stats, want_plan = walk(
        x, weights, experts, gate, up, down, n_experts=e, tm=tm, held=held,
        valid=valid, act=act, offset=offset)

    got_y, got_stats = jax.jit(
        lambda x, w, ex, g, u, dn, off, v: dropless.dropless_moe(
            x, jnp.zeros((x.shape[1], e)), g, u, dn, k, expert_offset=off,
            valid=v, routing=(w, ex), experts_held=held, act=act))(
        x, weights, experts, gate, up, down, jnp.int32(offset), valid)
    assert type(got_stats) is (dropless.HeldMoEStats if held
                               else dropless.MoEStats)
    assert [int(s) for s in got_stats] == want_stats
    got_y = np.asarray(got_y)
    assert got_y.dtype == want_y.dtype
    assert got_y.tobytes() == want_y.tobytes(), np.abs(got_y - want_y).max()

    # the plan itself, element for element
    local = experts.reshape(-1) - (held[0] if held else 0)
    real = np.repeat(valid, k) if valid is not None else True
    flat = np.where(real & (local >= 0) & (local < count), local, count)
    tile_expert, n_live, token, row, counts = dropless.routing_plan(
        flat.astype(np.int32), count, k, tm)
    assert int(n_live) == want_plan[1]
    for got, want in zip((tile_expert, token, row, counts),
                         (want_plan[0], want_plan[2], want_plan[3],
                          want_plan[4])):
        assert np.array_equal(np.asarray(got), want)


# -- what the plan lowers to -------------------------------------------------

def _stablehlo_ops(n, k, e, held, d, f, act):
    """The layer lowered for a TPU from here (the two kernels are custom
    calls, so every loop, scatter and gather counted is the plan's)."""
    count = held[1] if held else e
    gate = None if act == "relu2" else jax.ShapeDtypeStruct(
        (count, d, f), jnp.bfloat16)

    def layer(x, w, ex, g, u, dn):
        return dropless.dropless_moe(
            x, jnp.zeros((d, e)), g, u, dn, k, routing=(w, ex),
            experts_held=held, act=act, interpret=False)

    S = jax.ShapeDtypeStruct
    text = jax.jit(layer).trace(
        S((n, d), jnp.bfloat16), S((n, k), F32), S((n, k), jnp.int32), gate,
        S((count, d, f), jnp.bfloat16), S((count, f, d), jnp.bfloat16)
    ).lower(lowering_platforms=("tpu",)).as_text()
    return collections.Counter(re.findall(r'"?(?<!#)stablehlo\.(\w+)', text))


@pytest.mark.parametrize("shape", ["nemotron_tick", "kimi_prefill"])
def test_the_plan_lowers_to_no_loop_no_scatter_and_two_gathers(shape):
    """The chip reads the indices of a gather or a scatter one at a time
    (7-13 ns each: PERF.md section 6, PRs 57 and 58) and makes a ``while``
    of ``searchsorted`` and of a gather of windows; before PR 58 a call
    held 20 gathers, 4 scatters and a ``while``.  What is left: the rows
    into the kernels and the rows out, both ``d`` wide.  An edit that
    brings an index-at-a-time form back fails here by name."""
    n, k, e, held, act = {
        "nemotron_tick": (192, 22, 512, (0, 128), "relu2"),
        "kimi_prefill": (4096, 8, 256, (0, 32), "swiglu")}[shape]
    ops = _stablehlo_ops(n, k, e, held, 128, 128, act)
    assert ops["custom_call"] == 2            # the two grouped matmuls
    assert ops["gather"] == 2, ops
    for gone in ("while", "scatter", "dynamic_slice", "dynamic_gather",
                 "dynamic_update_slice", "case", "if"):
        assert ops[gone] == 0, (gone, ops)
    assert ops["sort"] == PLAN_SORTS


#: the stable sort by expert and the sort that inverts it
PLAN_SORTS = 2


@pytest.mark.parametrize("n, k, e", [(192, 22, 512), (4096, 8, 256)],
                         ids=["nemotron_tick", "kimi_prefill"])
def test_the_sigmoid_router_picks_its_weights_without_a_gather(n, k, e):
    """``route_sigmoid_topk``'s weights are the scores of the experts it
    chose: the same bits as ``take_along_axis`` gives, by a compare."""
    rng = np.random.default_rng(n)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    router_w = rng.standard_normal((8, e)).astype(np.float32)
    bias = (rng.standard_normal(e) / 8).astype(np.float32)
    route = jax.jit(lambda x, w, b: dropless.route_sigmoid_topk(
        x, w, b, k, scale=2.5, renormalize=False))
    text = route.lower(x, router_w, bias).as_text()
    assert "stablehlo.gather" not in text
    weights, experts = route(x, router_w, bias)

    @jax.jit
    def gathered(x, w, b):      # the router as it was before PR 58
        scores = jax.nn.sigmoid(jnp.dot(
            x, w, precision=jax.lax.Precision.HIGHEST))
        _, experts = jax.lax.top_k(scores + b, k)
        return jnp.take_along_axis(scores, experts, axis=-1) * 2.5, experts

    want, want_experts = gathered(x, router_w, bias)
    assert np.array_equal(np.asarray(experts), np.asarray(want_experts))
    assert np.asarray(weights).tobytes() == np.asarray(want).tobytes()
    picked = np.sort(np.asarray(experts), axis=-1)
    assert (picked[:, 1:] != picked[:, :-1]).all()
