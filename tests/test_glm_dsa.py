"""GLM-5.2 (latent attention over the rows a learned indexer picks): the two
kernels in interpret mode against their dense arms, the latent kernel under
a mask over positions (and without one, the call it was), the exact selection
with ties, the model against the benchmark's plain float32 reference, prefill
in chunks then decode through BOTH paged arrays (contexts at, one under and
one over ``index_topk``), the picked sets against the reference's own, a
``shared`` layer against the ``full`` one whose picks it takes, the indexer
keys' pages under copy, export, adoption and reuse, the shares of the experts
with the shared expert counted once against the uncut layer, the two-array
spec and the refusals.  CPU, the configuration file's ``rehearse`` sizes,
seeded weights.  (Its cell's rehearsal: tests/test_benchmark_cells.py.)"""
import dataclasses
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import drawn_once

from deepspeed_tpu.inference import ServeEngine
from deepspeed_tpu.inference.kv_cache import (PagedKVCacheSpec,
                                              init_paged_cache,
                                              paged_partition_specs)
from deepspeed_tpu.models import glm_dsa
from deepspeed_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel
from deepspeed_tpu.ops.pallas.runtime import interpret_scope

# the package exports a function of the module's own name
da = importlib.import_module("deepspeed_tpu.ops.pallas.decode_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import glm_dsa_reference  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "glm-5.2.json")) as _f:
    FILE = json.load(_f)
_SIZES = dict(FILE["rehearse"]["sizes"])
TINY = GlmDsaConfig(
    **{**_SIZES, "experts_held": tuple(_SIZES["experts_held"])},
    rope_parameters=FILE["rope_parameters"], attn_impl="dense")
TOPK = TINY.index_topk                      # 16: under every context below
SERVING = {"slots": 3, "page_len": 8, "max_seq_len": 96, "prefill_len": 32,
           "prefix_cache": False}
# float32 on the CPU: the model and the reference differ by summation order
# (measured 3e-5 on logits of size 2 at initializer_range 0.06 .. 0.3); a
# pick flipped by that order would move a logit by 1e-2, and none is
F32_TOL = 2e-4


def _params(cfg=TINY, seed=0):
    return drawn_once(GlmDsaModel, cfg, seed)


def _m(cfg):
    return dataclasses.asdict(cfg)


def _reference(params, tokens, cfg=TINY, **kw):
    with jax.default_matmul_precision("highest"):
        out = glm_dsa_reference.glm_dsa_logits(params, tokens, _m(cfg),
                                               block=32, **kw)
    return jax.tree.map(np.asarray, out)


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


# -- the kernels ----------------------------------------------------------

def test_index_score_kernel_streams_pages_and_masks_past_the_length(
        monkeypatch):
    """Two blocks of two pages a slot, a dead slot, a partly live block:
    the kernel's scores are the dense arm's, -inf from the length on."""
    monkeypatch.setattr(da, "PAGED_KV_VMEM_BUDGET", 2 * 2 * 8 * 128 * 4)
    rng = np.random.default_rng(0)
    S, J, D, page_len, max_pages, P = 4, 4, 128, 8, 4, 24
    assert da.latent_pages_per_block(page_len, D, 4, max_pages) == 2
    q = jnp.asarray(rng.normal(size=(S, J, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(S, J)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(P, page_len, D)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(P - 1)[:S * max_pages]
                        .reshape(S, max_pages), jnp.int32)
    lengths = jnp.asarray([0, 5, 32, 17], jnp.int32)
    got = np.asarray(da.index_score(q, w, pool, table, lengths,
                                    impl="pallas", interpret=True))
    want = np.asarray(da.index_score(q, w, pool, table, lengths,
                                     impl="dense"))
    assert got.shape == (S, max_pages * page_len)
    live = np.arange(32)[None] < np.asarray(lengths)[:, None]
    assert (np.isneginf(got) == ~live).all()
    np.testing.assert_allclose(got[live], want[live], atol=1e-4)
    assert np.abs(want[live]).max() > 1


def _masked_case(case, rng, S, cap, K):
    """(lengths [S], allowed [S, cap]) of a case of the masked kernel; 16
    positions a block."""
    lengths = np.asarray([40, 48, 23, 37], np.int32)
    scores = rng.normal(size=(S, cap)).astype(np.float32)
    if case == "tie_at_kth":
        scores = rng.integers(0, 4, (S, cap)).astype(np.float32)
    if case == "dead_slot":
        lengths[[0, 2]] = 0
    if case == "every_row":                     # contexts of K rows or fewer
        lengths = np.asarray([K, 1, K - 5, 3], np.int32)
    scores[np.arange(cap)[None] >= lengths[:, None]] = -np.inf
    allowed = np.array(glm_dsa._pick_mask(jnp.asarray(scores), K))
    if case == "empty_blocks":
        allowed[0, :16] = False                 # the slot's FIRST block
        allowed[1, 16:32] = False               # one in the middle
        allowed[2, 16:] = False                 # its last live one
        allowed[3] = False                      # every block: nothing to read
    return lengths, allowed


@pytest.mark.parametrize("case", ["picked", "empty_blocks", "dead_slot",
                                  "every_row", "tie_at_kth"])
def test_the_latent_kernel_under_a_mask_reads_the_allowed_rows_alone(
        monkeypatch, case):
    """Three blocks of two pages a slot under a mask over positions: the
    kernel is the dense arm under the same mask, whichever blocks hold no
    allowed key (a slot's first, so that nothing has scored when the next
    one runs; all of them: exact zeros); the mask of a context of ``K``
    rows or fewer allows every row, and the call is the unmasked one; with
    ties at the ``K``-th score the mask is the stable sort's set, and
    reading under it is a softmax over that set's rows, fetched by hand."""
    monkeypatch.setattr(da, "PAGED_KV_VMEM_BUDGET", 2 * 2 * 8 * 256 * 4)
    rng = np.random.default_rng(3)
    S, H, W, page_len, max_pages, P, K = 4, 3, 256, 8, 6, 30, 16
    cap = max_pages * page_len
    assert da.latent_pages_per_block(page_len, W, 4, max_pages) == 2
    pool = jnp.asarray(rng.normal(size=(P, page_len, W)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(S, H, W)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(P - 1)[:S * max_pages]
                        .reshape(S, max_pages), jnp.int32)
    lengths, allowed = _masked_case(case, rng, S, cap, K)
    got, want = (np.asarray(da.sparse_latent_decode_attention(
        q, pool, table, jnp.asarray(lengths), jnp.asarray(allowed), 128,
        sm_scale=0.1, impl=impl, interpret=True))
        for impl in ("pallas", "dense"))
    np.testing.assert_allclose(got, want, atol=1e-5)
    reads = allowed.any(axis=1)
    assert not got[~reads].any() and np.abs(got[reads]).min() > 0
    assert (allowed.sum(axis=1) <= np.minimum(lengths, K)).all()
    if case == "every_row":
        assert (allowed.sum(axis=1) == lengths).all()
        plain = np.asarray(da.latent_decode_attention(
            q, pool, table, jnp.asarray(lengths), 128, sm_scale=0.1,
            interpret=True))
        np.testing.assert_allclose(got, plain, atol=1e-6)
    if case in ("picked", "tie_at_kth"):
        # the stable sort's picks, their rows fetched through the page table
        scores = np.where(allowed, 1.0, -np.inf)
        rows = np.asarray(pool)[np.asarray(table)].reshape(S, cap, W)
        for slot in range(S):
            order = np.argsort(-scores[slot], kind="stable")
            order = order[:min(K, lengths[slot])]
            assert allowed[slot, order].all()
            picked = rows[slot, order]                          # [k, W]
            sc = np.asarray(q)[slot] @ picked.T * 0.1           # [H, k]
            p = np.exp(sc - sc.max(axis=1, keepdims=True))
            np.testing.assert_allclose(
                got[slot], p / p.sum(axis=1, keepdims=True)
                @ picked[:, :128], atol=1e-5)


def test_the_latent_kernel_without_a_mask_is_the_call_it_was():
    """A.X-K1's path: no mask, and the Mosaic call takes the page table,
    the lengths, the queries and the pool, as it always did; under a mask,
    one operand more and nothing else of the call moves."""
    S, H, W, page_len, max_pages, P = 2, 3, 128, 8, 4, 9
    args = (jnp.zeros((S, H, W)), jnp.zeros((P, page_len, W)),
            jnp.zeros((S, max_pages), jnp.int32), jnp.zeros((S,), jnp.int32))

    def call(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: da.latent_decode_attention(
            *a, 64, sm_scale=1.0, interpret=False, **kw))(*args)
        eqn, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return ([v.aval.shape for v in eqn.invars], eqn.params["name"],
                len(eqn.params["jaxpr"].invars))

    operands, name, refs = call()
    assert operands == [(S * max_pages,), (S,), (S, H, W),
                        (P, page_len, W)]
    assert name == da.LATENT_DECODE_ATTN_KERNEL
    under, _, more = call(allowed=jnp.ones((S, max_pages * page_len), bool))
    assert under == operands[:3] + [(S, 1, max_pages * page_len)] \
        + operands[3:]
    assert more == refs + 1


@pytest.mark.parametrize("pick", [glm_dsa._pick_mask,
                                  glm_dsa_reference.pick_mask],
                         ids=["program", "reference"])
def test_the_selection_is_exact_and_ties_go_to_the_lower_position(pick):
    rng = np.random.default_rng(2)
    scores = rng.integers(0, 6, (7, 40)).astype(np.float32)  # many ties
    scores[1, 25:] = -np.inf                    # 25 candidates
    scores[2, 3:] = -np.inf                     # fewer candidates than k
    scores[3] = -np.inf                         # none
    got = np.asarray(pick(jnp.asarray(scores), 10))
    for row, mask in zip(scores, got):
        order = np.argsort(-row, kind="stable")[:10]
        want = np.zeros(40, bool)
        want[order[np.isfinite(row[order])]] = True
        np.testing.assert_array_equal(mask, want)


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_apply_matches_the_reference_in_float32(attn_impl):
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    params, tokens = _params(cfg), _tokens((2, 40))
    got = np.asarray(GlmDsaModel(cfg).apply(params, tokens))
    want = _reference(params, tokens, cfg)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=F32_TOL)


@pytest.mark.parametrize("switch", ["round_acts", "low_keys", "skip_indexer",
                                    "stale_picks"])
def test_the_float32_tolerance_fails_each_control(switch):
    params, tokens = _params(), _tokens((1, 48))
    want = _reference(params, tokens)
    off = _reference(params, tokens, act_dtype=jnp.bfloat16,
                     **{switch: True})
    assert np.abs(off - want).max() > 10 * F32_TOL


def _paged(model, params, prompt, forced, chunks, impl, page_len=8, slots=3,
           max_pages=12):
    """Prefill ``prompt`` in ``chunks``, then one decode tick a forced
    token, in the middle slot of two arrays of its own.  Returns the logits
    of every prompt position and tick, the ticks' picked positions [ticks,
    full layers, K]."""
    cfg = model.config
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=slots, heads=1, pages=1 + max_pages,
        page_len=page_len, head_dim=cfg.d_head, max_pages=max_pages,
        dtype=jnp.float32, v_head_dim=cfg.d_head_v, values_in_keys=True,
        index_layers=cfg.n_index_layer, index_dim=cfg.d_index)
    cache = init_paged_cache(spec)
    pool, keys = cache["k"], cache["index_k"]
    row = np.zeros((max_pages,), np.int32)
    n_pages = -(-(len(prompt) + len(forced)) // page_len)
    row[:n_pages] = 1 + np.arange(n_pages)
    bucket, done, rows, picked = 32, 0, [], []
    prefill = jax.jit(lambda p, t, n, pre, r, k, ik: model.prefill_paged(
        p, t, n, pre, r, k, None, index_pool=ik))
    decode = jax.jit(lambda p, t, k, ik, tab, ln, act: model.
                     decode_step_paged(p, t, k, None, tab, ln, act,
                                       impl=impl, index_pool=ik, aux=True))
    for n in chunks:
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt[done:done + n]
        logits, pool, none, keys = prefill(
            params, padded, np.int32(n), np.int32(done), row, pool, keys)
        assert none is None
        rows.append(np.asarray(logits[0, :n]))
        done += n
    slot = slots // 2
    table = np.zeros((slots, max_pages), np.int32)
    table[slot] = row
    active = np.zeros((slots,), bool)
    active[slot] = True
    lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(done)
    for token in forced:
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(int(token))
        logits, pool, none, keys, lengths, aux = decode(
            params, tokens, pool, keys, table, lengths, active)
        rows.append(np.asarray(logits[slot])[None])
        picked.append(np.asarray(aux["index_picks"][:, slot]))
        assert int(aux["index_scored_rows"]) \
            == cfg.count("full") * int(lengths[slot])
        assert int(aux["index_selected_rows"]) \
            == cfg.n_layer * min(cfg.index_topk, int(lengths[slot]))
        assert int(aux["latent_kv_tokens"]) \
            == cfg.n_layer * int(lengths[slot])
    return np.concatenate(rows), np.stack(picked)


@pytest.mark.parametrize("chunks", [(27,), (16, 11), (8, 8, 11), (15,)],
                         ids=["whole", "two_chunks", "three_chunks",
                              "under_topk"])
@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_paged_steps_and_their_picks_against_the_reference(attn_impl,
                                                           chunks):
    """Prefill (whole; in chunks that read the keys ahead of them from the
    pages) writes both arrays; the ticks score the cached keys, pick and
    attend every live row under the picks' mask: every logit is the
    reference's full forward's and every
    picked set the reference's own.  ``under_topk``: the ticks run at
    contexts of 16 (= index_topk), then 17, after a prefill of 15."""
    cfg = dataclasses.replace(TINY, attn_impl=attn_impl)
    model, params = GlmDsaModel(cfg), _params(cfg)
    n = sum(chunks)
    prompt, forced = _tokens((n,), 4), _tokens((9,), 5)
    with interpret_scope(True):
        got, picked = _paged(model, params, prompt, forced, chunks,
                             "pallas" if attn_impl == "flash" else "dense")
    seq = np.concatenate([prompt, forced])[None]
    want, sets = _reference(params, seq, cfg, pick_rows=n + np.arange(9))
    np.testing.assert_allclose(got, want[0], atol=F32_TOL)
    assert picked.shape == (9, 2, TOPK)
    for i in range(9):
        count = min(TOPK, n + i + 1)
        for layer in range(2):
            mine = np.zeros(n + 9, bool)
            mine[picked[i, layer, :count]] = True
            assert mine.sum() == count
            np.testing.assert_array_equal(mine, sets[0, layer, i])
    # the controls: without an indexer, or with the first layer's picks
    # everywhere, the ticks are far from these
    if n > TOPK:
        for switch in ("skip_indexer", "stale_picks"):
            off = _reference(params, seq, cfg, **{switch: True})[0]
            assert np.abs(off[n:] - want[0, n:]).max() > 10 * F32_TOL


@pytest.mark.parametrize("chunks", [(27,), (16, 11), (8, 8, 11)],
                         ids=["whole", "two_chunks", "three_chunks"])
def test_a_prefill_counts_the_rows_its_kernel_walked_and_the_pairs_it_let(
        chunks):
    """A prefill's counters against counts made in numpy: the context's
    rows ``ds_latent_context_attn`` walked, a layer each; the (query, key)
    pairs its masks let through a head (a query at position p sees the
    ``min(index_topk, p + 1)`` keys its full layer picked, a padding row of
    the bucket none), a layer each, float32; and what the tick's kernels
    count stays 0 (the benchmark divides by ``latent_kv_tokens``)."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = GlmDsaModel(cfg), _params(cfg)
    prompt = _tokens((sum(chunks),), 4)
    spec = PagedKVCacheSpec(
        layers=cfg.n_layer, slots=1, heads=1, pages=13, page_len=8,
        head_dim=cfg.d_head, max_pages=12, dtype=jnp.float32,
        v_head_dim=cfg.d_head_v, values_in_keys=True,
        index_layers=cfg.n_index_layer, index_dim=cfg.d_index)
    cache = init_paged_cache(spec)
    pool, keys = cache["k"], cache["index_k"]
    row = np.zeros((12,), np.int32)
    row[:4] = 1 + np.arange(4)
    prefill = jax.jit(lambda p, t, n, pre, r, k, ik: model.prefill_paged(
        p, t, n, pre, r, k, None, index_pool=ik, aux=True))
    done = 0
    with interpret_scope(True):
        for n in chunks:
            padded = np.zeros((1, 32), np.int32)
            padded[0, :n] = prompt[done:done + n]
            _, pool, _, keys, aux = prefill(
                params, padded, np.int32(n), np.int32(done), row, pool, keys)
            done += n
            assert aux["latent_context_rows"].dtype == jnp.int32
            assert int(aux["latent_context_rows"]) == cfg.n_layer * done
            seen = np.minimum(TOPK, 1 + np.arange(done - n, done))
            assert aux["latent_context_pairs"].dtype == jnp.float32
            assert float(aux["latent_context_pairs"]) \
                == cfg.n_layer * seen.sum()
            assert [int(aux[k]) for k in (
                "latent_kv_tokens", "index_scored_rows",
                "index_selected_rows")] == [0, 0, 0]


def test_a_shared_layer_of_the_tick_takes_its_full_layers_mask(monkeypatch):
    """The tick, traced: layers 1-2 read under the very mask layer
    0 made and layer 4 under layer 3's; the two masks are two selections."""
    seen = []
    real = da.sparse_latent_decode_attention

    def spy(q, pool, table, lengths, allowed, *a, **kw):
        seen.append(allowed)
        return real(q, pool, table, lengths, allowed, *a, **kw)

    monkeypatch.setattr(da, "sparse_latent_decode_attention", spy)
    model, params = GlmDsaModel(TINY), _params()
    spec = PagedKVCacheSpec(
        layers=5, slots=2, heads=1, pages=9, page_len=8, head_dim=TINY.d_head,
        max_pages=4, dtype=jnp.float32, v_head_dim=TINY.d_head_v,
        values_in_keys=True, index_layers=2, index_dim=TINY.d_index)
    cache = jax.eval_shape(lambda: init_paged_cache(spec))
    jax.eval_shape(
        lambda p, k, ik: model.decode_step_paged(
            p, jnp.zeros((2,), jnp.int32), k, None,
            jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), bool), impl="dense", index_pool=ik),
        params, cache["k"], cache["index_k"])
    assert list(TINY.indexer_types) \
        == ["full", "shared", "shared", "full", "shared"]
    assert len(seen) == 5 and seen[0].shape == (2, 32)
    assert seen[0] is seen[1] is seen[2] and seen[3] is seen[4]
    assert seen[0] is not seen[3]


def test_a_shared_layer_takes_its_full_layers_picks():
    """Layers 1-2 take layer 0's sets and layer 4 takes layer 3's: the
    model whose layer 1 scores for itself (the same weights + an indexer
    there) differs, and the reference follows both."""
    params = _params()
    kinds = list(TINY.indexer_types)
    assert kinds == ["full", "shared", "shared", "full", "shared"]
    kinds[1] = "full"
    every = dataclasses.replace(TINY, indexer_types=tuple(kinds))
    extra = GlmDsaModel(every).init(jax.random.PRNGKey(7))["indexer"]
    own = dict(params, indexer={
        k: (v[0], extra[k][1], v[1]) for k, v in params["indexer"].items()})
    tokens = _tokens((1, 48), 3)
    shared = np.asarray(GlmDsaModel(TINY).apply(params, tokens))
    scored = np.asarray(GlmDsaModel(every).apply(own, tokens))
    assert np.abs(shared - scored).max() > 100 * F32_TOL
    np.testing.assert_allclose(shared, _reference(params, tokens),
                               atol=F32_TOL)
    np.testing.assert_allclose(scored, _reference(own, tokens, every),
                               atol=F32_TOL)


def test_the_sixteen_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """The routed parts of all shares (each computes the shared expert too:
    counted once) add up to the uncut layer, under a selection bias that
    moves the choice and not the weights; the reference's share is the
    program's."""
    cfg = dataclasses.replace(TINY, experts_held=None, n_routed_experts=32)
    params = _params(cfg, 2)
    bias = jnp.asarray(np.random.RandomState(3).randn(32) * 0.2, jnp.float32)
    params["moe"] = dict(params["moe"], router_bias=(bias,) * cfg.count("moe"))
    x = jnp.asarray(np.random.RandomState(8).randn(12, 64), jnp.float32)

    def layer(c, p):
        ep = glm_dsa.at(p["moe"], 0)
        out, st = glm_dsa._experts(c, ep, glm_dsa.stacked_experts(p), 0, x,
                                   None)
        return out, glm_dsa.shared_expert(ep, x), st

    full, shared, stats = layer(cfg, params)
    unbiased, _, _ = layer(cfg, dict(params, moe=dict(
        params["moe"], router_bias=(0 * bias,) * cfg.count("moe"))))
    assert int(stats.rows) == 12 * 3 and float(jnp.abs(shared).max()) > 0
    assert float(jnp.abs(full - unbiased).max()) > 1e-3
    routed, rows, elsewhere = 0.0, 0, 0
    for first in range(0, 32, 2):                   # sixteen shares of two
        share = dataclasses.replace(cfg, experts_held=(first, 2))
        held = dict(params, moe={
            k: (v[:, first:first + 2] if k in ("gate_w", "up_w", "down_w")
                else v) for k, v in params["moe"].items()})
        part, same, st = layer(share, held)
        np.testing.assert_array_equal(same, shared)
        routed = routed + (part - same)
        rows += int(st.rows)
        elsewhere += int(st.rows_elsewhere)
    np.testing.assert_allclose(routed + shared, full, atol=1e-6)
    assert rows == 12 * 3 and elsewhere == 15 * 12 * 3
    held = dataclasses.replace(cfg, experts_held=(4, 8))
    cut = dict(params, moe={
        k: (v[:, 4:12] if k in ("gate_w", "up_w", "down_w") else v)
        for k, v in params["moe"].items()})
    tokens = _tokens((1, 24))
    got = np.asarray(GlmDsaModel(held).apply(cut, tokens))
    assert np.abs(got - _reference(cut, tokens, held)).max() < F32_TOL


# -- through the engine -------------------------------------------------------

@pytest.mark.parametrize("serving", [
    {}, {"prefix_cache": True}, {"prefill_chunk_len": 8},
    {"pages": 14}],
    ids=["plain", "prefix_cache", "chunked", "pages_reused"])
def test_engine_streams_sit_on_the_reference_logits(serving):
    """Through ``ServeEngine``: prompts that share two pages, one over the
    chunk length, contexts past ``index_topk``; every emitted token is the
    reference's argmax.  ``pages_reused``: a pool of 13 pages, so a later
    request writes its keys over a finished one's."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    model, params = GlmDsaModel(cfg), _params(cfg)
    eng = ServeEngine(model, {"serving": {**SERVING, **serving}},
                      params=params)
    try:
        base = list(_tokens((20,), 6))
        prompts = [base + list(_tokens((n,), 7 + n)) for n in (5, 9)] \
            + [list(_tokens((3,), 9))]
        reqs = []
        for p in prompts:
            reqs.append(eng.submit([int(t) for t in p], max_new_tokens=10))
            eng.run_until_idle()
        assert eng._decode_fn._cache_size() == 1
        if serving.get("prefix_cache"):
            assert eng.prefix.hits >= 1 and reqs[1].shared_len == 16
        else:
            assert eng.pool.free_count == eng.cache_spec.pages - 1
        ticks = [v for _, kind, v in eng.aux_log if kind == "decode"]
        assert ticks and all(
            v["index_selected_rows"] <= v["latent_kv_tokens"]
            and v["index_scored_rows"] * cfg.n_layer
            == v["latent_kv_tokens"] * cfg.count("full") for v in ticks)
        assert any(v["index_selected_rows"] < v["latent_kv_tokens"]
                   for v in ticks)
    finally:
        eng.close()
    for prompt, r in zip(prompts, reqs):
        seq = np.asarray([int(t) for t in prompt] + list(r.tokens))[None]
        rows = _reference(params, seq[:, :-1], cfg)[0][len(prompt) - 1:]
        assert len(r.tokens) == 10
        slack = rows.max(axis=1) - rows[np.arange(10), r.tokens]
        assert slack.max() < F32_TOL, slack


def test_engine_holds_two_arrays_under_one_page_table(tmp_path):
    model = GlmDsaModel(TINY)
    eng = ServeEngine(model, {
        "serving": SERVING,
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}},
        params=_params())
    try:
        # 96 / 8 = 12 pages a slot + the scratch page; 32 + 8 = 40 lanes;
        # 2 of the 5 layers score, their keys 16 wide
        assert sorted(eng.cache) == ["index_k", "k", "lengths"]
        assert eng.cache["k"].shape == (5, 37, 1, 8, 40)
        assert eng.cache["index_k"].shape == (2, 37, 1, 8, 16)
        spec = eng.cache_spec
        assert spec.pool_names == ("k", "index_k")
        assert spec.index_page_bytes == 2 * 8 * 16 * 4
        assert spec.page_bytes == 5 * 8 * 40 * 4 + spec.index_page_bytes
        assert spec.bytes == eng.kv_bytes \
            == eng.cache["k"].nbytes + eng.cache["index_k"].nbytes
        assert eng.state_bytes == {"latent": eng.cache["k"].nbytes,
                                   "index_k": eng.cache["index_k"].nbytes}
        assert eng.page_leaf_nbytes() == [5 * 8 * 40 * 4, 2 * 8 * 16 * 4]
        reg = eng.telemetry.registry
        layers = reg.gauge("serve_cache_layers", "")
        assert (layers.value(kind="latent"), layers.value(kind="index")) \
            == (5, 2)
        held = reg.gauge("serve_state_bytes", "")
        assert held.value(kind="index_k") == eng.cache["index_k"].nbytes
        assert held.value(kind="latent") == eng.cache["k"].nbytes
    finally:
        eng.close()


def test_a_copied_page_and_an_exported_one_carry_both_arrays():
    """Copy-on-write of a shared page and the export / adoption of a
    request's pages walk ``pool_names``: the indexer's keys go with the
    rows, and the adopted request picks what the whole one picks."""
    cfg = dataclasses.replace(TINY, attn_impl="flash")
    params = _params(cfg)
    serving = {**SERVING, "prefix_cache": True}
    prompt = [int(t) for t in _tokens((20,), 11)]
    engines = [ServeEngine(GlmDsaModel(cfg), {"serving": serving},
                           params=params) for _ in range(2)]
    try:
        a, b = engines
        first = a.submit(prompt, max_new_tokens=6)
        a.run_until_idle()
        again = a.submit(prompt, max_new_tokens=6)      # identical: a COW
        a.run_until_idle()
        assert a.prefix.cow >= 1 and again.tokens == first.tokens
        with a._pallas_scope():
            a.cache = a._copy_fn(a.cache, np.int32(1), np.int32(30))
        for name in ("k", "index_k"):
            page = np.asarray(a.cache[name][:, 1])
            assert np.abs(page).max() > 0
            np.testing.assert_array_equal(np.asarray(a.cache[name][:, 30]),
                                          page)
        moved = a.submit(prompt[:12], max_new_tokens=1, detach_kv=True)
        a.run_until_idle()
        pages = a.export_pages(moved)
        assert [len(p) for p in pages] == [a.cache_spec.page_bytes] * 2
        adopted = b.adopt_request(prompt[:12], moved.tokens[0], 8, None,
                                  pages)
        b.run_until_idle()
        whole = b.submit(prompt[:12], max_new_tokens=8)
        b.run_until_idle()
        assert len(adopted.tokens) == 8 and adopted.tokens == whole.tokens
    finally:
        for e in engines:
            e.close()


def test_the_specs_without_an_index_are_what_they_were():
    spec = PagedKVCacheSpec(layers=2, slots=3, heads=1, pages=5, page_len=8,
                            head_dim=40, max_pages=4, v_head_dim=32,
                            values_in_keys=True)
    assert spec.pool_names == ("k",) and spec.index_page_bytes == 0
    assert spec.page_bytes == 2 * 8 * 40 * 4
    assert sorted(init_paged_cache(spec)) == ["k", "lengths"]
    assert sorted(paged_partition_specs(values_in_keys=True)) \
        == ["k", "lengths"]
    assert sorted(paged_partition_specs(values_in_keys=True, indexed=True)) \
        == ["index_k", "k", "lengths"]
    with pytest.raises(ValueError, match="index_layers"):
        dataclasses.replace(spec, index_layers=2)
    with pytest.raises(ValueError, match="index_layers"):
        PagedKVCacheSpec(layers=2, slots=3, heads=1, pages=5, page_len=8,
                         head_dim=40, max_pages=4, dtype=jnp.int8,
                         quant=True, index_layers=1, index_dim=16)


# -- the refusals ---------------------------------------------------------

@pytest.mark.parametrize("serving,named", [
    ({"page_len": 0}, "page_len"),
    ({"speculate_k": 2, "draft": {"d_model": 32, "n_layer": 1,
                                  "n_head": 2}}, "speculate_k"),
    ({"quantization": {"kv": "int8"}}, "quantization"),
    ({"lora": {"rank": 4, "alpha": 8.0, "max_adapters": 4,
               "hbm_adapter_slots": 2, "targets": ["qkv_w"]}}, "lora"),
])
def test_engine_refuses_the_arms_these_steps_lack(serving, named):
    with pytest.raises(ValueError,
                       match=f"GlmDsaModel cannot be served.*{named}"):
        ServeEngine(GlmDsaModel(TINY), {"serving": {**SERVING, **serving}},
                    params=_params())


@pytest.mark.parametrize("field,value,named", [
    ("topk_method", "none", "topk_method"),
    ("n_group", 8, "group-limited"),
    ("scoring_func", "softmax", "scoring_func"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 1e4},
     "rope_type"),
    ("indexer_types", ("shared", "full", "full", "full", "full"), "layer 0"),
    ("indexer_types", ("full", "shared"), "name each"),
    ("mlp_layer_types", ("dense", "moe", "moe", "moe", "moe"), "name each"),
])
def test_config_refuses_what_is_not_built(field, value, named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(TINY, **{field: value})


def test_config_reads_the_published_row():
    """The file's own keys build the configuration as cut (one period of
    the indexer pattern after the dense layer, 2 ``full`` of 7) and, with
    ``published`` over them, as published: 78 layers of which 21 score, 3
    dense, rows 640 wide at rest over values of 512, keys 128 wide."""
    fields = {f.name for f in dataclasses.fields(GlmDsaConfig)}
    keys = {k: v for k, v in FILE.items()
            if k in fields and k != "experts_held"}
    keys["n_routed_experts"] = FILE["published"]["n_routed_experts"]
    cut = GlmDsaConfig(**keys, experts_held=tuple(FILE["experts_held"]))
    assert (cut.n_layer, cut.n_index_layer, cut.count("dense")) == (7, 2, 1)
    assert cut.indexer_types == ("full", "shared", "shared", "shared", "full",
                                 "shared", "shared")
    assert cut.held == (0, 16) and cut.rope_theta == 8e6
    for k in ("indexer_types", "mlp_layer_types"):
        keys.pop(k)
    keys.update({k: v for k, v in FILE["published"].items()
                 if isinstance(v, int)})
    cfg = GlmDsaConfig(**keys)
    assert (cfg.count("dense"), cfg.count("moe"), cfg.count("full")) \
        == (3, 75, 21)
    assert [i for i, k in enumerate(cfg.indexer_types) if k == "full"] \
        == [0, 1, 2] + list(range(6, 78, 4))
    # the cut is published layers 2-8
    assert cfg.indexer_types[2:9] == cut.indexer_types
    assert cfg.mlp_layer_types[2:9] == cut.mlp_layer_types
    assert (cfg.n_layer, cfg.n_head, cfg.n_kv_head) == (78, 64, 1)
    assert (cfg.d_head, cfg.d_head_v, cfg.qk_head_dim, cfg.d_index) \
        == (640, 512, 256, 128)
    assert cfg.values_in_keys and cfg.held == (0, 256)
    assert cfg.index_topk == 2048 and cfg.index_n_heads == 32


def test_the_reference_hands_back_the_logits_of_a_span_of_rows():
    """``logit_rows`` is the head on those rows alone: the same numbers as
    the rows of the whole, at any first row."""
    params = _params()
    tokens = np.random.default_rng(11).integers(0, 256, (1, 40)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(glm_dsa_reference.glm_dsa_logits(
            params, tokens, _m(TINY)))
        part = jax.jit(lambda first: glm_dsa_reference.glm_dsa_logits(
            params, tokens, _m(TINY), logit_rows=(first, 12)))
        for first in (0, 9, 28):
            np.testing.assert_allclose(np.asarray(part(first)),
                                       whole[:, first:first + 12], atol=1e-6)


def test_draws_are_as_surprising_as_their_distribution_says():
    """The streams' statistic (``lib/glm_dsa_family.py::_surprise``): for
    draws FROM softmax(logits / T) the mean of -log p(draw) less the entropy
    lies within three of its stated standard errors of 0.  Draws from other
    logits (a skipped selection), or at another temperature, lie far from
    it, by what the control's exact cross-entropy states.  Noise in the
    logits that is INDEPENDENT of them hardly shows (it adds as much
    cross-entropy as it takes entropy away): that is the probe's to see."""
    from lib import glm_dsa_family as fam
    rng = np.random.default_rng(5)
    N, V, T = 4000, 300, 1.0
    logits = rng.normal(size=(N, V)).astype(np.float32) * 1.5
    logp, entropy, spread = fam._surprise(logits, T)
    np.testing.assert_allclose(np.exp(logp).sum(1), 1.0, atol=1e-5)
    se = np.sqrt(spread.sum()) / N

    def excess(from_logits):
        p = np.exp(fam._log_softmax(from_logits / T)).astype(np.float64)
        p /= p.sum(1, keepdims=True)
        drawn = np.array([rng.choice(V, p=row) for row in p])
        exact = float(np.mean(-np.sum(p * logp, axis=1) - entropy))
        return float(np.mean(-logp[np.arange(N), drawn] - entropy)), exact

    own, exact = excess(logits)
    assert abs(own) < 3 * se and abs(exact) < 1e-6, (own, se, exact)
    other, exact = excess(rng.normal(size=(N, V)).astype(np.float32) * 1.5)
    assert exact > 20 * se and abs(other - exact) < 5 * se, (other, exact)
    cold, exact = excess(logits * 2)
    assert exact < -10 * se and abs(cold - exact) < 4 * se, (cold, exact)
    hot, exact = excess(logits / 2)
    assert exact > 10 * se and abs(hot - exact) < 5 * se, (hot, exact)
    noisy, exact = excess(
        logits + rng.normal(size=(N, V)).astype(np.float32) * 0.7)
    assert abs(exact) < 3 * se, (noisy, exact, se)

