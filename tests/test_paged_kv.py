"""Paged KV cache with prefix reuse (docs/serving.md):

* kernel parity matrix at page-boundary-covering lengths — fp32
  BITWISE dense-paged vs the pre-page dense reference (the ``jnp.take``
  anchor); pallas-paged vs dense and vs the pre-page pallas kernel at
  the established kernel tolerance, and at the edges of the kernel's
  blocks of pages (every head count, fp32 and bf16, a shuffled table,
  poisoned dead pages and tails),
* token-stream identity of the paged engine vs the pre-page engine,
* the zero-recompile contract across mixed page-count request waves,
* prefix cache: shared-template reuse, copy-on-write of the last
  partial page, leaf-LRU eviction, pool accounting,
* pool-exhaustion backpressure + the pool-aware ``kv_capacity`` finish,
* the batched-``device_put`` satellite, deque free lists, config
  validation, telemetry flow, flight-recorder depth fields, and
  admitted requests at one KV-byte budget against the slot layout.
"""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import chip
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.inference import (PagedKVCacheSpec, ServeEngine,
                                     init_paged_cache, shard_cache)
from deepspeed_tpu.inference.kv_cache import (KVCacheSpec, init_cache,
                                              paged_cache_shardings,
                                              validate_paged_cache_mesh)
from deepspeed_tpu.inference.scheduler import (PagePool, PrefixCache,
                                               SlotScheduler)
from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2Model,
                                       gpt2_prefill, gpt2_prefill_paged)
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention, decode_attention_paged, paged_decode_arm,
    paged_gather, paged_pages_per_block)
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.stages import reset_fault_injection

TINY = GPT2Config(vocab_size=128, n_positions=64, d_model=32, n_layer=2,
                  n_head=4, remat=None, attn_impl="dense")
TINY_FLASH = GPT2Config(**{**TINY.__dict__, "attn_impl": "flash"})

_CHAOS_ENVS = ("DS_STAGE_FAULT", "DS_STAGE_DELAY_S")


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    for env in _CHAOS_ENVS:
        monkeypatch.delenv(env, raising=False)
    reset_fault_injection()
    yield
    reset_fault_injection()


def _tokens(n, vocab=128, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, (n,)).astype(np.int32)


def _pool_and_table(S, H, page_len, max_pages, Dh, seed=0):
    """A filled pool + disjoint per-slot tables (page 0 = scratch)."""
    rng = np.random.RandomState(seed)
    P = 1 + S * max_pages
    kp = jnp.asarray(rng.randn(P, H, page_len, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(P, H, page_len, Dh), jnp.float32)
    pt = np.arange(1, P).reshape(S, max_pages).astype(np.int32)
    return kp, vp, jnp.asarray(pt)


# ---------------------------------------------------------------------------
# kernel parity matrix at page-boundary-covering lengths
# ---------------------------------------------------------------------------

#: len < page_len, == page_len, spanning 3 pages, plus the free slot
PAGE_BOUNDARY_LENGTHS = [0, 7, 16, 2 * 16 + 5]


def test_paged_kernel_parity_matrix():
    """fp32 parity at page-boundary lengths: dense-paged is BITWISE
    against the pre-page dense reference on the gathered layout (the
    jnp.take anchor); pallas-paged holds the established kernel
    tolerance against dense AND against the pre-page pallas kernel at
    the paged kernel's block size.  (The paged kernel was bitwise
    against the pre-page one while both took a (slot, head) a grid
    step; since it takes all heads of a block of pages in two matmuls
    it sums the same products in another order, and the honest pin is
    the tolerance.)"""
    S, H, page_len, max_pages, Dh = 4, 3, 16, 3, 32
    kp, vp, pt = _pool_and_table(S, H, page_len, max_pages, Dh)
    q = jnp.asarray(np.random.RandomState(1).randn(S, H, Dh), jnp.float32)
    lengths = jnp.asarray(PAGE_BOUNDARY_LENGTHS, jnp.int32)
    out_d = decode_attention_paged(q, kp, vp, pt, lengths, impl="dense")
    out_p = decode_attention_paged(q, kp, vp, pt, lengths, impl="pallas",
                                   interpret=True)
    # the pre-page reference arms over the SAME values, gathered dense
    kg, vg = paged_gather(kp, pt), paged_gather(vp, pt)
    ref_d = decode_attention(q, kg, vg, lengths, impl="dense")
    ppb = paged_pages_per_block(H, page_len, Dh, 4, max_pages)
    ref_p = decode_attention(q, kg, vg, lengths, impl="pallas",
                             interpret=True, block_k=ppb * page_len)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(ref_d))
    np.testing.assert_allclose(out_p, ref_p, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(out_p, out_d, atol=2e-6, rtol=2e-6)
    # free slot (length 0) outputs exact zeros on both paged arms
    assert (np.asarray(out_d[0]) == 0).all()
    assert (np.asarray(out_p[0]) == 0).all()


# What a block of several pages can get wrong: its edges.  Lengths are
# given in units the block defines, so every head count and dtype meets
# its own boundaries.
BLOCK_LENGTHS = {
    "free": lambda page_len, bk, full: 0,
    "one": lambda page_len, bk, full: 1,
    "page": lambda page_len, bk, full: page_len,
    "block_less_1": lambda page_len, bk, full: bk - 1,
    "block": lambda page_len, bk, full: bk,
    "block_plus_1": lambda page_len, bk, full: bk + 1,
    "full": lambda page_len, bk, full: full,
}
POISON = 1e4

_paged_both = jax.jit(
    lambda q, k, v, t, n, kc, vc, tc: (
        decode_attention_paged(q, k, v, t, n, impl="pallas", interpret=True),
        decode_attention_paged(q, kc, vc, tc, n, impl="dense")))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("H", [3, 12, 25])
@pytest.mark.parametrize("which", list(BLOCK_LENGTHS))
def test_paged_kernel_block_boundaries(which, H, dtype):
    """The block-of-pages kernel against ``impl='dense'`` at the edges
    of its blocks: H = 25 is not a sublane multiple, the page table is
    shuffled and not contiguous, every page the slot does not own, the
    scratch page and the tail of the last live page are poisoned, and
    the table's dead entries name poisoned pages.  The neighbouring
    slot always has two blocks and a ragged tail."""
    page_len, Dh, S = 16, 64, 3
    itemsize = jnp.dtype(dtype).itemsize
    ppb = paged_pages_per_block(H, page_len, Dh, itemsize, 1 << 20)
    max_pages = 2 * ppb + 1          # three blocks, the last one padded
    bk, full = ppb * page_len, max_pages * page_len
    lens = [BLOCK_LENGTHS[which](page_len, bk, full), bk + page_len + 3, 0]
    rng = np.random.RandomState(H)
    P = 1 + S * max_pages
    k = rng.randn(P, H, page_len, Dh).astype(np.float32)
    v = rng.randn(P, H, page_len, Dh).astype(np.float32)
    table = rng.permutation(np.arange(1, P)).reshape(S, max_pages) \
        .astype(np.int32)
    k_bad, v_bad, t_bad = k.copy(), v.copy(), table.copy()
    for s, n in enumerate(lens):
        live = -(-n // page_len)
        for pool in (k_bad, v_bad):
            pool[table[s, live:]] = POISON           # pages beyond the end
            if n % page_len:                         # tail of the last page
                pool[table[s, live - 1], :, n % page_len:] = POISON
        # dead entries: the scratch page and a poisoned page, in turn
        if live < max_pages:
            t_bad[s, live:] = (0, table[s, -1])[s % 2]
    k_bad[0] = v_bad[0] = POISON
    q = jnp.asarray(rng.randn(S, H, Dh), dtype)
    as_dtype = lambda x: jnp.asarray(x, dtype)
    out_p, out_d = _paged_both(
        q, as_dtype(k_bad), as_dtype(v_bad), jnp.asarray(t_bad),
        jnp.asarray(lens, jnp.int32), as_dtype(k), as_dtype(v),
        jnp.asarray(table))
    tol = 2e-6 if dtype == jnp.float32 else 1.6e-2
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_d, np.float32),
                               atol=tol, rtol=tol)
    for s, n in enumerate(lens):
        if n == 0:
            assert (np.asarray(out_p[s], np.float32) == 0).all()


def test_paged_kernel_masks_dead_pages():
    """Garbage in pages beyond a slot's live length, in the dead table
    entries pointing at the scratch page, and in the tail of the last
    live page must never leak."""
    S, H, page_len, max_pages, Dh = 2, 2, 8, 3, 16
    kp, vp, pt = _pool_and_table(S, H, page_len, max_pages, Dh, seed=2)
    q = jnp.asarray(np.random.RandomState(3).randn(S, H, Dh), jnp.float32)
    lengths = jnp.asarray([5, 8], jnp.int32)  # only page 0 of each live
    ptn = np.asarray(pt).copy()
    poisoned_pt = ptn.copy()
    poisoned_pt[:, 1:] = 0                    # dead entries -> scratch
    kp_bad = kp.at[ptn[0, 1]].set(1e4).at[0].set(-1e4)
    vp_bad = vp.at[ptn[0, 1]].set(1e4).at[0].set(-1e4)
    # slot 0 owns 5 rows of its first page: rows 5.. are a dead tail
    kp_bad = kp_bad.at[ptn[0, 0], :, 5:].set(1e4)
    vp_bad = vp_bad.at[ptn[0, 0], :, 5:].set(-1e4)
    for impl in ("dense", "pallas"):
        clean = decode_attention_paged(q, kp, vp, pt, lengths, impl=impl)
        dirty = decode_attention_paged(q, kp_bad, vp_bad,
                                       jnp.asarray(poisoned_pt),
                                       lengths, impl=impl)
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(dirty))


# The direct arm (head 128, 16 heads: a page at rest is the matmul
# operand) copies a slot's live pages by hand, one block ahead, and
# nothing else: each case is a batch of lengths, in units of its page
# and block.  The neighbour is ragged, slots between are free.
DIRECT_BATCHES = {
    "free": lambda pl_, bk, full: [0, bk + pl_ + 3, 0, 5],
    "one": lambda pl_, bk, full: [1, bk + pl_ + 3, 0, 5],
    "page": lambda pl_, bk, full: [pl_, bk + pl_ + 3, 0, 5],
    "page_plus_1": lambda pl_, bk, full: [pl_ + 1, bk + pl_ + 3, 0, 5],
    "block": lambda pl_, bk, full: [bk, bk + pl_ + 3, 0, 5],
    "block_plus_1": lambda pl_, bk, full: [bk + 1, bk + pl_ + 3, 0, 5],
    "full": lambda pl_, bk, full: [full, bk + pl_ + 3, 0, 5],
    "ragged_first_free": lambda pl_, bk, full: [0, 2 * pl_ + 1, 0, full],
    "ragged_last_free": lambda pl_, bk, full: [2 * bk, 0, bk - 1, 0],
    "all_free": lambda pl_, bk, full: [0, 0, 0, 0],
}


@pytest.mark.parametrize("which", list(DIRECT_BATCHES))
def test_paged_kernel_direct_arm(which):
    """OLMoE's pool shape against ``impl='dense'`` in interpret mode.
    The scratch page and every page behind a dead table entry are NaN,
    in both pools: a dead key that reaches either matmul, masked or
    not, fails the case (0 x NaN).  The tail of a last live page holds
    a large finite value, as an evicted request leaves it."""
    H, page_len, Dh, S = 16, 16, 128, 4
    assert paged_decode_arm(H, page_len, Dh, 2) == "direct"
    ppb = paged_pages_per_block(H, page_len, Dh, 2, 1 << 20)
    max_pages = 2 * ppb + 1          # three blocks, the last one padded
    bk, full = ppb * page_len, max_pages * page_len
    lens = DIRECT_BATCHES[which](page_len, bk, full)
    rng = np.random.RandomState(len(which))
    P = 1 + S * max_pages
    k = rng.randn(P, H, page_len, Dh).astype(np.float32)
    v = rng.randn(P, H, page_len, Dh).astype(np.float32)
    table = rng.permutation(np.arange(1, P)).reshape(S, max_pages) \
        .astype(np.int32)
    k_bad, v_bad, t_bad = k.copy(), v.copy(), table.copy()
    for s, n in enumerate(lens):
        live = -(-n // page_len)
        for pool in (k_bad, v_bad):
            pool[table[s, live:]] = np.nan
            if n % page_len:
                pool[table[s, live - 1], :, n % page_len:] = POISON
        if live < max_pages:
            t_bad[s, live:] = (0, table[s, -1])[s % 2]
    k_bad[0] = v_bad[0] = np.nan
    bf16 = lambda x: jnp.asarray(x, jnp.bfloat16)
    out_p, out_d = _paged_both(
        bf16(rng.randn(S, H, Dh)), bf16(k_bad), bf16(v_bad),
        jnp.asarray(t_bad), jnp.asarray(lens, jnp.int32), bf16(k), bf16(v),
        jnp.asarray(table))
    out_p = np.asarray(out_p, np.float32)
    np.testing.assert_allclose(out_p, np.asarray(out_d, np.float32),
                               atol=1.6e-2, rtol=1.6e-2)
    for s, n in enumerate(lens):
        assert n or (out_p[s] == 0).all()


XL_SERVING = dict(S=32, H=25, page_len=16, max_pages=64, Dh=64, P=833)


def _paged_call(S, H, page_len, max_pages, Dh, P):
    """The Mosaic call that ``impl='pallas'`` traces."""
    sds = jax.ShapeDtypeStruct
    pool = sds((P, H, page_len, Dh), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: decode_attention_paged(
        *a, impl="pallas", interpret=True))(
            sds((S, H, Dh), jnp.bfloat16), pool, pool,
            sds((S, max_pages), jnp.int32), sds((S,), jnp.int32))
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0]


def _paged_call_grid(**shapes):
    return tuple(_paged_call(**shapes).params["grid_mapping"].grid)


def _paged_call_operands(**shapes):
    return len(_paged_call(**shapes).invars)


def test_paged_kernel_grid_at_xl_serving_shapes():
    """The mechanism, counted: at the benchmark's serving shapes a
    layer's call has at most S * max_pages / ppb grid steps (the parent
    took S * H * max_pages = 51,200), and the pages a step covers
    follow the pool's shape, never a configuration key, an environment
    variable or a model's name."""
    import importlib
    import inspect
    module = importlib.import_module(
        "deepspeed_tpu.ops.pallas.decode_attention")
    x = XL_SERVING
    ppb = paged_pages_per_block(x["H"], x["page_len"], x["Dh"], 2,
                                x["max_pages"])
    assert ppb == 8
    grid = _paged_call_grid(**x)
    assert int(np.prod(grid)) <= x["S"] * x["max_pages"] // ppb == 256
    # fewer pages a step as a page grows, from the shapes alone
    assert [paged_pages_per_block(25, pl_, 64, 2, 1024 // pl_)
            for pl_ in (16, 64, 128)] == [8, 2, 1]
    assert _paged_call_grid(**{**x, "page_len": 64, "max_pages": 16,
                               "P": 209}) == (32, 8)
    # shapes only: q_heads (PR 34) is the query heads over grouped keys,
    # v_head_dim (PR 36) the values' width where it is not the keys',
    # head_major (PR 59) that a page rests [H, page_len, Dh] at a group of
    # one too: how the pool lies, not a switch of the environment
    assert list(inspect.signature(paged_pages_per_block).parameters) == [
        "heads", "page_len", "head_dim", "itemsize", "max_pages", "q_heads",
        "v_head_dim", "head_major"]
    source = inspect.getsource(module)
    assert "environ" not in source and "getenv" not in source


OLMOE_SERVING = dict(S=64, H=16, page_len=16, max_pages=128, Dh=128,
                     P=3457)


def test_paged_arm_follows_the_pool_shape_alone():
    """Which body ``ds_paged_decode_attn`` runs is read off (heads,
    page_len, head_dim, itemsize): GPT-2 XL's pool keeps the packed arm,
    8 pages a block and the grid 32 x 8 (its program is the parent's);
    OLMoE's takes the direct one, whose pools stay in HBM (six operands
    whatever the block, where the packed arm has one a page)."""
    import inspect
    # shapes only (q_heads, PR 34: the query heads over grouped keys)
    assert list(inspect.signature(paged_decode_arm).parameters) == [
        "heads", "page_len", "head_dim", "itemsize", "q_heads", "head_major"]
    # 30 heads on 30 (Olmo Hybrid, PR 59): no whole sublane tile of heads,
    # so a pool that rested by key would take the packed arm (every page
    # copied into a buffer whose heads are padded to 32 rows); read as
    # ``walked.PagePool`` lays it, by head, a page is the operand
    assert paged_decode_arm(30, 64, 128, 2) == "packed"
    assert paged_decode_arm(30, 64, 128, 2, head_major=True) == "direct"
    assert paged_pages_per_block(30, 64, 128, 2, 48, head_major=True) == 2
    x, o = XL_SERVING, OLMOE_SERVING
    assert paged_decode_arm(x["H"], x["page_len"], x["Dh"], 2) == "packed"
    assert paged_pages_per_block(x["H"], x["page_len"], x["Dh"], 2,
                                 x["max_pages"]) == 8
    assert _paged_call_grid(**x) == (32, 8)
    assert _paged_call_operands(**x) == 4 + 2 * 8
    assert paged_decode_arm(o["H"], o["page_len"], o["Dh"], 2) == "direct"
    ppb = paged_pages_per_block(o["H"], o["page_len"], o["Dh"], 2,
                                o["max_pages"])
    assert _paged_call_grid(**o) == (64, o["max_pages"] // ppb)
    assert _paged_call_operands(**o) == 6
    # whole lanes, whole sublane tiles of heads, nothing folded
    assert {shape: paged_decode_arm(*shape) for shape in [
        (32, 16, 128, 2), (16, 16, 128, 4), (16, 16, 256, 2),
        (8, 16, 128, 2), (25, 16, 128, 2), (16, 16, 64, 2),
        (12, 16, 64, 2)]} == {
        (32, 16, 128, 2): "direct", (16, 16, 128, 4): "direct",
        (16, 16, 256, 2): "direct", (8, 16, 128, 2): "packed",
        (25, 16, 128, 2): "packed", (16, 16, 64, 2): "packed",
        (12, 16, 64, 2): "packed"}


def test_decode_prep_span_counts_live_blocks(tmp_path):
    """``serve/decode_prep`` notes the kernel's reach (telemetry on):
    the pages the active slots own and, of the blocks the fp paged
    kernel's grid steps through a head group, how many hold live keys."""
    eng = ServeEngine(GPT2Model(TINY_FLASH), {
        "serving": {"slots": 3, "max_seq_len": 64, "prefill_len": 32,
                    "page_len": 8},
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    ppb = eng._pages_per_block
    assert ppb == paged_pages_per_block(4, 8, 8, 4, 8) > 1
    eng.submit(list(_tokens(20)), max_new_tokens=3)
    eng.run_until_idle()
    eng.close()
    with open(tmp_path / "trace.json") as f:
        events = json.load(f)["traceEvents"]
    prep = [e["args"] for e in events if e["name"] == "serve/decode_prep"
            and e["args"].get("active")]
    # 20 prompt + 1-2 decoded rows: three pages of 8, one block
    assert prep[-1]["live_pages"] == 3
    assert prep[-1]["page_blocks"] == f"1/{3 * -(-8 // ppb)}"


def test_paged_kernel_single_compile_across_tables():
    """Page table AND lengths are traced: one jit cache entry no
    matter the mix."""
    S, H, page_len, max_pages, Dh = 3, 2, 8, 2, 16
    kp, vp, pt = _pool_and_table(S, H, page_len, max_pages, Dh)
    q = jnp.asarray(np.random.RandomState(4).randn(S, H, Dh), jnp.float32)
    f = jax.jit(lambda q, k, v, t, l: decode_attention_paged(
        q, k, v, t, l, impl="pallas"))
    for tab, lens in ((pt, [0, 3, 16]),
                      (jnp.zeros_like(pt), [0, 0, 0]),
                      (pt[::-1], [8, 8, 1])):
        f(q, kp, vp, tab, jnp.asarray(lens, jnp.int32)).block_until_ready()
    assert f._cache_size() == 1


def test_paged_kernel_rejects_unknown_impl():
    S, H, page_len, max_pages, Dh = 2, 2, 8, 2, 16
    kp, vp, pt = _pool_and_table(S, H, page_len, max_pages, Dh)
    q = jnp.asarray(np.zeros((S, H, Dh)), jnp.float32)
    with pytest.raises(ValueError, match="impl"):
        decode_attention_paged(q, kp, vp, pt,
                               jnp.zeros((S,), jnp.int32), impl="cuda")


# ---------------------------------------------------------------------------
# paged prefill: float32-ulp parity with the pre-page prefill when no prefix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [TINY, TINY_FLASH],
                         ids=["dense", "flash"])
def test_paged_prefill_no_prefix_matches_prepage(cfg):
    """The ``prefix_len == 0`` arm of the paged prefill runs the
    model's OWN attention (dense or flash).  It is still a different
    program from ``gpt2_prefill`` (padded to the bucket, K/V scattered
    into pages, a ``lax.cond`` around the attention), so logits and
    written K/V are held to a few float32 ulps at their scale, not to
    equality."""
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    page_len, max_pages = 8, 3
    t_prompt = 13                                  # spans 2 pages
    toks = _tokens(t_prompt, seed=5)[None]
    logits_ref, ks, vs = gpt2_prefill(cfg, params, jnp.asarray(toks))
    L, H, Dh = cfg.n_layer, cfg.n_head, cfg.d_head
    P = 1 + max_pages
    kp = jnp.zeros((L, P, H, page_len, Dh), jnp.float32)
    vp = jnp.zeros((L, P, H, page_len, Dh), jnp.float32)
    row = np.zeros((max_pages,), np.int32)
    npg = -(-t_prompt // page_len)
    row[:npg] = np.arange(1, 1 + npg)
    pad = np.zeros((1, 16), np.int32)
    pad[0, :t_prompt] = toks[0]
    logits, kp, vp = gpt2_prefill_paged(
        cfg, params, jnp.asarray(pad), np.int32(t_prompt), np.int32(0),
        jnp.asarray(row), kp, vp)
    def close(got, want):
        want = np.asarray(want)
        tol = 4 * np.finfo(np.float32).eps * max(1.0, np.abs(want).max())
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=tol)

    close(logits[0, :t_prompt], logits_ref[0])
    for layer in range(L):
        got_k = paged_gather(kp[layer], jnp.asarray(row)[None])[0]
        got_v = paged_gather(vp[layer], jnp.asarray(row)[None])[0]
        close(got_k[:, :t_prompt], ks[layer, 0])
        close(got_v[:, :t_prompt], vs[layer, 0])


# ---------------------------------------------------------------------------
# engine: token streams identical to the pre-page engine
# ---------------------------------------------------------------------------


def _serve_cfg(slots=4, max_seq=32, prefill=24, telemetry_path=None,
               **serving_extra):
    cfg = {"serving": {"slots": slots, "max_seq_len": max_seq,
                       "prefill_len": prefill, **serving_extra}}
    if telemetry_path is not None:
        cfg["telemetry"] = {"enabled": True,
                            "output_path": str(telemetry_path)}
    return cfg


#: prompt lengths covering every page boundary of page_len=8: inside
#: the first page, == page_len, and spanning 3 pages
BOUNDARY_PROMPTS = [1, 3, 8, 17, 20]


@pytest.mark.parametrize("cfg", [TINY, TINY_FLASH],
                         ids=["dense", "flash"])
def test_paged_engine_token_streams_match_prepage(cfg):
    """THE engine-level acceptance bar: the paged engine emits
    token-for-token the same streams as the pre-page engine — for
    single-page-sufficient requests AND page-spanning ones, on both
    kernel arms."""
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(n, seed=10 + i))
               for i, n in enumerate(BOUNDARY_PROMPTS)]

    def run(extra):
        eng = ServeEngine(model, _serve_cfg(**extra), params=params)
        rs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run_until_idle()
        toks = [r.tokens for r in rs]
        assert all(r.error is None for r in rs)
        assert all(r.finish_reason == "length" for r in rs)
        eng.close()
        return toks

    assert run({}) == run({"page_len": 8})


def test_paged_engine_dense_decode_is_bitwise_vs_prepage():
    """On the dense arm the whole paged chain (prefill + every decode
    tick) is bitwise, so even argmax TIES can't diverge: compare full
    greedy streams at an adversarially long generation."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    p = list(_tokens(9, seed=33))

    def run(extra):
        eng = ServeEngine(model, _serve_cfg(slots=1, **extra),
                          params=params)
        r = eng.submit(p, max_new_tokens=23)   # to the kv_capacity edge
        eng.run_until_idle()
        out = (r.tokens, r.finish_reason)
        eng.close()
        return out

    assert run({}) == run({"page_len": 8})


def test_paged_zero_recompiles_mixed_page_count_waves(tmp_path):
    """Acceptance bar: one compiled decode program (and one prefill,
    one COW copy) survives waves of requests with VARYING page counts —
    zero recompiles, cache size 1."""
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(
        slots=3, page_len=8, telemetry_path=tmp_path))
    rng = np.random.default_rng(7)
    reqs = []
    for wave in range(3):
        for i in range(5):
            n = int(rng.integers(1, 24))       # 1..3 pages per prompt
            reqs.append(eng.submit(
                list(_tokens(n, seed=100 * wave + i)),
                max_new_tokens=int(rng.integers(1, 9))))
        eng.run_until_idle()
    assert all(r.error is None for r in reqs)
    eng.telemetry.compile_monitor.sample()
    reg = eng.telemetry.registry
    for prog in ("serve_decode", "serve_prefill", "serve_copy_page"):
        assert reg.counter("recompiles_total").value(program=prog) == 0
    assert eng._decode_fn._cache_size() == 1
    assert eng._prefill_fn._cache_size() == 1
    eng.close()


def test_paged_submit_validation():
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(
        slots=2, page_len=8, pages=3, prefill=24))
    # 2 usable pages: a 3-page prompt can never be admitted
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit(list(_tokens(17, seed=1)))
    eng.close()


# ---------------------------------------------------------------------------
# chunked prefill: parity, co-scheduling, zero recompiles, KV migration
# ---------------------------------------------------------------------------


#: prompt lengths covering the chunk/page boundary matrix for
#: prefill_chunk_len=4 on page_len=8: sub-chunk, == chunk, chunk
#: boundary inside a page, == page, and final chunks landing inside,
#: at, and across page boundaries
CHUNK_PROMPTS = [1, 3, 4, 8, 11, 17, 20]


def test_chunked_prefill_stream_parity_across_boundaries():
    """Acceptance bar: splitting prefill into fixed-size chunks changes
    WHEN the prompt's KV is computed, never WHAT — token streams are
    bitwise the unchunked paged streams at every chunk/page-boundary
    class."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(n, seed=40 + i))
               for i, n in enumerate(CHUNK_PROMPTS)]

    def run(extra):
        eng = ServeEngine(model, _serve_cfg(page_len=8, **extra),
                          params=params)
        rs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run_until_idle()
        assert all(r.error is None for r in rs)
        toks = [list(r.tokens) for r in rs]
        eng.close()
        return toks

    assert run({}) == run({"prefill_chunk_len": 4})


def test_chunked_prefill_coschedules_decode_ticks():
    """While a long prompt is mid-chunks, decode-phase slots keep
    producing a token EVERY tick — chunked prefill bounds the decode
    stall to one chunk per step instead of a whole-prompt prefill
    (Sarathi-Serve co-scheduling, docs/serving.md)."""
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(
        slots=2, page_len=8, prefill_chunk_len=4))
    short = eng.submit(list(_tokens(2, seed=1)), max_new_tokens=24)
    eng.step()
    assert len(short.tokens) >= 1          # short is decoding
    long = eng.submit(list(_tokens(20, seed=2)), max_new_tokens=4)
    eng.step()                             # admits long + chunk 1
    assert long.prefilling                 # 20 tokens = 5 chunks
    stalls = 0
    while long.prefilling:
        before = len(short.tokens)
        eng.step()
        stalls += (len(short.tokens) == before)
    assert stalls == 0                     # decode never starved
    assert long.tokens                     # final chunk stamped TTFT
    eng.run_until_idle()
    assert short.error is None and long.error is None
    assert long.finish_reason == "length" and len(long.tokens) == 4
    eng.close()


def test_chunked_prefill_zero_recompiles_mixed_lengths(tmp_path):
    """One compiled prefill program serves EVERY chunk: varying prompt
    lengths, chunk counts, and final-chunk widths cost zero recompiles
    — the chunk position rides the traced prefix_len, not a shape."""
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(
        slots=3, page_len=8, prefill_chunk_len=4,
        telemetry_path=tmp_path))
    rng = np.random.default_rng(11)
    reqs = []
    for wave in range(3):
        for i in range(5):
            n = int(rng.integers(1, 24))   # 1..6 chunks per prompt
            reqs.append(eng.submit(
                list(_tokens(n, seed=200 * wave + i)),
                max_new_tokens=int(rng.integers(1, 9))))
        eng.run_until_idle()
    assert all(r.error is None for r in reqs)
    eng.telemetry.compile_monitor.sample()
    reg = eng.telemetry.registry
    for prog in ("serve_decode", "serve_prefill", "serve_copy_page"):
        assert reg.counter("recompiles_total").value(program=prog) == 0
    assert eng._prefill_fn._cache_size() == 1
    assert eng._decode_fn._cache_size() == 1
    eng.close()


def test_kv_migration_export_adopt_stream_parity():
    """Engine-level disaggregation parity: prefill on engine A with
    ``detach_kv`` (1 token), ship the exported page payloads into
    engine B via ``adopt_request``, and the combined stream is bitwise
    what a single engine produces — at page-boundary-covering prompt
    lengths."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(n, seed=60 + i))
               for i, n in enumerate([3, 8, 11])]
    budget = 10

    def single():
        eng = ServeEngine(model, _serve_cfg(page_len=8),
                          params=params)
        rs = [eng.submit(p, max_new_tokens=budget) for p in prompts]
        eng.run_until_idle()
        assert all(r.error is None for r in rs)
        toks = [list(r.tokens) for r in rs]
        eng.close()
        return toks

    def migrated():
        a = ServeEngine(model, _serve_cfg(page_len=8), params=params)
        b = ServeEngine(model, _serve_cfg(page_len=8), params=params)
        assert a.page_leaf_nbytes() == b.page_leaf_nbytes()
        out = []
        for p in prompts:
            r = a.submit(p, max_new_tokens=1, detach_kv=True)
            a.run_until_idle()
            assert r.error is None and r.pages is not None
            payloads = a.export_pages(r)
            a.release_detached(r)
            assert r.pages is None         # capacity returned
            rb = b.adopt_request(p, r.tokens[0], budget, None,
                                 payloads)
            assert rb is not None
            b.run_until_idle()
            assert rb.error is None
            out.append(list(rb.tokens))
        a.close()
        b.close()
        return out

    assert single() == migrated()


def test_kv_adoption_backpressure_returns_none():
    """adopt_request under slot/page pressure parks instead of raising
    — the router's retry contract."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    a = ServeEngine(model, _serve_cfg(page_len=8), params=params)
    p = list(_tokens(9, seed=5))
    r = a.submit(p, max_new_tokens=1, detach_kv=True)
    a.run_until_idle()
    payloads = a.export_pages(r)
    a.release_detached(r)
    # slot pressure: a 1-slot engine mid-request has no free slot
    b = ServeEngine(model, _serve_cfg(slots=1, page_len=8),
                    params=params)
    held = b.submit(list(_tokens(2, seed=6)), max_new_tokens=30)
    b.step()
    assert b.adopt_request(p, r.tokens[0], 4, None, payloads) is None
    b.run_until_idle()
    assert held.error is None
    # page-count mismatch is a config error, not backpressure
    with pytest.raises(ValueError, match="pages"):
        b.adopt_request(p, r.tokens[0], 4, None, payloads[:-1])
    a.close()
    b.close()


def test_chunked_prefill_config_needs_paged_layout():
    with pytest.raises(DeepSpeedConfigError, match="page_len"):
        ServeEngine(GPT2Model(TINY), _serve_cfg(prefill_chunk_len=4))


# ---------------------------------------------------------------------------
# prefix cache: shared templates, COW, eviction, accounting
# ---------------------------------------------------------------------------


def test_prefix_cache_shared_template_prefills_delta_only(tmp_path):
    """K requests sharing a template: the prefill computes the full
    prompt once and only the delta afterwards; token streams stay
    identical to prefix-cache-off."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    template = list(_tokens(16, seed=40))          # exactly 2 pages
    prompts = [template + list(_tokens(3, seed=41 + i))
               for i in range(4)]

    def run(prefix_cache, tel=None):
        eng = ServeEngine(model, _serve_cfg(
            page_len=8, prefix_cache=prefix_cache, telemetry_path=tel),
            params=params)
        rs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        out = [r.tokens for r in rs]
        computed = [r.computed_len for r in rs]
        shared = [r.shared_len for r in rs]
        stats = (eng.prefix.hits, eng.prefix.misses,
                 eng.prefix.hit_tokens) if eng.prefix else None
        reg = (eng.telemetry.registry if eng.telemetry else None)
        hits_counter = (reg.counter("serve_prefix_hits_total").value()
                        if reg else None)
        eng.close()
        assert all(r.error is None for r in rs)
        return out, computed, shared, stats, hits_counter

    on = run(True, tel=tmp_path)
    off = run(False)
    assert on[0] == off[0], "prefix cache changed the token streams"
    # first request misses and computes everything; later ones compute
    # only the 3-token suffix + the uncacheable last-page remainder
    assert on[1][0] == 19 and all(c == 3 for c in on[1][1:])
    assert on[2][0] == 0 and all(s == 16 for s in on[2][1:])
    assert on[3] == (3, 1, 48)
    assert on[4] == 3
    # prefix-cache-off never shares
    assert all(c == 19 for c in off[1])


def test_prefix_cache_cow_on_divergent_append():
    """Identical prompts share down INTO the last partial page; the
    divergent append triggers copy-on-write, and the streams match a
    no-prefix-cache run bit for bit."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    prompt = list(_tokens(13, seed=50))            # 1 full + 5-token tail

    def run(prefix_cache):
        eng = ServeEngine(model, _serve_cfg(
            page_len=8, prefix_cache=prefix_cache), params=params)
        rs = [eng.submit(list(prompt), max_new_tokens=6)
              for _ in range(3)]
        eng.run_until_idle()
        out = [r.tokens for r in rs]
        cow = eng.prefix.cow if eng.prefix else None
        eng.close()
        assert all(r.error is None for r in rs)
        return out, cow

    on, cow = run(True)
    off, _ = run(False)
    assert on == off
    # requests 2 and 3 hit the partial tail (4 cacheable tokens of it)
    # and each must COW before appending
    assert cow == 2


def test_prefix_cache_last_token_never_cached():
    """The vLLM rule: a full-prompt hit still computes >= 1 token so
    prefill has logits to emit the first generated token from."""
    pool = PagePool(8)
    pc = PrefixCache(4, pool)
    prompt = list(range(8))                        # exactly 2 pages
    pages = pool.alloc(2)
    pc.insert(prompt, pages)
    # an identical prompt may share at most len-1 = 7 tokens -> only
    # the first full page (4) + 3 tokens of the second
    shared, spages, cow = pc.match(prompt)
    assert shared == 7 and len(spages) == 2 and cow
    pc.release(spages)


def test_prefix_cache_leaf_lru_eviction_keeps_chains_reachable():
    pool = PagePool(16)
    pc = PrefixCache(4, pool)
    # two chains sharing nothing: A (2 full pages + tail), B (1 full)
    a = [1] * 4 + [2] * 4 + [3, 3]
    b = [9] * 4 + [8, 8]
    pa = pool.alloc(3)
    pc.insert(a, pa)
    pb = pool.alloc(2)
    pc.insert(b, pb)
    held = pc.entries
    assert held == 5
    # evict until 12 pages free: leaf-first order means a chain's inner
    # page is never dropped while a deeper entry still chains through it
    pc.evict(12)
    for d, fe in pc.full.items():
        parent = fe.parent
        while parent:
            assert parent in pc.full, "evicted an inner chain page"
            parent = pc.full[parent].parent
    for parent in pc.partials:
        assert parent == "" or parent in pc.full


def test_page_pool_contracts():
    pool = PagePool(5)
    assert pool.free_count == 4 and pool.used_count == 0
    got = pool.alloc(2)
    assert len(got) == 2 and 0 not in got
    assert pool.alloc(3) is None                   # no side effects
    assert pool.free_count == 2
    pool.ref(got[0])
    pool.deref(got[0])
    assert pool.free_count == 2                    # still held once
    pool.deref(got[0])
    assert pool.free_count == 3                    # freed
    pool.deref(got[1])
    with pytest.raises(AssertionError, match="double free"):
        pool.deref(got[1])
    with pytest.raises(ValueError, match="scratch"):
        pool.ref(0)
    with pytest.raises(ValueError, match="2 pages"):
        PagePool(1)


def test_slot_scheduler_free_list_is_deque():
    from collections import deque
    s = SlotScheduler(4)
    assert isinstance(s.free, deque)
    eng = ServeEngine(GPT2Model(TINY), _serve_cfg(page_len=8))
    assert isinstance(eng.pool.free, deque)
    eng.close()


def test_paged_pool_accounting_after_drain():
    """Every page returns to the free list once its holders are gone:
    slots release on finish, the prefix cache holds only its entries."""
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(page_len=8, prefix_cache=True))
    usable = eng.cache_spec.pages - 1
    rs = [eng.submit(list(_tokens(n, seed=60 + n)), max_new_tokens=4)
          for n in (3, 9, 17)]
    eng.run_until_idle()
    assert all(r.error is None for r in rs)
    # only the prefix cache still holds pages — one per entry
    assert eng.pool.used_count == eng.prefix.entries
    assert sum(eng.pool.refs.values()) == eng.prefix.entries
    eng.prefix.clear()
    assert eng.pool.free_count == usable
    eng.close()


# ---------------------------------------------------------------------------
# pool exhaustion: backpressure + pool-aware kv_capacity
# ---------------------------------------------------------------------------


def test_pool_exhaustion_admission_backpressure():
    """More demand than pages: admission parks requests (order
    preserved) until releases free pages — every request completes."""
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(
        slots=4, page_len=8, pages=5, prefix_cache=False))
    # each request needs 2 pages (prompt 9) but only 4 are usable
    rs = [eng.submit(list(_tokens(9, seed=70 + i)), max_new_tokens=3)
          for i in range(4)]
    saw_pending = False
    ticks = 0
    while eng.scheduler.active or eng._pending or eng.queue.qsize():
        eng.step()
        saw_pending = saw_pending or bool(eng._pending)
        ticks += 1
        assert ticks < 1000
    assert saw_pending, "pool never backpressured"
    for r in rs:
        assert r.error is None and r.finish_reason == "length"
    eng.close()


def test_pool_exhaustion_decode_append_finishes_kv_capacity():
    """A request that can't grow into a new page finishes with the
    pool-exhaustion-aware kv_capacity reason instead of wedging."""
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(
        slots=2, page_len=8, pages=2, prefix_cache=False))
    r = eng.submit(list(_tokens(8, seed=80)), max_new_tokens=50)
    eng.run_until_idle()
    # prompt fills the single usable page; the first append needs a
    # second page that doesn't exist
    assert r.finish_reason == "kv_capacity"
    assert len(r.tokens) == 1                      # the prefill token
    assert r.error is None
    eng.close()


def test_paged_close_fails_parked_requests():
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(
        slots=4, page_len=8, pages=3, prefix_cache=False))
    rs = [eng.submit(list(_tokens(9, seed=90 + i)), max_new_tokens=4)
          for i in range(3)]
    eng.step()              # admits the first, parks/queues the rest
    eng.close()
    # every request the pool backpressured (parked OR still queued)
    # fails typed at close instead of hanging its waiter
    failed = [r for r in rs if r.error is not None]
    assert len(failed) == 2
    for r in failed:
        assert r.done.is_set()
        with pytest.raises(RuntimeError, match="closed"):
            r.result(timeout=0)


# ---------------------------------------------------------------------------
# sharding: batched placement + TP/DP paged serving
# ---------------------------------------------------------------------------


def test_shard_cache_issues_one_batched_device_put(monkeypatch):
    """The PR 3/4 idiom: ONE list-form jax.device_put for every cache
    leaf, both layouts — a put per leaf is a dispatch per leaf."""
    calls = []
    real = jax.device_put

    def spy(x, device=None, **kw):
        calls.append(x)
        return real(x, device, **kw)

    mesh = build_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    spec = PagedKVCacheSpec(layers=2, slots=4, heads=4, pages=8,
                            page_len=4, head_dim=8, max_pages=2)
    monkeypatch.setattr(jax, "device_put", spy)
    cache = shard_cache(init_paged_cache(spec), mesh,
                        paged_cache_shardings(mesh))
    assert len(calls) == 1 and isinstance(calls[0], list)
    assert cache["k"].shape == (2, 8, 4, 4, 8)
    calls.clear()
    legacy = KVCacheSpec(layers=2, slots=8, heads=4, max_len=8,
                         head_dim=4)
    shard_cache(init_cache(legacy), mesh)
    assert len(calls) == 1 and isinstance(calls[0], list)


def test_paged_cache_mesh_validation():
    spec = PagedKVCacheSpec(layers=2, slots=4, heads=4, pages=7,
                            page_len=4, head_dim=8, max_pages=2)
    with pytest.raises(ValueError, match="pages"):
        validate_paged_cache_mesh(
            build_mesh(dp=2, devices=jax.devices()[:2]), spec)
    spec2 = PagedKVCacheSpec(layers=2, slots=4, heads=3, pages=8,
                             page_len=4, head_dim=8, max_pages=2)
    with pytest.raises(ValueError, match="model axis"):
        validate_paged_cache_mesh(
            build_mesh(dp=1, tp=2, devices=jax.devices()[:2]), spec2)
    assert spec.page_bytes == 2 * 2 * 4 * 4 * 8 * 4
    assert spec.bytes == spec.page_bytes * spec.pages


#: benchmark/configs/<name>.json -> (pool names, their shapes, bytes) of the
#: cache ``ServeEngine`` made for the cell when PR 62 moved the rules into
#: ``PagedKVCacheSpec.for_model``
CELL_CACHES = {
    "a.x-k1": (("k",), {"k": (6, 12289, 1, 64, 640)}, 6040289280),
    "command-a-plus-05-2026": (("k", "v"), {
        "k": (1, 12289, 8, 64, 128), "v": (1, 12289, 8, 64, 128)},
        3221487616),
    "dots3-note-prev": (("k", "index_k"), {
        "k": (3, 12289, 1, 64, 640), "index_k": (3, 12289, 1, 64, 128)},
        3624173568),
    "glm-5.2": (("k", "index_k"), {
        "k": (7, 7169, 1, 64, 640), "index_k": (2, 7169, 1, 64, 128)},
        4345905152),
    "gpt2-xl": (("k", "v"), {
        "k": (48, 833, 25, 16, 64), "v": (48, 833, 25, 16, 64)}, 4094361600),
    "kimi-linear-48b-a3b": (("k",), {"k": (2, 24577, 1, 64, 640)},
                            4026695680),
    # 8 key heads of 64 rest as 4 paired heads of 128
    # (``walked.PairedPagePool``): the published bytes
    "lfm2-24b-a2b": (("k", "v"), {
        "k": (2, 11265, 4, 64, 128), "v": (2, 11265, 4, 64, 128)},
        2 * 2 * 11265 * 4 * 64 * 128 * 2),
    "mimo-v2.5": (("k", "v"), {
        "k": (2, 13825, 4, 64, 256), "v": (2, 13825, 4, 64, 128)},
        5436211200),
    "nemotron-3-super-120b-a12b": (("k", "v"), {
        "k": (1, 24577, 2, 16, 128), "v": (1, 24577, 2, 16, 128)}, 402669568),
    "olmo-hybrid-7b": (("k", "v"), {
        "k": (2, 3073, 30, 64, 128), "v": (2, 3073, 30, 64, 128)},
        6041763840),
    "olmoe-1b-7b": (("k", "v"), {
        "k": (12, 3457, 16, 16, 128), "v": (12, 3457, 16, 16, 128)},
        5437390848),
}
_CONFIGS = os.path.join(chip.ROOT, "benchmark", "configs")


def test_every_served_configuration_has_its_cache_pinned():
    served = set()
    for name in os.listdir(_CONFIGS):
        with open(os.path.join(_CONFIGS, name)) as f:
            if "serving" in json.load(f):
                served.add(name[:-len(".json")])
    assert served == set(CELL_CACHES)


@pytest.mark.parametrize("name", sorted(CELL_CACHES))
def test_spec_for_model_is_the_cells_cache(name):
    """``PagedKVCacheSpec.for_model`` on the model the benchmark builds
    from the file, under the file's ``serving`` block: the pools the
    engine had, by name, shape and bytes, and a table row a slot.  No
    engine is built and nothing is allocated."""
    model, file = chip.served_model(name, cut=False)
    spec, cache, _ = chip.served_cache(name, cut=False)
    serving = file["serving"]
    names, shapes, nbytes = CELL_CACHES[name]
    assert spec.pool_names == names
    assert {k: cache[k].shape for k in names} == shapes
    assert all(cache[k].dtype == jnp.dtype(file["dtype"]) for k in names)
    assert spec.bytes == nbytes
    assert cache["lengths"].shape == (serving["slots"],)
    assert spec.max_pages * spec.page_len >= serving["max_seq_len"] \
        > (spec.max_pages - 1) * spec.page_len
    quant = PagedKVCacheSpec.for_model(
        model.config, slots=1, pages=2, page_len=spec.page_len,
        max_seq_len=spec.page_len, dtype=jnp.float32,
        quant=not (spec.values_in_keys or spec.index_layers))
    assert quant.dtype == (jnp.int8 if quant.quant else jnp.float32)


def test_paged_tp_dp_sharded_matches_single_device():
    model = GPT2Model(TINY_FLASH)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(_tokens(5, seed=i)) for i in range(4)]

    def run(mesh):
        eng = ServeEngine(model, _serve_cfg(page_len=8), mesh=mesh,
                          params=params)
        rs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_idle()
        toks = [r.tokens for r in rs]
        eng.close()
        return toks

    base = run(None)
    sharded = run(build_mesh(dp=2, tp=2, devices=jax.devices()[:4]))
    assert base == sharded


# ---------------------------------------------------------------------------
# config block
# ---------------------------------------------------------------------------


def test_paged_serving_config_validation():
    from deepspeed_tpu.config.config import DeepSpeedServingConfig
    ok = DeepSpeedServingConfig({"serving": {"page_len": 16,
                                             "pages": 64}})
    assert ok.page_len == 16 and ok.pages == 64 and ok.prefix_cache
    off = DeepSpeedServingConfig({"serving": {}})
    assert off.page_len == 0 and off.pages == 0
    with pytest.raises(DeepSpeedConfigError, match="page_len"):
        DeepSpeedServingConfig({"serving": {"page_len": -1}})
    with pytest.raises(DeepSpeedConfigError, match="page_len"):
        DeepSpeedServingConfig({"serving": {"pages": 8}})
    with pytest.raises(DeepSpeedConfigError, match="scratch"):
        DeepSpeedServingConfig({"serving": {"page_len": 8, "pages": 1}})
    with pytest.raises(DeepSpeedConfigError, match="prefix_cache"):
        DeepSpeedServingConfig({"serving": {"prefix_cache": "false"}})


# ---------------------------------------------------------------------------
# telemetry: gauges -> sync scalars -> summarize rows; flight recorder
# ---------------------------------------------------------------------------


def test_paged_telemetry_flows_to_summarize(tmp_path, capsys):
    from deepspeed_tpu.telemetry.cli import summarize
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(
        page_len=8, telemetry_path=tmp_path, flush_interval_ticks=2),
        params=model.init(jax.random.PRNGKey(0)))
    template = list(_tokens(16, seed=95))
    for i in range(3):
        eng.submit(template + list(_tokens(2, seed=96 + i)),
                   max_new_tokens=4)
    eng.run_until_idle()
    reg = eng.telemetry.registry
    assert reg.gauge("serve_pages_total").value() == \
        eng.cache_spec.pages - 1
    assert reg.counter("serve_prefix_hits_total").value() == 2
    eng.close()
    events = os.path.join(str(tmp_path), "events.jsonl")
    report = summarize(events)
    out = capsys.readouterr().out
    assert report["serve_page_utilization"] is not None
    assert report["serve_free_pages"] is not None
    assert report["serve_prefix_hit_ratio"] == pytest.approx(2 / 3)
    assert report["serve_prefix_hit_tokens"] == 32
    assert "kv page pool" in out and "prefix cache" in out


def test_serve_stage_depth_snapshots_include_free_pages():
    """The flight-recorder satellite: every serve stage ring event now
    carries the pool's free-page count next to the queue depth."""
    model = GPT2Model(TINY)
    eng = ServeEngine(model, _serve_cfg(page_len=8))
    eng.submit(list(_tokens(5, seed=97)), max_new_tokens=3)
    eng.run_until_idle()
    snap = eng.stage.flight_snapshot()
    assert snap["events"], "no stage events recorded"
    for ev in snap["events"]:
        assert "free_pages" in ev and "depth" in ev
        assert 0 <= ev["free_pages"] <= eng.cache_spec.pages - 1
    eng.close()
    # the pre-page engine keeps its plain int depth
    eng2 = ServeEngine(model, _serve_cfg())
    eng2.submit(list(_tokens(3, seed=98)), max_new_tokens=2)
    eng2.run_until_idle()
    evs = eng2.stage.flight_snapshot()["events"]
    assert evs and all("depth" in e and "free_pages" not in e
                       for e in evs)
    eng2.close()


# ---------------------------------------------------------------------------
# injected prefill device time ∝ computed pages
# ---------------------------------------------------------------------------


def test_prefix_hit_prefill_pays_delta_chunks_only(monkeypatch):
    monkeypatch.setenv("DS_STAGE_DELAY_S", "serve:0.05")
    reset_fault_injection()
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, _serve_cfg(page_len=8), params=params)
    template = list(_tokens(16, seed=99))
    r1 = eng.submit(template + [1, 2], max_new_tokens=1)
    eng.run_until_idle()
    r2 = eng.submit(template + [3, 4], max_new_tokens=1)
    eng.run_until_idle()
    eng.close()
    # r1 computed 18 tokens = 3 chunks -> 2 extra delay units inside
    # the prefill window; r2 computed 2 tokens -> 0 extra
    assert r1.prefill_s >= 0.10
    assert r2.prefill_s < 0.05


# ---------------------------------------------------------------------------
# one KV-byte budget: pages admit what fixed strides cannot
# ---------------------------------------------------------------------------


def _drain_peak_active(eng, work):
    """Submit ``(prompt, budget)`` pairs at once and step to idle;
    returns the requests and the most that were ever admitted
    together."""
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
    peak = 0
    while eng.scheduler.active or eng._pending or eng.queue.qsize():
        eng.step()
        peak = max(peak, len(eng.scheduler.active))
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return reqs, peak


def test_paged_admits_twice_the_slot_arm_at_one_kv_byte_budget():
    """The capacity claim as counts.  The budget is what two slots of
    fixed ``max_seq_len`` strides cost (``KVCacheSpec.bytes``); the
    paged engine gets the pages those bytes buy
    (``PagedKVCacheSpec.page_bytes``) plus the scratch page, which
    holds no request.  Under a mix of one long request in four it
    admits at least twice as many together, and never diverges: a
    stream the pool cut short (the ``kv_capacity`` finish) is a prefix
    of the slot arm's.  Then K template sharers: K-1 prefix hits, and
    well under the no-prefix run's prefill tokens."""
    model = GPT2Model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    page_len, max_seq, budget_slots = 8, 32, 2
    work = [(list(_tokens(16 if i % 4 == 3 else 4, seed=300 + i)),
             16 if i % 4 == 3 else 4) for i in range(8)]

    slot_eng = ServeEngine(model, _serve_cfg(slots=budget_slots,
                                             max_seq=max_seq),
                           params=params)
    budget = slot_eng.cache_spec.bytes
    shape = dict(layers=TINY.n_layer, heads=TINY.n_head,
                 head_dim=TINY.d_head, dtype=slot_eng.cache_spec.dtype)
    assert budget == KVCacheSpec(slots=budget_slots, max_len=max_seq,
                                 **shape).bytes
    page_bytes = PagedKVCacheSpec(slots=1, pages=1, page_len=page_len,
                                  max_pages=1, **shape).page_bytes
    slot_reqs, slot_peak = _drain_peak_active(slot_eng, work)
    slot_eng.close()

    paged_eng = ServeEngine(model, _serve_cfg(
        slots=4 * budget_slots, max_seq=max_seq, page_len=page_len,
        pages=budget // page_bytes + 1), params=params)
    assert paged_eng.cache_spec.bytes - page_bytes <= budget
    paged_reqs, paged_peak = _drain_peak_active(paged_eng, work)
    paged_eng.close()
    assert slot_peak == budget_slots
    assert paged_peak >= 2 * slot_peak, (paged_peak, slot_peak)
    for rs, rp in zip(slot_reqs, paged_reqs):
        assert rp.tokens == rs.tokens[:len(rp.tokens)]

    template = list(_tokens(16, seed=340))
    sharers = [(template + list(_tokens(4, seed=341 + i)), 2)
               for i in range(3)]
    computed = {}
    for prefix_cache in (True, False):
        eng = ServeEngine(model, _serve_cfg(
            page_len=page_len, prefix_cache=prefix_cache), params=params)
        reqs, _ = _drain_peak_active(eng, sharers)
        computed[prefix_cache] = (sum(r.computed_len for r in reqs),
                                  [r.tokens for r in reqs])
        if prefix_cache:
            assert eng.prefix.hits == 2
        eng.close()
    assert computed[True][1] == computed[False][1]
    assert computed[True][0] < 0.75 * computed[False][0]
