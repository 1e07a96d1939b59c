"""``deepspeed_tpu/models/walked.py``: what the walked families share.

* no module under ``deepspeed_tpu/models/`` imports a name from a sibling
  family module: a family stands on ``walked``, ``ops`` and ``moe`` alone
  (read from the sources, nothing is imported);
* the two index preludes state once the rule five files relied on: an
  inactive slot and a padded prefill row name page 0, the engine's scratch
  page, and attend over / count as length 0;
* latent attention's expanded form over a paged context, which two
  families share: every key under the causal rule, or the keys a mask
  allows beside it;
* the form a leaf rests in inside an engine (``serving_layouts``, PR 55):
  a family declares its query projections and nothing else, the engine
  makes its own copy of them so and emits the same tokens, and the
  caller's tree is the same objects afterwards.
"""
import ast
import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import walked

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deepspeed_tpu", "models")
FAMILIES = ("olmoe", "nemotron_h", "mimo_v2", "axk1", "cohere2_moe",
            "glm_dsa", "kimi_linear", "olmo_hybrid")


def _imported_modules(path):
    """Every module a source file imports, relative ones by their last
    name (``from .olmoe import x``, ``from . import olmoe``, ``from
    deepspeed_tpu.models.olmoe import x`` all give ``olmoe``)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module:
                yield node.module.rsplit(".", 1)[-1]
            else:                       # from . import a, b
                yield from (a.name for a in node.names)
        elif isinstance(node, ast.Import):
            yield from (a.name.rsplit(".", 1)[-1] for a in node.names)


@pytest.mark.parametrize("family", FAMILIES)
def test_no_model_imports_from_a_sibling_family(family):
    importers = []
    for name in sorted(os.listdir(MODELS)):
        if name.endswith(".py") and name != family + ".py" \
                and family in _imported_modules(os.path.join(MODELS, name)):
            importers.append(name)
    assert not importers, f"{importers} import from models/{family}.py"
    # and the family itself stands on the shared module
    assert "walked" in set(_imported_modules(
        os.path.join(MODELS, family + ".py")))


@pytest.mark.parametrize("low_rank_q,rotate", [(True, True), (False, True),
                                               (False, False)])
def test_latent_projections_without_a_low_rank_query_or_a_rotation(
        low_rank_q, rotate):
    """The two arguments its first two callers do not pass: a query
    straight from ``q_w`` (``c_q`` None), and nothing rotated (positions
    not read); the default is the low-rank, rotated form it always was."""
    rng = np.random.default_rng(0)
    d, H, nope, rot, rank, rq = 24, 2, 8, 4, 16, 12

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)

    ap = {"q_a_w": w(d, rq), "q_a_norm": jnp.ones((rq,)),
          "q_b_w": w(rq, H * (nope + rot)), "q_w": w(d, H * (nope + rot)),
          "kv_a_w": w(d, rank + rot), "kv_a_norm": jnp.ones((rank,))}
    h = w(1, 5, d)
    positions = jnp.arange(5, dtype=jnp.int32)[None]
    c_q, q_nope, q_rope, c_kv, k_rope = walked.latent_projections(
        ap, h, positions if rotate else None, heads=H, nope=nope,
        kv_rank=rank, eps=1e-6, theta=1e4, low_rank_q=low_rank_q,
        rotate=rotate)
    src = walked.rms_norm(h @ ap["q_a_w"], ap["q_a_norm"], 1e-6) \
        if low_rank_q else h
    q = walked.project_heads(src, ap["q_b_w" if low_rank_q else "q_w"], H)
    kv = h @ ap["kv_a_w"]

    def turned(t):
        return walked.rope(t, positions, 1e4) if rotate else t

    assert (c_q is None) == (not low_rank_q)
    np.testing.assert_array_equal(q_nope, q[..., :nope])
    np.testing.assert_array_equal(q_rope, turned(q[..., nope:]))
    np.testing.assert_array_equal(c_kv, walked.rms_norm(
        kv[..., :rank], ap["kv_a_norm"], 1e-6))
    np.testing.assert_array_equal(k_rope,
                                  turned(kv[:, None, :, rank:])[:, 0])
    if rotate:      # the rotation does turn them
        assert np.abs(np.asarray(q_rope - q[..., nope:])).max() > 1e-3


def test_decode_index_sends_an_inactive_slot_to_page_0_and_length_0():
    page_len, n_positions = 8, 64
    table = jnp.asarray([[3, 4, 5], [6, 7, 9], [10, 11, 12]], jnp.int32)
    lengths = jnp.asarray([9, 17, 23], jnp.int32)
    active = jnp.asarray([True, False, True])
    got_len, positions, att_len, page_ids, offs = walked.decode_index(
        table, lengths, active, page_len, n_positions)
    np.testing.assert_array_equal(got_len, [9, 17, 23])
    np.testing.assert_array_equal(positions, [9, 17, 23])
    # slot 0 writes row 1 of its second page, slot 2 row 7 of its third;
    # the inactive slot 1 names the scratch page and attends over nothing
    np.testing.assert_array_equal(page_ids, [4, 0, 12])
    np.testing.assert_array_equal(offs, [1, 1, 7])
    np.testing.assert_array_equal(att_len, [10, 0, 24])
    # a slot at the end of its pages stays inside them
    full = walked.decode_index(table, jnp.asarray([24, 24, 99], jnp.int32),
                               active, page_len, n_positions)
    np.testing.assert_array_equal(full[1], [23, 23, 23])
    # ... and inside the model's positions, where those are fewer
    assert int(walked.decode_index(table, lengths, active, page_len,
                                   16)[1].max()) == 15


@pytest.mark.parametrize("prefix_len", [None, 0, 11])
def test_prefill_index_sends_a_padded_row_to_page_0(prefix_len):
    page_len, Tq, delta_len = 8, 16, 5
    page_row = jnp.asarray([7, 2, 9, 4], jnp.int32)
    prefix = None if prefix_len is None else jnp.int32(prefix_len)
    valid, page_ids, offs, abs_pos, positions = walked.prefill_index(
        page_row, jnp.int32(delta_len), Tq, page_len, prefix, 20)
    start = prefix_len or 0
    at = start + np.arange(Tq)
    np.testing.assert_array_equal(valid, np.arange(Tq) < delta_len)
    np.testing.assert_array_equal(abs_pos, at)
    want = np.asarray(page_row)[np.minimum(at, 31) // page_len]
    np.testing.assert_array_equal(page_ids[:delta_len], want[:delta_len])
    assert not np.asarray(page_ids[delta_len:]).any()   # the scratch page
    np.testing.assert_array_equal(offs, np.minimum(at, 31) % page_len)
    if prefix_len is None:
        assert positions is None        # a whole prompt: the family's arange
    else:
        np.testing.assert_array_equal(positions, np.minimum(at, 19)[None])


def test_a_row_that_is_not_kept_is_written_back_as_it_was():
    """What page 0 is for: ``PagePool.write`` reads the old row of an
    index that is not kept and writes it back."""
    pool = jnp.arange(2 * 3 * 2 * 4 * 2, dtype=jnp.float32).reshape(
        2, 3, 2, 4, 2)                  # [L, pages, Hkv, page_len, D]
    page_ids = jnp.asarray([2, 0], jnp.int32)
    offs = jnp.asarray([1, 3], jnp.int32)
    keep = jnp.asarray([True, False])
    rows = walked.PagePool((pool,), page_ids, offs, keep)
    rows.write(1, -jnp.ones((2, 2, 2)))
    got, = rows.arrays()
    want = np.asarray(pool).copy()
    want[1, 2, :, 1] = -1.0             # layer 1, page 2, both heads, row 1
    np.testing.assert_array_equal(got, want)


def _latent_case(rng, *, H=2, nope=8, rot=4, C=16, dv=8, page_len=8,
                 pad=4, keys=30, page_ids=(5, 2, 7, 1, 0, 0, 0, 0)):
    """Weights, ``keys`` cached rows on the first pages of ``page_ids``
    (out of order in the pool, ``pad`` lanes of zeros after the rope's),
    and what lies past them in the last page LARGE: a key the masks let
    through by mistake shows."""
    ap = {"k_b_w": jnp.asarray(rng.normal(size=(H, nope, C)), jnp.float32),
          "v_b_w": jnp.asarray(rng.normal(size=(H, C, dv)), jnp.float32)}
    width = walked.whole_tiles(C + rot) + pad
    c_kv = jnp.asarray(rng.normal(size=(keys, C)), jnp.float32)
    k_rope = jnp.asarray(rng.normal(size=(keys, rot)), jnp.float32)
    rows = np.asarray(walked.latent_rows(c_kv, k_rope, width))
    assert not rows[:, C + rot:].any()
    page_ids = np.asarray(page_ids, np.int32)
    used = -(-keys // page_len)
    padded = np.full((used * page_len, width), 50.0, np.float32)
    padded[:keys] = rows
    pool = np.zeros((page_ids.max() + 2, page_len, width), np.float32)
    pool[page_ids[:used]] = padded.reshape(used, page_len, width)
    return ap, c_kv, k_rope, jnp.asarray(pool), page_ids


def _dense_latent_attention(ap, q_nope, q_rope, c_kv, k_rope, ok, scale):
    """The float32 formula over the cached rows; ``ok`` [Tq, keys]; a
    query with no key gives zeros."""
    k_nope, v = walked.expand_latents(ap, c_kv, jnp.float32)
    s = (jnp.einsum("htn,hkn->htk", q_nope, k_nope)
         + jnp.einsum("htr,kr->htk", q_rope, k_rope)) * scale
    p = np.asarray(jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))) * ok[None]
    total = p.sum(-1, keepdims=True)
    return np.einsum("htk,hkv->htv", p / np.where(total == 0, 1, total), v)


def _with_blocks(monkeypatch, block_q, block_k):
    """``walked.latent_context_attention`` through the kernel at these
    block sizes (its own are a chip's: one block at the tests' sizes)."""
    import functools
    from deepspeed_tpu.ops.pallas import context_attention
    monkeypatch.setattr(
        context_attention, "latent_context_attention", functools.partial(
            context_attention.latent_context_attention, block_q=block_q,
            block_k=block_k))


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "allowed"])
def test_latent_context_attention_reads_the_pages_under_a_mask(masked,
                                                               monkeypatch):
    """Queries at positions 20..29 over 30 cached rows on pages out of
    order, two key blocks of two pages: the kernel's blocked online
    softmax is the dense one; ``allowed`` (learned sparse attention's
    picks) takes keys out beside the causal rule, and all-true is None.
    The context ends inside the second key block, what the page holds
    after it is never seen."""
    rng = np.random.default_rng(0)
    C, Tq = 16, 10
    assert (walked.whole_tiles(C + 4), walked.whole_tiles(576),
            walked.whole_tiles(128)) == (20, 640, 128)
    ap, c_kv, k_rope, pool, page_ids = _latent_case(rng)
    assert pool.shape == (9, 8, 24)
    q_nope = jnp.asarray(rng.normal(size=(2, Tq, 8)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(2, Tq, 4)), jnp.float32)
    abs_pos = 20 + jnp.arange(Tq, dtype=jnp.int32)
    allowed = rng.random((Tq, 64)) < 0.6
    allowed[np.arange(Tq), 20 + np.arange(Tq)] = True     # its own key

    def attend(mask):
        return np.asarray(walked.latent_context_attention(
            ap, q_nope, q_rope, pool, page_ids, abs_pos, jnp.int32(30),
            kv_rank=C, sm_scale=0.3,
            allowed=None if mask is None else jnp.asarray(mask)))

    whole = attend(allowed if masked else None)          # one block of all
    _with_blocks(monkeypatch, 32, 16)
    got = attend(allowed if masked else None)
    ok = np.arange(30)[None, :] <= np.asarray(abs_pos)[:, None]
    if masked:
        ok &= allowed[:, :30]
    want = _dense_latent_attention(ap, q_nope, q_rope, c_kv, k_rope, ok, 0.3)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(whole, got, atol=1e-5)
    if not masked:
        np.testing.assert_array_equal(attend(np.ones((Tq, 64), bool)), got)


def test_latent_context_attention_gives_zeros_to_a_row_that_sees_no_key(
        monkeypatch):
    """A query whose mask names no key under the causal rule, one at a
    position below 0 (a bucket's padding row) and one whose only allowed
    keys lie past the context: exact zeros, the rows beside them whole."""
    rng = np.random.default_rng(1)
    C, Tq = 16, 6
    ap, c_kv, k_rope, pool, page_ids = _latent_case(rng)
    q_nope = jnp.asarray(rng.normal(size=(2, Tq, 8)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(2, Tq, 4)), jnp.float32)
    abs_pos = np.array([24, 25, -1, 27, 28, 31], np.int32)
    allowed = rng.random((Tq, 64)) < 0.5
    allowed[0, :] = False
    allowed[1, :26] = False             # only keys after its own
    allowed[5, :30] = False             # only keys past the context
    allowed[5, 30:] = True
    _with_blocks(monkeypatch, 32, 16)
    got = np.asarray(walked.latent_context_attention(
        ap, q_nope, q_rope, pool, page_ids, jnp.asarray(abs_pos),
        jnp.int32(30), kv_rank=C, sm_scale=0.3, allowed=jnp.asarray(allowed)))
    assert not got[:, [0, 1, 2, 5]].any()
    ok = (np.arange(30)[None, :] <= abs_pos[:, None]) & allowed[:, :30]
    want = _dense_latent_attention(ap, q_nope, q_rope, c_kv, k_rope, ok, 0.3)
    assert np.abs(want[:, [3, 4]]).min() > 0
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and the same rows counted: the pairs the masks let through a head
    assert float(walked.latent_context_pairs(
        jnp.asarray(abs_pos), jnp.int32(30), jnp.asarray(allowed))) \
        == ok.sum()
    assert float(walked.latent_context_pairs(
        jnp.asarray(abs_pos), jnp.int32(30))) \
        == np.clip(np.minimum(abs_pos + 1, 30), 0, None).sum()


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "allowed"])
def test_latent_context_attention_skips_the_blocks_above_the_diagonal(
        masked, monkeypatch):
    """A whole prompt of 64 from position 0, two query blocks of 32 over
    four key blocks of 16 on pages out of order: the first query block
    never reaches key blocks 2 and 3 (its running max and sum stay what
    they were), every row is the dense one."""
    rng = np.random.default_rng(2)
    C, Tq = 16, 64
    ap, c_kv, k_rope, pool, page_ids = _latent_case(
        rng, keys=64, page_ids=(6, 1, 8, 3, 5, 2, 7, 4))
    q_nope = jnp.asarray(rng.normal(size=(2, Tq, 8)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(2, Tq, 4)), jnp.float32)
    abs_pos = jnp.arange(Tq, dtype=jnp.int32)
    ok = np.tril(np.ones((Tq, Tq), bool))
    allowed = None
    if masked:
        allowed = rng.random((Tq, 64)) < 0.3
        allowed[np.arange(Tq), np.arange(Tq)] = True
        ok &= allowed
        allowed = jnp.asarray(allowed)
    _with_blocks(monkeypatch, 32, 16)
    got = np.asarray(walked.latent_context_attention(
        ap, q_nope, q_rope, pool, page_ids, abs_pos, jnp.int32(Tq),
        kv_rank=C, sm_scale=0.3, allowed=allowed))
    want = _dense_latent_attention(ap, q_nope, q_rope, c_kv, k_rope, ok, 0.3)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_latent_context_attention_at_axk1s_widths_without_a_mask(
        monkeypatch):
    """A.X-K1's widths (heads of 128 + 64 against rows 640 wide of rank
    512, values 128 wide; the keys' 192 lanes padded to 256), four heads in
    groups the VMEM budget makes two of, bfloat16, no mask: the kernel is
    its module's own dense reference, and the float32 formula to
    bfloat16's rounding."""
    from deepspeed_tpu.ops.pallas import context_attention
    rng = np.random.default_rng(3)
    H, nope, rot, C, dv, Tq, keys = 4, 128, 64, 512, 128, 24, 40
    ap, c_kv, k_rope, pool, page_ids = _latent_case(
        rng, H=H, nope=nope, rot=rot, C=C, dv=dv, pad=0, keys=keys)
    bf = jnp.bfloat16
    ap = {k: (v * 0.05).astype(bf) for k, v in ap.items()}
    pool = pool.astype(bf)
    assert pool.shape[-1] == 640
    q_nope = jnp.asarray(rng.normal(size=(H, Tq, nope)), bf)
    q_rope = jnp.asarray(rng.normal(size=(H, Tq, rot)), bf)
    abs_pos = 16 + jnp.arange(Tq, dtype=jnp.int32)
    seen = {}
    kernel = context_attention.latent_context_attention

    def spy(q, k_w, v_w, *args, **kw):
        seen.update(q=q, k_w=k_w, v_w=v_w, args=args, kw=kw)
        return kernel(q, k_w, v_w, *args, block_q=32, block_k=16, **kw)

    monkeypatch.setattr(context_attention, "latent_context_attention", spy)
    monkeypatch.setattr(context_attention, "CONTEXT_VMEM_BUDGET", 3 << 20)
    shape = (32, 256, dv, 640, C, 16, 2, False)
    assert context_attention.context_heads_per_step(H, 3 << 20, *shape) == 2
    got = walked.latent_context_attention(
        ap, q_nope, q_rope, pool, page_ids, abs_pos, jnp.int32(keys),
        kv_rank=C, sm_scale=0.07)
    assert seen["q"].shape == (H, Tq, 256) and seen["k_w"].shape \
        == (H, 640, 256)
    want = context_attention.latent_context_reference(
        seen["q"], seen["k_w"], seen["v_w"], *seen["args"], **seen["kw"])
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    ok = np.arange(keys)[None, :] <= np.asarray(abs_pos)[:, None]
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    dense = _dense_latent_attention(
        {k: f32(v) for k, v in ap.items()}, f32(q_nope), f32(q_rope),
        f32(c_kv.astype(bf)), f32(k_rope.astype(bf)), ok, 0.07)
    np.testing.assert_allclose(np.asarray(got, np.float32), dense, atol=3e-2)


# -- the form a leaf rests in (PR 55) ----------------------------------------

#: family -> (its cell's configuration file, its classes' prefix, the leaves
#: it declares: kind -> name; A.X-K1 declares none, ``models/axk1.py`` says
#: why)
LAYOUT_FAMILIES = {
    "mimo_v2": ("mimo-v2.5", "MimoV2", {"full": "q_w", "window": "q_w"}),
    "axk1": ("a.x-k1", "AxK1", {}),
    "cohere2_moe": ("command-a-plus-05-2026", "Cohere2Moe",
                    {"full": "q_w", "window": "q_w"}),
    "glm_dsa": ("glm-5.2", "GlmDsa", {"attn": "q_b_w"}),
    "kimi_linear": ("kimi-linear-48b-a3b", "KimiLinear", {"mla": "q_w"})}


def _toy(family):
    """The family at its cell's rehearsal sizes (``benchmark/configs``):
    (model, the rehearsal's ``serving`` block)."""
    name, prefix, _ = LAYOUT_FAMILIES[family]
    with open(os.path.join(os.path.dirname(MODELS), "..", "benchmark",
                           "configs", name + ".json")) as f:
        file = json.load(f)
    module = importlib.import_module("deepspeed_tpu.models." + family)
    config = getattr(module, prefix + "Config")
    fields = {f.name for f in dataclasses.fields(config)}
    sizes = {k: v for k, v in file.items() if k in fields}
    sizes.update(file["rehearse"]["sizes"])
    sizes = {k: tuple(v) if isinstance(v, list) else v
             for k, v in sizes.items()}
    return (getattr(module, prefix + "Model")(config(**sizes)),
            dict(file["rehearse"]["serving"], prefix_cache=False))


def test_project_heads_reads_a_projection_as_either_side_holds_it():
    """``w`` as a caller holds it and ``OutputMajor.of(w)`` as an engine
    does give the same heads; the form is a pytree node around ``w.T``."""
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(2, 3, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    held = walked.OutputMajor.of(w)
    assert [x.shape for x in jax.tree.leaves(held)] == [(12, 8)]
    got = jax.jit(walked.project_heads, static_argnums=2)(h, held, 4)
    assert got.shape == (2, 4, 3, 3)
    np.testing.assert_allclose(got, walked.project_heads(h, w, 4),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("family", sorted(LAYOUT_FAMILIES))
def test_serving_layouts_names_the_query_projections_and_nothing_else(family):
    model, _ = _toy(family)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layouts = model.serving_layouts(params)
    assert jax.tree.structure(layouts, is_leaf=lambda x: x is None) \
        == jax.tree.structure(params)
    declared = {jax.tree_util.keystr(path): layout for path, layout
                in jax.tree_util.tree_flatten_with_path(layouts)[0]}
    want = {f"['{kind}']['{name}'][{i}]"
            for kind, name in LAYOUT_FAMILIES[family][2].items()
            for i in range(len(params[kind][name]))}
    assert set(declared) == want
    assert all(form is walked.OutputMajor for form in declared.values())
    # the rule knows no shape and no name of a model: a family that names
    # no projection declares nothing
    assert type(model).query_projections == tuple(
        sorted(set(LAYOUT_FAMILIES[family][2].values())))


@pytest.mark.parametrize("family", sorted(LAYOUT_FAMILIES))
def test_an_engine_on_relaid_leaves_emits_the_same_tokens(family):
    """A prefill (in chunks where the cell's rehearsal has them) and 8
    greedy ticks of three requests: the engine that holds the declared
    leaves output-major against one whose model declares nothing; the tree
    the caller handed over holds the same arrays afterwards, none
    wrapped."""
    from deepspeed_tpu.inference import ServeEngine
    model, serving = _toy(family)
    params = model.init(jax.random.PRNGKey(3))
    before, structure = jax.tree.flatten(params)
    plain = type("Undeclared", (type(model),), {"query_projections": ()})(
        model.config)

    def streams(m):
        eng = ServeEngine(m, {"serving": serving}, params=params)
        try:
            reqs = [eng.submit(list(range(5 + i, 27 + 3 * i)),
                               max_new_tokens=8) for i in range(3)]
            eng.run_until_idle()
            assert eng._decode_fn._cache_size() == 1
            return ([r.result() for r in reqs], eng.params_relaid_leaves,
                    eng.params_relaid_bytes, eng.params)
        finally:
            eng.close()

    tokens, leaves, nbytes, held = streams(model)
    declared = jax.tree.leaves(model.serving_layouts(params),
                               is_leaf=lambda form: form is None)
    queries = [x for x, form in zip(before, declared) if form]
    assert leaves == len(queries) and nbytes == sum(
        x.nbytes for x in queries)
    assert bool(queries) == bool(LAYOUT_FAMILIES[family][2])
    # the engine's leaf is the caller's transposed, a new array; every
    # other leaf has the caller's shape
    for x, mine, form in zip(before, jax.tree.leaves(held), declared):
        assert mine.shape == (x.shape[::-1] if form else x.shape)
        if form:
            np.testing.assert_array_equal(mine, x.T)
    for kind, name in LAYOUT_FAMILIES[family][2].items():
        assert all(isinstance(leaf, walked.OutputMajor)
                   for leaf in held[kind][name])
    assert streams(plain)[:3] == (tokens, 0, 0)
    after, unchanged = jax.tree.flatten(params)
    assert unchanged == structure and all(
        a is b for a, b in zip(before, after))
