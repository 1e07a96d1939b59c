"""``deepspeed_tpu/models/walked.py``: what the walked families share.

* no module under ``deepspeed_tpu/models/`` imports a name from a sibling
  family module: a family stands on ``walked``, ``ops`` and ``moe`` alone
  (read from the sources, nothing is imported);
* the two index preludes state once the rule five files relied on: an
  inactive slot and a padded prefill row name page 0, the engine's scratch
  page, and attend over / count as length 0;
* latent attention's expanded form over a paged context, which two
  families share: every key under the causal rule, or the keys a mask
  allows beside it.
"""
import ast
import os

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import walked

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deepspeed_tpu", "models")
FAMILIES = ("olmoe", "nemotron_h", "mimo_v2", "axk1", "cohere2_moe",
            "glm_dsa")


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


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "allowed"])
def test_latent_context_attention_reads_the_pages_under_a_mask(masked):
    """Queries at positions 20..29 over 30 cached rows on pages out of
    order, two blocks of pages: the blocked online softmax is the dense
    one; ``allowed`` (learned sparse attention's picks) takes keys out
    beside the causal rule, and all-true is None."""
    rng = np.random.default_rng(0)
    H, nope, rot, C, dv, page_len, Tq = 2, 8, 4, 16, 8, 8, 10
    ap = {"k_b_w": jnp.asarray(rng.normal(size=(H, nope, C)), jnp.float32),
          "v_b_w": jnp.asarray(rng.normal(size=(H, C, dv)), jnp.float32)}
    width = walked.whole_tiles(C + rot)
    assert (width, walked.whole_tiles(576), walked.whole_tiles(128)) \
        == (20, 640, 128)
    c_kv = jnp.asarray(rng.normal(size=(30, C)), jnp.float32)
    k_rope = jnp.asarray(rng.normal(size=(30, rot)), jnp.float32)
    rows = walked.latent_rows(c_kv, k_rope, width + 4)   # 4 lanes of zeros
    assert rows.shape == (30, 24) and not np.asarray(rows[:, 20:]).any()
    page_ids = np.array([5, 2, 7, 1, 0, 0, 0, 0], np.int32)
    pool = np.zeros((9, page_len, 24), np.float32)
    padded = np.concatenate([np.asarray(rows), np.zeros((2, 24))])
    pool[page_ids[:4]] = padded.reshape(4, page_len, 24)
    q_nope = jnp.asarray(rng.normal(size=(H, Tq, nope)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(H, Tq, rot)), jnp.float32)
    abs_pos = 20 + jnp.arange(Tq, dtype=jnp.int32)
    allowed = rng.random((Tq, 64)) < 0.6
    allowed[np.arange(Tq), 20 + np.arange(Tq)] = True     # its own key

    def attend(mask, block):
        walked._CONTEXT_BLOCK, was = block, walked._CONTEXT_BLOCK
        try:
            return np.asarray(walked.latent_context_attention(
                ap, q_nope, q_rope, jnp.asarray(pool), page_ids, abs_pos,
                jnp.int32(30), kv_rank=C, sm_scale=0.3,
                allowed=None if mask is None else jnp.asarray(mask)))
        finally:
            walked._CONTEXT_BLOCK = was

    got = attend(allowed if masked else None, 16)
    k_nope, v = walked.expand_latents(ap, c_kv, jnp.float32)
    s = (jnp.einsum("htn,hkn->htk", q_nope, k_nope)
         + jnp.einsum("htr,kr->htk", q_rope, k_rope)) * 0.3
    ok = np.arange(30)[None, :] <= np.asarray(abs_pos)[:, None]
    if masked:
        ok &= allowed[:, :30]
    p = np.asarray(jnp.exp(s)) * ok[None]
    want = np.einsum("htk,hkv->htv", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if not masked:
        np.testing.assert_array_equal(
            attend(np.ones((Tq, 64), bool), 16), got)
        np.testing.assert_allclose(attend(None, 32), got, atol=1e-5)
