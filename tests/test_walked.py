"""``deepspeed_tpu/models/walked.py``: what the walked families share.

* no module under ``deepspeed_tpu/models/`` imports a name from a sibling
  family module: a family stands on ``walked``, ``ops`` and ``moe`` alone
  (read from the sources, nothing is imported);
* the two index preludes state once the rule five files relied on: an
  inactive slot and a padded prefill row name page 0, the engine's scratch
  page, and attend over / count as length 0.
"""
import ast
import os

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import walked

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deepspeed_tpu", "models")
FAMILIES = ("olmoe", "nemotron_h", "mimo_v2", "axk1", "cohere2_moe")


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
