"""BERT's masked-LM loss from the labelled rows alone (PR 47;
``models/mlm_head.py``): ``BertModel.loss_fn`` against the dense head it
replaced, which is kept here as the plain reference, and what the
compiled program holds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops import mlm_head
from deepspeed_tpu.models.bert import BertConfig, BertModel
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.utils import hlo

ROWS, SEQ, VOCAB, BLOCK = 8, 16, 96, 16
TINY = BertConfig(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=1,
                  num_attention_heads=4, intermediate_size=64,
                  max_position_embeddings=SEQ, hidden_dropout_prob=0.0,
                  attention_probs_dropout_prob=0.0, attn_impl="dense")
KEY = jax.random.PRNGKey(5)


def dense_loss(model, params, batch):
    """The head ``loss_fn`` had: logits for every position, their float32
    ``log_softmax``, times the label mask."""
    mlm_logits, nsp_logits = model.apply(params, batch, KEY, train=False)
    labels = batch["masked_lm_labels"]
    logp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    logp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), -1)
    return loss - jnp.mean(jnp.take_along_axis(
        logp, batch["next_sentence_label"][:, None], -1))


def _labelled(case: str):
    """Which of the ``ROWS x SEQ`` positions carry a label."""
    rng = np.random.default_rng(7)
    flat = np.zeros(ROWS * SEQ, bool)
    if case == "all":
        flat[:] = True
    elif case == "whole_blocks":
        flat[rng.permutation(flat.size)[:2 * BLOCK]] = True
    elif case in ("ragged", "model2"):
        flat[rng.permutation(flat.size)[:2 * BLOCK + 5]] = True
    elif case == "data4":
        # four shards of two rows: the second holds no label, the third
        # more than a block of them, the others a few
        mask = rng.random((ROWS, SEQ)) < 0.15
        mask[2:4] = False
        mask[4:6] = rng.random((2, SEQ)) < 0.8
        return mask
    return flat.reshape(ROWS, SEQ)


def _batch(case: str):
    rng = np.random.default_rng(1)
    ids = rng.integers(0, VOCAB, (ROWS, SEQ)).astype(np.int32)
    return {"input_ids": ids,
            "masked_lm_labels": np.where(_labelled(case), ids, -100
                                         ).astype(np.int32),
            "next_sentence_label": rng.integers(0, 2, (ROWS,)
                                                ).astype(np.int32)}


def _mesh(case: str):
    if case == "data4":
        return build_mesh(dp=4, devices=jax.devices()[:4])
    if case == "model2":
        return build_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    return None


CASES = ["none", "all", "whole_blocks", "ragged", "data4", "model2"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_loss_and_every_gradient_match_the_dense_head(monkeypatch, case,
                                                      dtype):
    """Blocks of 16 rows: one device walks up to eight, a shard of the
    data mesh up to two of its own 32 rows."""
    monkeypatch.setattr(mlm_head, "HEAD_BLOCK_ROWS", BLOCK)
    model = BertModel(TINY)
    master = model.init(jax.random.PRNGKey(0))
    # the reference reads the values the compute dtype holds, in float32
    params = jax.tree.map(lambda x: x.astype(dtype), master)
    exact = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    batch = _batch(case)
    want_loss, want = jax.value_and_grad(
        lambda p: dense_loss(model, p, batch))(exact)

    def run(loss_of):
        """Loss and gradients on the case's mesh, placed as the engine
        places them."""
        fn = jax.jit(jax.value_and_grad(loss_of))
        mesh = _mesh(case)
        if mesh is None:
            return fn(params, batch)
        with jax.set_mesh(mesh):
            placed = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params, model.param_partition_specs(params),
                is_leaf=lambda s: isinstance(s, P))
            rows = jax.tree.map(
                lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))),
                batch)
            return fn(placed, rows)

    loss, grads = run(lambda p, b: model.loss_fn(p, b, KEY, train=False))
    low = dtype == jnp.bfloat16
    np.testing.assert_allclose(float(loss), float(want_loss),
                               rtol=2e-2 if low else 2e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    # in bfloat16 no leaf is further from the exact gradient than the
    # dense head's own rounding puts it on the same mesh (the encoder's
    # share of it is the same program in both)
    dense = run(lambda p, b: dense_loss(model, p, b))[1] if low else want

    def far(got, ref):
        ref = np.asarray(ref)
        return np.abs(np.asarray(got, np.float32) - ref).max() \
            / (np.abs(ref).max() + 1e-6)

    for (path, got), ref, was in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(want), jax.tree.leaves(dense)):
        assert got.dtype == dtype, path
        assert far(got, ref) < 1.5 * far(was, ref) + (1e-2 if low else 2e-5), \
            (jax.tree_util.keystr(path), far(got, ref), far(was, ref))
    if case == "none":
        # no block ran: the head's leaves got an exact zero
        for name in ("mlm_transform_w", "mlm_ln_scale", "mlm_bias"):
            assert not np.asarray(grads[name], np.float32).any(), name


def test_eval_loss_matches_the_dense_head(monkeypatch):
    """``train=False`` without a gradient runs the walk that takes none."""
    monkeypatch.setattr(mlm_head, "HEAD_BLOCK_ROWS", BLOCK)
    model = BertModel(TINY)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch("ragged")
    np.testing.assert_allclose(
        float(model.loss_fn(params, batch, KEY, train=False)),
        float(dense_loss(model, params, batch)), rtol=2e-6)


def test_rows_that_do_not_fill_whole_blocks(monkeypatch):
    """128 rows in blocks of 48: the order is padded past the last row,
    and the padding neither reads nor writes one."""
    monkeypatch.setattr(mlm_head, "HEAD_BLOCK_ROWS", 48)
    model = BertModel(TINY)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch("all")
    want_loss, want = jax.value_and_grad(
        lambda p: dense_loss(model, p, batch))(params)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, KEY, train=False)))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    for name in ("word_embeddings", "mlm_transform_w", "position_embeddings"):
        np.testing.assert_allclose(grads[name], want[name], atol=2e-6)


def test_apply_still_returns_logits_for_every_position():
    model = BertModel(TINY)
    params = model.init(jax.random.PRNGKey(0))
    mlm_logits, nsp_logits = model.apply(params, _batch("ragged"), KEY,
                                         train=False)
    assert mlm_logits.shape == (ROWS, SEQ, VOCAB)
    assert nsp_logits.shape == (ROWS, 2)


# ---------------------------------------------------------------------------
# the compiled program: no [rows, vocab] array, the decoder under a loop
# whose trip count the device decides
# ---------------------------------------------------------------------------
WIDE = dataclasses.replace(TINY, vocab_size=2048, max_position_embeddings=64)
WIDE_ROWS, WIDE_SEQ, WIDE_BLOCK = 8, 64, 32


@pytest.fixture(scope="module")
def compiled():
    model = BertModel(WIDE)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {
        "input_ids": jax.ShapeDtypeStruct((WIDE_ROWS, WIDE_SEQ), jnp.int32),
        "masked_lm_labels": jax.ShapeDtypeStruct((WIDE_ROWS, WIDE_SEQ),
                                                 jnp.int32),
        "next_sentence_label": jax.ShapeDtypeStruct((WIDE_ROWS,), jnp.int32)}
    block = mlm_head.HEAD_BLOCK_ROWS
    mlm_head.HEAD_BLOCK_ROWS = WIDE_BLOCK
    try:
        return jax.jit(jax.value_and_grad(
            lambda p, b: model.loss_fn(p, b, KEY, train=True))).lower(
                params, batch).compile()
    finally:
        mlm_head.HEAD_BLOCK_ROWS = block


def test_the_step_holds_no_logits_of_every_row(compiled):
    rows = WIDE_ROWS * WIDE_SEQ
    assert compiled.memory_analysis().temp_size_in_bytes \
        < rows * WIDE.vocab_size * 4
    held = {dims for dt, dims in hlo._arrays(compiled.as_text())}
    assert (rows, WIDE.vocab_size) not in held
    assert (WIDE_ROWS, WIDE_SEQ, WIDE.vocab_size) not in held
    assert (WIDE_BLOCK, WIDE.vocab_size) in held


def test_the_decoder_runs_in_a_loop_of_a_traced_trip_count(compiled):
    """The skip is control flow the device executes, not a ``select``
    over work done anyway: every matmul that writes or reads a block's
    logits lies in a ``while`` whose count the text does not state."""
    found = hlo.matmuls(compiled.as_text())
    wide = {(WIDE_BLOCK, WIDE.vocab_size),                # the logits
            (WIDE_BLOCK, WIDE.hidden_size),               # dlogits @ E
            (WIDE.vocab_size, WIDE.hidden_size)}          # dlogits^T @ h
    decoder = [m for m in found
               if any(dims == (WIDE_BLOCK, WIDE.vocab_size)
                      or (dims == (WIDE.vocab_size, WIDE.hidden_size)
                          and dt == "f32") for dt, dims in m.shapes)]
    assert len(decoder) >= 2, found
    assert all(m.at_run_time for m in decoder), decoder
    assert wide >= {dims for m in decoder for _, dims in m.shapes}
    # the encoder's matmuls are not under it
    assert any(not m.at_run_time for m in found)


def test_matmuls_tells_a_branch_from_a_select():
    """``lax.cond`` is a ``conditional`` in the text; under ``vmap`` it
    becomes a ``select`` and its matmul runs whatever the predicate."""
    w = jnp.ones((64, 48))

    def one(x, go):
        return jax.lax.cond(go, lambda x: x @ w, lambda x: x[:, :48], x)

    x = jnp.ones((3, 32, 64))
    branch = jax.jit(one).lower(x[0], True).compile().as_text()
    select = jax.jit(jax.vmap(one)).lower(
        x, jnp.array([True, False, True])).compile().as_text()
    assert [m.at_run_time for m in hlo.matmuls(branch)] == [True]
    assert [m.at_run_time for m in hlo.matmuls(select)] == [False]
    scanned = jax.jit(lambda x: jax.lax.scan(
        lambda c, row: (c, row @ w), 0, x)[1]).lower(x).compile().as_text()
    assert [m.at_run_time for m in hlo.matmuls(scanned)] == [False]


# ---------------------------------------------------------------------------
# the gauges: what share of the head's rows were live
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("telemetry", [True, False])
def test_the_engine_counts_the_rows_of_the_head(tmp_path, monkeypatch,
                                                telemetry):
    """Two devices, two micro-batches of four rows of 16 a step: a device
    orders 2 x 2 x 16 rows a step in blocks of 16; the labelled rows are
    counted from the batch the host hands over, per device.  With
    telemetry off nothing is counted, and a batch that is on the device
    already is not pulled back."""
    import deepspeed_tpu
    monkeypatch.setattr(mlm_head, "HEAD_BLOCK_ROWS", BLOCK)
    config = {"train_micro_batch_size_per_gpu": 2,
              "gradient_accumulation_steps": 2,
              "steps_per_print": 10 ** 9,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
    if telemetry:
        config["telemetry"] = {"enabled": True, "output_path": str(tmp_path)}
    engine, *_ = deepspeed_tpu.initialize(
        model=BertModel(TINY), seed=0, config=config,
        mesh=build_mesh(dp=2, devices=jax.devices()[:2]))
    try:
        batch = _batch("ragged")
        assert np.isfinite(float(engine.train_batch(batch)))
        if not telemetry:
            assert engine.telemetry is None
            return
        gauge = engine.telemetry.registry.gauge("train_head_rows")
        labelled = int((batch["masked_lm_labels"] >= 0).sum())
        assert {k: gauge.value(kind=k) for k in ("all", "block", "labelled")
                } == {"all": 64, "block": BLOCK, "labelled": labelled / 2}
        engine.train_batch(jax.tree.map(jnp.asarray, _batch("all")))
        assert gauge.value(kind="labelled") == labelled / 2
        engine.train_batch(_batch("all"))
        assert gauge.value(kind="labelled") == ROWS * SEQ / 2
    finally:
        engine.close()
