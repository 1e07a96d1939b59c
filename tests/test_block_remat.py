"""What ``remat="block"`` keeps across its boundary (PR 46;
``runtime/activation_checkpointing/block_remat.py``): the saved flash
output and log-sum-exp change no number, the budget rule keeps them where
the device holds them, and the engine reports its choice.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.bert import BertConfig, BertModel
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.ops.pallas.flash_attention import FLASH_LSE, FLASH_OUT
from deepspeed_tpu.ops.pallas.runtime import interpret_scope
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.activation_checkpointing.block_remat import (
    HOLD_BACK, SAVED, RematBudget, activation_room, checkpoint_block,
    remat_budget_scope, saved_bytes)

LAYERS, ROWS, SEQ = 2, 2, 32
KEY = jax.random.PRNGKey(3)
BERT = BertConfig(vocab_size=128, hidden_size=64, num_hidden_layers=LAYERS,
                  num_attention_heads=4, intermediate_size=128,
                  max_position_embeddings=64)
GPT2 = GPT2Config(vocab_size=128, n_positions=64, d_model=64, n_layer=LAYERS,
                  n_head=4, dropout=0.1, embd_dropout=0.1)
# a budget that is all room, and one that is none
ROOMY = RematBudget(bytes_limit=10 ** 12, resident_bytes=0)
TIGHT = RematBudget(bytes_limit=10 ** 4, resident_bytes=0)


def _model(name, **replace):
    if name == "bert":
        return BertModel(dataclasses.replace(BERT, **replace))
    return GPT2Model(dataclasses.replace(GPT2, **replace))


def _batch(name):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (ROWS, SEQ + 1)).astype(np.int32)
    if name == "gpt2":
        return ids
    labelled = rng.random((ROWS, SEQ)) < 0.3
    return {"input_ids": ids[:, :SEQ],
            "masked_lm_labels": np.where(labelled, ids[:, 1:], -100
                                         ).astype(np.int32),
            "next_sentence_label": np.array([0, 1], np.int32)}


@functools.lru_cache(maxsize=None)
def _params(name):
    return _model(name).init(jax.random.PRNGKey(0))


def _loss_and_grads(name, budget=None, jit=False, **replace):
    """Loss and gradients with dropout on and the flash kernel
    interpreted, traced under ``budget`` (None: the bare checkpoint).
    Not jitted, every primitive runs as its own program, so two runs that
    apply the same operations to the same values agree to the bit
    whatever is saved between them."""
    model, batch = _model(name, **replace), _batch(name)
    fn = jax.value_and_grad(lambda p: model.loss_fn(p, batch, KEY, True))
    with interpret_scope(True), remat_budget_scope(budget):
        return (jax.jit(fn) if jit else fn)(_params(name))


@functools.lru_cache(maxsize=None)
def _unrolled_baselines(name):
    return (_loss_and_grads(name, scan_layers=False),
            _loss_and_grads(name, scan_layers=False, remat=None))


def _flash_forwards(name, budget):
    """``ds_flash_fwd`` calls of the scanned loss-and-gradient program, a
    scan's body times its length."""
    model, batch = _model(name), _batch(name)
    calls = 0

    def walk(jaxpr, times):
        nonlocal calls
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" \
                    and eqn.params["name"] == "ds_flash_fwd":
                calls += times
            inner = eqn.params["length"] if eqn.primitive.name == "scan" \
                else 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, times * inner)

    with interpret_scope(True), remat_budget_scope(budget):
        walk(jax.make_jaxpr(jax.grad(
            lambda p: model.loss_fn(p, batch, KEY, True)))(
                _params(name)).jaxpr, 1)
    return calls


@pytest.mark.parametrize("case", ["kept", "recomputed"])
@pytest.mark.parametrize("name", ["bert", "gpt2"])
def test_saved_flash_results_change_no_bit_of_loss_or_gradient(name, case):
    """With the flash kernel's output and log-sum-exp kept across the
    block's boundary, and with a budget that has no room for them, loss
    and every gradient leaf equal the bare ``remat="block"`` and
    ``remat=None`` exactly (unrolled, a primitive a program); the scanned
    form, whose body the CPU's compiler fuses as it likes, agrees to a
    rounding, and runs the forward kernel once a layer when its results
    are kept, twice when they are not."""
    budget = ROOMY if case == "kept" else TIGHT
    loss, grads = _loss_and_grads(name, budget, scan_layers=False)
    for base_loss, base_grads in _unrolled_baselines(name):
        assert float(loss) == float(base_loss)
        jax.tree.map(np.testing.assert_array_equal, grads, base_grads)
    scan_loss, scan_grads = _loss_and_grads(name, budget, jit=True)
    np.testing.assert_allclose(float(scan_loss), float(loss), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=1e-7), scan_grads, grads)
    assert _flash_forwards(name, budget) == \
        LAYERS * (1 if case == "kept" else 2)


def test_no_budget_and_dense_attention_get_the_bare_checkpoint():
    """No budget in scope (no engine, the CPU, an offload tier), a budget
    with no room, and a body whose attention is not the flash kernel all
    get ``jax.checkpoint`` itself."""
    carry = jax.ShapeDtypeStruct((ROWS, SEQ, 64), jnp.bfloat16)
    sizes = dict(trips=LAYERS, heads=4, ffn_width=256, head_width=128)
    assert checkpoint_block(carry, **sizes) is jax.checkpoint
    with remat_budget_scope(TIGHT):
        assert checkpoint_block(carry, **sizes) is jax.checkpoint
    with remat_budget_scope(ROOMY):
        assert checkpoint_block(carry, attn_sites=0, **sizes) \
            is jax.checkpoint
        assert checkpoint_block(carry, **sizes) is not jax.checkpoint


# ---------------------------------------------------------------------------
# the budget rule, at the training cells' shapes and at made-up limits
# ---------------------------------------------------------------------------
V5E = 16_909_336_064    # bytes_limit of a v5e chip (my chip run, PR 46)
# one device's view of a cell: carry, sizes, resident bytes (the allocator's
# count on the chip) and the parameters held where the gradients are placed
CELLS = {
    # BERT-large, 32 x 512 tokens on one chip, nothing sharded
    "bert_large": (
        jax.ShapeDtypeStruct((32, 512, 1024), jnp.bfloat16),
        dict(trips=24, heads=16, ffn_width=4096, head_width=30522,
             head_rows=512),
        4_070_263_296, 336_226_108),
    # GPT-2 XL over four chips, ZeRO-2: 8 x 1,023 tokens a chip
    "gpt2_xl_dp4": (
        jax.ShapeDtypeStruct((8, 1023, 1600), jnp.bfloat16),
        dict(trips=48, heads=25, ffn_width=6400, head_width=50257),
        5_800_000_000, 389_400_000),
}


def _room_and_cost(cell, limit):
    carry, sizes, resident, held = CELLS[cell]
    budget = RematBudget(bytes_limit=limit, resident_bytes=resident,
                         copy_bytes=2 * held, grad_bytes=6 * held)
    cost = sum(saved_bytes(carry, trips=sizes["trips"],
                           heads=sizes["heads"]).values())
    with remat_budget_scope(budget):
        kept = checkpoint_block(carry, **sizes) is not jax.checkpoint
    return activation_room(
        budget, carry, **{k: v for k, v in sizes.items() if k != "heads"}
    ), cost, kept


def test_saved_bytes_are_the_rows_as_they_lie_in_hbm():
    """A head of 64 takes rows of 128 lanes: BERT-large's output stack is
    24 x 512 x 512 x 128 x 2 B (what the chip's compiler allots it), the
    log-sum-exp one float32 a row; GPT-2 XL's 1,023 rows pad to 1,024."""
    carry, sizes, *_ = CELLS["bert_large"]
    assert saved_bytes(carry, trips=24, heads=16) == {
        FLASH_OUT: 24 * 512 * 512 * 128 * 2, FLASH_LSE: 24 * 512 * 512 * 4}
    carry, sizes, *_ = CELLS["gpt2_xl_dp4"]
    assert saved_bytes(carry, trips=48, heads=25) == {
        FLASH_OUT: 48 * 200 * 1023 * 128 * 2, FLASH_LSE: 48 * 200 * 1024 * 4}


@pytest.mark.parametrize("cell,room", [
    # GPT-2's head writes float32 logits for every row: as PR 46 reckoned
    ("gpt2_xl_dp4", 4_092_113_753),
    # BERT's walks its rows 512 at a time (PR 47): two blocks of logits
    # and the decoder's float32 gradient, 0.25 GB, where every row's were
    # 4.0 GB and the room 5,669,800,993; the backward of a body beside
    # the gradient tree (3.09 GB) is now the phase that counts
    ("bert_large", 6_579_282_105)])
def test_the_head_term_is_what_the_head_holds_at_once(cell, room):
    assert _room_and_cost(cell, V5E)[0] == room
    carry, sizes, resident, held = CELLS[cell]
    budget = RematBudget(bytes_limit=V5E, resident_bytes=resident,
                         copy_bytes=2 * held, grad_bytes=6 * held)
    sizes = {k: v for k, v in sizes.items() if k not in ("heads",
                                                         "head_rows")}
    rows, width = carry.shape[0] * carry.shape[1], carry.shape[2]
    # a head that walks all its rows in one block holds the decoder's
    # float32 gradient more than the dense one
    assert activation_room(budget, carry, **sizes) - activation_room(
        budget, carry, head_rows=rows, **sizes) in (
            0, width * sizes["head_width"] * 4)
    # and a block never counts more rows than the device has
    assert activation_room(budget, carry, head_rows=10 * rows, **sizes) \
        == activation_room(budget, carry, head_rows=rows, **sizes)


@pytest.mark.parametrize("cell,limit,kept", [
    ("bert_large", V5E, True), ("gpt2_xl_dp4", V5E, True),
    ("bert_large", int(11.0e9), False), ("gpt2_xl_dp4", int(14.0e9), False),
    ("bert_large", int(32e9), True), ("gpt2_xl_dp4", int(8e9), False)])
def test_the_rule_keeps_the_flash_results_where_the_device_holds_them(
        cell, limit, kept):
    """Both training cells keep them on a v5e with room to spare, not by
    a tie (a gigabyte and more beyond the 10 % held back), and fall back
    to the bare checkpoint on a device a quarter smaller (a third for
    BERT since its head holds a block of rows, PR 47)."""
    room, cost, chose = _room_and_cost(cell, limit)
    assert chose is kept
    assert (room - cost > 1e9) if kept else (room < cost)


# ---------------------------------------------------------------------------
# the engine hands the budget over while it traces the step, and reports
# ---------------------------------------------------------------------------
def _limit(monkeypatch, limit):
    monkeypatch.setattr(
        "deepspeed_tpu.runtime.engine.collect_memory_stats",
        lambda: {"devices": [{"id": d.id, "bytes_limit": limit,
                              "bytes_in_use": 0} for d in jax.devices()],
                 "host_rss_bytes": None})


def _engine(tmp_path, monkeypatch, limit):
    import deepspeed_tpu
    _limit(monkeypatch, limit)
    engine, *_ = deepspeed_tpu.initialize(
        model=_model("gpt2"), mesh=build_mesh(dp=2, devices=jax.devices()[:2]),
        seed=0, config={
            "train_micro_batch_size_per_gpu": ROWS,
            "steps_per_print": 10 ** 9, "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    return engine


@pytest.mark.parametrize("kept", [True, False])
def test_the_engine_sets_the_saved_bytes_gauge_while_it_traces_the_step(
        tmp_path, monkeypatch, kept):
    """``train_remat_saved_bytes{name=}`` reads the bytes one device keeps
    over the whole stack, from the shapes (two devices, ``ROWS`` rows of
    4 heads each, a head of 16 in rows of 128 lanes), and 0 for a name
    that is recomputed; the limit is made up so that the room just holds
    them, or just does not."""
    full = {FLASH_OUT: LAYERS * ROWS * 4 * SEQ * 128 * 2,
            FLASH_LSE: LAYERS * ROWS * 4 * 128 * 4}
    # the step is traced at the first ``train_batch``: read the engine's
    # own budget at a made-up limit, and give it the limit whose room (it
    # is linear in the limit) is the saved bytes, give or take 64
    engine = _engine(tmp_path, monkeypatch, 10 ** 9)
    budget = engine._remat_budget()
    carry = jax.ShapeDtypeStruct((ROWS, SEQ, 64), jnp.bfloat16)
    held = budget.bytes_limit * (1 - HOLD_BACK) - activation_room(
        budget, carry, trips=LAYERS, ffn_width=256, head_width=128)
    _limit(monkeypatch, int((held + sum(full.values())
                             + (64 if kept else -64)) / (1 - HOLD_BACK)))
    try:
        batch = np.concatenate([_batch("gpt2")] * 2)
        loss = float(engine.train_batch(batch))
        gauge = engine.telemetry.registry.gauge("train_remat_saved_bytes")
        assert {n: gauge.value(name=n) for n in SAVED} == {
            n: full[n] if kept else 0 for n in SAVED}
        assert np.isfinite(loss)
    finally:
        engine.close()
