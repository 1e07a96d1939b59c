"""The training texts, compiled for a described v5e (``tests/chip.py``):
the flash kernel under a mesh of four chips, BERT-large's layer stack and
whole step, and what ``remat="block"`` keeps.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from chip import (BF16, _compile, _program_args, _sds, _unscoped_percent,
                  gpt2_124m)
from deepspeed_tpu.models.gpt2 import GPT2Model
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.runtime import interpret_scope
from deepspeed_tpu.utils.hlo import rng_fusions

SEQ, DH = 1024, 64
GPT2_124M = gpt2_124m()


def _flash_grad_on_four_chips(topo, attend, *, dp=1, sp=1, tp=1):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel import build_mesh
    mesh = build_mesh(dp=dp, sp=sp, tp=tp, devices=topo.devices)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        _compile(jax.grad(loss, argnums=(0, 1, 2)),
                 NamedSharding(mesh, P("data", "model")),
                 *[_sds((16, 12, SEQ, DH))] * 3)


@pytest.mark.parametrize("dp,tp", [(4, 1), (2, 2)], ids=["dp4", "dp2xtp2"])
def test_flash_backward_compiles_on_four_chips(topo, dp, tp):
    """Rows over four chips, as under dp=4 ZeRO or dp x tp: GSPMD refuses
    to partition a bare Mosaic call, so the models call the kernel
    through ``sharded_flash_attention``, inside a shard_map
    (chip_smoke.py --chips 4 runs the dp4 path)."""
    from deepspeed_tpu.parallel.attention import sharded_flash_attention
    _flash_grad_on_four_chips(
        topo, lambda q, k, v: sharded_flash_attention(
            q, k, v, causal=True, interpret=False), dp=dp, tp=tp)


def test_flash_nested_in_a_partial_shard_map_is_still_refused(topo):
    """The known limit, pinned: Ulysses is manual over 'seq' only, and
    with 'data' larger than one its flash call is refused on real chips,
    bare or under a second shard_map over the remaining axes
    (parallel/mesh.py ``_kernel_mesh``).  Sequence parallelism beside
    data parallelism on chips needs the enclosing shard_map to be manual
    over the whole mesh; when that lands this test turns into a compile."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.parallel import ulysses_attention
    seq = P(None, None, "seq", None)

    def attend(q, k, v):
        with interpret_scope(False):
            return jax.shard_map(
                lambda a, b, c: ulysses_attention(a, b, c, causal=True),
                in_specs=(seq, seq, seq), out_specs=seq,
                axis_names={"seq"}, check_vma=False)(q, k, v)

    with pytest.raises(NotImplementedError, match="Mosaic kernels cannot"):
        _flash_grad_on_four_chips(topo, attend, dp=2, sp=2)


@pytest.mark.parametrize("model", ["gpt2", "bert"])
def test_training_flash_calls_are_what_they_were(model):
    """A window, a sink, grouped keys and a second width were added to
    ``ds_flash_fwd`` for the serving prefill.  A training step's three
    flash calls take the operands they took before (6, 9 and 9: no sink
    tile), over the whole causal or bidirectional grid (no band), with
    blocks as wide as the keys, and their kernels are bound with none of
    the new switches."""
    def loss(q, k, v, mask=None):
        return flash_attention(q, k, v, causal=model == "gpt2",
                               key_mask=mask, interpret=False
                               ).astype(jnp.float32).sum()

    shape = (2, 12, SEQ, DH) if model == "gpt2" else (2, 16, 512, DH)
    qkv = [_sds(shape)] * 3
    mask = () if model == "gpt2" else (_sds(shape[::2], jnp.bool_),)
    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))
                           )(*qkv, *mask)
    calls = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.setdefault(eqn.params["name"], eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(calls) == ["ds_flash_bwd_dkv", "ds_flash_bwd_dq",
                             "ds_flash_fwd"]
    bh, blocks = shape[0] * shape[1], shape[2] // 512
    for name, operands in (("ds_flash_fwd", 6), ("ds_flash_bwd_dq", 9),
                           ("ds_flash_bwd_dkv", 9)):
        eqn = calls[name]
        assert len(eqn.invars) == operands, (name, len(eqn.invars))
        assert eqn.params["grid_mapping"].grid == (bh, blocks, blocks)
    fwd = calls["ds_flash_fwd"]
    assert [tuple(v.aval.shape) for v in fwd.outvars][0] == (bh,) + shape[2:]
    text = str(fwd.params["jaxpr"])
    assert "window" not in text and "sink" not in text



# ---------------------------------------------------------------------------
# BERT-large: hidden dropout's masks in the layer stack (PR 43).  Threefry
# fused into the output projections cost more than the matmuls; the masks
# now come from a counter hash (ops/dropout.py).
# ---------------------------------------------------------------------------
BERT_STACK_ROWS = 8     # x 512: the cell's layers, a quarter of its batch
_FUSION = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) fusion\(.*"
                     r'op_name="([^"]*)".*"estimated_cycles":"(\d+)"')


def _bert_layer_stack(one_chip, hidden_dropout: float) -> str:
    """The loss-and-gradient program of two scanned BERT-large layers
    under ``remat='block'`` (no embedding, no head), as the chip's
    compiler leaves it."""
    from deepspeed_tpu.models.bert import BertConfig, BertModel
    cfg = BertConfig(vocab_size=30522, hidden_size=1024, num_hidden_layers=2,
                     num_attention_heads=16, intermediate_size=4096,
                     max_position_embeddings=512,
                     hidden_dropout_prob=hidden_dropout)
    model = BertModel(cfg)
    layers = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"]

    def loss(layers, x, key):
        def body(h, xs):
            lp, i = xs
            return model.layer(lp, h, None, jax.random.fold_in(key, i),
                               True), None

        y, _ = jax.lax.scan(jax.checkpoint(body), x,
                            (layers, jnp.arange(cfg.num_hidden_layers)))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    shapes = (jax.tree.map(lambda s: _sds(s.shape), layers),
              _sds((BERT_STACK_ROWS, 512, 1024)), _sds((2,), jnp.uint32))
    with interpret_scope(False):
        return _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
                        *shapes).as_text()


def _ffn_cycles(text: str) -> dict:
    """``estimated_cycles`` of the FFN's forward matmul fusions, by
    (in | out, first | recomputed)."""
    found = {}
    for line in text.splitlines():
        m = _FUSION.match(line)
        if not m or not m.group(2).endswith("layer/mlp/dot_general") \
                or "transpose(jvp())/while/body/closed_call/checkpoint/layer" \
                in m.group(2):
            continue
        side = "in" if f"[{BERT_STACK_ROWS},512,4096]" in m.group(1) else "out"
        run = "recomputed" if "rematted_computation" in m.group(2) else "first"
        assert (side, run) not in found, line[:200]
        found[side, run] = int(m.group(3))
    return found


@pytest.fixture(scope="module")
def bert_stacks(one_chip):
    return {rate: _bert_layer_stack(one_chip, rate) for rate in (0.1, 0.0)}


def test_rng_fusions_reads_a_threefry_draw_in_the_chips_text(one_chip):
    def drawn(x, key):
        keep = jax.random.bernoulli(key, 0.9, x.shape)
        return jnp.where(keep, x / 0.9, 0.0).astype(x.dtype)

    args = _program_args((_sds((BERT_STACK_ROWS, 512, 1024)),
                          _sds((2,), jnp.uint32)), one_chip)
    found = rng_fusions(jax.jit(drawn).lower(*args).compile().as_text())
    assert [(f.elements, f.times) for f in found] == [
        (BERT_STACK_ROWS * 512 * 1024, 1)]
    assert found[0].cycles > 100_000      # 153,776 when this was written


def test_bert_layer_stack_draws_no_random_bits_an_element(bert_stacks):
    """The parent's stack held nine such fusions at this shape (threefry
    in the attention-output and FFN-out matmuls, first and recomputed, and
    in five fusions of the backward); the whole step's text held five."""
    assert rng_fusions(bert_stacks[0.1]) == []
    assert rng_fusions(bert_stacks[0.0]) == []


@pytest.mark.parametrize("run", ["first", "recomputed"])
def test_hashed_dropout_costs_the_ffn_out_matmul_next_to_nothing(bert_stacks,
                                                                 run):
    """The compiler's own estimate of the FFN-out fusion (matmul, bias,
    dropout, residual, LayerNorm's sums): with the hash it is within a
    tenth of what it is with no dropout at all (411,960 against 399,028
    first, 410,197 against 394,932 recomputed, when this was written; the
    parent's threefry made it 538,709), and under 1.6 x the FFN-in
    fusion's, which has the same FLOPs and an ``erf`` epilogue (1.49 and
    1.47; with no dropout 1.29 and 1.42, so the 1.3 x the issue asked
    for is not the draw's to give)."""
    hashed, none = (_ffn_cycles(bert_stacks[r]) for r in (0.1, 0.0))
    assert sorted(hashed) == sorted(none) == [
        ("in", "first"), ("in", "recomputed"),
        ("out", "first"), ("out", "recomputed")]
    assert hashed["out", run] <= 1.1 * none["out", run], (hashed, none)
    assert hashed["out", run] <= 1.6 * hashed["in", run], hashed


# ---------------------------------------------------------------------------
# What remat="block" keeps (PR 46): with the flash kernel's output and
# log-sum-exp saved across the block's boundary the recomputed forward's
# kernel has no consumer, and the chip's program runs it once a layer.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def bert_large_step(one_chip):
    """BERT-large's loss-and-gradient program at bf16 weights, as the
    chip's compiler leaves it, traced with a budget that is all room (the
    flash results kept)."""
    from deepspeed_tpu.models.bert import BERT_LARGE, BertModel
    from deepspeed_tpu.runtime.activation_checkpointing.block_remat import (
        RematBudget, remat_budget_scope)
    model = BertModel(BERT_LARGE)
    params = jax.tree.map(lambda s: _sds(s.shape),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = {"input_ids": _sds((BERT_STACK_ROWS, 512), jnp.int32),
             "masked_lm_labels": _sds((BERT_STACK_ROWS, 512), jnp.int32),
             "next_sentence_label": _sds((BERT_STACK_ROWS,), jnp.int32)}
    with interpret_scope(False), remat_budget_scope(
            RematBudget(bytes_limit=10 ** 12, resident_bytes=0)):
        return _compile(jax.value_and_grad(model.loss_fn), one_chip, params,
                        batch, _sds((2,), jnp.uint32)).as_text()


def test_the_step_runs_the_flash_forward_once_a_layer_when_its_output_is_kept(
        bert_large_step):
    """``utils/hlo.py::kernel_calls`` on the chip's text, loops counted:
    BERT-large's step holds 24 ``ds_flash_fwd`` calls (48 under the bare
    checkpoint, the parent's: the test below; GPT-2's, on four chips:
    the last test), the backward kernels run once a layer, and no fusion
    draws random bits an element."""
    from deepspeed_tpu.utils.hlo import kernel_calls
    assert kernel_calls(bert_large_step) == {
        "ds_flash_fwd": 24, "ds_flash_bwd_dq": 24, "ds_flash_bwd_dkv": 24}
    assert rng_fusions(bert_large_step) == []


def test_the_step_holds_the_logits_of_one_block_of_labelled_rows(
        bert_large_step):
    """PR 47, on the chip's text: no array of every row's 30,522 logits
    (the parent's text names ``[rows, 512, 30522]`` in float32 and bf16), and
    the decoder's three matmuls lie in a ``while`` whose trip count the
    text does not state: the label count decides how often they run."""
    from deepspeed_tpu.ops.mlm_head import HEAD_BLOCK_ROWS
    from deepspeed_tpu.utils.hlo import _arrays, matmuls
    held = {dims for _, dims in _arrays(bert_large_step)}
    assert not {(BERT_STACK_ROWS, 512, 30522),
                (BERT_STACK_ROWS * 512, 30522)} & held
    decoder = [m for m in matmuls(bert_large_step)
               if any(30522 in dims for _, dims in m.shapes)]
    assert sorted(dims for m in decoder for _, dims in m.shapes) == [
        (HEAD_BLOCK_ROWS, 30522), (30522, 1024)], decoder
    assert all(m.at_run_time for m in decoder)
    walked = [m for m in matmuls(bert_large_step) if m.at_run_time]
    assert (HEAD_BLOCK_ROWS, 1024) in {
        dims for m in walked for _, dims in m.shapes}       # dlogits @ E


def test_the_bare_checkpoint_runs_the_flash_forward_twice_a_layer(
        bert_stacks):
    """What the parent's step did, and what a step still does where no
    budget is handed over: the two scanned layers of ``bert_stacks`` run
    ``ds_flash_fwd`` four times, the backward kernels twice."""
    from deepspeed_tpu.utils.hlo import kernel_calls
    assert kernel_calls(bert_stacks[0.1]) == {
        "ds_flash_fwd": 4, "ds_flash_bwd_dq": 2, "ds_flash_bwd_dkv": 2}


def test_saved_flash_results_stay_with_their_rows_on_four_chips(topo):
    """dp=4, the flash call inside ``sharded_flash_attention``'s manual
    region: the kept output leaves it as the batch is sharded (a device's
    stack holds its own 4 of 16 rows x 12 heads, no more), the forward
    kernel runs once a layer, and the program's collectives are the bare
    checkpoint's, instruction for instruction."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.runtime.activation_checkpointing.block_remat import (
        RematBudget, remat_budget_scope)
    from deepspeed_tpu.utils.hlo import collectives, kernel_calls
    mesh = build_mesh(dp=4, devices=topo.devices)
    layers = 2
    model = GPT2Model(dataclasses.replace(GPT2_124M, n_layer=layers))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16,
                                       sharding=NamedSharding(mesh, P())),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((16, SEQ + 1), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))

    def text(budget):
        with jax.set_mesh(mesh), interpret_scope(False), \
                remat_budget_scope(budget):
            return jax.jit(jax.value_and_grad(model.loss_fn)).lower(
                params, tokens, key).compile().as_text()

    bare = text(None)
    assert kernel_calls(bare)["ds_flash_fwd"] == 2 * layers
    assert f"bf16[{layers},48,{SEQ},{DH}]" not in bare
    kept = text(RematBudget(bytes_limit=10 ** 12, resident_bytes=0))
    assert kernel_calls(kept) == {"ds_flash_fwd": layers,
                                  "ds_flash_bwd_dq": layers,
                                  "ds_flash_bwd_dkv": layers}
    assert f"bf16[{layers},48,{SEQ},{DH}]" in kept      # 4 rows x 12 heads
    assert f"bf16[{layers},192,{SEQ},{DH}]" not in kept
    assert sorted((c.op, c.shapes, c.times) for c in collectives(kept)) == \
        sorted((c.op, c.shapes, c.times) for c in collectives(bare))



def test_the_layer_map_owns_the_steps_estimated_cycles(bert_large_step):
    """At most what PR 54 read, 0.1 %, + 2 points of BERT-large's step
    belong to no scope of the layer map (or to two)."""
    assert _unscoped_percent(bert_large_step, "bert.train") <= 0.1 + 2.0
