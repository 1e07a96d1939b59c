"""ZeRO never shards the layer axis a model scans over (runtime/zero.py).

With the ``data`` axis on dim 0 of the stacked ``[L, ...]`` block leaves,
every iteration of the layer scan all-gathered all L layers to use one
(PERF.md, PR 31).  These tests read the compiled program's own collectives
(bytes and counts; no time is read on the CPU), hold ZeRO-2 at dp=4 to
ZeRO-0's numbers, and show that a checkpoint does not care which axis was
cut."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime import checkpointing
from deepspeed_tpu.utils.hlo import collectives

DP = 4
# L divisible by dp: the layout the old rule cut along the layer axis
CFG = GPT2Config(vocab_size=256, n_positions=32, d_model=64, n_layer=4,
                 n_head=4)


def _engine(stage, dp=DP, bf16=True, seed=0, **over):
    mesh = build_mesh(dp=dp, devices=jax.devices()[:dp])
    config = {"bf16": {"enabled": bf16},
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": stage},
              "train_micro_batch_size_per_gpu": 8 // dp,
              "gradient_accumulation_steps": 1,
              "steps_per_print": 10 ** 9, **over}
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2Model(CFG), mesh=mesh, config=config, seed=seed)
    return engine


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (8, 33)).astype(np.int32)
            for _ in range(n)]


def _step_text(engine):
    placed = engine._shard_batch(_batches(1)[0])
    with engine._pallas_scope():
        return engine._train_step.lower(
            engine.state, placed).compile().as_text()


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_compiled_step_gathers_one_layer_an_iteration(stage):
    """No all-gather yields more than one layer of a stacked leaf (or one
    whole unstacked leaf), the stacked leaves' gathers sit inside the
    layer loops, and a whole step gathers at most 3 x the parameters
    (forward, recomputed forward, slack).  The parent gathered every
    whole stack: inside the loop on the TPU (48 x a step), hoisted out of
    it by the CPU's compiler (the form this test sees and refuses)."""
    engine = _engine(stage)
    found = [c for c in collectives(_step_text(engine))
             if c.op == "all-gather"]
    assert found, "a dp=4 ZeRO step with no all-gather reads nothing"
    params = engine.state.master_params
    layer = max(math.prod(x.shape[1:])
                for x in jax.tree.leaves(params["blocks"]))
    whole = max(x.size for k, v in params.items() if k != "blocks"
                for x in jax.tree.leaves(v))

    # the CPU backend computes bf16 as f32: count elements, not bytes
    def elems(c):
        return sum(math.prod(dims) for _, dims in c.shapes)

    too_big = [c for c in found if elems(c) > max(layer, whole)]
    assert not too_big, f"a whole stack is gathered: {too_big}"
    looped = [c for c in found if c.in_loop]
    sharded = [x for x in jax.tree.leaves(params["blocks"])
               if "data" in x.sharding.spec]
    assert len(looped) >= len(sharded) == 10, \
        "the layers' gathers are not in the layer loops"
    assert all(c.times == CFG.n_layer for c in looped), looped
    n_params = sum(x.size for x in jax.tree.leaves(params))
    gathered = sum(elems(c) * c.times for c in found)
    assert gathered <= 3 * n_params, (gathered, n_params)
    engine.close()


def test_zero2_dp4_matches_zero0_and_restores_at_dp2(tmp_path):
    """Same losses over two steps as the replicated engine and the same
    gradients as the unpartitioned reduction, within the tolerance
    ``verify_gradient_partitioning`` holds them to, and the same
    parameters; the stage-2 dp=4 checkpoint restores into a dp=2 engine,
    which then takes the same third step."""
    batches = _batches(3)
    # eps well above the summation-order noise of a gradient: Adam's
    # first steps are lr * g / (|g| + eps), and at the default 1e-8 an
    # element whose gradient is noise gets a whole lr of either sign
    adam = {"optimizer": {"type": "Adam",
                          "params": {"lr": 1e-3, "eps": 1e-4}}}
    z0, z2 = _engine(0, **adam), _engine(2, **adam)
    for b in batches[:2]:
        l0, l2 = float(z0.train_batch(b)), float(z2.train_batch(b))
        np.testing.assert_allclose(l2, l0, rtol=2e-5, atol=2e-5)
    # parameters: a twentieth of the two steps' travel (2 x lr).  The two
    # programs round a bf16 gradient at different points of its reduction
    # (2^-8 of a gradient), which Adam's division carries into the update;
    # the gradients themselves are held to 2e-5 just below
    for a, b in zip(jax.tree.leaves(z0.state.master_params),
                    jax.tree.leaves(z2.state.master_params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=0, atol=1e-4)
    z2.verify_gradient_partitioning(batch=batches[2], rtol=2e-5, atol=2e-5)
    # off the layer axis, on a feature axis, sharded four ways
    fc_w = z2.state.master_params["blocks"]["fc_w"]
    assert fc_w.sharding.spec == P(None, "data", "model")
    assert fc_w.sharding.shard_shape(fc_w.shape) == (4, 16, 256)

    z2.save_checkpoint(str(tmp_path), tag="dp4")
    half = _engine(2, dp=2, seed=7, **adam)
    path, _ = half.load_checkpoint(str(tmp_path), tag="dp4")
    assert path is not None
    for a, b in zip(jax.tree.leaves(z2.state.master_params),
                    jax.tree.leaves(half.state.master_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(float(half.train_batch(batches[2])),
                               float(z2.train_batch(batches[2])),
                               rtol=2e-5, atol=2e-5)
    for e in (z0, z2, half):
        e.close()


@pytest.mark.parametrize("saved,loaded", [
    (P("data", None, None), P(None, "data", None)),   # the parent's files
    (P(None, "data", None), P("data", None, None)),
    (P(None, None, "data"), P(None, "data", None)),
])
def test_shard_files_merge_whatever_axis_was_cut(saved, loaded, tmp_path,
                                                 monkeypatch):
    """Per-process shard files carry their global index, and the loader
    writes each into its box: a ZeRO checkpoint written with the layer
    axis cut (the parent's layout) loads into the feature-axis layout and
    back.  (One process owns every shard here; the multi-host writer is
    forced by calling no leaf fully addressable.)"""
    mesh = build_mesh(dp=DP, devices=jax.devices()[:DP])
    value = np.arange(8 * 12 * 16, dtype=np.float32).reshape(8, 12, 16)
    tree = {"w": jax.device_put(value, NamedSharding(mesh, saved))}
    monkeypatch.setattr(checkpointing, "_is_fully_addressable",
                        lambda leaf: False)
    checkpointing.save_tree(str(tmp_path), tree)
    assert len(list(tmp_path.glob("leaf_00000.proc0_*.npy"))) == DP
    target = {"w": jax.device_put(jnp.zeros_like(value),
                                  NamedSharding(mesh, loaded))}
    got = checkpointing.load_tree(str(tmp_path), target)["w"]
    np.testing.assert_array_equal(np.asarray(got), value)
    assert got.sharding.spec == loaded


def test_placement_gauge_and_summary(tmp_path):
    """``zero_sharded_leaves{axis=}`` is set once at initialize; a model
    that declares its stacked leaves has none on a scanned axis."""
    engine = _engine(2, telemetry={"enabled": True,
                                   "output_path": str(tmp_path)})
    gauge = engine.telemetry.registry.gauge("zero_sharded_leaves")
    counts, names = engine.zero_plan.placement_summary(
        engine.state.master_params)
    assert gauge.value(axis="scanned") == counts["scanned"] == 0
    assert gauge.value(axis="other") == counts["other"] == 14
    # fc_b [L, 4d] and qkv_b [L, 3, d]: dim 0 is scanned, the other dims
    # are the model axis' or indivisible
    assert gauge.value(axis="replicated") == counts["replicated"] == 2
    assert names == ["['blocks']['fc_b'][4, 256]",
                     "['blocks']['qkv_b'][4, 3, 64]"]
    engine.close()


def _gpt2(**kw):
    return GPT2Model(GPT2Config(vocab_size=64, n_positions=16, d_model=16,
                                n_layer=2, n_head=2, **kw)), "blocks"


def _bert(**kw):
    from deepspeed_tpu.models.bert import BertConfig, BertModel
    return BertModel(BertConfig(
        vocab_size=64, hidden_size=16, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=32,
        max_position_embeddings=16, **kw)), "layers"


def _moe(scan_layers=True, **kw):
    from deepspeed_tpu.models.gpt2_moe import GPT2MoEConfig, GPT2MoEModel
    return GPT2MoEModel(GPT2MoEConfig(
        vocab_size=64, n_positions=16, d_model=16, n_layer=4, n_head=2,
        n_experts=2, scan_groups=scan_layers, **kw)), "attn dense_ffn moe"


@pytest.mark.parametrize("build", [_gpt2, _bert, _moe])
def test_models_declare_what_they_scan_over(build):
    """``stacked_param_spec`` marks exactly the leaves under the stacked
    keys whenever the model scans; ``streaming_param_spec`` is the same
    marks, and only for a model that also fetches its own slices."""
    model, stacked_keys = build()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    marks = model.stacked_param_spec(params)
    assert jax.tree.structure(marks) == jax.tree.structure(params)
    for key, sub in marks.items():
        assert set(jax.tree.leaves(sub)) == {key in stacked_keys.split()}
    assert model.streaming_param_spec(params) is None
    unrolled, _ = build(scan_layers=False)
    assert unrolled.stacked_param_spec(params) is None
    if build is not _bert:          # BERT has no streaming form
        streams, _ = build(stream_scan=True)
        assert streams.streaming_param_spec(params) == marks


def _model_batch(build, rows=8, seq=16):
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 64, (rows, seq + (build is not _bert))).astype(
        np.int32)
    if build is not _bert:
        return ids
    return {"input_ids": ids,
            "masked_lm_labels": np.where(rng.random(ids.shape) < 0.2, ids,
                                         -100).astype(np.int32),
            "next_sentence_label": rng.integers(0, 2, (rows,),
                                                dtype=np.int32)}


@pytest.mark.parametrize("build", [_bert, _moe])
def test_other_scanned_models_shard_off_their_layer_axis(build):
    """BERT's layer scan and the MoE flavor's group scan (several rows of
    a stack a tick: ``keep_leading``) under ZeRO-2 at dp=4: no stacked
    leaf is cut along dim 0, the partitioned gradients are the replicated
    reduction's, and no whole stack is gathered."""
    model, stacked_keys = build(attn_impl="dense") if build is _moe \
        else build()
    mesh = build_mesh(dp=DP, devices=jax.devices()[:DP])
    engine, *_ = deepspeed_tpu.initialize(model=model, mesh=mesh, config={
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1, "steps_per_print": 10 ** 9})
    params = engine.state.master_params
    stacked = [x for k in stacked_keys.split()
               for x in jax.tree.leaves(params[k])]
    assert all(x.sharding.shard_shape(x.shape)[0] == x.shape[0]
               for x in stacked)
    assert engine.zero_plan.placement_summary(params)[0]["scanned"] == 0
    batch = _model_batch(build)
    engine.verify_gradient_partitioning(batch=batch)
    assert np.isfinite(float(engine.train_batch(batch)))
    placed = engine._shard_batch(batch)
    with engine._pallas_scope():
        text = engine._train_step.lower(engine.state,
                                        placed).compile().as_text()
    biggest_row = max(math.prod(x.shape[1:]) for x in stacked)
    whole = max(x.size for k, v in params.items()
                if k not in stacked_keys.split()
                for x in jax.tree.leaves(v))
    rows = 2                # the group scan takes two attention rows a tick
    for c in collectives(text):
        if c.op == "all-gather":
            assert max(math.prod(d) for _, d in c.shapes) <= max(
                rows * biggest_row, whole), c
    engine.close()


# the forms only the TPU's compiler writes (no CPU compile shows them): a
# loop without a stated trip count, and one asynchronous collective
# repeated in its start and done fusions under one channel
_TPU_STYLE = """
HloModule jit_train_step

%cond (p: (s32[], bf16[4,8])) -> pred[] {
  %p = (s32[]{:T(128)}, bf16[4,8]{1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  %n = s32[]{:T(128)} constant(48)
  ROOT %lt = pred[]{:T(512)} compare(%i, %n), direction=LT
}

%async_start (a: bf16[1,8]) -> (bf16[1,8], bf16[4,8]) {
  %a = bf16[1,8]{1,0} parameter(0)
  %all-gather.1 = bf16[4,8]{1,0:T(8,128)(2,1)} all-gather(%a), channel_id=7, replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %cc = (bf16[1,8]{1,0}, bf16[4,8]{1,0}) custom-call(%all-gather.1), custom_call_target="AsyncCollectiveStart"
}

%async_done (a: bf16[1,8]) -> bf16[4,8] {
  %a = bf16[1,8]{1,0} parameter(0)
  %all-gather.2 = bf16[4,8]{1,0:T(8,128)(2,1)} all-gather(%a), channel_id=7, replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %cc = bf16[4,8]{1,0} custom-call(%all-gather.2), custom_call_target="AsyncCollectiveDone"
}

%body (p: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %p = (s32[]{:T(128)}, bf16[4,8]{1,0}) parameter(0)
  %x = bf16[1,8]{1,0} constant({...})
  %f1 = (bf16[1,8]{1,0}, bf16[4,8]{1,0}) fusion(%x), kind=kCustom, calls=%async_start
  %f2 = bf16[4,8]{1,0} fusion(%x), kind=kCustom, calls=%async_done
  %ar = (f32[8]{0}, f32[2,8]{1,0}) all-reduce(%x, %x), channel_id=9, to_apply=%add
  ROOT %t = (s32[]{:T(128)}, bf16[4,8]{1,0}) tuple(%p, %f2)
}

ENTRY %main (a: bf16[4,8]) -> bf16[4,8] {
  %a = bf16[4,8]{1,0} parameter(0)
  %ag = bf16[16,8]{1,0} all-gather(%a), channel_id=3, dimensions={0}
  %w = (s32[]{:T(128)}, bf16[4,8]{1,0}) while(%t0), condition=%cond, body=%body
  ROOT %r = bf16[4,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_collectives_reads_the_tpu_compilers_text():
    from deepspeed_tpu.utils.hlo import Collective, collective_report
    found = collectives(_TPU_STYLE)
    assert found == [
        Collective("all-gather", (("bf16", (16, 8)),), 256, 1, False),
        Collective("all-gather", (("bf16", (4, 8)),), 64, 48, True),
        Collective("all-reduce", (("f32", (8,)), ("f32", (2, 8))), 96, 48,
                   True),
    ]
    report = collective_report(_TPU_STYLE)
    assert "all-gather: 2 instructions, 49 executions" in report
    assert "in a loop x48: all-reduce f32[8], f32[2,8]" in report
    with pytest.raises(ValueError, match="ENTRY"):
        collectives("%lonely (a: f32[]) -> f32[] {\n}\n")


# the forms of a decode tick compiled for a v5e (tests/test_chip_*.py
# read the real ones): a stacked weight cut by a fusion that writes one
# layer to HBM and one to fast memory, a per-layer weight brought to fast
# memory by one copy, a weight written to HBM transposed through a bitcast,
# a weight fused with its matmul, the compiler's own prefetch, and a cache
# that is no weight
_REWRITES = """\
HloModule jit_fn, is_scheduled=true

%fused_slice (p: bf16[2,64,32]) -> bf16[32,64] {
  %p = bf16[2,64,32]{2,1,0} parameter(0)
  ROOT %s = bf16[32,64]{0,1:T(8,128)(2,1)} bitcast(%p)
}

ENTRY %main (w: bf16[2,64,32], q: bf16[64,32], o: bf16[64,32], kv: bf16[64,32]) -> bf16[4,32] {
  %p__stacked__.1 = bf16[2,64,32]{2,1,0:T(8,128)(2,1)} parameter(0), sharding={replicated}
  %p__q_w___0_.1 = bf16[64,32]{1,0:T(8,128)(2,1)} parameter(1), sharding={replicated}
  %p__o_w___0_.1 = bf16[64,32]{1,0:T(8,128)(2,1)} parameter(2)
  %kv.1 = bf16[64,32]{1,0:T(8,128)(2,1)} parameter(3)
  %slice_bitcast_fusion = bf16[32,64]{0,1:T(8,128)(2,1)} fusion(%p__stacked__.1), kind=kLoop, calls=%fused_slice
  %slice_bitcast_fusion.remat = (bf16[32,64]{0,1:T(8,128)(2,1)}, bf16[32,64]{0,1:T(8,128)(2,1)S(1)}) fusion(%p__stacked__.1), kind=kLoop, calls=%fused_slice
  %copy.160 = bf16[64,32]{0,1:T(8,128)(2,1)S(1)} copy(%p__q_w___0_.1), sharding={replicated}
  %copy.161 = bf16[64,32]{0,1:T(8,128)(2,1)} copy(%kv.1)
  %bitcast.9 = bf16[32,64]{0,1:T(8,128)(2,1)} bitcast(%p__o_w___0_.1)
  %copy.99 = bf16[32,64]{1,0:T(8,128)(2,1)} copy(%bitcast.9)
  %copy-start.2 = (bf16[64,32]{1,0:T(8,128)(2,1)S(1)}, bf16[64,32]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%p__o_w___0_.1)
  ROOT %fusion.7 = bf16[4,32]{1,0:T(8,128)(2,1)S(1)} fusion(%copy.160, %p__o_w___0_.1), kind=kOutput, calls=%fused_slice
}
"""


def test_parameter_rewrites_lists_the_weights_a_program_moves():
    from deepspeed_tpu.utils.hlo import Rewrite, parameter_rewrites
    assert parameter_rewrites(_REWRITES, 3) == [
        Rewrite("slice_bitcast_fusion", "fusion", 0, 4096, 4096),
        Rewrite("slice_bitcast_fusion.remat", "fusion", 0, 8192, 4096),
        Rewrite("copy.160", "copy", 1, 4096, 0),
        # a whole weight transposed to HBM: the copy of a bitcast of it
        Rewrite("copy.99", "copy", 2, 4096, 4096),
    ]
    # the matmul fused with ``o_w`` writes 256 B of activations: under an
    # eighth of the weight; a share of 0 lists it too
    assert [r.instruction for r in parameter_rewrites(_REWRITES, 3, 0.0)][-1] \
        == "fusion.7"
    # the cache is a weight only if the caller counts it among them
    assert [r.instruction for r in parameter_rewrites(_REWRITES, 4)
            if r.parameter == 3] == ["copy.161"]
