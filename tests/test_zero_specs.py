"""Unit tests for the ZeRO spec helpers (runtime/zero.py).

The sanitize rule is the no-padding contract: an axis assignment survives
only if the leaf dim is divisible by the mesh-axis size; tuple entries are
retained greedily major-to-minor (reference ZeRO likewise pads nothing and
falls back per-tensor, stage2.py partitioning)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.zero import sanitize_base_spec, shard_spec_for_leaf


@pytest.fixture(scope="module")
def mesh():
    # dp=4 × tp=2 on the 8-device CPU mesh
    return build_mesh(dp=4, tp=2)


def test_divisible_entry_kept(mesh):
    assert sanitize_base_spec(P("data", None), (8, 3), mesh) == P("data",
                                                                  None)


def test_indivisible_entry_dropped(mesh):
    assert sanitize_base_spec(P("data", None), (6, 3), mesh) == P(None, None)


def test_tuple_entry_retains_divisible_major_axes(mesh):
    # dim 8 divides dp=4 but not dp*tp=8? 8 % 8 == 0 → full tuple kept
    assert sanitize_base_spec(P(("data", "model"),), (8,), mesh) == P(
        ("data", "model"))
    # dim 4 divides dp=4 but not dp*tp=8 → keep the major 'data' sub-axis
    # instead of replicating the whole dim
    assert sanitize_base_spec(P(("data", "model"),), (4,), mesh) == P(
        ("data",))
    # dim 2: 'data' (4) fails but 'model' (2) divides — the minor axis is
    # retained alone (any divisible sub-axis set is a valid placement;
    # the fallback shards as much as divisibility allows)
    assert sanitize_base_spec(P(("data", "model"),), (2,), mesh) == P(
        "model")
    # nothing divides a prime dim
    assert sanitize_base_spec(P(("data", "model"),), (3,), mesh) == P(None)


def test_rank_mismatch_raises(mesh):
    with pytest.raises(ValueError, match="more entries"):
        sanitize_base_spec(P("data", None, None), (4, 4), mesh)


def test_shard_spec_first_divisible_dim():
    assert shard_spec_for_leaf((3, 8), 4) == P(None, "data")
    assert shard_spec_for_leaf((3, 5), 4) == P(None, None)
    assert shard_spec_for_leaf((4,), 1) == P(None)


def test_shard_spec_respects_base():
    # base consumes 'data' (expert-parallel weights): nothing to add
    assert shard_spec_for_leaf((8, 16), 4, base_spec=P("data")) == P(
        "data", None)
    # base TP spec on dim 1; ZeRO takes dim 0
    assert shard_spec_for_leaf((8, 16), 4, base_spec=P(None, "model")) == P(
        "data", "model")


def test_spec_tree_structure_mismatch_raises(mesh):
    """A model whose param_partition_specs tree disagrees structurally
    with its param tree must ERROR, not silently replicate everything
    (the positional spec-to-leaf matching would mis-assign or drop all
    tensor-parallel placement)."""
    from deepspeed_tpu.runtime.zero import ZeroShardingPlan

    params = {"w": np.zeros((8, 4), np.float32),
              "b": np.zeros((4,), np.float32)}
    bad_specs = {"w": P(None, "model")}  # missing "b"
    with pytest.raises(ValueError, match="does not match"):
        ZeroShardingPlan(stage=2, mesh=mesh, base_param_specs=bad_specs,
                         params=params)
    # an extra key is just as structural a mismatch
    bad_specs2 = {"w": P(None, "model"), "b": P(None), "ghost": P()}
    with pytest.raises(ValueError, match="does not match"):
        ZeroShardingPlan(stage=2, mesh=mesh, base_param_specs=bad_specs2,
                         params=params)
    # the matching tree still works and keeps TP placement
    plan = ZeroShardingPlan(
        stage=2, mesh=mesh,
        base_param_specs={"w": P(None, "model"), "b": P(None)},
        params=params)
    assert plan.master_param_specs(params)["w"] == P("data", "model")


def test_spec_leaf_count_mismatch_raises_at_query(mesh):
    """Plans built WITHOUT params (no construction-time check) must still
    refuse positional matching against a tree with a different leaf
    count at query time."""
    from deepspeed_tpu.runtime.zero import ZeroShardingPlan

    plan = ZeroShardingPlan(
        stage=2, mesh=mesh,
        base_param_specs={"w": P(None, "model")})
    two_leaves = {"w": np.zeros((8, 4), np.float32),
                  "b": np.zeros((4,), np.float32)}
    with pytest.raises(ValueError, match="leaf count"):
        plan.master_param_specs(two_leaves)


# ---------------------------------------------------------------------------
# leaves stacked over a scanned layer axis: dim 0 is never cut
# (TrainModule.stacked_param_spec -> ZeroShardingPlan(stacked=...))
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,base,want", [
    # GPT-2 XL's fc_w at dp 4: off the layer axis, onto the first feature
    # axis the data axis divides
    ((48, 1600, 6400), None, P(None, "data", None)),
    ((48, 1600), None, P(None, "data")),
    # no other divisible dim: replicated, never the layer axis
    ((48, 3), None, P(None, None)),
    ((48,), None, P(None)),
    # the model's own tensor-parallel dim is kept, ZeRO takes the next
    ((48, 1600, 6400), P(None, None, "model"), P(None, "data", "model")),
    ((48, 6400, 1600), P(None, "model", None), P(None, "model", "data")),
    # a base spec that already uses the data axis (expert parallel) stands
    ((48, 8, 64), P(None, "data"), P(None, "data", None)),
])
def test_shard_spec_skips_the_scanned_axis(shape, base, want):
    assert shard_spec_for_leaf(shape, 4, base_spec=base,
                               first_dim=1) == want


def test_plan_reads_the_models_stacked_marks(mesh):
    """One rule for master, gradients and moments; an unmarked leaf, and a
    plan given no marks at all, keep the first-divisible-dim rule."""
    from deepspeed_tpu.runtime.zero import ZeroShardingPlan

    params = {"blocks": {"w": np.zeros((48, 1600, 64), np.float32),
                         "b": np.zeros((48, 3), np.float32)},
              "wte": np.zeros((48, 1600), np.float32)}
    marks = {"blocks": {"w": True, "b": True}, "wte": False}
    plan = ZeroShardingPlan(stage=2, mesh=mesh, params=params, stacked=marks)
    want = {"blocks": {"w": P(None, "data", None), "b": P(None, None)},
            "wte": P("data", None)}
    assert plan.master_param_specs(params) == want
    assert plan.grad_specs(params) == want
    moments = {"mu": params, "nu": params, "count": np.zeros((), np.int32)}
    got = plan.opt_state_specs(moments, params)
    assert got["mu"] == want and got["nu"] == want and got["count"] == P()
    # stage 3 stores the compute copy under the same rule; stage 2 not at all
    assert ZeroShardingPlan(stage=3, mesh=mesh, params=params, stacked=marks
                            ).compute_param_specs(params) == want
    assert plan.compute_param_specs(params)["blocks"]["w"] == P()
    counts, names = plan.placement_summary(params)
    assert counts == {"scanned": 0, "other": 2, "replicated": 1}
    assert names == ["['blocks']['b'][48, 3]"]

    old = ZeroShardingPlan(stage=2, mesh=mesh, params=params)
    assert old.master_param_specs(params)["blocks"]["w"] == P(
        "data", None, None)
    with pytest.raises(ValueError, match="stacked_param_spec"):
        ZeroShardingPlan(stage=2, mesh=mesh, params=params,
                         stacked={"blocks": {"w": True}}
                         ).master_param_specs(params)


def test_placement_summary_names_a_scanned_axis_the_model_itself_cut(mesh):
    """Only a base spec can still put the data axis on a scanned dim 0;
    the summary counts it so that ``zero_sharded_leaves{axis="scanned"}``
    shows it."""
    from deepspeed_tpu.runtime.zero import ZeroShardingPlan

    params = {"w": np.zeros((8, 16), np.float32)}
    plan = ZeroShardingPlan(stage=2, mesh=mesh, params=params,
                            base_param_specs={"w": P("data", None)},
                            stacked={"w": True})
    assert plan.placement_summary(params) == (
        {"scanned": 1, "other": 0, "replicated": 0}, [])
