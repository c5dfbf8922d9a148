"""ParallelPlan: validation errors, roofline-driven auto_plan selection
(pinning the paper's Table 5/6 preferences), build products, serialization,
and checkpoint plan-mismatch detection.

Multi-device build/step tests live in tests/test_parallel_equiv.py; the
in-process tests here marked ``needs_8_devices`` only run under the tier-1b
pass (scripts/run_tier1.sh sets XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import estimate_block_time
from repro.core.config import af2_initial, af2_finetune, af2_tiny
from repro.parallel.plan import (BuiltPlan, ParallelPlan, PlanError,
                                 auto_plan)
from repro.train import checkpoint as ck

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 fake devices (tier-1b pass)")


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_branch_extent_limited_to_two():
    with pytest.raises(PlanError, match="exactly two dependency-free"):
        ParallelPlan(branch=3).validate()


def test_bp_requires_parallel_variant():
    with pytest.raises(PlanError, match="parallel"):
        ParallelPlan(branch=2, variant="af2").validate()
    # variant can also come from the config
    with pytest.raises(PlanError, match="parallel"):
        ParallelPlan(branch=2).validate(af2_tiny(variant="multimer"))
    ParallelPlan(branch=2).validate(af2_tiny(variant="parallel"))


def test_dap_divisibility_checked_against_all_stacks():
    cfg = af2_tiny()  # n_seq=8, n_extra_seq=12, n_res=16
    with pytest.raises(PlanError, match="n_seq"):
        ParallelPlan(dap=3).validate(cfg)          # 3 divides 12 but not 8
    with pytest.raises(PlanError, match="n_extra_seq"):
        ParallelPlan(dap=8).validate(cfg)          # 8 divides 8/16 but not 12
    ParallelPlan(dap=2).validate(cfg)


def test_compress_requires_pod_axis():
    with pytest.raises(PlanError, match="pod=1"):
        ParallelPlan(compress_pod_grads=True).validate()
    ParallelPlan(pod=2, data=2, compress_pod_grads=True).validate()


def test_unknown_impl_names_rejected():
    with pytest.raises(PlanError, match="attention_impl"):
        ParallelPlan(attention_impl="flash2").validate()
    with pytest.raises(PlanError, match="remat"):
        ParallelPlan(remat="full").validate()


def test_from_flags_derives_data_extent():
    p = ParallelPlan.from_flags(8, bp=2, dap=2)
    assert (p.data, p.branch, p.dap) == (2, 2, 2)
    with pytest.raises(PlanError, match="divide"):
        ParallelPlan.from_flags(8, bp=2, dap=3)


def test_apply_to_config_sets_both_stacks():
    cfg = af2_tiny(variant="af2")
    plan = ParallelPlan(variant="parallel", attention_impl="reference",
                        remat="none")
    c2 = plan.apply_to(cfg)
    assert c2.evoformer.variant == "parallel"
    assert c2.extra.variant == "parallel"
    assert c2.extra.attention_impl == "reference"
    assert c2.remat == "none"
    # None fields leave the config untouched
    assert ParallelPlan().apply_to(cfg) is cfg


def test_serialization_roundtrip_and_unknown_fields():
    plan = ParallelPlan(pod=2, data=4, branch=2, dap=2, variant="parallel",
                        compress_pod_grads=True)
    assert ParallelPlan.from_dict(plan.to_dict()) == plan
    with pytest.raises(PlanError, match="unknown"):
        ParallelPlan.from_dict({"data": 2, "tensor_parallel": 4})
    # overlap_dap serializes (and hence lands in checkpoint manifests)
    plan = ParallelPlan(data=4, dap=2, overlap_dap=True)
    assert "overlap_dap" in plan.to_dict()
    assert ParallelPlan.from_dict(plan.to_dict()) == plan
    assert "overlap_dap=on" in plan.describe()
    assert "overlap_dap" not in ParallelPlan(data=4, dap=2).describe()


def test_overlap_dap_validation():
    cfg = af2_tiny(variant="parallel")
    ParallelPlan(data=4, dap=2, overlap_dap=True).validate(cfg)
    with pytest.raises(PlanError, match="no DAP collectives"):
        ParallelPlan(data=8, overlap_dap=True).validate(cfg)
    with pytest.raises(PlanError, match="hybrid"):
        ParallelPlan(data=2, branch=2, dap=2, overlap_dap=True).validate(cfg)
    with pytest.raises(PlanError, match="parallel"):
        ParallelPlan(dap=2, variant="af2", overlap_dap=True).validate()
    with pytest.raises(PlanError, match="parallel"):
        ParallelPlan(dap=2, overlap_dap=True).validate(af2_tiny(variant="af2"))


def test_overlap_dap_auto_resolution():
    """overlap_dap=None resolves ON exactly for pure-DAP 'parallel' groups;
    an explicit value always wins."""
    cfg = af2_tiny(variant="parallel")
    assert ParallelPlan(data=4, dap=2).resolve_overlap(cfg) is True
    assert ParallelPlan(data=4, dap=2, overlap_dap=False).resolve_overlap(cfg) is False
    assert ParallelPlan(data=2, branch=2, dap=2).resolve_overlap(cfg) is False
    assert ParallelPlan(data=8).resolve_overlap(cfg) is False
    assert ParallelPlan(data=4, dap=2).resolve_overlap(
        af2_tiny(variant="af2")) is False
    # without a config the variant is unknowable -> stay sync
    assert ParallelPlan(data=4, dap=2).resolve_overlap(None) is False
    # a plan-level variant override makes the config irrelevant
    assert ParallelPlan(data=4, dap=2, variant="parallel").resolve_overlap(
        af2_tiny(variant="af2")) is True


# ---------------------------------------------------------------------------
# auto_plan: the paper's Table 5/6 preferences, pinned
# ---------------------------------------------------------------------------

def test_auto_plan_serial_dp_when_batch_covers_devices():
    p = auto_plan(8, af2_initial(), global_batch=8)
    assert (p.data, p.branch, p.dap) == (8, 1, 1)


def test_auto_plan_prefers_bp_not_dap_at_initial_shapes():
    """Paper Table 5: at initial-training shapes (r=256, s=128) the roofline
    prefers BP over DAP for a forced 2-device group — DAP's collectives and
    lost per-op intensity outweigh its halved FLOPs."""
    cfg = af2_initial()
    p = auto_plan(256, cfg, global_batch=128)
    assert (p.branch, p.dap) == (2, 1), p
    assert estimate_block_time(cfg, bp=2, dap=1) < \
        estimate_block_time(cfg, bp=1, dap=2)


def test_auto_plan_prefers_hybrid_at_finetune_shapes():
    """Paper Table 6, re-derived under the overlap-aware comm model: the
    8-device fine-tuning group (r=384, s=512) still picks the BP x DAP
    hybrid, but the 4-device group shifts to pure overlapped DAP — hiding
    the per-block gathers behind compute beats halving them via BP (the
    long-sequence shift the FastFold duplex schedule predicts).  The paper's
    original sync-schedule preference is pinned with overlap=False."""
    cfg = af2_finetune()
    p4 = auto_plan(512, cfg, global_batch=128)
    assert (p4.branch, p4.dap) == (1, 4), p4
    p8 = auto_plan(1024, cfg, global_batch=128)
    assert (p8.branch, p8.dap) == (2, 4), p8
    # sync schedule (Table 6 as printed): hybrid beats pure DAP at 4 devices
    assert estimate_block_time(cfg, bp=2, dap=2, overlap=False) < \
        estimate_block_time(cfg, bp=1, dap=4, overlap=False)
    # ...and the overlapped pure-DAP beats the hybrid, driving the flip
    # (the hybrid keeps the sync schedule: cond-arm dispatch precludes the
    # shared prefetch carry)
    assert estimate_block_time(cfg, bp=1, dap=4, overlap=True) < \
        estimate_block_time(cfg, bp=2, dap=2, overlap=False)


def test_auto_plan_dap_wins_back_at_finetune_group2():
    """Paper Table 5's flip side: at fine-tuning shapes a 2-device group
    prefers DAP (BP's exchange outweighs its balanced-branch win)."""
    p = auto_plan(256, af2_finetune(), global_batch=128)
    assert (p.branch, p.dap) == (1, 2), p


def test_auto_plan_respects_variant_and_divisibility():
    # serial variant: BP infeasible, group 2 must fall to DAP
    p = auto_plan(16, af2_finetune(variant="af2"), global_batch=8)
    assert (p.branch, p.dap) == (1, 2)
    # no feasible split at all -> actionable error
    with pytest.raises(PlanError, match="no feasible plan"):
        auto_plan(3, af2_tiny(), global_batch=1)


def test_auto_plan_pod_extent():
    p = auto_plan(16, af2_initial(), global_batch=8, pod=2)
    assert p.pod == 2 and p.n_devices == 16
    assert p.pod * p.data <= 8


# ---------------------------------------------------------------------------
# build products
# ---------------------------------------------------------------------------

def test_af2_small_preset_is_really_20m_params():
    """examples/train_af2.py --preset small promises a ~20M-param model
    (it used to silently alias tiny's 83k params)."""
    from repro.core import model as af2
    from repro.core.config import af2_small
    shapes = jax.eval_shape(
        lambda: af2.init_params(jax.random.PRNGKey(0), af2_small()))
    n = sum(int(s.size) for s in jax.tree_util.tree_leaves(shapes))
    assert 18e6 < n < 22e6, f"{n:,} params"


def test_build_serial_single_device():
    built = ParallelPlan().build(jax.devices()[:1], cfg=af2_tiny())
    assert isinstance(built, BuiltPlan)
    assert dict(built.mesh.shape) == {"data": 1}
    assert built.block_fn is None and built.stack_io is None
    assert built.sync_axes == ()


def test_build_device_count_mismatch_is_actionable():
    with pytest.raises(PlanError, match="covers 4 devices"):
        ParallelPlan(data=2, branch=2).build(jax.devices()[:1])


def test_build_rejects_invalid_plan_before_touching_devices():
    with pytest.raises(PlanError, match="exactly two"):
        ParallelPlan(branch=4).build(jax.devices()[:1])


def test_metadata_fingerprint():
    built = ParallelPlan().build(jax.devices()[:1], cfg=af2_tiny())
    meta = built.metadata()
    assert meta["plan"]["data"] == 1
    assert meta["mesh_fingerprint"]["n_devices"] == 1
    assert "axes" in meta["mesh_fingerprint"]


@needs_8_devices
def test_build_hybrid_mesh_axes():
    plan = ParallelPlan(data=2, branch=2, dap=2)
    built = plan.build(jax.devices(), cfg=af2_tiny())
    assert dict(built.mesh.shape) == {"data": 2, "branch": 2, "dap": 2}
    assert built.sync_axes == ("branch", "dap")
    assert built.block_fn is not None and built.stack_io is not None
    assert built.batch_spec == jax.sharding.PartitionSpec("data")


@needs_8_devices
def test_build_refactors_production_model_axis():
    from repro.parallel.mesh_utils import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = ParallelPlan.for_mesh(mesh, branch=2, dap=2)
    built = plan.build(mesh, cfg=af2_tiny())
    assert dict(built.mesh.shape) == {"data": 2, "branch": 2, "dap": 2}
    # bad factorization is refused with the extents in the message
    with pytest.raises(PlanError, match="model"):
        ParallelPlan.for_mesh(mesh, branch=2, dap=4).build(mesh)


# ---------------------------------------------------------------------------
# checkpoint plan metadata
# ---------------------------------------------------------------------------

def _state():
    return {"w": jnp.arange(4.0)}


def test_checkpoint_records_and_accepts_matching_plan(tmp_path):
    built = ParallelPlan().build(jax.devices()[:1], cfg=af2_tiny())
    mgr = ck.CheckpointManager(tmp_path, async_save=False,
                               plan_meta=built.metadata())
    mgr.save(3, _state())
    stored = ck.checkpoint_meta(tmp_path)
    assert stored["plan"] == built.plan.to_dict()
    restored, step = mgr.restore_latest(_state())
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(_state()["w"]))


def test_checkpoint_refuses_mismatched_plan(tmp_path):
    built = ParallelPlan().build(jax.devices()[:1], cfg=af2_tiny())
    ck.CheckpointManager(tmp_path, async_save=False,
                         plan_meta=built.metadata()).save(1, _state())
    other = dict(built.metadata())
    other["plan"] = {**other["plan"], "dap": 4, "branch": 2}
    mgr2 = ck.CheckpointManager(tmp_path, async_save=False, plan_meta=other)
    with pytest.raises(ck.PlanMismatchError, match="dap"):
        mgr2.restore_latest(_state())
    # explicit adapt restores anyway (elastic/mesh-agnostic format)
    restored, step = mgr2.restore_latest(_state(), adapt_plan=True)
    assert step == 1


def test_checkpoint_mesh_fingerprint_mismatch_alone_is_allowed(tmp_path):
    built = ParallelPlan().build(jax.devices()[:1], cfg=af2_tiny())
    ck.CheckpointManager(tmp_path, async_save=False,
                         plan_meta=built.metadata()).save(1, _state())
    grown = dict(built.metadata())
    grown["mesh_fingerprint"] = {**grown["mesh_fingerprint"],
                                 "n_devices": 64, "axes": {"data": 64}}
    mgr = ck.CheckpointManager(tmp_path, async_save=False, plan_meta=grown)
    _, step = mgr.restore_latest(_state())  # elastic restart: no error
    assert step == 1


def test_checkpoint_without_meta_stays_compatible(tmp_path):
    ck.save_checkpoint(tmp_path, 2, _state())   # legacy: no meta
    built = ParallelPlan().build(jax.devices()[:1], cfg=af2_tiny())
    mgr = ck.CheckpointManager(tmp_path, async_save=False,
                               plan_meta=built.metadata())
    _, step = mgr.restore_latest(_state())      # nothing stored -> no check
    assert step == 2
