"""Multi-device numerical equivalence (8 fake XLA host devices, subprocess —
the main pytest process keeps exactly 1 device).

These validate the paper's core claims at the semantics level:
* BP is NOT an approximation — BP=2 == serial, fwd and bwd (Fig. 4);
* DAP == serial for all three Evoformer variants;
* hybrid BP x DAP == serial;
* the full distributed AF2 train step gives identical losses/params under
  DP-only vs BP meshes;
* int8 error-feedback pod-gradient compression stays within tolerance.
"""
import pytest

from tests.util import run_subprocess

pytestmark = pytest.mark.slow


def test_bp_and_dap_stack_equivalence():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.config import af2_tiny
from repro.core import model as af2
from repro.parallel import dap as dap_lib
from repro.parallel.branch import bp_evoformer_block, bp_dap_evoformer_block
from repro.parallel.mesh_utils import make_mesh, smap

cfg = af2_tiny(variant="parallel")
ev = cfg.evoformer
from repro.nn.layers import randomize

params = randomize(af2.stack_init(jax.random.PRNGKey(0), ev, 2, scan=True),
                   jax.random.PRNGKey(7))
s, r = cfg.n_seq, cfg.n_res
msa = jax.random.normal(jax.random.PRNGKey(1), (s, r, ev.c_m))
z = jax.random.normal(jax.random.PRNGKey(2), (r, r, ev.c_z))
ref_msa, ref_z = jax.jit(lambda p, m, zz: af2.evoformer_stack(
    p, ev, 2, m, zz, scan=True, remat=False))(params, msa, z)

# BP=2
mesh = make_mesh((2,), ("branch",))
bp = jax.jit(smap(lambda p, m, zz: af2.evoformer_stack(
    p, ev, 2, m, zz, scan=True, remat=False, block_fn=bp_evoformer_block),
    mesh, (P(), P(), P()), (P(), P())))
bm, bz = bp(params, msa, z)
np.testing.assert_allclose(np.asarray(ref_msa), np.asarray(bm), rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(ref_z), np.asarray(bz), rtol=2e-4, atol=2e-4)
print("BP ok")

# DAP=4 on 'af2' serial variant
ev_af2 = af2_tiny(variant="af2").evoformer
ra, rz = jax.jit(lambda p, m, zz: af2.evoformer_stack(
    p, ev_af2, 2, m, zz, scan=True, remat=False))(params, msa, z)
mesh = make_mesh((4,), ("dap",))
def dap_stack(p, m, zz):
    m_l, z_l = dap_lib.shard_inputs(m, zz)
    m_l, z_l = af2.evoformer_stack(p, ev_af2, 2, m_l, z_l, scan=True,
                                   remat=False,
                                   block_fn=dap_lib.make_dap_block_fn(s))
    return dap_lib.unshard_outputs(m_l, z_l)
dm, dz = jax.jit(smap(dap_stack, mesh, (P(), P(), P()), (P(), P())))(params, msa, z)
np.testing.assert_allclose(np.asarray(ra), np.asarray(dm), rtol=3e-4, atol=3e-4)
np.testing.assert_allclose(np.asarray(rz), np.asarray(dz), rtol=3e-4, atol=3e-4)
print("DAP ok")

# hybrid BP=2 x DAP=2 x data=2, with gradients
mesh = make_mesh((2, 2, 2), ("data", "branch", "dap"))
def hybrid_stack(p, m, zz):
    m_l, z_l = dap_lib.shard_inputs(m, zz)
    def bf(bp_, c, mm, zzz, rng=None, deterministic=True):
        return bp_dap_evoformer_block(bp_, c, mm, zzz, rng=rng,
                                      deterministic=deterministic,
                                      n_seq_total=s)
    m_l, z_l = af2.evoformer_stack(p, ev, 2, m_l, z_l, scan=True, remat=False,
                                   block_fn=bf)
    return dap_lib.unshard_outputs(m_l, z_l)
def loss_h(p):
    m, zz = smap(hybrid_stack, mesh, (P(), P(), P()), (P(), P()))(p, msa, z)
    return jnp.sum(m**2) + jnp.sum(zz**2)
def loss_r(p):
    m, zz = af2.evoformer_stack(p, ev, 2, msa, z, scan=True, remat=False)
    return jnp.sum(m**2) + jnp.sum(zz**2)
gh = jax.jit(jax.grad(loss_h))(params)
gr = jax.jit(jax.grad(loss_r))(params)
for a, b in zip(jax.tree_util.tree_leaves(gr), jax.tree_util.tree_leaves(gh)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-2)
print("hybrid grad ok")
""", timeout=560)


def test_bp_and_dap_with_evo_pallas_impl():
    """The fused Pallas attention + fused OPM must stay exact under both
    parallelism schemes (the kernels run inside shard_map; DAP feeds the
    kernel its gathered sharded bias)."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.config import af2_tiny
from repro.core import model as af2
from repro.parallel import dap as dap_lib
from repro.parallel.branch import bp_evoformer_block
from repro.parallel.mesh_utils import make_mesh, smap

cfg = af2_tiny(variant="parallel", attention_impl="evo_pallas")
ev = cfg.evoformer
from repro.nn.layers import randomize
params = randomize(af2.stack_init(jax.random.PRNGKey(0), ev, 1, scan=True),
                   jax.random.PRNGKey(7))
s, r = cfg.n_seq, cfg.n_res
msa = jax.random.normal(jax.random.PRNGKey(1), (s, r, ev.c_m))
z = jax.random.normal(jax.random.PRNGKey(2), (r, r, ev.c_z))
ref_m, ref_z = jax.jit(lambda p, m, zz: af2.evoformer_stack(
    p, ev, 1, m, zz, scan=True, remat=False))(params, msa, z)

mesh = make_mesh((2,), ("branch",))
bm, bz = jax.jit(smap(lambda p, m, zz: af2.evoformer_stack(
    p, ev, 1, m, zz, scan=True, remat=False, block_fn=bp_evoformer_block),
    mesh, (P(), P(), P()), (P(), P())))(params, msa, z)
np.testing.assert_allclose(np.asarray(ref_m), np.asarray(bm), rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(ref_z), np.asarray(bz), rtol=2e-4, atol=2e-4)
print("BP evo_pallas ok")

mesh = make_mesh((2,), ("dap",))
def dap_stack(p, m, zz):
    m_l, z_l = dap_lib.shard_inputs(m, zz)
    m_l, z_l = af2.evoformer_stack(p, ev, 1, m_l, z_l, scan=True, remat=False,
                                   block_fn=dap_lib.make_dap_block_fn(s))
    return dap_lib.unshard_outputs(m_l, z_l)
def loss_d(p):
    m, zz = smap(dap_stack, mesh, (P(), P(), P()), (P(), P()))(p, msa, z)
    return jnp.sum(m**2) + jnp.sum(zz**2)
def loss_r(p):
    m, zz = af2.evoformer_stack(p, ev, 1, msa, z, scan=True, remat=False)
    return jnp.sum(m**2) + jnp.sum(zz**2)
dm, dz = jax.jit(smap(dap_stack, mesh, (P(), P(), P()), (P(), P())))(params, msa, z)
np.testing.assert_allclose(np.asarray(ref_m), np.asarray(dm), rtol=3e-4, atol=3e-4)
np.testing.assert_allclose(np.asarray(ref_z), np.asarray(dz), rtol=3e-4, atol=3e-4)
gd = jax.jit(jax.grad(loss_d))(params)
gr = jax.jit(jax.grad(loss_r))(params)
for a, b in zip(jax.tree_util.tree_leaves(gr), jax.tree_util.tree_leaves(gd)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-2)
print("DAP evo_pallas fwd+grad ok")
""", devices=2, timeout=560)


def test_dap_overlap_collective_counts_and_bitwise_equality():
    """Satellites of the overlapped-DAP schedule, pinned at the jaxpr level:

    * per block the overlap schedule issues exactly ONE fewer `all_gather`
      than the sync schedule (the replicated z_full prefetch replaces both
      the row-attention bias gather and the tri-mult-outgoing operand
      gather, at the price of the single z_full issue gather), for both
      triangle-mult impls;
    * `all_to_all` counts are untouched (the end-bias hoist moves the bias
      projection off the transpose critical path without adding traffic);
    * on a real 2-block scan stack, the overlapped schedule is BITWISE
      identical to the sync one — gather-as-concat commutes with the
      per-position LN/dense math it was hoisted across.
    """
    run_subprocess("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.config import af2_tiny
from repro.core import model as af2
from repro.parallel import dap as dap_lib
from repro.parallel.mesh_utils import make_mesh, smap
from tests.util import count_prims, randomize

cfg = af2_tiny(variant="parallel")
s, r = cfg.n_seq, cfg.n_res
mesh = make_mesh((2,), ("dap",))

# --- per-block collective counts (prefetch passed as an input so the count
# reflects steady-state blocks; the one-off seed gather lives in the stack) --
EXPECT = {  # impl -> (sync all_gather, overlap all_gather, all_to_all)
    "reference": (6, 5, 7),
    "chunked":   (6, 5, 6),
}
for impl, (ag_sync, ag_ov, a2a) in EXPECT.items():
    ev = dataclasses.replace(cfg.evoformer, tri_mult_impl=impl)
    params = af2.stack_init(jax.random.PRNGKey(0), ev, 1, scan=False)[0]
    msa = jnp.zeros((s, r, ev.c_m)); z = jnp.zeros((r, r, ev.c_z))
    for overlap, want_ag in ((False, ag_sync), (True, ag_ov)):
        bf = dap_lib.make_dap_block_fn(s, overlap=overlap)
        def one(p, m, zz, zf):
            m_l, z_l = dap_lib.shard_inputs(m, zz)
            if overlap:
                return bf(p, ev, m_l, z_l, prefetch=zf)
            return bf(p, ev, m_l, z_l)
        out_specs = (P("dap"), P("dap"), P()) if overlap else (P("dap"), P("dap"))
        jaxpr = jax.make_jaxpr(smap(one, mesh, (P(), P(), P(), P()), out_specs))(
            params, msa, z, z)
        got = count_prims(jaxpr, {"all_gather", "all_to_all"})
        mode = "overlap" if overlap else "sync"
        assert got["all_gather"] == want_ag, (impl, mode, got)
        assert got["all_to_all"] == a2a, (impl, mode, got)
        print(f"{impl} {mode}: {got} ok")

# --- bitwise equality on a 2-block scan stack (default chunked impl) -------
ev = cfg.evoformer
params = randomize(af2.stack_init(jax.random.PRNGKey(0), ev, 2, scan=True),
                   jax.random.PRNGKey(7))
msa = jax.random.normal(jax.random.PRNGKey(1), (s, r, ev.c_m))
z = jax.random.normal(jax.random.PRNGKey(2), (r, r, ev.c_z))
def run_stack(overlap):
    bf = dap_lib.make_dap_block_fn(s, overlap=overlap)
    def fn(p, m, zz):
        m_l, z_l = dap_lib.shard_inputs(m, zz)
        m_l, z_l = af2.evoformer_stack(p, ev, 2, m_l, z_l, scan=True,
                                       remat=False, block_fn=bf)
        return dap_lib.unshard_outputs(m_l, z_l)
    return jax.jit(smap(fn, mesh, (P(), P(), P()), (P(), P())))(params, msa, z)
sm, sz = run_stack(False)
om, oz = run_stack(True)
assert np.array_equal(np.asarray(sm), np.asarray(om)), "msa drifted"
assert np.array_equal(np.asarray(sz), np.asarray(oz)), "pair drifted"
print("overlap == sync bitwise ok")
""", devices=2, timeout=560)


def test_af2_train_step_plan_matrix_vs_oracle():
    """Satellite of the ParallelPlan refactor: serial-DP / BP / DAP / hybrid
    plans (plus the auto_plan pick) all produce the same losses and updated
    params as the single-device oracle, through make_af2_train_step.  Also
    pins the extra-MSA OPM denominator fix: n_extra_seq != n_seq here, so a
    block_fn hard-coding cfg.n_seq would diverge under DAP."""
    run_subprocess("""
import dataclasses, os
import jax, jax.numpy as jnp, numpy as np
from repro.core.config import af2_tiny
from repro.core import model as af2
from repro.parallel.plan import ParallelPlan, auto_plan
from repro.train.optim import sgd
from repro.train.trainstep import make_af2_train_step
from repro.data.protein import protein_batch
from tests.util import randomize

cfg = af2_tiny(variant="parallel", n_evoformer=1, n_extra_msa_blocks=1,
               n_res=8, n_seq=4, n_extra_seq=12, remat="none")
# randomize: AF2's residual outputs are zero-init, which would make the OPM
# denominator (and most of the block) invisible to the forward pass; SGD
# makes the post-step param delta proportional to the gradient, so the
# params comparison IS the grads comparison
opt = sgd(0.1)
params = randomize(af2.init_params(jax.random.PRNGKey(0), cfg),
                   jax.random.PRNGKey(7))
batch = protein_batch(0, 0, 8, cfg)

def run(plan):
    ts, built = make_af2_train_step(
        cfg, opt, plan, n_recycle=1,
        devices=jax.devices()[:plan.n_devices])
    state = {"params": params, "opt": opt.init(params)}
    state, m = jax.jit(ts)(state, batch, jax.random.PRNGKey(0))
    return float(m["loss"]), state

l_ref, s_ref = run(ParallelPlan())                       # 1-device oracle
auto = auto_plan(8, cfg, global_batch=4)
assert auto.group > 1            # 8 devices, batch 4 forces a 2-device group
plans = {
    "dp8":    ParallelPlan(data=8),
    "dap":    ParallelPlan(data=4, dap=2),
    "hybrid": ParallelPlan(data=2, branch=2, dap=2),
    # the roofline pick for this scenario (BP at small shapes) runs too:
    "auto":   auto,
    # Pallas triangle-mult kernel under DAP row-sharding (the cfg default is
    # 'chunked', so the 'dap' plan above covers that impl; this one pins the
    # fused kernel against the same single-device chunked oracle)
    "dap_tri_pallas": ParallelPlan(data=4, dap=2, tri_mult_impl="pallas"),
    # communication-overlapped DAP: the double-buffered prefetch schedule is
    # bit-compatible with the sync schedule, so it must hit the same oracle
    "dap_overlap": ParallelPlan(data=4, dap=2, overlap_dap=True),
}
if os.environ.get("REPRO_FORCE_OVERLAP_DAP") == "1":
    # tier-1f: force the overlapped schedule onto every eligible plan so the
    # whole matrix re-runs through the prefetch carry
    plans = {n: (dataclasses.replace(p, overlap_dap=True)
                 if p.dap > 1 and p.branch == 1 else p)
             for n, p in plans.items()}
    print("forced overlap_dap on eligible plans")
assert (auto.branch, auto.dap) == (2, 1)  # covers the BP row of the matrix
for name, plan in plans.items():
    l, s = run(plan)
    np.testing.assert_allclose(l_ref, l, rtol=2e-3, atol=2e-3,
                               err_msg=name)
    for a, b in zip(jax.tree_util.tree_leaves(s_ref["params"]),
                    jax.tree_util.tree_leaves(s["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3, err_msg=name)
    print(f"plan {name} == oracle ok ({plan.describe()})")
""", timeout=1400)


def test_grad_compression_error_feedback():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.grad_sync import compressed_psum_tree, zeros_error_state
from repro.parallel.mesh_utils import make_mesh, smap

mesh = make_mesh((4,), ("pod",))
g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64,)),
     "b": jax.random.normal(jax.random.PRNGKey(1), (8,)) * 1e-3}

def body(g, err):
    red, err = compressed_psum_tree(g, "pod", err)
    return red, err

fn = jax.jit(smap(body, mesh, (P(), P()), (P(), P())))
err = zeros_error_state(g)
red, err = fn(g, err)
exact = jax.tree_util.tree_map(lambda x: 4.0 * x, g)  # 4 identical pods
for a, b in zip(jax.tree_util.tree_leaves(red), jax.tree_util.tree_leaves(exact)):
    rel = np.abs(np.asarray(a) - np.asarray(b)).max() / (np.abs(np.asarray(b)).max() + 1e-9)
    assert rel < 0.02, rel  # int8 -> <2% single-shot error
# error feedback: residual is exactly the quantization error
summed, err2 = fn(g, err)
# applying twice with feedback: cumulative mean error shrinks
e1 = np.abs(np.asarray(red["w"]) - np.asarray(exact["w"])).mean()
e2 = np.abs(0.5 * (np.asarray(red["w"]) + np.asarray(summed["w"])) - np.asarray(exact["w"])).mean()
assert e2 <= e1 + 1e-7
print("compression ok")
""", timeout=400)


def test_bp_on_dense_parallel_block():
    """Beyond-paper: Branch Parallelism on a PaLM-style dense LM layer —
    attention branch on device 0, MLP branch on device 1, exact."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.models import dense
from repro.models.lmconfig import LMConfig
from repro.parallel.mesh_utils import make_mesh, smap

cfg = LMConfig(arch_id="t", family="dense", n_layer=1, d_model=64, n_head=4,
               n_kv_head=2, d_ff=128, vocab=64, parallel_block=True,
               scan_layers=False, remat="none", attention_chunk=16)
p = dense.layer_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
ref, _ = dense.layer_apply(p, cfg, x, pos)

mesh = make_mesh((2,), ("branch",))
bp = jax.jit(smap(lambda p, x: dense.bp_parallel_layer(p, cfg, x, pos)[0],
                  mesh, (P(), P()), P()))
out = bp(p, x)
np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-4, atol=2e-4)
# and the serial parallel-block decode stays consistent with forward
params = dense.init_params(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(2), (2, 10), 0, 64)
logits = dense.forward(params, cfg, toks)
cache = dense.init_cache(cfg, 2, 16)
lg, cache = dense.prefill(params, cfg, toks[:, :8], cache)
np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(logits[:, 7]),
                           rtol=5e-2, atol=5e-2)
lg, cache = dense.decode_step(params, cfg, toks[:, 8:9], cache)
np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(logits[:, 8]),
                           rtol=5e-2, atol=5e-2)
print("dense BP parallel-block ok")
""", devices=2, timeout=400)


def test_refactor_mesh_axes():
    run_subprocess("""
import jax
from repro.parallel.mesh_utils import make_mesh, refactor_mesh
mesh = make_mesh((2, 4), ("data", "model"))
m2 = refactor_mesh(mesh, {"model": [("branch", 2), ("dap", 2)]})
assert m2.axis_names == ("data", "branch", "dap"), m2.axis_names
assert dict(m2.shape) == {"data": 2, "branch": 2, "dap": 2}
# device order preserved
assert (m2.devices.reshape(-1) == mesh.devices.reshape(-1)).all()
try:
    refactor_mesh(mesh, {"model": [("a", 3)]})
    raise SystemExit("expected ValueError")
except ValueError:
    pass
print("refactor ok")
""", timeout=300)
