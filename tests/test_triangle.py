"""Triangle-multiplicative update: chunked + Pallas impls vs the fp32
reference (acceptance: fwd 1e-5 / grads 1e-4 at r in {64, 128}), jaxpr
memory bounds, bf16-accumulation pin, and impl dispatch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import evoformer as evo
from repro.core.config import af2_initial, af2_tiny
from repro.nn import layers as nn
from tests.util import max_eqn_elems, randomize, run_subprocess

pallas_interpret = pytest.mark.pallas_interpret

# (c_z, c_hidden) per path of the chunked impl: c_hidden <= c_z runs in one
# pass, c_hidden > c_z in row slabs with a k-scan
PATHS = {"onepass": (16, 16), "slab": (8, 32)}
over_paths = pytest.mark.parametrize("c_z,c", list(PATHS.values()),
                                     ids=list(PATHS))


def _cfg(impl, chunk=64):
    return dataclasses.replace(af2_tiny().evoformer, tri_mult_impl=impl,
                               tri_mult_chunk=chunk)


def _setup(r, c_z=16, c=16, seed=0):
    p = randomize(evo.triangle_mult_init(jax.random.PRNGKey(seed), c_z, c),
                  jax.random.PRNGKey(7))
    z = jax.random.normal(jax.random.PRNGKey(1), (r, r, c_z))
    return p, z


def _grads(p, cfg, z, outgoing, k_mask=None):
    w = jnp.cos(jnp.arange(z.shape[-1]))  # non-uniform cotangent

    def loss(p, z):
        return (evo.tri_mult_apply(p, cfg, z, outgoing=outgoing,
                                   k_mask=k_mask) * w).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1)))(p, z)


def _assert_impl_matches(impl, r, chunk=64, fwd_tol=1e-5, grad_tol=1e-4,
                         c_z=16, c=16, k_mask=None):
    p, z = _setup(r, c_z, c)
    for outgoing in (True, False):
        ref = evo.tri_mult_apply(p, _cfg("reference"), z, outgoing=outgoing,
                                 k_mask=k_mask)
        out = jax.jit(lambda p, z: evo.tri_mult_apply(
            p, _cfg(impl, chunk), z, outgoing=outgoing, k_mask=k_mask))(p, z)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=fwd_tol, atol=fwd_tol,
                                   err_msg=f"{impl} fwd outgoing={outgoing}")
        gp_r, gz_r = _grads(p, _cfg("reference"), z, outgoing, k_mask)
        gp, gz = _grads(p, _cfg(impl, chunk), z, outgoing, k_mask)
        np.testing.assert_allclose(np.asarray(gz_r), np.asarray(gz),
                                   rtol=grad_tol, atol=grad_tol,
                                   err_msg=f"{impl} dz outgoing={outgoing}")
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(gp_r),
                jax.tree_util.tree_leaves_with_path(gp)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=grad_tol, atol=grad_tol,
                err_msg=f"{impl} d{jax.tree_util.keystr(path)} "
                        f"outgoing={outgoing}")


@over_paths
@pytest.mark.parametrize("r", [64, 128])
def test_chunked_matches_reference(r, c_z, c):
    _assert_impl_matches("chunked", r, c_z=c_z, c=c)


@over_paths
def test_chunked_non_dividing_chunk(c_z, c):
    """Padded k columns project through non-zero biases — they must be
    masked out, not silently summed (48 % 20 != 0 exercises both pads of
    the slab path; the one-pass path ignores the chunk)."""
    _assert_impl_matches("chunked", 48, chunk=20, c_z=c_z, c=c)


@pytest.mark.parametrize("c_z,c", [*PATHS.values(), (16, 8)],
                         ids=[*PATHS, "onepass_narrow"])
def test_chunked_k_mask_matches_reference(c_z, c):
    """A padded bucket's residues leave the k-contraction on both paths,
    forward and gradients (a padded entry's gated projection is not 0)."""
    k_mask = jnp.arange(40) < 33
    _assert_impl_matches("chunked", 40, chunk=16, c_z=c_z, c=c,
                         k_mask=k_mask)


@pallas_interpret
@pytest.mark.parametrize("r", [64, 128])
def test_pallas_matches_reference(r):
    _assert_impl_matches("pallas", r)


@pallas_interpret
def test_pallas_residual_fwd_consistent():
    """Residual-mode forward (what the custom_vjp saves) must agree with the
    plain forward and emit the true fp32 pre-LN contraction."""
    from repro.kernels import triangle as tk
    r, c_z, c = 32, 8, 12
    p, z = _setup(r, c_z, c)
    x = nn.layernorm(p["ln_in"], z)
    w_a, b_a, w_b, b_b = evo._tri_mult_packed_weights(p)
    args = (x, x, x, w_a, b_a, w_b, b_b, p["ln_out"]["scale"],
            p["ln_out"]["bias"], p["out"]["w"], p["out"]["b"],
            p["gate"]["w"], p["gate"]["b"])
    out0 = tk.triangle_mult_fwd(*args, interpret=True)
    out1, s = tk.triangle_mult_fwd(*args, interpret=True,
                                   return_residuals=True)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1))
    a = jax.nn.sigmoid(nn.dense(p["a_gate"], x)) * nn.dense(p["a"], x)
    b = jax.nn.sigmoid(nn.dense(p["b_gate"], x)) * nn.dense(p["b"], x)
    s_ref = jnp.einsum("ikc,jkc->ijc", a, b,
                       preferred_element_type=jnp.float32)
    assert s.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)


@pallas_interpret
def test_pallas_rectangular_dap_shapes():
    """The kernel's DAP contract: rectangular (r_i, r_k) x (r_j, r_k)
    operands (a row shard vs the gathered rep) match the dense einsum."""
    from repro.kernels import ops as kops
    ri, rj, rk, c_z, c = 4, 16, 16, 6, 10
    p, _ = _setup(rj, c_z, c)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    xa = jax.random.normal(ks[0], (ri, rk, c_z))
    xb = jax.random.normal(ks[1], (rj, rk, c_z))
    xg = jax.random.normal(ks[2], (ri, rj, c_z))
    w_a, b_a, w_b, b_b = evo._tri_mult_packed_weights(p)

    def ref(xa, xb, xg):
        a = jax.nn.sigmoid(nn.dense(p["a_gate"], xa)) * nn.dense(p["a"], xa)
        b = jax.nn.sigmoid(nn.dense(p["b_gate"], xb)) * nn.dense(p["b"], xb)
        o = jnp.einsum("ikc,jkc->ijc", a, b,
                       preferred_element_type=jnp.float32)
        o = nn.dense(p["out"], nn.layernorm(p["ln_out"], o))
        return jax.nn.sigmoid(nn.dense(p["gate"], xg)) * o

    fused = lambda xa, xb, xg: kops.triangle_mult(
        xa, xb, xg, w_a, b_a, w_b, b_b, p["ln_out"]["scale"],
        p["ln_out"]["bias"], p["out"]["w"], p["out"]["b"],
        p["gate"]["w"], p["gate"]["b"])
    np.testing.assert_allclose(np.asarray(ref(xa, xb, xg)),
                               np.asarray(fused(xa, xb, xg)),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda *a: ref(*a).sum(), argnums=(0, 1, 2))(xa, xb, xg)
    g2 = jax.grad(lambda *a: fused(*a).sum(), argnums=(0, 1, 2))(xa, xb, xg)
    for name, a, b in zip("xa xb xg".split(), g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pallas_interpret
def test_pallas_falls_back_on_unaligned_lengths():
    """r with a tiny power-of-two divisor (10) must silently take the
    chunked path — same numbers, no degenerate tiling."""
    p, _ = _setup(16)
    z = jax.random.normal(jax.random.PRNGKey(5), (10, 10, 16))
    out_p = evo.tri_mult_apply(p, _cfg("pallas"), z, outgoing=True)
    out_r = evo.tri_mult_apply(p, _cfg("reference"), z, outgoing=True)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_unknown_impl_rejected():
    p, z = _setup(16)
    with pytest.raises(ValueError, match="tri_mult"):
        evo.tri_mult_apply(p, _cfg("fused2"), z, outgoing=True)


# ---------------------------------------------------------------------------
# Satellite: fp32 accumulation in the reference under the AMP policy
# ---------------------------------------------------------------------------

def test_reference_contraction_accumulates_fp32_under_bf16():
    """Under the AMP policy a/b are bf16; the r-contraction must request
    fp32 accumulation (a bf16 sum over r >= 128 terms has ulp ~1 at
    magnitude ~r) or the reference is no oracle.  Pinned structurally: the
    jaxpr's k-contraction dot_general must emit fp32."""
    from repro.analysis.static.jaxpr_walk import iter_eqns
    from repro.analysis.static.passes.precision import (
        contraction_extents, find_low_precision_contractions)
    r, c_z, c = 128, 16, 16
    p, z = _setup(r, c_z, c)
    p16 = nn.BF16.cast(p)
    z16 = z.astype(jnp.bfloat16)
    for outgoing in (True, False):
        jaxpr = jax.make_jaxpr(lambda p, z: evo.triangle_mult(
            p, z, outgoing=outgoing))(p16, z16)
        assert any(e.primitive.name == "dot_general"
                   and r in contraction_extents(e)
                   for e, _ in iter_eqns(jaxpr)), (
            "detector: no r-contraction dot_general found")
        hits = find_low_precision_contractions(jaxpr, extents={r})
        assert not hits, (
            f"k-contraction accumulates in bf16, not fp32 "
            f"(outgoing={outgoing}): {hits}")
    # and the bf16 output stays close to the fp32 oracle
    ref32 = evo.triangle_mult(p, z, outgoing=True)
    out16 = evo.triangle_mult(p16, z16, outgoing=True)
    np.testing.assert_allclose(np.asarray(ref32),
                               np.asarray(out16, np.float32),
                               rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# Satellite: jaxpr memory bound for the chunked path
# ---------------------------------------------------------------------------

def test_chunked_materializes_no_gated_projection_pair():
    """Acceptance check: the chunked path must not create ANY intermediate
    as large as even ONE full (r, r, c_hidden) gated-projection tensor
    (a fortiori not the (r, r, 2c) pair) — per-slab epilogue included, the
    largest things alive are the (r, r, c_z) input/output and chunk slabs."""
    r, c_z, c, chunk = 32, 8, 32, 8
    p, _ = _setup(r, c_z, c)
    z = jax.random.normal(jax.random.PRNGKey(2), (r, r, c_z))
    one_proj = r * r * c

    ref_peak = max_eqn_elems(jax.make_jaxpr(
        lambda z: evo.triangle_mult(p, z, outgoing=True))(z))
    assert ref_peak >= one_proj, "detector sanity: reference must hit it"

    cfg = _cfg("chunked", chunk)
    for outgoing in (True, False):
        peak = max_eqn_elems(jax.make_jaxpr(
            lambda z: evo.tri_mult_apply(p, cfg, z,
                                         outgoing=outgoing))(z))
        assert peak < one_proj, (
            f"chunked tri-mult materialized {peak} elems >= a full "
            f"(r, r, c_hidden) projection tensor ({one_proj})")
        # nothing beyond the input/output rep and the per-slab accumulator
        assert peak <= max(r * r * c_z, chunk * r * c)


def test_chunked_backward_also_bounded():
    """The VJP of the chunked path must not reintroduce the (r, r, 2c)
    gated-projection pair.  The largest allowed intermediate is the stacked
    fp32 contraction residual (r, r, c) — the same residual the Pallas
    custom_vjp saves; its recompute would cost a second O(r^3) pass."""
    r, c_z, c, chunk = 32, 8, 32, 8
    p, _ = _setup(r, c_z, c)
    z = jax.random.normal(jax.random.PRNGKey(2), (r, r, c_z))
    cfg = _cfg("chunked", chunk)
    peak = max_eqn_elems(jax.make_jaxpr(jax.grad(
        lambda z: evo.tri_mult_apply(p, cfg, z, outgoing=True).sum()))(z))
    assert peak <= r * r * c, peak
    assert peak < r * r * 2 * c, peak


def test_onepass_bounded_by_the_rep():
    """The one-pass path builds each gated projection whole, which is only
    allowed because c_hidden <= c_z keeps it no larger than the rep: forward
    and backward, nothing reaches the (r, r, 2c) pair, and nothing exceeds
    the larger of the rep and the fp32 contraction (r, r, c)."""
    r = 32
    for c_z, c in ((16, 16), (16, 12)):
        p, _ = _setup(r, c_z, c)
        z = jax.random.normal(jax.random.PRNGKey(2), (r, r, c_z))
        cfg = _cfg("chunked")
        for outgoing in (True, False):
            f = lambda z: evo.tri_mult_apply(p, cfg, z, outgoing=outgoing)
            for name, jx in (
                    ("fwd", jax.make_jaxpr(f)(z)),
                    ("grad", jax.make_jaxpr(jax.grad(
                        lambda z: f(z).sum()))(z))):
                peak = max_eqn_elems(jx)
                assert peak < r * r * 2 * c, (c_z, c, outgoing, name, peak)
                assert peak <= max(r * r * c_z, r * r * c), (
                    c_z, c, outgoing, name, peak)


def test_onepass_clean_for_materialization_pass_at_model1_widths():
    """The lint's TRIMULT_PAIR_MATERIALIZED bound holds for the one-pass
    path at the benchmark cells' widths (r 256, c_z = c_hidden_mul 128)."""
    from repro.analysis.static.core import Program
    from repro.analysis.static.passes import MaterializationPass
    from repro.analysis.static.passes.materialization import size_thresholds
    cfg = af2_initial()
    ev = cfg.evoformer
    assert ev.tri_mult_impl == "chunked" and ev.c_hidden_mul <= ev.c_z
    assert any(code == "TRIMULT_PAIR_MATERIALIZED"
               for *_, code in size_thresholds(cfg)), "bound not armed"
    p = jax.eval_shape(lambda: evo.triangle_mult_init(
        jax.random.PRNGKey(0), ev.c_z, ev.c_hidden_mul))
    z = jax.ShapeDtypeStruct((cfg.n_res, cfg.n_res, ev.c_z), jnp.bfloat16)

    def both(p, z):
        return sum(evo.tri_mult_apply(p, ev, z, outgoing=o).astype(
            jnp.float32).sum() for o in (True, False))

    jaxprs = {"fwd": jax.make_jaxpr(both)(p, z),
              "step": jax.make_jaxpr(jax.grad(both, argnums=(0, 1)))(p, z)}
    res = MaterializationPass().run(Program(
        name="tri_mult:af2_initial", kind="fixture", jaxprs=jaxprs,
        meta={"cfg": cfg}))
    assert not res.skipped
    hits = [f for f in res.findings if f.code == "TRIMULT_PAIR_MATERIALIZED"]
    assert not hits, [f.message for f in hits]


@pytest.mark.parametrize("c_z,c,engaged", [(16, 16, True), (16, 8, True),
                                           (8, 32, False)])
def test_onepass_scope_marks_engagement(c_z, c, engaged):
    """``tri_mult_onepass`` names the path's ops in the compiled program's
    op metadata (what the device trace reads) exactly when c_hidden <= c_z."""
    p, z = _setup(16, c_z, c)
    cfg = _cfg("chunked", chunk=8)
    for outgoing in (True, False):
        hlo = jax.jit(lambda p, z: evo.tri_mult_apply(
            p, cfg, z, outgoing=outgoing)).lower(p, z).compile().as_text()
        assert ("tri_mult_onepass" in hlo) == engaged, (outgoing, engaged)


def test_dap_onepass_matches_unsharded():
    """DAP's tri-mult (row-sharded a, gathered b) takes the one-pass path
    under the same rule and matches the unsharded one, in both edge
    directions, with and without a k_mask, forward and gradients."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import evoformer as evo
from repro.core.config import af2_tiny
from repro.parallel import dap as dap_lib
from repro.parallel.mesh_utils import local_slice, make_mesh, smap
from tests.util import randomize

ev = af2_tiny().evoformer
assert ev.tri_mult_impl == "chunked" and ev.c_hidden_mul <= ev.c_z
r = 16
p = randomize(evo.triangle_mult_init(jax.random.PRNGKey(0), ev.c_z,
                                     ev.c_hidden_mul), jax.random.PRNGKey(7))
z = jax.random.normal(jax.random.PRNGKey(1), (r, r, ev.c_z))
w = jnp.cos(jnp.arange(ev.c_z))
mesh = make_mesh((2,), ("dap",))
for outgoing in (True, False):
    for k_mask in (None, jnp.arange(r) < 13):
        def dap(p, z):
            def body(p, z):
                z_l = local_slice(z, "dap", 0)
                y = dap_lib.dap_triangle_mult(
                    p, z_l, outgoing=outgoing, axis_name="dap",
                    impl="chunked", chunk=ev.tri_mult_chunk, k_mask=k_mask)
                return dap_lib._all_gather(y, "dap", 0)
            return smap(body, mesh, (P(), P()), P())(p, z)
        one = lambda p, z: evo.tri_mult_apply(p, ev, z, outgoing=outgoing,
                                              k_mask=k_mask)
        hlo = jax.jit(dap).lower(p, z).compile().as_text()
        assert "tri_mult_onepass" in hlo, "DAP did not take the one-pass path"
        np.testing.assert_allclose(np.asarray(jax.jit(one)(p, z)),
                                   np.asarray(jax.jit(dap)(p, z)),
                                   rtol=1e-5, atol=1e-5)
        g1 = jax.jit(jax.grad(lambda p, z: (one(p, z) * w).sum(),
                              argnums=(0, 1)))(p, z)
        g2 = jax.jit(jax.grad(lambda p, z: (dap(p, z) * w).sum(),
                              argnums=(0, 1)))(p, z)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)
        print("dap one-pass ok", outgoing, k_mask is not None)
""", devices=2, timeout=300)


# ---------------------------------------------------------------------------
# Block-level integration: all impls interchangeable inside pair_branch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_pair_branch_impl_equivalence(impl):
    """Forward + parameter gradients of the whole pair branch match the
    reference impl (marked pallas case runs in the tier-1c interpret tier
    too via test_pallas_matches_reference; this pins the block wiring)."""
    cfg_r = _cfg("reference")
    cfg_x = _cfg(impl, chunk=8)
    blk = randomize(evo.evoformer_block_init(jax.random.PRNGKey(0), cfg_r),
                    jax.random.PRNGKey(11))
    z = jax.random.normal(jax.random.PRNGKey(1), (16, 16, cfg_r.c_z))
    z1 = jax.jit(lambda p, z: evo.pair_branch(p, cfg_r, z))(blk, z)
    z2 = jax.jit(lambda p, z: evo.pair_branch(p, cfg_x, z))(blk, z)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(z2),
                               rtol=2e-5, atol=2e-5)
    w = jnp.sin(jnp.arange(cfg_r.c_z))
    g1 = jax.jit(jax.grad(lambda p: (evo.pair_branch(p, cfg_r, z) * w).sum()))(blk)
    g2 = jax.jit(jax.grad(lambda p: (evo.pair_branch(p, cfg_x, z) * w).sum()))(blk)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g1),
                                 jax.tree_util.tree_leaves_with_path(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))
