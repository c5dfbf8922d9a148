"""predict(): adaptive early-exit recycling + padded-bucket correctness +
confidence-head utilities (ISSUE 4 satellites; marker: serve)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import heads as heads_lib
from repro.core import model as af2
from repro.core.config import af2_tiny
from repro.data.protein import protein_sample

from util import randomize

pytestmark = pytest.mark.serve


def _params(cfg, seed=0):
    return randomize(af2.init_params(jax.random.PRNGKey(seed), cfg),
                     jax.random.PRNGKey(seed + 1))


def _infer_feats(sample, cfg):
    keep = ("msa_feat", "extra_msa_feat", "target_feat", "residue_index")
    f = {k: sample[k] for k in keep}
    f["res_mask"] = jnp.ones((cfg.n_res,), jnp.float32)
    return f


def _batchify(*samples):
    return {k: jnp.stack([s[k] for s in samples]) for k in samples[0]}


# ---------------------------------------------------------------------------
# Confidence utilities
# ---------------------------------------------------------------------------

def test_plddt_from_logits_range_and_monotonicity():
    nb = 50
    # certain mass in bin b -> score ascends strictly as b grows (bins are
    # ordered by increasing lDDT-Cα, the plddt_loss target), inside [0, 100]
    eye = 40.0 * jnp.eye(nb)
    scores = heads_lib.plddt_from_logits(eye)
    assert scores.shape == (nb,)
    assert float(scores.min()) >= 0.0 and float(scores.max()) <= 100.0
    assert np.all(np.diff(np.asarray(scores)) > 0), \
        "mass in a higher-lDDT bin must strictly raise pLDDT"
    # uniform logits -> expected value of symmetric centers = 50
    flat = heads_lib.plddt_from_logits(jnp.zeros((3, nb)))
    np.testing.assert_allclose(np.asarray(flat), 50.0, atol=1e-4)


def test_contact_probs_range_monotonicity_and_cutoff():
    nb = 64
    eye = 40.0 * jnp.eye(nb)
    probs = heads_lib.contact_probs_from_distogram(eye)
    assert float(probs.min()) >= 0.0 and float(probs.max()) <= 1.0
    # mass below the cutoff -> ~1; above -> ~0; never increasing with bin
    edges = np.linspace(2.3125, 21.6875, nb - 1)
    n_contact = int((edges <= 8.0).sum())
    probs = np.asarray(probs)
    assert probs[0] > 0.99 and probs[n_contact - 1] > 0.99
    assert probs[n_contact] < 0.01 and probs[-1] < 0.01
    assert np.all(np.diff(probs) <= 1e-6)
    # mixed distribution: contact prob == the sub-cutoff bin mass
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(5, 5, nb)))
    p = heads_lib.contact_probs_from_distogram(logits)
    soft = jax.nn.softmax(logits, -1)
    np.testing.assert_allclose(np.asarray(p),
                               np.asarray(soft[..., :n_contact].sum(-1)),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# predict(): fixed-recycle equivalence + early exit
# ---------------------------------------------------------------------------

def test_predict_tol0_matches_fixed_recycle_forward():
    cfg = af2_tiny()
    params = _params(cfg)
    s = protein_sample(jax.random.PRNGKey(7), cfg)
    out = jax.jit(lambda p, b: af2.forward(
        p, cfg, b, n_recycle=3, dtype=jnp.float32))(params, s)
    batch = _batchify(_infer_feats(s, cfg))
    pred = jax.jit(lambda p, b: af2.predict(
        p, cfg, b, max_recycle=3, tol=0.0, dtype=jnp.float32))(params, batch)
    assert int(pred["n_recycles"][0]) == 3
    assert not bool(pred["converged"][0])
    np.testing.assert_allclose(np.asarray(pred["coords"][0]),
                               np.asarray(out["trans"]), atol=1e-5)
    # heads agree with applying them to forward's outputs directly
    ref_plddt = heads_lib.plddt_from_logits(
        heads_lib.plddt_logits(params["heads"], out["s_final"]))
    np.testing.assert_allclose(np.asarray(pred["plddt"][0]),
                               np.asarray(ref_plddt), atol=1e-3)


def _frac_changed(coords_a, coords_b, r):
    bins_a = af2.recycle_distance_bins(jnp.asarray(coords_a))
    bins_b = af2.recycle_distance_bins(jnp.asarray(coords_b))
    return float(jnp.mean((bins_a != bins_b).astype(jnp.float32)))


def _simulate_convergence(fracs, tol, max_recycle):
    """predict()'s convergence rule on a per-transition frac sequence:
    (n_recycles, converged)."""
    for k, f in enumerate(fracs[:max_recycle]):
        if f < tol:
            return k + 1, True
    return max_recycle, False


def test_predict_early_exit_freezes_converged_sample():
    """A converged sample stops changing while an unconverged batchmate
    keeps recycling; per-sample n_recycles records the divergence.

    The test self-calibrates: it measures each sample's per-transition
    binned-distance change from fixed-recycle runs, then picks a tolerance
    under which the convergence rule predicts DIFFERENT recycle counts for
    the two samples, and checks predict() realizes exactly that schedule.
    """
    cfg = af2_tiny()
    # seeds whose two samples take different recycle schedules under JAX's
    # default (partitionable) threefry stream
    params = randomize(af2.init_params(jax.random.PRNGKey(0), cfg),
                       jax.random.PRNGKey(2), scale=0.1)
    sa = _infer_feats(protein_sample(jax.random.PRNGKey(11), cfg), cfg)
    sb = _infer_feats(protein_sample(jax.random.PRNGKey(12), cfg), cfg)
    batch = _batchify(sa, sb)

    # reference trajectory: fixed-recycle coords after k = 1, 2, 3 cycles
    fixed = {}
    for k in (1, 2, 3):
        fixed[k] = jax.jit(lambda p, b, k=k: af2.predict(
            p, cfg, b, max_recycle=k, tol=0.0,
            dtype=jnp.float32))(params, b=batch)
    zeros = np.zeros((cfg.n_res, 3), np.float32)
    coords = {0: [zeros, zeros],
              **{k: [np.asarray(fixed[k]["coords"][i]) for i in (0, 1)]
                 for k in (1, 2, 3)}}
    fracs = [[_frac_changed(coords[k][i], coords[k + 1][i], cfg.n_res)
              for k in (0, 1, 2)] for i in (0, 1)]

    # a tolerance that separates the two samples' schedules
    cands = sorted(set(f for fr in fracs for f in fr))
    mids = [(a + b) / 2 for a, b in zip(cands, cands[1:])] + \
        [cands[0] / 2, cands[-1] * 1.01 + 1e-6]
    pick = None
    for tol in mids:
        exp = [_simulate_convergence(fr, tol, 3) for fr in fracs]
        if exp[0][0] != exp[1][0]:
            pick = (tol, exp)
            break
    assert pick is not None, \
        f"seeds give indistinguishable convergence schedules: {fracs}"
    tol, exp = pick

    pred = jax.jit(lambda p, b: af2.predict(
        p, cfg, b, max_recycle=3, tol=tol,
        dtype=jnp.float32))(params, batch)
    for i in (0, 1):
        n_exp, conv_exp = exp[i]
        assert int(pred["n_recycles"][i]) == n_exp
        assert bool(pred["converged"][i]) == conv_exp
        # each sample carries exactly its fixed-recycle state at n_exp
        np.testing.assert_allclose(np.asarray(pred["coords"][i]),
                                   coords[n_exp][i], atol=1e-6)
    # the freeze is non-vacuous: the early-exited sample WOULD have moved
    fast = int(np.argmin([e[0] for e in exp]))
    n_fast = exp[fast][0]
    assert np.abs(coords[n_fast + 1][fast]
                  - coords[n_fast][fast]).max() > 1e-4, \
        "freeze test is vacuous: the sample stopped moving on its own"


def test_predict_tol_one_exits_after_single_cycle():
    cfg = af2_tiny()
    params = _params(cfg)
    s = _infer_feats(protein_sample(jax.random.PRNGKey(5), cfg), cfg)
    pred = jax.jit(lambda p, b: af2.predict(
        p, cfg, b, max_recycle=4, tol=1.1,
        dtype=jnp.float32))(params, _batchify(s))
    assert int(pred["n_recycles"][0]) == 1
    assert bool(pred["converged"][0])


# ---------------------------------------------------------------------------
# Padded-bucket correctness (the evoformer.py padded-k gating, model level)
# ---------------------------------------------------------------------------

def _padded_pair(att, tri):
    """(unpadded cfg+batch, padded cfg+batch) for one impl selection."""
    def with_impls(cfg):
        return dataclasses.replace(
            cfg,
            evoformer=dataclasses.replace(cfg.evoformer, attention_impl=att,
                                          tri_mult_impl=tri),
            extra=dataclasses.replace(cfg.extra, attention_impl=att,
                                      tri_mult_impl=tri))

    cfg_b = with_impls(af2_tiny())                 # bucket: r16 s8 se12
    r, s_rows, se = 12, 6, 10
    cfg_u = dataclasses.replace(cfg_b, n_res=r, n_seq=s_rows, n_extra_seq=se)
    smp = protein_sample(jax.random.PRNGKey(3), cfg_u)
    feats = _infer_feats(smp, cfg_u)
    feats["msa_row_mask"] = jnp.ones((s_rows,), jnp.float32)
    feats["extra_row_mask"] = jnp.ones((se,), jnp.float32)

    from repro.serve.fold_steps import Bucket, pad_to_bucket
    padded = pad_to_bucket(
        {k: np.asarray(feats[k]) for k in
         ("msa_feat", "extra_msa_feat", "target_feat", "residue_index")},
        Bucket(cfg_b.n_res, cfg_b.n_seq, cfg_b.n_extra_seq))
    padded = {k: jnp.asarray(v) for k, v in padded.items()}
    return cfg_u, _batchify(feats), cfg_b, _batchify(padded), r


@pytest.mark.parametrize("att,tri", [("chunked", "chunked"),
                                     ("evo_pallas", "pallas")])
def test_padded_fold_matches_unpadded(att, tri):
    """Folding a length-r protein padded to a bucket r_b > r matches the
    unpadded fold to fwd tolerance — masks flow through gated attention,
    OPM, triangle mult (incl. the Pallas kernels) and IPA end to end."""
    cfg_u, b_u, cfg_b, b_p, r = _padded_pair(att, tri)
    params = _params(cfg_b)
    pu = jax.jit(lambda p, b: af2.predict(
        p, cfg_u, b, max_recycle=2, dtype=jnp.float32))(params, b_u)
    pp = jax.jit(lambda p, b: af2.predict(
        p, cfg_b, b, max_recycle=2, dtype=jnp.float32))(params, b_p)
    np.testing.assert_allclose(np.asarray(pp["coords"][0][:r]),
                               np.asarray(pu["coords"][0]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(pp["plddt"][0][:r]),
                               np.asarray(pu["plddt"][0]), atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(pp["contact_probs"][0][:r, :r]),
        np.asarray(pu["contact_probs"][0]), atol=1e-4)


def test_bp_block_rejects_masks():
    from repro.core.evoformer import EvoMasks
    from repro.parallel.branch import bp_evoformer_block
    cfg = af2_tiny().evoformer
    masks = EvoMasks(jnp.ones((4,)), jnp.ones((8,)))
    with pytest.raises(ValueError, match="for_inference"):
        bp_evoformer_block({}, cfg, jnp.zeros(()), jnp.zeros(()), masks=masks)
