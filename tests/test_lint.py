"""Static-analyzer tier (marker: lint, tier-1j in scripts/run_tier1.sh).

Two halves:

  * known-bad fixtures — every pass must FIRE on a minimal program that
    reconstructs its bug class (an analyzer that never fires is worse than
    none: it certifies bugs as clean), and stay quiet on the fixed twin;
  * the gate — ``python -m repro.analysis.lint`` over the full ParallelPlan
    matrix must exit 0 against the committed baseline, and the waiver
    machinery (fingerprint stability, stale detection) must behave.
"""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.static.core import Finding, Program, Report
from repro.analysis.static.passes import (CollectivesPass, MaterializationPass,
                                          PrecisionPass, RetracePass, RngPass)
from repro.analysis.static.program import lint_config
from tests.util import _repo_root, run_subprocess

pytestmark = pytest.mark.lint


def _fixture(name, jaxprs, **meta):
    return Program(name=f"fixture:{name}", kind="fixture", jaxprs=jaxprs,
                   meta=meta)


def _codes(result):
    return {f.code for f in result.findings}


# ---------------------------------------------------------------------------
# Pass 1: materialization
# ---------------------------------------------------------------------------

def test_unfused_opm_fixture_fires():
    """The naive OPM materializes the (r, r, c, c) outer tensor — exactly
    the bound the fused impl promises to avoid."""
    from repro.core import evoformer as evo
    cfg = lint_config()
    r, s, c = cfg.n_res, cfg.n_seq, cfg.evoformer.c_hidden_opm

    def naive(a, b):
        outer = jnp.einsum("sic,sjd->ijcd", a, b) / s      # (r, r, c, c)
        return outer.reshape(r, r, -1).sum(-1)

    jx = jax.make_jaxpr(naive)(
        jax.ShapeDtypeStruct((s, r, c), jnp.float32),
        jax.ShapeDtypeStruct((s, r, c), jnp.float32))
    res = MaterializationPass().run(_fixture("unfused_opm", {"fwd": jx},
                                             cfg=cfg))
    assert "OPM_OUTER_MATERIALIZED" in _codes(res)
    # and the shape guard keeps it from cross-firing the tri-mult bound
    assert "TRIMULT_PAIR_MATERIALIZED" not in _codes(res)


def test_trimult_gated_pair_fixture_fires():
    cfg = lint_config()
    r, c_mul = cfg.n_res, cfg.evoformer.c_hidden_mul

    def gated_pair(a, b, ga, gb):
        return jnp.concatenate([a * ga, b * gb], axis=-1)  # (r, r, 2*c_mul)

    sds = jax.ShapeDtypeStruct((r, r, c_mul), jnp.float32)
    jx = jax.make_jaxpr(gated_pair)(sds, sds, sds, sds)
    res = MaterializationPass().run(_fixture("tri_pair", {"fwd": jx},
                                             cfg=cfg))
    assert "TRIMULT_PAIR_MATERIALIZED" in _codes(res)


def test_unchunked_attention_scores_fixture_fires():
    """An unchunked q·k over a chunked extent builds the full (h, S, S)
    score matrix; the chunked impl only ever builds (h, q_chunk, S)."""
    cfg = lint_config()
    h, r, c = cfg.evoformer.n_head_msa, cfg.n_res, 8

    def naive_attention(q, k, v):
        scores = jnp.einsum("hqc,hkc->hqk", q, k)          # (h, r, r) dot
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,hkc->qhc", w, v)

    sds = jax.ShapeDtypeStruct((h, r, c), jnp.float32)
    jx = jax.make_jaxpr(naive_attention)(sds, sds, sds)
    res = MaterializationPass().run(_fixture("full_scores", {"fwd": jx},
                                             cfg=cfg))
    assert "FULL_ATTENTION_SCORES" in _codes(res)


def test_chunked_attention_slab_stays_clean():
    """A (h, chunk, S) slab — what the chunked impl actually builds — must
    NOT read as full scores."""
    cfg = lint_config()
    h, r, c, chunk = cfg.evoformer.n_head_msa, cfg.n_res, 8, 4

    def chunked_slab(q, k):
        return jnp.einsum("hqc,hkc->hqk", q, k)            # (h, 4, r)

    jx = jax.make_jaxpr(chunked_slab)(
        jax.ShapeDtypeStruct((h, chunk, c), jnp.float32),
        jax.ShapeDtypeStruct((h, r, c), jnp.float32))
    res = MaterializationPass().run(_fixture("chunk_slab", {"fwd": jx},
                                             cfg=cfg))
    assert res.findings == []


# ---------------------------------------------------------------------------
# Pass 2: collectives (needs a real mesh -> subprocess with 8 fake devices)
# ---------------------------------------------------------------------------

def test_grad_completion_audit_fires_and_clears():
    """The PR-2 bug in miniature: a shard_map'd gradient of a psum'd loss is
    PARTIAL wrt replicated params.  Without the completing psum the step is
    indistinguishable from the no-completion baseline -> the audit fires;
    with it the step carries strictly more psums -> clean."""
    out = run_subprocess("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel.mesh_utils import smap
        from jax.sharding import Mesh
        from repro.analysis.static.core import Program
        from repro.analysis.static.passes import CollectivesPass

        mesh = Mesh(np.array(jax.devices()[:2]), ("bp",))

        def loss(w, x):
            return jax.lax.psum(jnp.sum(w * x), "bp")

        def buggy(w, x):                    # PARTIAL grad, never completed
            return jax.grad(loss)(w, x)

        def fixed(w, x):
            return jax.lax.psum(jax.grad(loss)(w, x), "bp")

        w = jax.ShapeDtypeStruct((8,), jnp.float32)
        x = jax.ShapeDtypeStruct((2, 8), jnp.float32)

        def cap(f):
            return jax.make_jaxpr(smap(f, mesh, (P(), P("bp")), P()))(w, x)

        base = cap(buggy)
        for step_fn, expect in ((buggy, True), (fixed, False)):
            prog = Program(name="fixture:completion", kind="train",
                           jaxprs={"step": cap(step_fn),
                                   "grad_nocomplete": base},
                           meta={"sync_axes": ("bp",), "dp_axes": ()})
            res = CollectivesPass().run(prog)
            fired = any(f.code == "GRAD_COMPLETION_MISSING"
                        for f in res.findings)
            assert fired == expect, (expect, res.findings)
        print("COMPLETION_AUDIT_OK")
    """)
    assert "COMPLETION_AUDIT_OK" in out


def test_dp_reduce_missing_fires():
    """A train step with a dp axis but zero psums over it never reduces
    gradients across replicas."""
    jx = jax.make_jaxpr(lambda x: x * 2.0)(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    res = CollectivesPass().run(Program(
        name="fixture:no_dp_reduce", kind="train", jaxprs={"step": jx},
        meta={"sync_axes": (), "dp_axes": ("data",)}))
    assert "DP_GRAD_REDUCE_MISSING" in _codes(res)


# ---------------------------------------------------------------------------
# Pass 3: precision
# ---------------------------------------------------------------------------

def test_bf16_accumulation_fixture_fires():
    r, h, c = 24, 2, 8

    def weighted_sum(w, v):                # contract k=r, output keeps q=r
        return jnp.einsum("hqk,khc->qhc", w, v)

    jx = jax.make_jaxpr(weighted_sum)(
        jax.ShapeDtypeStruct((h, r, r), jnp.bfloat16),
        jax.ShapeDtypeStruct((r, h, c), jnp.bfloat16))
    res = PrecisionPass().run(_fixture("bf16_dot", {"fwd": jx},
                                       seq_extents=(r,)))
    assert "BF16_ACCUM" in _codes(res)


def test_weight_gradient_shaped_dot_stays_clean():
    """A dot contracting ALL sequence dims away (channel-only output) is a
    weight gradient: bf16 by AMP design, must not flag."""
    r = 24

    def wgrad(act, cot):
        return jnp.einsum("rc,rd->cd", act, cot)

    jx = jax.make_jaxpr(wgrad)(
        jax.ShapeDtypeStruct((r, 8), jnp.bfloat16),
        jax.ShapeDtypeStruct((r, 16), jnp.bfloat16))
    res = PrecisionPass().run(_fixture("wgrad", {"fwd": jx},
                                       seq_extents=(r,)))
    assert "BF16_ACCUM" not in _codes(res)


def test_f32_accumulation_stays_clean():
    r, h, c = 24, 2, 8

    def weighted_sum(w, v):
        return jnp.einsum("hqk,khc->qhc", w, v,
                          preferred_element_type=jnp.float32)

    jx = jax.make_jaxpr(weighted_sum)(
        jax.ShapeDtypeStruct((h, r, r), jnp.bfloat16),
        jax.ShapeDtypeStruct((r, h, c), jnp.bfloat16))
    res = PrecisionPass().run(_fixture("f32_accum", {"fwd": jx},
                                       seq_extents=(r,)))
    assert "BF16_ACCUM" not in _codes(res)


def test_f64_fixture_fires():
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(lambda x: jnp.sum(x * 2.0))(
            jax.ShapeDtypeStruct((4,), jnp.float64))
    res = PrecisionPass().run(_fixture("f64", {"fwd": jx},
                                       seq_extents=()))
    assert "F64_PRESENT" in _codes(res)


def test_low_precision_norm_fixture_fires():
    def handrolled_ln(x):                  # no f32 upcast before rsqrt
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5)

    jx = jax.make_jaxpr(handrolled_ln)(
        jax.ShapeDtypeStruct((4, 8), jnp.bfloat16))
    res = PrecisionPass().run(_fixture("bf16_ln", {"fwd": jx},
                                       seq_extents=()))
    assert "LOW_PRECISION_NORM" in _codes(res)
    # the repo's layernorm upcasts: must stay clean
    from repro.nn import layers as nn
    p = jax.eval_shape(lambda: nn.layernorm_init(8))
    jx2 = jax.make_jaxpr(nn.layernorm)(
        p, jax.ShapeDtypeStruct((4, 8), jnp.bfloat16))
    res2 = PrecisionPass().run(_fixture("repo_ln", {"fwd": jx2},
                                        seq_extents=()))
    assert "LOW_PRECISION_NORM" not in _codes(res2)


# ---------------------------------------------------------------------------
# Pass 4: RNG hygiene
# ---------------------------------------------------------------------------

def test_reused_dropout_key_fixture_fires():
    def reuse(key, x):
        keep = jax.random.bernoulli(key, 0.9, x.shape)     # site 1
        noise = jax.random.normal(key, x.shape)            # site 2: same key
        return x * keep + noise

    jx = jax.make_jaxpr(reuse)(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4,), jnp.float32))
    res = RngPass().run(_fixture("key_reuse", {"step": jx}))
    assert "KEY_REUSED" in _codes(res)


def test_split_keys_stay_clean():
    def proper(key, x):
        k1, k2 = jax.random.split(key)
        keep = jax.random.bernoulli(k1, 0.9, x.shape)
        noise = jax.random.normal(k2, x.shape)
        return x * keep + noise

    jx = jax.make_jaxpr(proper)(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((4,), jnp.float32))
    res = RngPass().run(_fixture("key_split", {"step": jx}))
    assert res.findings == []


def test_loop_invariant_key_fixture_fires():
    def bad_loop(key, xs):
        def body(carry_key, x):            # key carried UNCHANGED: every
            noise = jax.random.normal(carry_key, x.shape)  # step re-draws it
            return carry_key, x + noise
        _, ys = jax.lax.scan(body, key, xs)
        return ys

    jx = jax.make_jaxpr(bad_loop)(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((3, 4), jnp.float32))
    res = RngPass().run(_fixture("loop_invariant", {"step": jx}))
    assert "RNG_LOOP_INVARIANT" in _codes(res)


def test_folded_loop_key_stays_clean():
    def good_loop(key, xs):
        def body(carry_key, x):
            step_key = jax.random.fold_in(carry_key, 0)
            nxt, sub = jax.random.split(carry_key)
            noise = jax.random.normal(sub, x.shape)
            del step_key
            return nxt, x + noise
        _, ys = jax.lax.scan(body, key, xs)
        return ys

    jx = jax.make_jaxpr(good_loop)(
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((3, 4), jnp.float32))
    res = RngPass().run(_fixture("loop_folded", {"step": jx}))
    assert "RNG_LOOP_INVARIANT" not in _codes(res)


# ---------------------------------------------------------------------------
# Pass 5: retrace / donation / overlap
# ---------------------------------------------------------------------------

def test_weak_type_input_fixture_fires():
    jx = jax.make_jaxpr(lambda x: x + 1)(2.0)   # Python float -> weak f32
    res = RetracePass().run(_fixture("weak", {"step": jx}))
    assert "WEAK_TYPE_INPUT" in _codes(res)
    jx2 = jax.make_jaxpr(lambda x: x + 1)(jnp.float32(2.0))
    res2 = RetracePass().run(_fixture("strong", {"step": jx2}))
    assert "WEAK_TYPE_INPUT" not in _codes(res2)


def test_static_recycle_retrace_fixture_fires():
    jx = jax.make_jaxpr(lambda x: x)(jnp.float32(0))
    res = RetracePass().run(_fixture(
        "static_recycle", {"step": jx},
        static_n_recycle=True, stochastic_recycling=True))
    assert "STATIC_RECYCLE_RETRACE" in _codes(res)


DONATION_DROPPED_HLO = """
HloModule jit_step, input_output_alias={  }

ENTRY %main {
  %p0 = f32[8]{0} parameter(0)
  ROOT %r = f32[8]{0} add(%p0, %p0)
}
"""

DONATION_KEPT_HLO = """
HloModule jit_step, input_output_alias={ {0}: (0, {}, must-alias) }

ENTRY %main {
  %p0 = f32[8]{0} parameter(0)
  ROOT %r = f32[8]{0} add(%p0, %p0)
}
"""


def test_donated_not_aliased_fixture_fires():
    jx = jax.make_jaxpr(lambda x: x)(jnp.float32(0))
    res = RetracePass().run(Program(
        name="fixture:donation_dropped", kind="fixture",
        jaxprs={"step": jx}, hlo_text=DONATION_DROPPED_HLO,
        meta={"donate_argnums": (0,), "backend": "tpu"}))
    assert "DONATED_NOT_ALIASED" in _codes(res)
    res2 = RetracePass().run(Program(
        name="fixture:donation_kept", kind="fixture",
        jaxprs={"step": jx}, hlo_text=DONATION_KEPT_HLO,
        meta={"donate_argnums": (0,), "backend": "tpu"}))
    assert "DONATED_NOT_ALIASED" not in _codes(res2)
    # CPU drops donation wholesale: skip, don't flag
    res3 = RetracePass().run(Program(
        name="fixture:donation_cpu", kind="fixture",
        jaxprs={"step": jx}, hlo_text=DONATION_DROPPED_HLO,
        meta={"donate_argnums": (0,), "backend": "cpu"}))
    assert "DONATED_NOT_ALIASED" not in _codes(res3)


EXPOSED_ASYNC_HLO = """
ENTRY %main {
  %p0 = bf16[16,4096]{1,0} parameter(0)
  %ags.1 = bf16[256,4096]{1,0} all-gather-start(%p0), replica_groups={{0,1}}
  %gte = f32[16,16]{1,0} get-tuple-element(%t), index=0
  %agd.1 = bf16[256,4096]{1,0} all-gather-done(%ags.1)
}
"""


def test_exposed_collective_fixture_fires():
    jx = jax.make_jaxpr(lambda x: x)(jnp.float32(0))
    res = RetracePass().run(Program(
        name="fixture:exposed", kind="fixture", jaxprs={"step": jx},
        hlo_text=EXPOSED_ASYNC_HLO, meta={"expect_overlap": True}))
    assert "EXPOSED_COLLECTIVE" in _codes(res)


# ---------------------------------------------------------------------------
# The gate: CLI over the full plan matrix + waiver machinery
# ---------------------------------------------------------------------------

def test_fingerprints_are_stable_and_waivable(tmp_path):
    f = Finding("precision", "BF16_ACCUM", "error", "train:serial",
                "message text may change freely",
                detail={"where": "a/volatile/path", "count": 3},
                detail_key={"role": "fwd", "out_shape": [24, 2, 8]})
    g = Finding("precision", "BF16_ACCUM", "error", "train:serial",
                "DIFFERENT message, same identity",
                detail={"where": "another/path", "count": 99},
                detail_key={"role": "fwd", "out_shape": [24, 2, 8]})
    assert f.fingerprint == g.fingerprint        # volatile detail excluded
    other = Finding("precision", "BF16_ACCUM", "error", "train:dap2",
                    "same code, other program",
                    detail_key={"role": "fwd", "out_shape": [24, 2, 8]})
    assert f.fingerprint != other.fingerprint

    from repro.analysis.static.core import PassResult
    report = Report(results=[PassResult("precision", "train:serial", [f])])
    unwaived, waived = report.partition({})
    assert len(unwaived) == 1 and not waived
    unwaived, waived = report.partition({f.fingerprint: "accepted: reason"})
    assert not unwaived and len(waived) == 1
    # round-trips through the report JSON with the waiver reason attached
    d = report.to_dict({f.fingerprint: "accepted: reason"})
    assert d["summary"]["n_unwaived"] == 0
    assert d["waived"][0]["waiver_reason"] == "accepted: reason"


def test_baseline_loader_rejects_unknown_version(tmp_path):
    from repro.analysis.lint import load_baseline
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"version": 2, "waivers": {}}))
    with pytest.raises(SystemExit):
        load_baseline(p)
    p.write_text(json.dumps({"version": 1, "waivers": {"abc": "why"}}))
    assert load_baseline(p)["waivers"] == {"abc": "why"}


def test_cli_full_matrix_gates_clean(tmp_path):
    """Tier-1j's teeth: the committed baseline admits ZERO unwaived findings
    across every train/fold plan in the matrix."""
    report = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint",
         "--report", str(report)],
        capture_output=True, text=True, timeout=560, cwd=_repo_root(),
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert proc.returncode == 0, (
        f"lint gate failed:\nSTDOUT:\n{proc.stdout[-4000:]}\n"
        f"STDERR:\n{proc.stderr[-4000:]}")
    assert "lint: OK" in proc.stdout
    data = json.loads(report.read_text())
    assert data["summary"]["n_unwaived"] == 0
    assert data["summary"]["n_programs"] == 8
    # every pass ran on every program
    assert data["summary"]["n_pass_runs"] == 8 * 5
