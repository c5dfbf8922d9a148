"""Kernel micro-benchmarks: Pallas(interpret) is a CORRECTNESS harness on
CPU — the meaningful CPU numbers are chunked-vs-reference XLA paths; Pallas
TPU timing comes from the roofline model (see EXPERIMENTS.md §Perf).

Every suite records structured rows (op, shape, impl, ms, bytes) via
``common.emit_kernel``; ``benchmarks.run`` dumps them to BENCH_kernels.json
at the repo root — the machine-readable perf trajectory subsequent PRs diff
against.  ``bytes`` is the impl's materialized-intermediate footprint
(0 = fully fused).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit_kernel, timeit
from repro.nn.attention import attention_chunked, attention_reference


def attention_paths():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, h, kv, d = 1, 512, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)
    t_ref = timeit(jax.jit(lambda q, k, v: attention_reference(
        q, k, v, causal=True)), q, k, v)
    emit_kernel("lm_attn", f"s{s}", "reference", t_ref, b * h * s * s * 4)
    for chunk in (64, 128, 256):
        t = timeit(jax.jit(lambda q, k, v: attention_chunked(
            q, k, v, causal=True, chunk_size=chunk)), q, k, v)
        emit_kernel("lm_attn", f"s{s}", f"chunked{chunk}", t,
                    b * h * s * chunk * 4, f"vs_ref={t_ref / t - 1:+.1%}")


def evoformer_attention_paths():
    """Paper hot path (Table 2: Evoformer row/triangle attention = 62-78% of
    step time): fused Pallas evo_attention vs chunked vs reference, all with
    the bias+gate epilogue included.  On CPU the Pallas number is
    interpret-mode — a correctness/trajectory harness, not a speed claim;
    on TPU the identical call lowers to Mosaic."""
    from repro.kernels import ops as kops
    L, s, h, c = 8, 128, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v, gate = (jax.random.normal(kk, (L, s, h, c), jnp.float32)
                     for kk in ks[:4])
    bias = jax.random.normal(ks[4], (h, s, s), jnp.float32)
    shape = f"L{L}s{s}"

    def gated(attn_out, g):
        # g must be the traced jit parameter, not the closed-over array —
        # otherwise sigmoid(gate) constant-folds out of the baseline timings
        return jax.nn.sigmoid(g) * attn_out

    t_ref = timeit(jax.jit(lambda q, k, v, b, g: gated(
        attention_reference(q, k, v, bias=b), g)), q, k, v, bias, gate)
    emit_kernel("evo_attn", shape, "reference", t_ref, L * h * s * s * 4)
    for chunk in (32, 64):
        t = timeit(jax.jit(lambda q, k, v, b, g, ch=chunk: gated(
            attention_chunked(q, k, v, bias=b, chunk_size=ch), g)),
            q, k, v, bias, gate)
        emit_kernel("evo_attn", shape, f"chunked{chunk}", t,
                    L * h * s * chunk * 4, f"vs_ref={t_ref / t - 1:+.1%}")
    t_pal = timeit(jax.jit(kops.evo_attention), q, k, v, bias, gate)
    emit_kernel("evo_attn", shape, "pallas", t_pal, 0,
                "interpret_on_cpu;mosaic_on_tpu")
    t_bwd = timeit(jax.jit(jax.grad(
        lambda q: kops.evo_attention(q, k, v, bias, gate).sum())), q)
    emit_kernel("evo_attn_bwd", shape, "pallas", t_bwd, 0,
                "flash_backward;no_chunked_recompute")


def opm_paths():
    """Outer-product mean: fused row-chunked contraction vs naive (which
    materializes the (r, r, c_opm^2) tensor before projecting)."""
    from repro.core import evoformer as evo
    s, r, c_m, c_opm, c_z = 32, 64, 32, 16, 64
    p = evo.opm_init(jax.random.PRNGKey(0), c_m, c_opm, c_z)
    msa = jax.random.normal(jax.random.PRNGKey(1), (s, r, c_m), jnp.float32)
    t_naive = timeit(jax.jit(lambda p, m: evo.outer_product_mean(p, m)),
                     p, msa)
    emit_kernel("opm", f"r{r}", "naive", t_naive, r * r * c_opm * c_opm * 4)
    for rc in (8, 16, 32):
        t = timeit(jax.jit(lambda p, m, rc=rc: evo.outer_product_mean_fused(
            p, m, row_chunk=rc)), p, msa)
        emit_kernel("opm", f"r{r}", f"fused_rc{rc}", t,
                    rc * r * c_opm * c_opm * 4,
                    f"vs_naive={t_naive / t - 1:+.1%}")


def triangle_mult_paths():
    """Triangle-multiplicative update (the pair-stack hot path this repo's
    PR 3 fuses): reference vs i/k-chunked online accumulation vs the fused
    Pallas kernel (interpret mode on CPU), fwd and fwd+bwd.  ``bytes`` =
    the (r, r, 2c) gated-projection pair (reference), the fp32 slab
    accumulator (chunked), or 0 (pallas: nothing between the LN'd input and
    the gated output touches HBM)."""
    import dataclasses
    from repro.core import evoformer as evo
    from repro.core.config import af2_tiny

    r, c_z, c = 64, 32, 32
    p = evo.triangle_mult_init(jax.random.PRNGKey(0), c_z, c)
    # out-proj weights are zero-init: randomize so nothing constant-folds
    p = jax.tree_util.tree_map(
        lambda l: l + 0.02 * jax.random.normal(jax.random.PRNGKey(7),
                                               l.shape, l.dtype), p)
    z = jax.random.normal(jax.random.PRNGKey(1), (r, r, c_z), jnp.float32)
    base = af2_tiny().evoformer
    chunk = 16
    footprint = {"reference": r * r * 2 * c * 4,
                 "chunked": chunk * r * c * 4,
                 "pallas": 0}
    times = {}
    for impl in ("reference", "chunked", "pallas"):
        cfg = dataclasses.replace(base, tri_mult_impl=impl,
                                  tri_mult_chunk=chunk)
        fwd = jax.jit(lambda p, z, cfg=cfg: evo.tri_mult_apply(
            p, cfg, z, outgoing=True))
        times[impl] = t = timeit(fwd, p, z)
        note = ("" if impl == "reference" else
                f"vs_ref={times['reference'] / t - 1:+.1%}")
        if impl == "pallas":
            note += ";interpret_on_cpu;mosaic_on_tpu"
        emit_kernel("tri_mult", f"r{r}", impl, t, footprint[impl], note)
        t_bwd = timeit(jax.jit(jax.grad(
            lambda z, cfg=cfg: evo.tri_mult_apply(
                p, cfg, z, outgoing=True).sum())), z)
        emit_kernel("tri_mult_bwd", f"r{r}", impl, t_bwd, footprint[impl],
                    "pallas_native_vjp" if impl == "pallas" else "")


def ssd_paths():
    from repro.models.ssm import ssd_chunked, ssd_reference
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    t, h, p, n = 1024, 8, 32, 16
    x = jax.random.normal(ks[0], (t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, h)) * 0.5)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (t, n))
    C = jax.random.normal(ks[4], (t, n))
    D = jnp.ones((h,))
    t_ref = timeit(jax.jit(lambda *a: ssd_reference(*a)), x, dt, A, B, C, D)
    emit_kernel("ssd", f"t{t}", "recurrence", t_ref, 0)
    for chunk in (64, 256):
        tt = timeit(jax.jit(lambda *a: ssd_chunked(*a, chunk=chunk)),
                    x, dt, A, B, C, D)
        emit_kernel("ssd", f"t{t}", f"chunked{chunk}", tt, 0,
                    f"speedup_vs_scan={t_ref / tt:.1f}x")


def dap_block_overlap_paths():
    """Overlap-vs-sync DAP block schedule (ParallelPlan.overlap_dap).

    The CPU backend executes shard_map collectives synchronously — there is
    no async scheduler to hide a gather behind compute, so wall-clock here
    cannot expose the overlap win (it only sees the consume phase's small
    replicated-math cost, a wash within host noise).  Following the
    fold_long_dap_derived convention, the rows price the schedule with the
    overlap-aware roofline (estimate_block_time's max-composition), CPU-
    CALIBRATED: the sync row's ms IS the measured per-block time (8 fake
    devices, 2-block scan stack, median of alternated rounds), and the
    overlap row scales it by the model's overlap/sync ratio.  The raw
    overlap measurement and the prediction/measurement ratio ride in
    ``derived`` — the ratio staying inside [0.5x, 2x] is the acceptance
    band for the max-composed cost model.  ``bytes`` is the per-device
    per-block collective payload (dap_comm_bytes, fp32)."""
    import json
    import os
    import subprocess
    import sys

    from repro.analysis.roofline import dap_comm_bytes, estimate_block_time
    from repro.core.config import af2_tiny

    shapes = ((16, 32), (16, 64))
    dap = 8
    code = f"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={dap}"
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.config import af2_tiny
from repro.core import model as af2
from repro.parallel import dap as dap_lib
from repro.parallel.mesh_utils import make_mesh, smap

mesh = make_mesh(({dap},), ("dap",))
out = {{}}
for (s, r) in {shapes!r}:
    cfg = af2_tiny(variant="parallel", n_seq=s, n_res=r)
    ev = cfg.evoformer
    params = af2.stack_init(jax.random.PRNGKey(0), ev, 2, scan=True)
    msa = jax.random.normal(jax.random.PRNGKey(1), (s, r, ev.c_m))
    z = jax.random.normal(jax.random.PRNGKey(2), (r, r, ev.c_z))
    fns = {{}}
    for name, overlap in (("sync", False), ("overlap", True)):
        bf = dap_lib.make_dap_block_fn(s, overlap=overlap)
        def fn(p, m, zz, bf=bf):
            m_l, z_l = dap_lib.shard_inputs(m, zz)
            m_l, z_l = af2.evoformer_stack(p, ev, 2, m_l, z_l, scan=True,
                                           remat=False, block_fn=bf)
            return dap_lib.unshard_outputs(m_l, z_l)
        fns[name] = jax.jit(smap(fn, mesh, (P(), P(), P()), (P(), P())))
    for f in fns.values():
        jax.block_until_ready(f(params, msa, z))
        jax.block_until_ready(f(params, msa, z))
    times = {{k: [] for k in fns}}
    for _ in range(15):  # alternate so drift hits both schedules equally
        for k, f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f(params, msa, z))
            times[k].append(time.perf_counter() - t0)
    out[f"s{{s}}r{{r}}"] = {{k: sorted(ts)[len(ts) // 2] / 2  # 2 blocks
                          for k, ts in times.items()}}
print("RESULT " + json.dumps(out))
"""
    # the child is pinned to the CPU: this parent may hold the chip, and
    # the rows measure the fake-device CPU schedule by design
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=900,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        raise RuntimeError(f"dap_block subprocess failed:\n"
                           f"{proc.stderr[-2000:]}")
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    measured = json.loads(line[len("RESULT "):])

    for (s, r) in shapes:
        cfg = af2_tiny(variant="parallel", n_seq=s, n_res=r)
        meas = measured[f"s{s}r{r}"]
        pred_sync = estimate_block_time(cfg, dap=dap, overlap=False,
                                        fwd_bwd=False, elt=4)
        pred_ov = estimate_block_time(cfg, dap=dap, overlap=True,
                                      fwd_bwd=False, elt=4)
        shape = f"s{s}r{r}d{dap}"
        emit_kernel("dap_block", shape, "sync", meas["sync"],
                    sum(dap_comm_bytes(cfg, dap, elt=4)),
                    f"measured;model_block_us={pred_sync * 1e6:.1f}")
        # calibrated overlap row: measured sync x the model's overlap ratio
        t_row = meas["sync"] * pred_ov / pred_sync
        ratio = t_row / meas["overlap"]
        assert 0.5 <= ratio <= 2.0, (
            f"max-composed roofline {t_row * 1e3:.2f}ms is not within 2x of "
            f"the measured overlap schedule {meas['overlap'] * 1e3:.2f}ms")
        emit_kernel("dap_block", shape, "overlap", t_row,
                    sum(dap_comm_bytes(cfg, dap, elt=4, overlap=True)),
                    f"calibrated;measured_us={meas['overlap'] * 1e6:.1f};"
                    f"pred_vs_meas={ratio:.2f}x;"
                    f"model_speedup={pred_sync / pred_ov:.2f}x")


ALL = [attention_paths, evoformer_attention_paths, opm_paths,
       triangle_mult_paths, ssd_paths, dap_block_overlap_paths]
