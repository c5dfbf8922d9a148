#!/usr/bin/env python3
"""Smoke test of the AF2 training path on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four chips: the BP and DAP plans

One chip, at AlphaFold2 model-1 width (``af2_initial``: c_m 256, c_z 128,
48 Evoformer blocks, 4 extra-MSA blocks, r 256, s 128, extra 1024) with
seeded random weights:

  (a) the default device must be a TPU;
  (b) the Pallas kernels (``evo_attention`` at the MSA-row, MSA-column and
      triangle shapes; ``triangle_mult`` outgoing and incoming) match their
      plain references, forward and gradients: with f32 inputs within the
      CPU tests' tolerances, both sides at ``highest`` matmul precision;
      with bf16 inputs, at the kernels' bf16 tiles, against the f32
      reference.  A control reading says which checks would catch a
      one-pass bf16 contraction;
  (c) ``TrainRunner`` takes 3 steps at batch 1 with the default impls and
      3 with ``evo_pallas`` attention + ``pallas`` tri-mult: losses finite,
      one compiled step program each.

``--four-chips`` runs only this: one step at global batch 2 (dropout off,
one recycle) under BP2 x DP2 and under overlapped DAP2 x DP2, each compared
with DP2 on the first two chips.

Exits non-zero on any failure, and at once when JAX finds no TPU.  The
last line of the output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# tolerances of the CPU tests (tests/test_kernels.py, tests/test_triangle.py,
# tests/test_parallel_equiv.py)
EVO_FWD_TOL = 2e-4      # f32 EVO_CASES
EVO_GRAD_TOL = 1e-3
EVO_BF16_TOL = 3e-2     # bf16 EVO_CASES
TRI_FWD_TOL = 1e-5
TRI_GRAD_TOL = 1e-4
TRI_BF16_TOL = 5e-2     # bf16 tri-mult against the f32 oracle
PLAN_LOSS_TOL = 2e-3
PLAN_PARAM_RTOL, PLAN_PARAM_ATOL = 2e-2, 2e-3
# Two f32 summation orders of n terms differ by about eps_f32 * sqrt(n)
# times the terms' size; SUM_MARGIN is the headroom over that estimate.
F32_EPS = 2.0 ** -23
SUM_MARGIN = 4.0


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    """A check that stays on under ``python -O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def f32_atol(want, tol: float, n_sum: int) -> float:
    """Absolute tolerance for an f32 tensor whose entries each sum
    ``n_sum`` products: the CPU tests' ``tol``, or the summation-order
    allowance ``SUM_MARGIN * eps_f32 * sqrt(n_sum) * mean|want|`` where
    that is larger (only the tri-mult weight gradients, n_sum = r^2)."""
    import numpy as np
    mean = float(np.mean(np.abs(np.asarray(want, np.float32))))
    return max(tol, SUM_MARGIN * F32_EPS * n_sum ** 0.5 * mean)


def bf16_atol(want, tol: float, name: str) -> float:
    """Absolute tolerance for a bf16 kernel against the f32 reference: the
    CPU tests' ``tol`` for the forward; for a gradient, which no CPU test
    checks in bf16, ``tol`` in units of its largest entry — the kernel
    rounds its operands and outputs to bf16, so its errors scale with the
    entries."""
    import numpy as np
    if name == "fwd":
        return tol
    return tol * float(np.max(np.abs(np.asarray(want, np.float32))))


def excess(got, want, rtol: float, atol: float):
    """(max |got - want|, max of |got - want| - (atol + rtol |want|)):
    the pair is close when the second is <= 0."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    d = np.abs(got - want)
    return float(np.max(d)), float(np.max(d - atol - rtol * np.abs(want)))


def check_close(name, got, want, rtol: float, atol: float) -> None:
    import numpy as np
    err, over = excess(got, want, rtol, atol)
    log(f"  {name}: max|diff| {err:.3e} (rtol {rtol:g}, atol {atol:.3g})")
    require(np.isfinite(err) and over <= 0,
            f"{name}: max|diff| {err:.3e} exceeds rtol {rtol:g} + atol "
            f"{atol:.3g} by {over:.3e}")


# ---------------------------------------------------------------------------
# (b) kernels against their references
# ---------------------------------------------------------------------------

def fwd_and_grads(fn, args, argnames, cot, precision):
    """[(name, tensor)] of ``fn``'s output and the gradients of
    ``sum(fn(*args) * cot)`` for every argument, at matmul ``precision``
    (``None``: JAX's default, as training runs)."""
    import contextlib
    import jax
    import jax.numpy as jnp

    def loss(*a):
        return (fn(*a).astype(jnp.float32) * cot).sum()

    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        out = jax.jit(fn)(*args)
        grads = jax.jit(jax.grad(loss, argnums=tuple(range(len(args)))))(
            *args)
    named = [("fwd", out)]
    for argname, g in zip(argnames, grads):
        named += [(f"d{argname}{jax.tree_util.keystr(path)}", leaf)
                  for path, leaf in jax.tree_util.tree_leaves_with_path(g)]
    return named


def kernel_vs_reference(kern, plain, args, argnames, cot, *, fwd_tol,
                        grad_tol, bf16_tol, n_sum=lambda name: 1):
    """Three readings of a kernel against its plain reference:

    f32 inputs — kernel and reference both at ``highest`` precision, the
    CPU tests' tolerances (``f32_atol`` for sums of ``n_sum(name)`` terms);
    control — the reference at ``default`` precision (one bf16 MXU pass)
    against itself at ``highest``, through the same tolerances: the tensors
    it flags are those whose check can see a one-pass contraction;
    bf16 inputs — the kernel at its bf16 tiles and JAX's default precision,
    as training runs it, against the f32 reference on the same values, at
    ``bf16_tol`` (``bf16_atol``).
    """
    import jax
    import jax.numpy as jnp

    got = fwd_and_grads(kern, args, argnames, cot, "highest")
    want = fwd_and_grads(plain, args, argnames, cot, "highest")
    loose = fwd_and_grads(plain, args, argnames, cot, "default")
    flagged = []
    for (name, g), (_, w), (_, lo) in zip(got, want, loose):
        tol = fwd_tol if name == "fwd" else grad_tol
        atol = f32_atol(w, tol, n_sum(name))
        check_close(name, g, w, tol, atol)
        err, over = excess(lo, w, tol, atol)
        if over > 0:
            flagged.append(f"{name} {err:.3e}")
    log(f"  control, reference at one bf16 pass: out of tolerance in "
        f"{len(flagged)}/{len(want)} tensors: " + ", ".join(flagged))

    args16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), args)
    args16_32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                       args16)
    log("  bf16 inputs:")
    got = fwd_and_grads(kern, args16, argnames, cot, None)
    want = fwd_and_grads(plain, args16_32, argnames, cot, "highest")
    for (name, g), (_, w) in zip(got, want):
        check_close(name, g, w, bf16_tol, bf16_atol(w, bf16_tol, name))


def check_attention(cfg):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.nn.attention import attention_reference

    ev = cfg.evoformer
    shapes = {   # name: (lead rows, sequence, heads, head channels, biased)
        "msa_row": (cfg.n_seq, cfg.n_res, ev.n_head_msa, ev.c_hidden_att,
                    True),
        "msa_col": (cfg.n_res, cfg.n_seq, ev.n_head_msa, ev.c_hidden_att,
                    False),
        "triangle": (cfg.n_res, cfg.n_res, ev.n_head_pair,
                     ev.c_hidden_pair_att, True),
    }
    for name, (L, s, h, c, biased) in shapes.items():
        ks = jax.random.split(jax.random.PRNGKey(len(name)), 5)
        q, k, v, gate = (jax.random.normal(kk, (L, s, h, c)) for kk in ks[:4])
        bias = jax.random.normal(ks[4], (h, s, s))
        w = jnp.cos(jnp.arange(c))          # non-uniform cotangent
        if biased:
            kern = ops.evo_attention
            plain = ref.evo_attention_ref
            args, argnames = (q, k, v, bias, gate), "q k v bias gate"
        else:
            kern = ops.evo_attention_nobias
            plain = lambda q, k, v, g: (        # noqa: E731
                jax.nn.sigmoid(g) * attention_reference(q, k, v))
            args, argnames = (q, k, v, gate), "q k v gate"
        log(f"evo_attention {name} (L={L} S={s} H={h} C={c} "
            f"bias={biased}):")
        kernel_vs_reference(kern, plain, args, argnames.split(), w,
                            fwd_tol=EVO_FWD_TOL, grad_tol=EVO_GRAD_TOL,
                            bf16_tol=EVO_BF16_TOL)


def check_triangle_mult(cfg):
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.core import evoformer as evo
    from repro.nn.layers import randomize

    ev = cfg.evoformer
    r, c_z, c = cfg.n_res, ev.c_z, ev.c_hidden_mul
    p = randomize(evo.triangle_mult_init(jax.random.PRNGKey(0), c_z, c),
                  jax.random.PRNGKey(7))
    z = jax.random.normal(jax.random.PRNGKey(1), (r, r, c_z))
    w = jnp.cos(jnp.arange(c_z))
    impl = {i: dataclasses.replace(ev, tri_mult_impl=i)
            for i in ("pallas", "reference")}
    # a weight gradient sums over all r^2 pair positions
    n_sum = lambda name: r * r if name.startswith("dp") else 1  # noqa: E731
    for outgoing in (True, False):
        def apply(ecfg):
            return lambda p, z: evo.tri_mult_apply(p, ecfg, z,
                                                   outgoing=outgoing)
        log(f"triangle_mult {'outgoing' if outgoing else 'incoming'} "
            f"(r={r} c_z={c_z} c_mul={c}):")
        kernel_vs_reference(apply(impl["pallas"]), apply(impl["reference"]),
                            (p, z), ("p", "z"), w, fwd_tol=TRI_FWD_TOL,
                            grad_tol=TRI_GRAD_TOL, bf16_tol=TRI_BF16_TOL,
                            n_sum=n_sum)


# ---------------------------------------------------------------------------
# (c) the training loop
# ---------------------------------------------------------------------------

def train_three_steps(cfg, plan, label):
    import math
    import jax
    from repro.obs import SpanTracer
    from repro.train.trainer import TrainRunner

    tracer = SpanTracer()
    t0 = time.perf_counter()
    runner = TrainRunner(cfg, plan, batch_size=1, seed=0, tracer=tracer)
    log(f"train [{label}] {runner.plan.describe()}: set-up "
        f"{time.perf_counter() - t0:.1f}s")
    runner.run(3)
    losses = list(runner.history["loss"])
    for i, span in enumerate(tracer.spans("step")):
        what = "compile + step" if i == 0 else "step"
        log(f"  step {i} ({what}, n_recycle "
            f"{runner.history['n_recycle'][i]}): "
            f"{span['dur'] / 1e6:.3f}s  loss {losses[i]:.4f}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"  train_compiles {runner.train_compiles}; peak_bytes_in_use "
        + (f"{peak} ({peak / 2**30:.2f} GiB, process peak so far)"
           if peak is not None else "not reported by this backend"))
    require(len(losses) == 3 and all(math.isfinite(x) for x in losses),
            f"3 finite losses, got {losses}")
    require(runner.train_compiles == 1,
            f"one compiled step, got {runner.train_compiles}")


def one_chip(cfg) -> None:
    from repro.parallel.plan import ParallelPlan

    check_attention(cfg)
    check_triangle_mult(cfg)
    train_three_steps(cfg, ParallelPlan(data=1), "default impls")
    gc.collect()
    train_three_steps(cfg, ParallelPlan(data=1, attention_impl="evo_pallas",
                                        tri_mult_impl="pallas"),
                      "evo_pallas + pallas")


# ---------------------------------------------------------------------------
# --four-chips: BP and DAP against DP on the same global batch
# ---------------------------------------------------------------------------

def four_chips(cfg) -> None:
    import concurrent.futures
    import contextlib
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import model as af2
    from repro.data.protein import protein_batch
    from repro.nn.layers import randomize
    from repro.parallel.plan import ParallelPlan
    from repro.train.optim import sgd
    from repro.train.trainstep import make_af2_train_step

    devices = jax.devices()
    require(len(devices) == 4, f"--four-chips needs 4 devices, got {devices}")
    # SGD makes the param delta proportional to the gradient, so comparing
    # updated params compares gradients (tests/test_parallel_equiv.py)
    opt = sgd(0.1)

    def inputs():
        params = randomize(af2.init_params(jax.random.PRNGKey(0), cfg),
                           jax.random.PRNGKey(7))
        return {"state": {"params": params, "opt": opt.init(params)},
                "batch": protein_batch(0, 0, 2, cfg),
                "key": jax.random.PRNGKey(0)}

    def make_inputs():
        # one program on the host CPU where JAX has one (it compiles while
        # the step programs trace), kept on the host between runs, so each
        # plan has the chips' memory to itself
        t0 = time.perf_counter()
        try:
            host = jax.default_device(jax.devices("cpu")[0])
        except RuntimeError:
            host = contextlib.nullcontext()
        with host:
            made = jax.device_get(jax.jit(inputs)())
        log(f"inputs made in {time.perf_counter() - t0:.1f}s")
        return made

    plans = (ParallelPlan(data=2), ParallelPlan(data=2, branch=2),
             ParallelPlan(data=2, dap=2, overlap_dap=True))
    shapes = jax.eval_shape(inputs)

    def compile_plan(plan):
        step, built = make_af2_train_step(
            cfg, opt, plan, n_recycle=1, deterministic=True,
            devices=devices[:plan.n_devices])
        sh = {"state": NamedSharding(built.mesh, built.state_spec),
              "batch": NamedSharding(built.mesh, built.batch_spec),
              "key": NamedSharding(built.mesh, P())}
        args = [jax.tree_util.tree_map(
            lambda x, s=sh[k]: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                    sharding=s), shapes[k])
            for k in ("state", "batch", "key")]
        t0 = time.perf_counter()
        compiled = jax.jit(step, donate_argnums=(0,)).lower(*args).compile()
        log(f"{plan.describe()}: compiled in {time.perf_counter() - t0:.1f}s")
        return compiled, sh

    # the inputs are made and the three programs compiled at once, on the
    # host's cores
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(plans) + 1) as pool:
        made = pool.submit(make_inputs)
        programs = list(pool.map(compile_plan, plans))
        made = made.result()
    log(f"set-up took {time.perf_counter() - t0:.1f}s")

    def run(plan, compiled, sh):
        b = jax.device_put(made["batch"], sh["batch"])
        state = jax.device_put(made["state"], sh["state"])
        spread = {len(x.sharding.device_set)
                  for x in jax.tree_util.tree_leaves(b)}
        t0 = time.perf_counter()
        state, m = compiled(state, b, jax.device_put(made["key"], sh["key"]))
        loss = float(m["loss"])
        dt = time.perf_counter() - t0
        out = {len(x.sharding.device_set)
               for x in jax.tree_util.tree_leaves((state, m))}
        log(f"{plan.describe()}: loss {loss:.6f}; step {dt:.1f}s; "
            f"batch on {spread} devices, outputs on {out}")
        require(spread == out == {plan.n_devices},
                f"batch and outputs on all {plan.n_devices} devices")
        return loss, jax.device_get(state["params"])

    l_ref, p_ref = run(plans[0], *programs[0])
    programs[0] = None      # its executable leaves the chips' memory
    gc.collect()
    failed = []
    for plan, program in zip(plans[1:], programs[1:]):
        loss, new_params = run(plan, *program)
        t0 = time.perf_counter()
        loss_err, loss_over = excess(loss, l_ref, PLAN_LOSS_TOL,
                                     PLAN_LOSS_TOL)
        log(f"  loss vs DP2: |diff| {loss_err:.3e} (tol {PLAN_LOSS_TOL:g})")
        worst, bad, nonfinite = -np.inf, [], []
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p_ref),
                                jax.tree_util.tree_leaves(new_params)):
            name = jax.tree_util.keystr(path)
            n_bad = int(np.size(b) - np.isfinite(b).sum())
            n_bad_ref = int(np.size(a) - np.isfinite(a).sum())
            if n_bad or n_bad_ref:
                nonfinite.append(f"{name} {n_bad}/{np.size(b)} "
                                 f"(DP2: {n_bad_ref})")
                continue
            _, over = excess(b, a, PLAN_PARAM_RTOL, PLAN_PARAM_ATOL)
            worst = max(worst, over)
            if over > 0:
                bad.append(f"{name} by {over:.3e}")
        log(f"  updated params vs DP2: worst excess over rtol "
            f"{PLAN_PARAM_RTOL:g} + atol {PLAN_PARAM_ATOL:g} is "
            f"{worst:.3e}; {len(bad)} leaves out of tolerance, "
            f"{len(nonfinite)} non-finite")
        for line in (bad + nonfinite)[:40]:
            log(f"    {line}")
        log(f"  compared in {time.perf_counter() - t0:.1f}s")
        if loss_over > 0 or bad or nonfinite:
            failed.append(plan.describe())
    require(not failed, f"plans that do not match DP2: {failed}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip BP/DAP vs DP comparison")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX's default device is {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}; jax {jax.__version__}; compile cache {cache}")
    from repro.core.config import af2_initial
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(af2_initial())
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
