"""Training launcher.

On the production pod this runs under the multi-host runtime (one process
per host; jax.distributed.initialize); on CPU it drives reduced configs for
end-to-end validation.  Integrates: sharded data pipeline, checkpoint
manager (atomic/keep-N/async + preemption save), straggler watchdog, and
either the AF2 shard_map step (BP x DAP x DP) or the LM GSPMD step.

The AF2 path is laid out by a ``repro.parallel.plan.ParallelPlan``: either
explicit extents (``--bp/--dap/--pods``) or ``--auto-plan`` (roofline-driven
DP x BP x DAP selection for the device count and batch).

Examples:
  PYTHONPATH=src python -m repro.launch.train --af2 tiny --steps 20 \
      --devices 8 --bp 2 --dap 2 --batch 8
  PYTHONPATH=src python -m repro.launch.train --af2 small --steps 20 \
      --devices 8 --auto-plan --batch 4
  PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke \
      --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# Async-collective-fusion preset for TPU pods (SNIPPETS.md Snippet 3): lets
# XLA issue DAP's all_gather/all_to_all as async pairs and schedule compute
# between start/done — the compiler-level half of the overlapped-DAP
# schedule (ParallelPlan.overlap_dap reorders the ops so there IS compute to
# slot in; these flags let the scheduler actually hide the transfer).
# Emitted by --print-tpu-env; eval the output in the launch shell:
#   eval "$(python -m repro.launch.train --print-tpu-env)"
# libtpu 0.0.34 accepts every flag here (it refuses to start on an unknown
# one).
TPU_ASYNC_COLLECTIVE_FLAGS = (
    "--xla_tpu_enable_flash_attention=false",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_tpu_scoped_vmem_limit_kib=98304",
    "--xla_tpu_enable_all_experimental_scheduler_features=true",
    "--xla_tpu_enable_scheduler_memory_pressure_tracking=true",
)


def print_tpu_env():
    """Print a shell line that appends the preset to ``$LIBTPU_INIT_ARGS``:
    flags the environment already sets (a machine may need its own) are
    kept, never replaced."""
    print("# async collective fusion preset (overlapped-DAP schedule): "
          "eval this in the launch shell")
    flags = " ".join(TPU_ASYNC_COLLECTIVE_FLAGS)
    print('export LIBTPU_INIT_ARGS="${LIBTPU_INIT_ARGS:+$LIBTPU_INIT_ARGS }'
          f'{flags}"')


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="assigned LM arch id")
    ap.add_argument("--af2", choices=["tiny", "small", "initial", "finetune"])
    ap.add_argument("--variant", default="parallel")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--devices", type=int, default=0,
                    help="fake host devices (CPU validation only)")
    ap.add_argument("--bp", type=int, default=1)
    ap.add_argument("--dap", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--auto-plan", action="store_true",
                    help="pick the DP x BP x DAP split from the roofline "
                         "cost model (overrides --bp/--dap)")
    ap.add_argument("--overlap-dap", choices=["auto", "on", "off"],
                    default="auto",
                    help="communication-overlapped DAP schedule (double-"
                         "buffered prefetch carry): 'auto' enables it for "
                         "pure-DAP 'parallel' groups, 'on'/'off' force it "
                         "(on is rejected for hybrid/serial plans)")
    ap.add_argument("--print-tpu-env", action="store_true",
                    help="print a shell line appending the async-"
                         "collective-fusion preset to $LIBTPU_INIT_ARGS "
                         "(eval it) and exit")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--recycle-sample", action="store_true",
                    help="AF2: stochastic recycling — per-step n_recycle ~ "
                         "Uniform{1..max-recycle} drawn on host, fed to ONE "
                         "compiled step as a traced bound")
    ap.add_argument("--max-recycle", type=int, default=0,
                    help="AF2: recycle-sampling upper bound "
                         "(0 = cfg.max_recycle)")
    ap.add_argument("--ema", type=float, default=0.999,
                    help="AF2: EMA decay for eval params (0 disables)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="AF2: lDDT-Cα eval cadence on the held-out split "
                         "(0 disables); also logs the input pipeline's "
                         "per-stage stall report at the same cadence")
    ap.add_argument("--data-workers", type=int, default=1,
                    help="AF2: host featurize worker threads (0 = inline "
                         "featurization in the train loop, no overlap)")
    ap.add_argument("--data-source", choices=["synthetic", "fasta"],
                    default="synthetic",
                    help="AF2: input source — 'synthetic' is the historic "
                         "deterministic protein_batch stream; 'fasta' runs "
                         "the record-ingest path (parse + MSA stack + "
                         "featurize_record) over --fasta or a bundled demo "
                         "set")
    ap.add_argument("--fasta", default="",
                    help="AF2: FASTA file for --data-source fasta (empty = "
                         "deterministic demo records)")
    ap.add_argument("--bucket-by-length", action="store_true",
                    help="AF2: group records of similar length per batch "
                         "(record sources only; batches still pad to the "
                         "config's terminal bucket so the compiled step "
                         "keeps one shape)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--adapt-plan", action="store_true",
                    help="allow --resume from a checkpoint written under a "
                         "different ParallelPlan")
    ap.add_argument("--compress-pod-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=1)
    # -- observability (DESIGN.md §14) --------------------------------------
    ap.add_argument("--metrics-out", default="",
                    help="AF2: write the obs metric stream (loss, step_s, "
                         "data stalls, attribution, ckpt timings) as JSONL "
                         "to this path")
    ap.add_argument("--trace-out", default="",
                    help="AF2: write host spans (featurize/device_put/step/"
                         "eval/checkpoint) as Chrome-trace JSON — load in "
                         "Perfetto or chrome://tracing")
    ap.add_argument("--profile-steps", default="",
                    help="AF2: 'A:B' — arm jax.profiler.trace over steps "
                         "[A, B), aligned to the host spans' step ids; the "
                         "device trace lands in <trace-out>.profile/ (or "
                         "./jax_profile)")
    ap.add_argument("--obs-every", type=int, default=0,
                    help="AF2: print a periodic console summary of the "
                         "latest metrics (incl. the data stall report) "
                         "every N steps (0 disables)")
    ap.add_argument("--hlo-check", action="store_true",
                    help="AF2: lower the train step once, check async-"
                         "collective overlap in the optimized HLO, record "
                         "the verdict as the train/async_overlap_ok metric")
    ap.add_argument("--lint", action="store_true",
                    help="AF2: run the static-analyzer pass suite (DESIGN.md "
                         "§15) over THIS launch's ParallelPlan before "
                         "training (on the calibrated lint probe config), "
                         "record lint/* metrics, and refuse to train if any "
                         "finding is unwaived in LINT_BASELINE.json")
    args = ap.parse_args()

    if args.print_tpu_env:
        print_tpu_env()
        return

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.af2:
        run_af2(args, jax, jnp, np)
    else:
        run_lm(args, jax, jnp, np)


def run_af2(args, jax, jnp, np):
    from repro.core.config import af2_tiny, af2_small, af2_initial, af2_finetune
    from repro.train.optim import adamw, af2_lr_schedule
    from repro.train.trainer import TrainRunner
    from repro.parallel.plan import ParallelPlan, auto_plan

    cfg = {"tiny": af2_tiny, "small": af2_small, "initial": af2_initial,
           "finetune": af2_finetune}[args.af2]()
    n_dev = len(jax.devices())
    overlap = {"auto": None, "on": True, "off": False}[args.overlap_dap]
    if args.auto_plan:
        plan = auto_plan(n_dev, cfg, global_batch=args.batch, pod=args.pods,
                         variant=args.variant, overlap_dap=overlap,
                         compress_pod_grads=args.compress_pod_grads)
    else:
        plan = ParallelPlan.from_flags(
            n_dev, bp=args.bp, dap=args.dap, pod=args.pods,
            variant=args.variant, overlap_dap=overlap,
            compress_pod_grads=args.compress_pod_grads)

    source = None
    if args.data_source == "fasta":
        from repro.data.ingest import FastaSource, demo_fasta
        if args.fasta:
            source = FastaSource(args.fasta, cfg, is_path=True)
        else:
            source = FastaSource(demo_fasta(cfg, seed=args.seed), cfg,
                                 is_path=False)
        print(f"data: fasta source, {len(source)} records"
              + (f" from {args.fasta}" if args.fasta else " (bundled demo)"))
    if args.bucket_by_length and source is None:
        raise SystemExit("--bucket-by-length needs --data-source fasta "
                         "(the synthetic stream is fixed-shape)")

    # -- telemetry wiring (DESIGN.md §14) -----------------------------------
    from repro.obs import (ConsoleSink, JsonlSink, MetricRegistry,
                           ProfileWindow, SpanTracer, parse_profile_steps)
    sinks = []
    if args.metrics_out:
        sinks.append(JsonlSink(args.metrics_out))
    if args.obs_every:
        sinks.append(ConsoleSink(every=args.obs_every,
                                 prefixes=("data/", "train/", "ckpt/")))
    obs = MetricRegistry(sinks=sinks)
    tracer = SpanTracer() if args.trace_out else None
    profile_window = None
    if args.profile_steps:
        lo, hi = parse_profile_steps(args.profile_steps)
        logdir = (f"{args.trace_out}.profile" if args.trace_out
                  else "jax_profile")
        profile_window = ProfileWindow(lo, hi, logdir)

    # -- pre-flight static analysis (DESIGN.md §15) -------------------------
    # Lints the LAUNCH plan, not the fixed CI matrix: the probe config is
    # the calibrated lint_config (launch configs like af2_tiny have channel
    # dims that collide with sequence extents — LINT_CFG_NOTES), the plan is
    # this run's.  A matrix waiver keyed on e.g. "train:dap2" does not carry
    # over to "train:launch" — launch-plan findings need their own entry.
    if args.lint:
        from repro.analysis.lint import DEFAULT_BASELINE, load_baseline
        from repro.analysis.static import all_passes
        from repro.analysis.static.program import capture_train, lint_config
        waivers = dict(load_baseline(DEFAULT_BASELINE).get("waivers", {}))
        prog = capture_train("launch", plan, lint_config(args.variant),
                             per_sample_clip=0.1)
        results = [p.run(prog) for p in all_passes()]
        findings = [f for r in results for f in r.findings]
        unwaived = [f for f in findings if f.fingerprint not in waivers]
        obs.record("lint/pass_runs", len(results), step=0)
        obs.record("lint/skipped",
                   sum(1 for r in results if r.skipped), step=0)
        obs.record("lint/findings", len(findings), step=0)
        obs.record("lint/unwaived", len(unwaived), step=0)
        obs.record("lint/ok", int(not unwaived), step=0)
        print(f"lint: {plan.describe()}: {len(findings)} findings "
              f"({len(unwaived)} unwaived) across {len(results)} passes"
              + "".join(f" [{r.pass_name}: skipped — {r.skip_reason}]"
                        for r in results if r.skipped))
        for f in unwaived:
            print(f"  UNWAIVED [{f.severity}] {f.fingerprint} "
                  f"{f.pass_name}/{f.code}: {f.message}")
        if unwaived:
            obs.flush()
            raise SystemExit(
                "lint: FAIL — this plan's step violates a pinned invariant; "
                "fix it or waive the fingerprint (with a reason) in "
                "LINT_BASELINE.json before training")

    # paper §5.2 / AF2 suppl. 1.11.3: clip each SAMPLE's gradient at 0.1
    opt = adamw(af2_lr_schedule(args.lr, warmup_steps=100),
                per_sample_clip=0.1)
    runner = TrainRunner(
        cfg, plan, optimizer=opt, batch_size=args.batch, seed=args.seed,
        recycle_sample=args.recycle_sample,
        max_recycle=args.max_recycle or None,
        ema_decay=args.ema or None, eval_every=args.eval_every,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        install_sigterm=True, deterministic=False,
        data_source=source, data_workers=args.data_workers,
        bucket_by_length=args.bucket_by_length,
        obs=obs, tracer=tracer, profile_window=profile_window,
        hlo_check=args.hlo_check,
        on_straggler=lambda s, dt, ema: print(
            f"  [watchdog] step {s} took {dt:.2f}s (EMA {ema:.2f}s)"))
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(runner.state["params"]))
    print(f"{plan.describe()}")
    print(f"mesh: {dict(runner.built.mesh.shape)}  devices={n_dev}")
    print(f"params: {n_params:,}  recycle_sample={args.recycle_sample} "
          f"(max {runner.max_recycle})  ema={args.ema or 'off'}")
    if args.ckpt_dir and args.resume:
        try:
            print(f"resumed from step {runner.restore(adapt_plan=args.adapt_plan)}")
        except FileNotFoundError:
            pass

    t_start = time.time()
    runner.run(args.steps, log_every=args.log_every)
    evals = runner.history["eval"]
    print(f"done: {args.steps} steps in {time.time() - t_start:.1f}s; "
          f"train compiles: {runner.train_compiles}; stragglers flagged: "
          f"{len(runner.watchdog.flagged)}"
          + (f"; final lDDT-Cα {evals[-1]['lddt_ca']:.2f}" if evals else ""))
    data = runner.history["data"]
    if data:
        d = data[-1]
        print(f"data ({args.data_workers} workers): stall "
              f"{d['stall_ms_per_step']}ms/step "
              f"({100 * d['stall_fraction']:.1f}% of loop), featurize "
              f"{d['featurize_ms_per_step']}ms, transfer "
              f"{d['transfer_ms_per_step']}ms, fill {d['mean_fill']:.2f}")
    # end-of-run attribution: roofline-vs-measured for the full run (when
    # --eval-every also produced windows, those rows are in the stream too),
    # over the mean of the steps' own host seconds
    from repro.obs import describe_attribution
    step_s = runner.history["step_s"]
    settled = step_s[1:] or step_s      # drop the compile step
    if settled:
        attr = runner.attribution(
            measured_step_s=sum(settled) / len(settled),
            n_recycle=(sum(runner.history["n_recycle"])
                       / max(len(runner.history["n_recycle"]), 1)),
            stall_fraction=(data[-1]["stall_fraction"] if data else 0.0),
            wall_s=time.time() - t_start, step=runner.step)
        print(describe_attribution(attr))
    if args.hlo_check:
        ov = runner.obs.series("train/async_overlap_ok")
        if ov:
            print(f"async_overlap_ok: {ov[-1]}")
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"trace: {len(tracer.spans())} spans -> {args.trace_out}")
    obs.flush()
    obs.close()
    if args.metrics_out:
        print(f"metrics: JSONL stream -> {args.metrics_out}")


def run_lm(args, jax, jnp, np):
    from repro import configs as cfglib
    from repro.models import get_model
    from repro.data.tokens import token_batch
    from repro.data.loader import ShardedLoader
    from repro.train.checkpoint import CheckpointManager, StepWatchdog
    from repro.train.optim import adamw, warmup_cosine
    from repro.train.trainstep import make_lm_train_step

    cfg = (cfglib.get_smoke_config(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    model = get_model(cfg)
    n_dev = len(jax.devices())
    from repro.parallel.mesh_utils import make_mesh
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    opt = adamw(warmup_cosine(args.lr, 20, args.steps), clip_norm=1.0)
    step_fn, state_shardings, batch_sharding = make_lm_train_step(
        model, cfg, opt, mesh)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"{cfg.arch_id}: {n_params:,} params (smoke={args.smoke})")
    state = {"params": params, "opt": opt.init(params)}

    def make_batch(step):
        b = token_batch(0, step, args.batch, args.seq, cfg.vocab)
        out = {"tokens": jnp.asarray(b["tokens"]),
               "labels": jnp.asarray(b["labels"])}
        if cfg.family == "audio":
            out["frames"] = jax.random.normal(
                jax.random.PRNGKey(step),
                (args.batch, cfg.n_frontend_tokens, cfg.frontend_dim),
                jnp.bfloat16)
        if cfg.family == "vlm":
            out["patches"] = jax.random.normal(
                jax.random.PRNGKey(step),
                (args.batch, cfg.n_frontend_tokens, cfg.frontend_dim),
                jnp.bfloat16)
        return out

    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start = 0
    if mgr and args.resume:
        try:
            state, start = mgr.restore_latest(state)
            print(f"resumed from step {start}")
        except FileNotFoundError:
            pass
    fn = jax.jit(step_fn, donate_argnums=(0,))
    wd = StepWatchdog()
    loader = ShardedLoader(make_batch, start_step=start)
    try:
        for step, batch in loader:
            if step >= args.steps:
                break
            wd.start_step()
            state, metrics = fn(state, batch)
            loss = float(metrics["loss"])
            wd.end_step(step)
            if step % args.log_every == 0:
                tokps = args.batch * args.seq / max(wd.ema or 1e-9, 1e-9)
                print(f"step {step:5d}  loss {loss:.4f}  ({tokps:,.0f} tok/s)")
            if mgr and step and step % args.ckpt_every == 0:
                mgr.save(step, state)
    finally:
        loader.close()
    if mgr:
        mgr.save(args.steps, state)
        mgr.wait()
    print("done")


if __name__ == "__main__":
    main()
