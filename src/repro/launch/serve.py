"""Serving launcher: LM batched decode (DecodeEngine) or AF2 fold serving
(FoldEngine).

  # LM decode smoke
  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
      --requests 6 --slots 2 --max-new 12

  # AF2 fold smoke: mixed-length queue over a 2-bucket table
  PYTHONPATH=src python -m repro.launch.serve --fold tiny --requests 6 \
      --micro-batch 2 --max-recycle 3 --tol 0.02

  # plan-aware: 8 fake devices, long buckets sharded data=4 x dap=2
  PYTHONPATH=src python -m repro.launch.serve --fold tiny --devices 8 \
      --dap 2 --requests 6
"""
from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="LM arch id (decode serving)")
    ap.add_argument("--fold", choices=["tiny", "small", "initial", "finetune"],
                    help="AF2 config (fold serving)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    # LM decode knobs
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    # fold knobs
    ap.add_argument("--devices", type=int, default=0,
                    help="fake host devices (CPU validation only)")
    ap.add_argument("--dap", type=int, default=1,
                    help="dap extent for long-bucket fold plans")
    ap.add_argument("--micro-batch", type=int, default=2)
    ap.add_argument("--max-recycle", type=int, default=3)
    ap.add_argument("--tol", type=float, default=0.0,
                    help="early-exit recycling tolerance (fraction of "
                         "changed CA-distance bins; 0 = fixed recycling)")
    ap.add_argument("--seed", type=int, default=0)
    # sustained-traffic knobs (DESIGN.md §12): --arrival-rate > 0 switches
    # run() (drain a pre-built queue) to serve() (admission scheduling over
    # Poisson arrivals on a virtual clock)
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="offered load in requests/s of VIRTUAL time; > 0 "
                         "enables the continuous-batching serve() path")
    ap.add_argument("--policy", choices=["continuous", "fifo"],
                    default="continuous",
                    help="admission policy (fifo = PR 4 drain baseline)")
    ap.add_argument("--cache-capacity", type=int, default=64,
                    help="sequence-hash result cache entries (0 disables)")
    ap.add_argument("--deadline-slack", type=float, default=0.0,
                    help="per-request deadline = arrival + slack seconds "
                         "of virtual time (0 = no deadlines)")
    ap.add_argument("--duplicates", type=float, default=0.3,
                    help="fraction of requests repeating an earlier "
                         "sequence (exercises the result cache)")
    ap.add_argument("--featurize-workers", type=int, default=0,
                    help="featurize-stage threads (0 = inline)")
    ap.add_argument("--starvation-steps", type=int, default=16,
                    help="steps a lane may be passed over before it is "
                         "force-scheduled")
    # -- observability (DESIGN.md §14), fold path ---------------------------
    ap.add_argument("--metrics-out", default="",
                    help="fold: write the obs metric stream (serve/* "
                         "counters, per-call deltas, report gauges) as "
                         "JSONL to this path")
    ap.add_argument("--trace-out", default="",
                    help="fold: write host spans (admit/recycle_step/"
                         "harvest/fold_step) as Chrome-trace JSON")
    args = ap.parse_args()

    if not args.arch and not args.fold:
        raise SystemExit("pass --arch <lm-arch> (decode) or --fold "
                         "<tiny|small|initial|finetune> (AF2)")
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.fold:
        run_fold(args)
    else:
        run_lm_decode(args)


def run_lm_decode(args):
    import jax
    import numpy as np
    from repro import configs as cfglib
    from repro.models import get_model
    from repro.serve.engine import DecodeEngine, Request

    try:
        cfg = (cfglib.get_smoke_config(args.arch) if args.smoke
               else cfglib.get_config(args.arch))
    except KeyError:
        # same actionable-error treatment as ParallelPlan.validate: say what
        # was wrong AND how to fix it, instead of a bare lookup traceback
        raise SystemExit(
            f"unknown --arch {args.arch!r}; known LM archs: "
            f"{', '.join(cfglib.ARCH_IDS)}.  AF2 fold serving uses --fold "
            "<tiny|small|initial|finetune> instead of --arch")
    if cfg.family in ("audio", "vlm"):
        raise SystemExit("serve demo supports token-prompt archs; "
                         "audio/vlm prefill needs frames/patches — see tests")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    engine = DecodeEngine(model, cfg, params, batch_slots=args.slots,
                          max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    done = engine.run(reqs)
    dt = time.time() - t0
    total = sum(len(v) for v in done.values())
    print(f"served {len(done)} requests, {total} tokens in {dt:.1f}s "
          f"({total / dt:.1f} tok/s aggregate)")
    for rid in sorted(done)[:3]:
        print(f"  req {rid}: {done[rid][:10]}...")


def make_fold_requests(cfg, n: int, seed: int = 0):
    """Synthetic mixed-length queue: lengths cycle through ~{0.3, 0.6, 1.0}
    of the config's shapes so a default bucket table sees >= 2 buckets."""
    import dataclasses
    import jax
    import numpy as np
    from repro.data.protein import protein_sample
    from repro.serve.fold_engine import FoldRequest

    fracs = (0.3, 0.6, 1.0)
    reqs = []
    for i in range(n):
        f = fracs[i % len(fracs)]
        c = dataclasses.replace(
            cfg, n_res=max(4, int(cfg.n_res * f)),
            n_seq=max(2, int(cfg.n_seq * f)),
            n_extra_seq=max(2, int(cfg.n_extra_seq * f)))
        smp = protein_sample(jax.random.fold_in(
            jax.random.PRNGKey(seed), i), c)
        feats = {k: np.asarray(smp[k]) for k in
                 ("msa_feat", "extra_msa_feat", "target_feat",
                  "residue_index")}
        reqs.append(FoldRequest(rid=i, features=feats))
    return reqs


def run_fold(args):
    import jax
    from repro.core.config import (af2_tiny, af2_small, af2_initial,
                                   af2_finetune)
    from repro.core import model as af2
    from repro.parallel.plan import ParallelPlan, PlanError
    from repro.serve.fold_engine import FoldEngine

    cfg = {"tiny": af2_tiny, "small": af2_small, "initial": af2_initial,
           "finetune": af2_finetune}[args.fold]()
    n_dev = len(jax.devices())
    if args.dap > 1 and n_dev % args.dap:
        raise SystemExit(
            f"--dap {args.dap} does not divide the {n_dev} available "
            f"devices; pass --devices as a multiple of --dap")
    long_plan = (ParallelPlan(data=n_dev // args.dap, dap=args.dap)
                 if args.dap > 1 else None)
    params = af2.init_params(jax.random.PRNGKey(0), cfg)
    from repro.obs import JsonlSink, MetricRegistry, SpanTracer
    obs = MetricRegistry(
        sinks=[JsonlSink(args.metrics_out)] if args.metrics_out else [])
    tracer = SpanTracer(process_name="fold-serve") if args.trace_out else None
    try:
        engine = FoldEngine(cfg, params, long_plan=long_plan,
                            micro_batch=args.micro_batch,
                            max_recycle=args.max_recycle, tol=args.tol,
                            obs=obs, tracer=tracer)
    except PlanError as e:
        raise SystemExit(f"fold plan rejected: {e}")
    print(f"fold engine: {args.fold} cfg, {n_dev} device(s), buckets "
          f"{[b.describe() for b in engine.buckets]}")
    print(f"  short plan {engine.plan.describe()}")
    if long_plan is not None:
        print(f"  long plan  {engine.long_plan.describe()} "
              f"(>= {engine.long_threshold} res)")
    reqs = make_fold_requests(cfg, args.requests, args.seed)
    if args.arrival_rate > 0:
        run_fold_traffic(args, engine, reqs)
        finish_fold_obs(args, engine)
        return
    t0 = time.time()
    done = engine.run(reqs)
    dt = time.time() - t0
    st = engine.last_stats    # THIS call's deltas, not lifetime totals
    saved = st["recycles_budget"] - st["recycles_run"]
    print(f"served {len(done)} folds in {dt:.1f}s "
          f"({len(done) / dt:.2f} folds/s aggregate), "
          f"{engine.compile_misses} compiles over {st['steps']} steps, "
          f"{saved}/{st['recycles_budget']} recycles saved by early exit")
    for rid in sorted(done)[:4]:
        r = done[rid]
        print(f"  req {rid}: len={r.coords.shape[0]} bucket<= "
              f"{r.bucket.n_res} plddt={r.plddt.mean():.1f} "
              f"recycles={r.n_recycles} converged={r.converged}")
    finish_fold_obs(args, engine)


def finish_fold_obs(args, engine):
    """Flush the fold engine's metric stream / host trace to disk."""
    engine.obs.tick()
    if engine.tracer is not None and args.trace_out:
        engine.tracer.save(args.trace_out)
        print(f"trace: {len(engine.tracer.spans())} spans -> "
              f"{args.trace_out}")
    engine.obs.close()
    if args.metrics_out:
        print(f"metrics: JSONL stream -> {args.metrics_out}")


def run_fold_traffic(args, engine, reqs):
    """Sustained-traffic serving: Poisson arrivals on the virtual clock,
    admission-scheduled (continuous batching) with the result cache and the
    decoupled featurize stage.  Step costs here are MEASURED wall time (the
    benchmark injects calibrated costs instead for determinism)."""
    import dataclasses as dc
    import numpy as np
    from repro.serve.result_cache import ResultCache
    from repro.serve.scheduler import VirtualClock

    rng = np.random.default_rng(args.seed)
    t, traffic = 0.0, []
    for i, r in enumerate(reqs):
        feats = (traffic[rng.integers(0, len(traffic))].features
                 if traffic and rng.random() < args.duplicates
                 else r.features)
        t += float(rng.exponential(1.0 / args.arrival_rate))
        traffic.append(dc.replace(
            r, features=feats, arrival_s=t,
            deadline_s=(t + args.deadline_slack
                        if args.deadline_slack > 0 else None)))
    cache = ResultCache(args.cache_capacity) if args.cache_capacity else None
    done = engine.serve(traffic, policy=args.policy, clock=VirtualClock(),
                        cache=cache,
                        featurize_workers=args.featurize_workers,
                        starvation_steps=args.starvation_steps)
    rep = engine.last_report
    print(f"served {len(done)}/{rep['requests']} folds under "
          f"{args.arrival_rate:.2f} req/s ({args.policy}): "
          f"p50 {rep['p50_ms']:.0f}ms p99 {rep['p99_ms']:.0f}ms, "
          f"goodput {rep['goodput_rps']:.2f} req/s, "
          f"on-time {rep['on_time_frac']:.0%}")
    sm = rep["stage_ms"]
    print(f"  stages: featurize {sm['featurize']:.2f}ms | queue "
          f"{sm['queue']:.0f}ms | service {sm['service']:.0f}ms; "
          f"utilization {rep['utilization']:.0%}, "
          f"{rep['steps']} steps, {engine.compile_misses} compiles, "
          f"cache hit rate {rep['hit_rate']:.0%}, "
          f"{rep['forced_admissions']} forced admissions")


if __name__ == "__main__":
    main()
