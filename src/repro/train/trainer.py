"""TrainRunner: the AF2 training loop (DESIGN.md §11) — the training-side
sibling of ``serve.FoldEngine``.

The paper's claim is two-sided: BP/Parallel Evoformer make *training* 36–39%
faster AND accuracy stays on par with AF2.  The raw train step can only show
the first half; this layer closes the loop so the repo can state a
loss-goes-down + lDDT-goes-up trajectory for every ParallelPlan:

1. **Stochastic recycle sampling** (AF2 suppl. 1.11.8) — per step,
   ``n_recycle ~ Uniform{1..max_recycle}`` is drawn ON HOST, deterministic
   in (seed, step): every DP worker computes the same draw with no
   broadcast, and resuming at step k reproduces the fresh-run draw.  The
   draw feeds the compiled step as a *traced* int32 bound on ``forward``'s
   recycling fori_loop, so ONE compiled step serves all draws — pinned by
   the ``compile_misses`` counter (``jax.jit``'s cache size, the same
   contract FoldEngine pins per bucket).
2. **EMA parameters** (``optim.ema``, decay 0.999; AF2 suppl. 1.11.7) —
   carried in train state next to the raw copy, updated inside the compiled
   step, used for every eval; ``CheckpointManager`` persists both copies
   under the existing plan-fingerprint manifest (they are just two subtrees
   of the state).
3. **lDDT-Cα validation** (``heads.lddt_ca``) — the superposition-free
   metric the paper reports for CASP14/CAMEO, evaluated with the EMA
   parameters on a held-out deterministic split (``data.protein`` val
   stream) every ``eval_every`` steps and logged alongside throughput.
   Eval runs the serial single-device path (block_fn=None): it is rare,
   forward-only, and must not depend on the training layout.

Input pipeline overlap comes from ``data.pipeline.DataPipeline`` (DESIGN.md
§13): the next batches are featurized on ``data_workers`` host threads while
the step runs, and each batch is ``jax.device_put`` onto the plan's sharding
one step ahead of consumption — ScaleFold's observation that the loop, not
the kernels, hides AF2 wall-clock once fusion is done.  ``data_source=None``
keeps the deterministic synthetic stream (bit-identical to the historical
``ShardedLoader`` path); an ``data.ingest`` source switches to record
featurization with an optional length-bucketed shuffle.  Per-stage input
accounting (featurize/queue/transfer/stall) lands in ``history["data"]``
and is logged alongside eval.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np


class TrainRunner:
    """Drive AF2 training for a config + ParallelPlan; see module docstring.

    ``ema_decay=None`` disables the EMA copy (eval then uses raw params);
    ``recycle_sample=False`` disables stochastic recycling and every step
    runs the fixed ``n_recycle``.  ``eval_every=0`` disables periodic eval
    (``evaluate()`` can still be called directly).
    """

    def __init__(self, cfg, plan=None, *, optimizer=None, batch_size: int = 1,
                 seed: int = 0, n_recycle: int = 1, recycle_sample: bool = True,
                 max_recycle: Optional[int] = None,
                 ema_decay: Optional[float] = 0.999,
                 eval_every: int = 0, eval_batches: int = 1,
                 eval_batch_size: int = 2, eval_n_recycle: Optional[int] = None,
                 ckpt_dir: str = "", ckpt_every: int = 50, keep: int = 3,
                 install_sigterm: bool = False,
                 deterministic: bool = False, devices=None,
                 on_straggler=None, data_source=None, data_workers: int = 1,
                 data_prefetch: int = 2, bucket_by_length: bool = False,
                 obs=None, tracer=None, profile_window=None,
                 hlo_check: bool = False):
        import jax
        from repro.core import model as af2
        from repro.obs import MetricRegistry
        from repro.parallel.plan import BuiltPlan, ParallelPlan
        from repro.train import optim as optim_lib
        from repro.train.checkpoint import CheckpointManager, StepWatchdog
        from repro.train.trainstep import make_af2_train_step

        if plan is None:
            n = len(devices) if devices is not None else len(jax.devices())
            plan = ParallelPlan(data=n)
        if isinstance(plan, BuiltPlan):
            # a pre-built plan already had apply_to run by whoever built it
            base_plan = plan.plan
        else:
            base_plan = plan
            cfg = plan.apply_to(cfg)
        self.cfg = cfg
        self.plan = base_plan
        self.seed = seed
        self.batch_size = batch_size
        self.n_recycle = n_recycle
        self.recycle_sample = recycle_sample
        self.max_recycle = max_recycle or cfg.max_recycle
        self.eval_every = eval_every
        self.eval_batches = eval_batches
        self.eval_batch_size = eval_batch_size
        self.eval_n_recycle = eval_n_recycle or self.max_recycle
        self.ckpt_every = ckpt_every
        self.devices = devices
        self.data_source = data_source
        self.data_workers = data_workers
        self.data_prefetch = data_prefetch
        self.bucket_by_length = bucket_by_length
        self.optimizer = optimizer or optim_lib.adamw(
            optim_lib.af2_lr_schedule(1e-3, warmup_steps=100),
            per_sample_clip=0.1)
        self.ema = optim_lib.ema(ema_decay) if ema_decay else None
        # telemetry (DESIGN.md §14): everything routes through a registry —
        # a sink-less default keeps the hot path near-free when nobody
        # listens, while `history` stays a live view of registry series
        self.obs = obs if obs is not None else MetricRegistry()
        self.tracer = tracer
        self.profile_window = profile_window
        self.hlo_check = hlo_check

        step_fn, built = make_af2_train_step(
            cfg, self.optimizer, plan, n_recycle=n_recycle,
            deterministic=deterministic, devices=devices, ema=self.ema)
        self.built = built
        # trace counters: the body of a jitted function runs only when jax
        # (re)traces it, so these count distinct compiled step PROGRAMS —
        # the quantity stochastic recycling must keep at 1 (a static bound
        # would retrace per draw).  XLA may additionally respecialize an
        # executable for input layouts (first call: fresh arrays; later
        # calls: step outputs) — that is draw-independent and not a retrace,
        # so it deliberately does not count.
        self._traces = {"train": 0}
        # the RAW step (no trace counter, no donation): the HLO-inspection
        # path lowers THIS so `train_compiles` keeps its =1 contract
        self._raw_step = step_fn

        def counted_step(state, batch, rng, nr):
            self._traces["train"] += 1
            return step_fn(state, batch, rng, nr)
        self._train_step = jax.jit(counted_step, donate_argnums=(0,))
        self._eval_eng = None   # lazy FoldEngine; see _eval_engine()
        self._lddt = None

        params = af2.init_params(jax.random.PRNGKey(seed), cfg)
        state = {"params": params, "opt": self.optimizer.init(params)}
        if self.ema is not None:
            state["ema"] = self.ema.init(params)
        if base_plan.compress_pod_grads:
            from repro.parallel.grad_sync import zeros_error_state
            state["err"] = zeros_error_state(params)
        self.state = self._place(state)
        self.step = 0
        self.mgr = (CheckpointManager(ckpt_dir, keep=keep,
                                      install_sigterm=install_sigterm,
                                      plan_meta=built.metadata(),
                                      obs=self.obs)
                    if ckpt_dir else None)
        self.watchdog = StepWatchdog(on_straggler=on_straggler)
        # thin views: each value IS the registry's live series list (same
        # object) — `history["loss"] is obs.series("train/loss")`, so legacy
        # consumers and sinks observe the identical stream
        self.history = {k: self.obs.series(f"train/{k}") for k in
                        ("loss", "n_recycle", "step_s", "eval", "data",
                         "attribution")}

    def _place(self, state):
        """Commit ``state`` to the replicated sharding the step returns it
        with.  Fresh arrays are uncommitted single-device ones, and jit keys
        its cache on sharding: without this the first and second calls
        would trace two programs."""
        import jax
        from jax.sharding import NamedSharding
        return jax.device_put(
            state, NamedSharding(self.built.mesh, self.built.state_spec))

    # -- compile accounting (the FoldEngine contract, training-side) --------

    @property
    def train_compiles(self) -> int:
        """Distinct traced train-step programs so far — stays 1 across every
        stochastic recycle draw (the draw is a traced argument; see the
        counter note in ``__init__``)."""
        return self._traces["train"]

    @property
    def eval_compiles(self) -> int:
        """Eval goes through the serving-side step cache: this is the eval
        FoldEngine's ``compile_misses`` — bounded by its (single-bucket)
        bucket table, not by how often ``evaluate()`` runs."""
        return self._eval_eng.compile_misses if self._eval_eng else 0

    @property
    def compile_misses(self) -> int:
        return self.train_compiles + self.eval_compiles

    # -- stochastic recycling ------------------------------------------------

    def recycle_draw(self, step: int) -> int:
        """Host-side ``n_recycle`` for this step: Uniform{1..max_recycle},
        deterministic in (seed, step) — no cross-host broadcast needed, and
        a resumed run reproduces the exact draw sequence."""
        if not self.recycle_sample:
            return self.n_recycle
        gen = np.random.default_rng([abs(self.seed), step])
        return int(gen.integers(1, self.max_recycle + 1))

    # -- eval ----------------------------------------------------------------

    def _eval_engine(self):
        """Eval rides the serving substrate (the carried ROADMAP item):
        ONE full-shape bucket, the training plan normalized with
        ``ParallelPlan.for_inference()`` (branch folds into data, remat
        drops, dap survives) so fine-tune-shape evals reuse the inference
        memory footprint and sharding instead of the training layout.  The
        jitted predict step lives in the engine's (bucket, plan) cache —
        compiled once, reused by every ``evaluate()`` call."""
        if self._eval_eng is None:
            import jax
            from repro.core import heads as heads_lib
            from repro.serve import fold_steps as fs
            from repro.serve.fold_engine import FoldEngine
            cfg = self.cfg
            devices = self.devices
            if devices is None:
                devices = jax.devices()[:self.plan.for_inference().n_devices]
            self._eval_eng = FoldEngine(
                cfg, self.state["params"],
                buckets=[fs.Bucket(cfg.n_res, cfg.n_seq, cfg.n_extra_seq)],
                plan=self.plan, micro_batch=self.eval_batch_size,
                max_recycle=self.eval_n_recycle, tol=0.0, devices=devices)
            self._lddt = jax.jit(jax.vmap(heads_lib.lddt_ca))
        return self._eval_eng

    def eval_params(self):
        """Parameters eval runs with: the EMA copy when enabled, else raw."""
        return self.state.get("ema", self.state["params"])

    def evaluate(self) -> dict:
        """lDDT-Cα over the held-out split (see ``protein_batch(split='val')``)
        with the EMA parameters.  Returns the mean, the per-sample profile,
        and the predicted coords (so callers can re-score with a standalone
        oracle — pinned to 1e-5 in tests).

        Runs ``core.model.predict`` (tol=0: exactly ``eval_n_recycle``
        cycles, reproducing ``forward``) through the eval FoldEngine's
        cached step — see ``_eval_engine``.
        """
        from repro.data.protein import protein_batch
        from repro.serve import fold_steps as fs
        eng = self._eval_engine()
        eng.params = params = self.eval_params()
        bucket = eng.buckets[0]
        step = eng.step_for(bucket)
        ext = eng.slots_for(bucket)
        keys = fs.REQUEST_FEATURE_KEYS + ("res_mask",)
        lddts, coords, truths, masks = [], [], [], []
        for b in range(self.eval_batches):
            batch = protein_batch(self.seed, b, self.eval_batch_size,
                                  self.cfg, split="val")
            fb = {k: np.asarray(batch[k]) for k in keys}
            if ext > self.eval_batch_size:    # round up to the plan's
                fb = {k: np.concatenate(      # data extent; extras dropped
                    [v, np.repeat(v[-1:], ext - self.eval_batch_size, 0)])
                    for k, v in fb.items()}
            out = step(params, fb)
            c = np.asarray(out["coords"])[:self.eval_batch_size]
            tt = np.asarray(batch["true_trans"])
            rm = np.asarray(batch["res_mask"])
            lddts.append(np.asarray(self._lddt(c, tt, rm)))
            coords.append(c)
            truths.append(tt)
            masks.append(rm)
        lddts = np.concatenate(lddts)
        return {"lddt_ca": float(lddts.mean()),
                "per_sample": lddts,
                "coords": np.concatenate(coords),
                "true_trans": np.concatenate(truths),
                "res_mask": np.concatenate(masks)}

    # -- checkpointing -------------------------------------------------------

    def restore(self, *, adapt_plan: bool = False) -> int:
        """Resume from the latest checkpoint (raw + EMA params + optimizer),
        cross-checked against this runner's plan fingerprint."""
        if self.mgr is None:
            raise ValueError("TrainRunner has no ckpt_dir; nothing to restore")
        state, self.step = self.mgr.restore_latest(
            self.state, adapt_plan=adapt_plan)
        self.state = self._place(state)
        return self.step

    # -- the input pipeline --------------------------------------------------

    def make_pipeline(self):
        """The streaming input pipeline for this runner (DESIGN.md §13).

        ``data_source=None`` keeps the synthetic ``protein_batch`` stream
        (byte-identical to every prior release); a record source switches to
        ``featurize_record`` + bucket scheduling, padded onto the config's
        single terminal train bucket so the compiled step keeps ONE shape
        even when ``bucket_by_length`` groups similar lengths per batch.
        Batches are device_put onto the built plan's (mesh, batch_spec)
        sharding one step ahead of consumption.
        """
        from jax.sharding import NamedSharding
        from repro.data.bucketing import train_bucket
        from repro.data.pipeline import DataPipeline
        return DataPipeline(
            self.cfg, source=self.data_source, batch_size=self.batch_size,
            seed=self.seed, start_step=self.step, workers=self.data_workers,
            prefetch=self.data_prefetch,
            bucket_by_length=self.bucket_by_length,
            pad_to=(train_bucket(self.cfg) if self.data_source is not None
                    else None),
            sharding=NamedSharding(self.built.mesh, self.built.batch_spec),
            obs=self.obs, tracer=self.tracer)

    # -- attribution / HLO observables (DESIGN.md §14) -----------------------

    def attribution(self, *, measured_step_s: float, n_recycle: float,
                    stall_fraction: float = 0.0, overhead_s: float = 0.0,
                    wall_s: Optional[float] = None,
                    step: Optional[int] = None) -> dict:
        """Roofline-vs-measured report for this runner's plan/config —
        recorded into ``history["attribution"]`` (see obs.attribution)."""
        from repro.obs import attribution_report
        rep = attribution_report(
            self.cfg, self.plan,
            device_kind=self.built.mesh.devices.flat[0].device_kind,
            global_batch=self.batch_size,
            n_recycle=n_recycle, measured_step_s=measured_step_s,
            stall_fraction=stall_fraction, overhead_s=overhead_s,
            wall_s=wall_s, step=step)
        self.obs.record("train/attribution", rep, step=step)
        return rep

    def record_async_overlap(self, batch) -> dict:
        """Promote ``analysis.hlo.check_async_overlap`` to an obs metric:
        lower the RAW train step (uncounted, undonated — ``train_compiles``
        stays 1), inspect the optimized HLO for hidden collectives, record
        the verdict (or the skip reason: CPU backends don't split
        collectives into start/done pairs) as ``train/async_overlap_ok``.
        A step that fails to lower raises: it could not train either."""
        import jax
        from repro.analysis.hlo import check_async_overlap
        txt = (jax.jit(self._raw_step)
               .lower(self.state, batch, jax.random.PRNGKey(0),
                      self.max_recycle if self.recycle_sample else None)
               .compile().as_text())
        ok, rep = check_async_overlap(txt)
        row = {"ok": ok, "skipped": ok is None,
               "reason": (None if ok is not None else
                          "no async collective start/done pairs in HLO")}
        for k in ("pairs", "overlapped", "exposed"):
            if k in rep:
                row[k] = rep[k]
        self.obs.record("train/async_overlap_ok", row, step=self.step)
        return row

    # -- the loop ------------------------------------------------------------

    def run(self, steps: int, *, log_every: int = 0, log=print) -> dict:
        """Train until global step ``steps`` (continues from ``self.step``).

        Per step: draw n_recycle on host -> one compiled step (loss, grads,
        optimizer, EMA) -> history.  Every ``eval_every`` steps: lDDT-Cα
        with the EMA params on the held-out split, logged with throughput,
        the input pipeline's per-stage stall report, and the
        roofline-vs-measured attribution report.  Returns ``self.history``
        (input accounting under ``history["data"]``, attribution rows under
        ``history["attribution"]``) — every value a live view of the
        registry's series (DESIGN.md §14).
        """
        import jax
        from repro.obs import get_tracer, step_span, trace_span

        pipeline = self.make_pipeline()
        base_rng = jax.random.PRNGKey(self.seed)
        tracer = self.tracer if self.tracer is not None else get_tracer()
        obs = self.obs
        # cached instruments: dict lookups off the hot path (the pipeline
        # mirrors its own data/* gauges before each yield)
        h_step = obs.histogram("train/step_s")
        # attribution window: reset at every report so each row attributes
        # ITS interval (not the run-so-far average)
        win_t0 = time.perf_counter()
        win_i0 = len(self.history["step_s"])
        win_overhead = 0.0
        try:
            for step, batch in pipeline:
                if step >= steps:
                    break
                if self.profile_window is not None:
                    self.profile_window.maybe_start(step)
                if self.hlo_check and not self.history["step_s"]:
                    self.record_async_overlap(batch)
                nr = self.recycle_draw(step)
                self.watchdog.start_step()
                # fixed-recycle runs pass None: the factory's static bound
                # keeps forward's unrolled recycling (no dead while_loop)
                with step_span(step, tracer=tracer, n_recycle=nr):
                    self.state, metrics = self._train_step(
                        self.state, batch, jax.random.fold_in(base_rng, step),
                        nr if self.recycle_sample else None)
                    if tracer is not None:
                        # host spans must bound device work honestly
                        jax.block_until_ready(metrics)
                    loss = float(metrics["loss"])  # blocks: wall-time real
                self.watchdog.end_step(step)
                # this step's own host seconds; the watchdog's EMA is for
                # straggler detection only
                dt = self.watchdog.last_s
                obs.record("train/loss", loss, step=step)
                obs.record("train/n_recycle", nr, step=step)
                obs.record("train/step_s", dt, step=step)
                obs.record("train/input_wait_s", pipeline.wait_s, step=step)
                h_step.observe(dt)
                self.step = step + 1
                if log_every and step % log_every == 0:
                    log(f"step {step:5d}  loss {loss:.4f}  n_recycle {nr}  "
                        f"({self.batch_size / max(dt, 1e-9):.2f} protein/s)")
                if self.eval_every and self.step % self.eval_every == 0:
                    t_ev = time.perf_counter()
                    with trace_span("eval", tracer=tracer, step=self.step):
                        ev = self.evaluate()
                    win_overhead += time.perf_counter() - t_ev
                    obs.record("train/eval",
                               {"step": self.step, "lddt_ca": ev["lddt_ca"]},
                               step=self.step)
                    obs.record("train/data",
                               dict(pipeline.report.as_dict(), step=self.step),
                               step=self.step)
                    win = self.history["step_s"][win_i0:]
                    nrs = self.history["n_recycle"][win_i0:]
                    attr = self.attribution(
                        measured_step_s=(sum(win) / len(win)) if win else 0.0,
                        n_recycle=(sum(nrs) / len(nrs)) if nrs else
                        float(self.n_recycle),
                        stall_fraction=pipeline.report.stall_fraction,
                        overhead_s=win_overhead,
                        wall_s=time.perf_counter() - win_t0, step=self.step)
                    win_t0 = time.perf_counter()
                    win_i0 = len(self.history["step_s"])
                    win_overhead = 0.0
                    if log_every:
                        log(f"  eval @ {self.step}: lDDT-Cα "
                            f"{ev['lddt_ca']:.2f} (ema={self.ema is not None},"
                            f" {self.batch_size / max(dt, 1e-9):.2f}"
                            f" protein/s)")
                        log(f"  {pipeline.report.describe()}")
                        from repro.obs import describe_attribution
                        log(f"  {describe_attribution(attr)}")
                if (self.mgr and self.step % self.ckpt_every == 0
                        and self.step < steps):
                    t_ck = time.perf_counter()
                    with trace_span("checkpoint", tracer=tracer,
                                    step=self.step):
                        self.mgr.save(self.step, self.state)
                    win_overhead += time.perf_counter() - t_ck
                obs.tick(step=step)
                if self.profile_window is not None:
                    self.profile_window.maybe_stop(step)
        finally:
            obs.record("train/data",
                       dict(pipeline.report.as_dict(), step=self.step),
                       step=self.step)
            pipeline.close()
            if self.profile_window is not None:
                self.profile_window.close()
        if self.mgr:
            with trace_span("checkpoint", tracer=tracer, step=self.step):
                self.mgr.save(self.step, self.state)
                self.mgr.wait()
        obs.tick(step=self.step)
        return self.history
