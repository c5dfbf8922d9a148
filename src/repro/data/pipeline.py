"""Streaming data-ingest pipeline (DESIGN.md §13): host featurize workers,
length-bucketed batch schedule, device-put double buffering, per-stage
accounting.

At AF2 scale the documented bottleneck is host-side feature preparation —
ScaleFold attributes much of its 11-day -> 10-hour training win to the data
pipeline, and ParaFold's whole thesis is splitting CPU featurization from
accelerator inference.  This module is that split for BOTH repo loops:
``TrainRunner`` consumes its batches and ``serve.FeaturizePipeline`` shares
its worker pool (``HostWorkerPool``).

Stages (each independently accounted in :class:`StageReport`):

1. **schedule** — ``data.bucketing.BucketSchedule``: (seed, step) ->
   (bucket, record indices), deterministic and worker-count-independent.
2. **featurize** — ``make_batch(step)`` on a thread pool (``workers > 0``)
   with ordered reassembly: completions buffer in a dict keyed by step and
   are released strictly in step order, so the consumed stream is
   BIT-IDENTICAL for 1 worker or 16 (the work function is pure in
   (seed, step, idx); only wall-clock changes).  ``workers=0`` featurizes
   inline in ``__next__`` — the no-overlap baseline the stall gate in
   ``benchmarks/data_bench.py`` measures against.
3. **device** — ``jax.device_put`` onto the plan's sharding ONE step ahead
   of consumption: step t+1's host->HBM transfer is issued (asynchronously)
   before step t is yielded, so the transfer overlaps the consumer's step
   compute the same way ``overlap_dap`` hides DAP gathers.

Worker exceptions NEVER hang the consumer: failures are wrapped and
re-raised from ``__next__`` (the ShardedLoader silent-hang fix, shared).

Lifecycle matches ``ShardedLoader``: one live iteration at a time,
``close()`` is idempotent, re-iteration restarts at ``start_step`` (resume
is "construct with the resumed start_step" — the schedule is a pure
function of (seed, step), so the resumed stream is bit-identical to the
fresh run's tail).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
import traceback
from collections import deque
from typing import Callable, Iterator, List, Optional

import numpy as np

from repro.data import bucketing as bk


class WorkerFailure:
    """An exception captured on a worker thread, carried to the consumer.

    ``item`` is the work item that failed (for ``DataPipeline`` that is the
    step number, which lets the consumer deliver the failure IN STREAM
    ORDER — steps before the failing one still yield normally)."""

    def __init__(self, exc: BaseException, item=None):
        self.exc = exc
        self.item = item
        self.tb = traceback.format_exc()

    def reraise(self):
        raise self.exc


class HostWorkerPool:
    """Bounded-in-flight thread pool: backlog -> workers -> ready queue.

    The shared substrate of the train-side featurize stage and the serving
    ``FeaturizePipeline``: ``submit`` enqueues an item, workers apply
    ``fn``, ``poll`` drains results.  ``cap`` bounds in-flight work — an
    int, or ``callable(head_item) -> int`` so callers can make the bound
    item-aware (the serving stage's bucket-depth policy).  Exceptions are
    captured as :class:`WorkerFailure` results (``poll(raise_failures=
    True)`` re-raises) — a failed item can therefore never strand the
    consumer on an empty queue.

    ``workers=0`` applies ``fn`` inline in ``submit`` (deterministic
    no-thread mode).
    """

    def __init__(self, fn: Callable, *, workers: int = 0, cap=None,
                 name: str = "host-stage"):
        self.fn = fn
        self.workers = workers
        self.cap = cap
        self.stats = {"done": 0, "busy_s": 0.0, "max_inflight": 0}
        self._ready: "queue.Queue" = queue.Queue()
        self._backlog: deque = deque()
        self._inflight = 0
        self._lock = threading.Lock()
        self._pool = None
        if workers > 0:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(max_workers=workers,
                                            thread_name_prefix=name)

    def _cap_for(self, item) -> int:
        if self.cap is None:
            return 1 << 30
        return self.cap(item) if callable(self.cap) else int(self.cap)

    def _run(self, item):
        t0 = time.perf_counter()
        try:
            out = self.fn(item)
        except BaseException as e:  # noqa: BLE001 — carried to the consumer
            out = WorkerFailure(e, item=item)
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats["done"] += 1
            self.stats["busy_s"] += dt
        return out

    def _worker(self, item):
        try:
            self._ready.put(self._run(item))
        finally:
            with self._lock:
                self._inflight -= 1
            self._pump()

    def _pump(self):
        while True:
            with self._lock:
                if not self._backlog:
                    return
                head = self._backlog[0]
                if self._inflight >= self._cap_for(head):
                    return
                self._backlog.popleft()
                self._inflight += 1
                self.stats["max_inflight"] = max(
                    self.stats["max_inflight"], self._inflight)
            self._pool.submit(self._worker, head)

    def submit(self, item) -> None:
        if self._pool is None:
            self._ready.put(self._run(item))
            return
        with self._lock:
            self._backlog.append(item)
        self._pump()

    def poll(self, block: bool = False, timeout: Optional[float] = None,
             raise_failures: bool = False) -> list:
        """Drain finished results; ``block=True`` waits for at least one
        (returns [] only on timeout or an idle pipeline)."""
        out: list = []
        if block and self._ready.empty() and self.pending:
            try:
                out.append(self._ready.get(timeout=timeout or 30.0))
            except queue.Empty:
                return out
        while True:
            try:
                out.append(self._ready.get_nowait())
            except queue.Empty:
                break
        if raise_failures:
            for r in out:
                if isinstance(r, WorkerFailure):
                    r.reraise()
        return out

    @property
    def pending(self) -> int:
        with self._lock:
            return self._inflight + len(self._backlog)

    def close(self):
        if self._pool is not None:
            self._pool.shutdown(wait=True)


# ---------------------------------------------------------------------------
# Per-stage accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StageReport:
    """Cumulative per-stage seconds for one pipeline iteration.

    ``featurize_s`` is worker wall time spent building batches (overlapped
    with step compute when workers > 0, so it is accounted, not added);
    ``queue_s`` is time finished host batches waited before pickup;
    ``transfer_s`` is host time submitting ``jax.device_put`` calls (the
    transfer itself is async); ``stall_s`` is what the consumer actually
    WAITED for input in ``__next__`` — the number the train loop feels, and
    the one the BENCH_data input-stall gate pins.
    """
    steps: int = 0
    batches: int = 0          # host batches accounted (>= steps: lookahead
                              # picks up step t+1's batch before t yields)
    featurize_s: float = 0.0
    queue_s: float = 0.0
    transfer_s: float = 0.0
    stall_s: float = 0.0
    wall_s: float = 0.0
    fill_sum: float = 0.0
    bucket_counts: dict = dataclasses.field(default_factory=dict)

    @property
    def stall_fraction(self) -> float:
        return self.stall_s / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def mean_fill(self) -> float:
        return self.fill_sum / self.batches if self.batches else 1.0

    def as_dict(self) -> dict:
        return {
            "steps": self.steps,
            "featurize_ms_per_step": round(
                1e3 * self.featurize_s / max(self.steps, 1), 3),
            "queue_ms_per_step": round(
                1e3 * self.queue_s / max(self.steps, 1), 3),
            "transfer_ms_per_step": round(
                1e3 * self.transfer_s / max(self.steps, 1), 3),
            "stall_ms_per_step": round(
                1e3 * self.stall_s / max(self.steps, 1), 3),
            "stall_fraction": round(self.stall_fraction, 4),
            "mean_fill": round(self.mean_fill, 4),
            "buckets": dict(self.bucket_counts),
        }

    def describe(self) -> str:
        d = self.as_dict()
        return (f"data: stall {d['stall_ms_per_step']}ms/step "
                f"({100 * d['stall_fraction']:.1f}% of loop), featurize "
                f"{d['featurize_ms_per_step']}ms, queue "
                f"{d['queue_ms_per_step']}ms, transfer "
                f"{d['transfer_ms_per_step']}ms, fill {d['mean_fill']:.2f}")


@dataclasses.dataclass
class _HostBatch:
    step: int
    batch: dict
    featurize_s: float
    fill: float
    bucket: Optional[bk.Bucket]
    ready_t: float            # perf_counter when the worker finished


# keys a TRAINING batch carries — exactly ``data.protein.protein_sample``'s
# contract (row masks are serving-side opt-ins; ``core.model.forward`` runs
# the unmasked fast path and the losses mask via res_mask)
TRAIN_BATCH_KEYS = ("msa_feat", "extra_msa_feat", "target_feat",
                    "residue_index", "res_mask", "true_msa",
                    "msa_mask_positions", "true_rots", "true_trans")


class DataPipeline:
    """Streaming (step, batch) iterator: schedule -> featurize -> device.

    ``source=None`` is the COMPAT path: ``make_batch(step)`` is exactly
    ``data.protein.protein_batch(seed, step, batch_size, cfg)`` — the
    stream every existing test/bench consumes, byte-identical, now behind
    the same pipeline interface.  A ``data.ingest`` Source switches to the
    record path: per-record ``featurize_record`` + ``BucketSchedule``
    composition + ``pad_record_to_bucket``.

    ``pad_to`` forces every batch onto ONE terminal bucket (training: one
    compiled step shape; bucketing still groups similar lengths per batch,
    which the ``mean_fill`` accounting makes visible).  Without it, each
    batch takes its schedule bucket's shape (serving-side feeding).

    ``sharding`` (any ``jax.sharding.Sharding``) enables the device stage:
    batches are ``jax.device_put`` onto it one step ahead of consumption.
    """

    def __init__(self, cfg, *, source=None, batch_size: int = 1,
                 seed: int = 0, start_step: int = 0, workers: int = 1,
                 prefetch: int = 2, bucket_by_length: bool = False,
                 buckets: Optional[list] = None,
                 pad_to: Optional[bk.Bucket] = None,
                 include_row_masks: bool = False, sharding=None,
                 make_batch: Optional[Callable] = None, obs=None,
                 tracer=None):
        self.cfg = cfg
        # obs MetricRegistry + SpanTracer (DESIGN.md §14): per-stage seconds
        # mirror into data/* gauges each step and featurize/device_put/
        # input_wait become host spans; None keeps the bare-report path
        self.obs = obs
        self.tracer = tracer
        self.source = source
        self.batch_size = batch_size
        self.seed = seed
        self.start_step = start_step
        self.workers = workers
        self.prefetch = max(1, prefetch)
        self.bucket_by_length = bucket_by_length
        self.pad_to = pad_to
        self.include_row_masks = include_row_masks
        self.sharding = sharding
        self.report = StageReport()
        # seconds the consumer waited for the batch just yielded: the
        # ``input_wait`` span's length, 0 when the batch was already placed
        self.wait_s = 0.0
        self._custom_make_batch = make_batch
        self.schedule = None
        if source is not None:
            buckets = buckets or (
                bk.length_bucket_table(cfg) if bucket_by_length
                else [pad_to or bk.train_bucket(cfg)])
            lengths = [source.record_length(i) for i in range(len(source))]
            self.schedule = bk.BucketSchedule(
                lengths, buckets, seed=seed, batch_size=batch_size,
                bucket_by_length=bucket_by_length)
        elif bucket_by_length:
            raise ValueError(
                "bucket_by_length needs a record source (the synthetic "
                "compat stream is fixed-shape); pass source=SyntheticSource("
                "cfg, vary_length=True) or a FastaSource")
        self._pool: Optional[HostWorkerPool] = None
        self._gen = None
        self._token = None
        self._live = False
        self._lock = threading.Lock()

    # -- batch synthesis (pure in (seed, step)) ------------------------------

    def _make_batch(self, step: int) -> _HostBatch:
        from repro.obs import trace_span
        with trace_span("featurize", tracer=self.tracer, step=step):
            return self._make_batch_inner(step)

    def _make_batch_inner(self, step: int) -> _HostBatch:
        t0 = time.perf_counter()
        if self._custom_make_batch is not None:
            batch, fill, bucket = self._custom_make_batch(step), 1.0, None
        elif self.source is None:
            from repro.data.protein import protein_batch
            batch = protein_batch(self.seed, step, self.batch_size, self.cfg)
            fill, bucket = 1.0, None
        else:
            from repro.data.ingest import featurize_record
            plan = self.schedule.batch_plan(step)
            bucket = self.pad_to or plan.bucket
            padded = []
            n_valid = 0
            for slot, rec_idx in enumerate(plan.indices):
                rec = self.source.record(rec_idx)
                feats = featurize_record(rec, self.cfg, seed=self.seed,
                                         step=step, idx=slot)
                n_valid += rec.n_res
                padded.append(bk.pad_record_to_bucket(feats, bucket))
            batch = bk.stack_batch(padded)
            if not self.include_row_masks:
                batch = {k: batch[k] for k in TRAIN_BATCH_KEYS}
            fill = n_valid / (len(plan.indices) * bucket.n_res)
        dt = time.perf_counter() - t0
        return _HostBatch(step=step, batch=batch, featurize_s=dt, fill=fill,
                          bucket=bucket, ready_t=time.perf_counter())

    # -- device stage --------------------------------------------------------

    def _place(self, hb: _HostBatch):
        if self.sharding is None:
            return hb.batch
        import jax
        from repro.obs import trace_span
        t0 = time.perf_counter()
        with trace_span("device_put", tracer=self.tracer, step=hb.step):
            placed = jax.device_put(hb.batch, self.sharding)
        self.report.transfer_s += time.perf_counter() - t0
        return placed

    # -- iteration -----------------------------------------------------------

    def __iter__(self) -> Iterator:
        with self._lock:
            if self._live:
                raise RuntimeError(
                    "DataPipeline is already being iterated; close() it "
                    "before starting a second iteration (two consumers "
                    "would race one ordered stream)")
            self._live = True
        self.report = StageReport()
        pool = None
        if self.workers > 0:
            pool = HostWorkerPool(self._make_batch, workers=self.workers,
                                  cap=self.prefetch + self.workers,
                                  name="featurize")
        token = object()
        self._pool, self._token = pool, token
        gen = self._run(pool, token)
        self._gen = gen
        return gen

    def _run(self, pool, token) -> Iterator:
        try:
            yield from self._iterate(pool)
        finally:
            # tear down THIS iteration only: a generator finalized late
            # (GC) must not clobber a newer iteration's state
            if pool is not None:
                pool.close()
            with self._lock:
                if self._token is token:
                    self._live = False
                    self._gen = self._pool = self._token = None

    def _iterate(self, pool) -> Iterator:
        buffer: dict = {}
        next_submit = self.start_step
        if pool is not None:
            for _ in range(self.prefetch + self.workers):
                pool.submit(next_submit)
                next_submit += 1

        def drain(block: bool) -> None:
            # failures are keyed by their STEP and delivered in stream
            # order from the consuming path, not raised at poll time —
            # steps before the failing one still yield normally
            for r in pool.poll(block=block):
                key = r.item if isinstance(r, WorkerFailure) else r.step
                buffer[key] = r

        def host_batch(step: int, block: bool) -> Optional[_HostBatch]:
            nonlocal next_submit
            if pool is None:
                return self._make_batch(step) if block else None
            drain(block=False)
            while block and step not in buffer:
                drain(block=True)
            hb = buffer.pop(step, None)
            if hb is not None:
                pool.submit(next_submit)
                next_submit += 1
            return hb

        from repro.obs import trace_span
        t_loop = time.perf_counter()
        pending: Optional[tuple] = None     # (step, placed) put one ahead
        step = self.start_step
        while True:
            t0 = time.perf_counter()
            wait_s = 0.0
            if pending is not None and pending[0] == step:
                placed = pending[1]
                pending = None
            else:
                with trace_span("input_wait", tracer=self.tracer, step=step):
                    hb = host_batch(step, block=True)
                wait_s = time.perf_counter() - t0
                if isinstance(hb, WorkerFailure):
                    raise RuntimeError(
                        f"DataPipeline worker failed at step {step} "
                        f"(make_batch raised)") from hb.exc
                self._account(hb)
                placed = self._place(hb)
            self.report.stall_s += time.perf_counter() - t0
            # issue step+1's device transfer BEFORE yielding step: the
            # (async) host->device copy overlaps the consumer's compute
            if pool is not None and self.sharding is not None:
                nb = host_batch(step + 1, block=False)
                if isinstance(nb, WorkerFailure):
                    buffer[step + 1] = nb    # re-buffer: raised when reached
                elif nb is not None:
                    self._account(nb)
                    pending = (step + 1, self._place(nb))
            self.report.steps += 1
            self.report.wall_s = time.perf_counter() - t_loop
            if self.obs is not None:
                self._mirror_report(step)
            self.wait_s = wait_s
            yield step, placed
            step += 1

    def _mirror_report(self, step: int) -> None:
        """Per-step mirror of the stage report into data/* gauges — the
        registry tick (driven by the consumer) flushes them to sinks, so
        the stall report surfaces mid-run through the console sink instead
        of only at eval/end-of-run."""
        r = self.report
        obs = self.obs
        obs.gauge("data/stall_fraction").set(r.stall_fraction)
        obs.gauge("data/featurize_s").set(r.featurize_s)
        obs.gauge("data/queue_s").set(r.queue_s)
        obs.gauge("data/transfer_s").set(r.transfer_s)
        obs.gauge("data/stall_s").set(r.stall_s)
        obs.gauge("data/mean_fill").set(r.mean_fill)

    def _account(self, hb: _HostBatch) -> None:
        self.report.batches += 1
        self.report.featurize_s += hb.featurize_s
        self.report.queue_s += max(0.0, time.perf_counter() - hb.ready_t)
        self.report.fill_sum += hb.fill
        if hb.bucket is not None:
            key = hb.bucket.describe()
            self.report.bucket_counts[key] = (
                self.report.bucket_counts.get(key, 0) + 1)

    def close(self):
        """Stop the current iteration (idempotent); the pipeline returns to
        a fresh state, so ``iter -> close -> iter`` restarts at
        ``start_step`` — the ShardedLoader lifecycle contract."""
        gen = self._gen
        if gen is not None:
            gen.close()     # raises GeneratorExit inside -> _run's finally
        with self._lock:
            if gen is not None and self._gen is gen:
                # the generator was never started: closing it cannot run
                # _run's finally, so release this iteration's state here
                if self._pool is not None:
                    self._pool.close()
                self._live = False
                self._gen = self._pool = self._token = None
