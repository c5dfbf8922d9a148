"""Telemetry subsystem contract (ISSUE 9; marker: obs).

Pins the obs layer's four load-bearing guarantees:

1. registry determinism — identical recording sequences produce
   bit-identical sink rows modulo the single wall-clock field;
2. span tracer invariants — nesting (child interval inside parent), depth
   accounting, and Chrome-trace/Perfetto schema validity;
3. thin views — ``TrainRunner.history`` IS the registry's series (same
   list objects), so legacy consumers and sinks see one stream;
4. lifetime vs per-call serve counters — ``FoldEngine.stats`` accumulates
   across calls, ``last_stats`` is the most recent call's delta (the
   inflated-ratio bug this PR pins).
"""
import json

import jax
import numpy as np
import pytest

from repro.core import model as af2
from repro.core.config import af2_tiny
from repro.obs import (ConsoleSink, JsonlSink, MemorySink, MetricRegistry,
                       SpanTracer, attribution_report, describe_attribution,
                       get_tracer, parse_profile_steps, set_tracer,
                       trace_span)
from repro.obs.sinks import strip_walltimes
from repro.parallel.plan import ParallelPlan

pytestmark = pytest.mark.obs


def _cfg():
    return af2_tiny(n_evoformer=1, n_extra_msa_blocks=1, n_res=8, n_seq=4,
                    n_extra_seq=6)


# ---------------------------------------------------------------------------
# Metric registry
# ---------------------------------------------------------------------------

def _drive(reg):
    c = reg.counter("serve/requests")
    g = reg.gauge("data/stall_fraction")
    h = reg.histogram("train/step_s")
    for step in range(5):
        c.inc(2)
        g.set(0.1 * step)
        h.observe(0.5 + 0.01 * step)
        reg.record("train/loss", 3.0 - 0.1 * step, step=step)
        reg.tick(step=step)


def test_registry_determinism_bit_identical_modulo_walltime(tmp_path):
    """Same recording sequence => bit-identical JSONL modulo the wall-clock
    field — the contract that makes metric streams diffable across runs."""
    paths = [tmp_path / "a.jsonl", tmp_path / "b.jsonl"]
    for p in paths:
        reg = MetricRegistry(sinks=[JsonlSink(p)])
        _drive(reg)
        reg.close()
    a, b = [strip_walltimes(p.read_text().splitlines()) for p in paths]
    assert a == b
    assert len(a) > 10
    # and the wall-clock field is the ONLY nondeterminism: raw lines differ
    # at most in "t"
    for la, lb in zip(paths[0].read_text().splitlines(),
                      paths[1].read_text().splitlines()):
        ra, rb = json.loads(la), json.loads(lb)
        ra.pop("t"), rb.pop("t")
        assert ra == rb


def test_registry_rows_ordered_and_tick_dedups():
    sink = MemorySink()
    reg = MetricRegistry(sinks=[sink])
    _drive(reg)
    seqs = [r["seq"] for r in sink.rows]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # an unchanged instrument is NOT re-emitted at the next tick
    reg.tick(step=99)
    kinds = [r["kind"] for r in sink.rows if r.get("step") == 99]
    assert kinds == ["tick"]


def test_registry_series_is_live_view():
    reg = MetricRegistry()
    view = reg.series("train/loss")
    reg.record("train/loss", 1.5, step=0)
    reg.record("train/loss", 1.25, step=1)
    assert view == [1.5, 1.25]
    assert reg.series("train/loss") is view


def test_registry_kind_collision_rejected():
    reg = MetricRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_histogram_quantiles():
    reg = MetricRegistry()
    h = reg.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    p = h.payload()
    assert p["count"] == 100 and p["min"] == 1.0 and p["max"] == 100.0
    assert abs(p["p50"] - 50.5) < 1.0
    assert p["p99"] >= 99.0


def test_console_sink_prints_stall_report_every_n_steps():
    lines = []
    sink = ConsoleSink(every=2, log=lines.append, prefixes=("data/",))
    reg = MetricRegistry(sinks=[sink])
    g = reg.gauge("data/stall_fraction")
    reg.gauge("train/ignored").set(1.0)   # filtered by prefix
    for step in range(5):
        g.set(0.1 * step)
        reg.tick(step=step)
    assert len(lines) == 3                # steps 0, 2, 4
    assert "data/stall_fraction" in lines[-1]
    assert "train/ignored" not in lines[-1]


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering_invariants():
    tr = SpanTracer()
    with tr.span("outer", step=1):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b"):
            pass
    inner_a, inner_b = tr.spans("inner_a")[0], tr.spans("inner_b")[0]
    outer = tr.spans("outer")[0]
    # children complete before the parent (completion-ordered event list)
    names = [e["name"] for e in tr.events]
    assert names == ["inner_a", "inner_b", "outer"]
    # child intervals nest inside the parent's
    for child in (inner_a, inner_b):
        assert child["ts"] >= outer["ts"]
        assert child["ts"] + child["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner_b["ts"] >= inner_a["ts"] + inner_a["dur"] - 1e-6
    assert outer["args"]["depth"] == 0
    assert inner_a["args"]["depth"] == 1
    assert outer["args"]["step"] == 1


def test_chrome_trace_schema_perfetto_loadable(tmp_path):
    """The exported JSON must carry the Chrome-trace fields Perfetto
    requires: top-level traceEvents, ph/pid/tid/ts (+dur for X events)."""
    tr = SpanTracer()
    with tr.span("step", step=0):
        with tr.span("featurize"):
            pass
    path = tmp_path / "trace.json"
    tr.save(path)
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert metas and spans
    assert any(e["name"] == "process_name" for e in metas)
    assert any(e["name"] == "thread_name" for e in metas)
    for e in spans:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert isinstance(e["ts"], float) and e["dur"] >= 0.0
        assert isinstance(e["tid"], int)


def test_trace_span_global_fallback_and_noop():
    with trace_span("nobody-listening"):   # no tracer anywhere: no-op
        pass
    tr = SpanTracer()
    prev = set_tracer(tr)
    try:
        assert get_tracer() is tr
        with trace_span("global"):
            pass
    finally:
        set_tracer(prev)
    assert len(tr.spans("global")) == 1


def test_worker_thread_spans_get_own_tid():
    import threading
    tr = SpanTracer()
    def work():
        with tr.span("featurize"):
            pass
    t = threading.Thread(target=work, name="featurize-0")
    with tr.span("step"):
        t.start()
        t.join()
    tids = {e["name"]: e["tid"] for e in tr.spans()}
    assert tids["step"] != tids["featurize"]
    meta_names = {e["args"]["name"]
                  for e in tr.to_chrome_trace()["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "featurize-0" in meta_names


def test_parse_profile_steps():
    assert parse_profile_steps("3:7") == (3, 7)
    with pytest.raises(ValueError, match="A < B"):
        parse_profile_steps("7:3")


# ---------------------------------------------------------------------------
# Attribution
# ---------------------------------------------------------------------------

def test_attribution_report_fields_and_bounds():
    cfg = _cfg()
    rep = attribution_report(
        cfg, ParallelPlan(), device_kind="TPU v5 lite", global_batch=2,
        n_recycle=2.0,
        measured_step_s=0.5, stall_fraction=0.1, overhead_s=1.0,
        wall_s=10.0, step=7)
    assert rep["step"] == 7
    assert rep["predicted_step_s"] > 0
    assert rep["measured_over_predicted"] > 0
    assert rep["model_flops_per_step"] > 0
    assert rep["achieved_flops"] == pytest.approx(
        rep["model_flops_per_step"] / 0.5)
    assert 0.0 <= rep["mfu"] <= 1.0
    # goodput = 1 - stall (0.1) - overhead fraction (1/10)
    assert rep["goodput"] == pytest.approx(0.8)
    assert "ParallelPlan" in rep["plan"]
    line = describe_attribution(rep)
    assert "MFU" in line and "goodput" in line and "stall" in line


def test_attribution_mfu_needs_a_known_device():
    """MFU divides by the measured device's published peak: the host CPU
    has none ("not measured"), and an unknown accelerator raises."""
    cfg = _cfg()
    kw = dict(global_batch=2, n_recycle=1.0, measured_step_s=0.5)
    v5e = attribution_report(cfg, ParallelPlan(), device_kind="TPU v5 lite",
                             **kw)
    assert v5e["mfu"] == pytest.approx(
        v5e["achieved_flops"] / 197e12)
    cpu = attribution_report(cfg, ParallelPlan(), device_kind="cpu", **kw)
    assert cpu["mfu"] is None
    assert "MFU not measured" in describe_attribution(cpu)
    with pytest.raises(KeyError, match="no published peaks"):
        attribution_report(cfg, ParallelPlan(), device_kind="TPU v99", **kw)


def test_predict_step_time_scales_with_batch_and_recycle():
    from repro.analysis.roofline import predict_step_time
    cfg = _cfg()
    t1 = predict_step_time(cfg, global_batch=1, n_recycle=1.0)
    t2 = predict_step_time(cfg, global_batch=2, n_recycle=1.0)
    t1r3 = predict_step_time(cfg, global_batch=1, n_recycle=3.0)
    assert t2["predicted_step_s"] == pytest.approx(
        2 * t1["predicted_step_s"])
    assert t1r3["predicted_step_s"] > t1["predicted_step_s"]
    # trunk scale folds the extra stack + structure module in: > 1
    assert t1["trunk_scale"] > 1.0
    # data sharding divides the local batch, not the model FLOPs
    t_dp = predict_step_time(cfg, global_batch=4, data=4, n_recycle=1.0)
    assert t_dp["predicted_step_s"] == pytest.approx(t1["predicted_step_s"])
    assert t_dp["model_flops_per_step"] == pytest.approx(
        4 * t1["model_flops_per_step"])


# ---------------------------------------------------------------------------
# TrainRunner integration: history-as-view + spans + attribution stream
# ---------------------------------------------------------------------------

def test_trainrunner_history_is_registry_view_and_spans_cover_stages(
        tmp_path):
    from repro.train.trainer import TrainRunner
    sink = MemorySink()
    reg = MetricRegistry(sinks=[sink])
    tr = SpanTracer()
    runner = TrainRunner(
        _cfg(), batch_size=2, seed=0, max_recycle=2, eval_every=2,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=2, obs=reg, tracer=tr,
        hlo_check=True)
    hist = runner.run(4)
    # thin views: the history lists ARE the registry series objects
    for key in ("loss", "n_recycle", "step_s", "eval", "data",
                "attribution"):
        assert hist[key] is reg.series(f"train/{key}")
    assert len(hist["loss"]) == 4
    # every loss value also reached the sink as an event row, in order
    sunk = [r["value"] for r in sink.events("train/loss")]
    assert sunk == pytest.approx(hist["loss"])
    # attribution rows at the eval cadence, with the promised fields
    assert len(hist["attribution"]) == 2
    for a in hist["attribution"]:
        assert {"measured_step_s", "predicted_step_s", "mfu", "goodput",
                "stall_fraction"} <= set(a)
    # async-overlap verdict recorded (CPU: skipped, with the reason)
    ov = reg.series("train/async_overlap_ok")
    assert len(ov) == 1
    assert ov[0]["skipped"] is True and ov[0]["reason"]
    # ONE compiled train program despite the hlo_check lowering
    assert runner.train_compiles == 1
    # spans cover the train-side stages
    names = {e["name"] for e in tr.spans()}
    assert {"featurize", "device_put", "step", "eval",
            "checkpoint"} <= names
    # step spans carry their step ids
    steps = sorted(e["args"]["step"] for e in tr.spans("step"))
    assert steps == [0, 1, 2, 3]
    # per-step events, one per step; no step counter (the step ids on every
    # event carry the count)
    assert len(reg.series("train/step_s")) == 4
    assert len(reg.series("train/input_wait_s")) == 4
    assert "train/steps" not in reg.snapshot()
    # checkpoint timings flowed through the registry
    assert len(reg.series("ckpt/save_s")) >= 1


@pytest.fixture(scope="module")
def runner():
    """One compiled TrainRunner (no SpanTracer) for the loop tests below;
    each continues from the step the last one left it at."""
    from repro.train.trainer import TrainRunner
    r = TrainRunner(_cfg(), batch_size=1, seed=0, n_recycle=1,
                    recycle_sample=False)
    r.run(1)                            # compiles the step
    return r


def test_trainrunner_step_s_is_each_steps_own_time(runner):
    """``train/step_s`` is the step's own host seconds, not the watchdog's
    EMA (which skips a straggler and so never sees a slow step)."""
    import time
    step, slow = runner._train_step, runner.step + 1

    def slowed(state, batch, rng, nr):
        out = step(state, batch, rng, nr)
        if runner.step == slow:
            time.sleep(0.5)
        return out
    runner._train_step = slowed
    try:
        hist = runner.run(slow + 1)
    finally:
        runner._train_step = step
    # the slowed step reads its sleep; the step before it carries nothing
    # of step 0, which compiled (an EMA would carry most of it)
    assert hist["step_s"][slow] >= 0.5
    assert hist["step_s"][slow - 1] < 0.5 * hist["step_s"][0]
    waits = runner.obs.series("train/input_wait_s")
    assert len(waits) == len(hist["step_s"]) == slow + 1
    assert all(w >= 0.0 for w in waits)


def _host_events(logdir, names):
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(ev.name, dict(ev.stats)) for ev in line.events
                        if ev.name in names]
    return out


def test_trainrunner_spans_reach_the_profiler(runner, tmp_path):
    """With no SpanTracer installed, the loop's spans still land on the
    profiler's host plane: ``step`` as a step annotation with its
    ``step_num``, and the loop's ``input_wait`` (the pipeline may already
    wait for the batch after the last step)."""
    assert get_tracer() is None and runner.tracer is None
    first = runner.step
    jax.profiler.start_trace(str(tmp_path))
    try:
        runner.run(first + 2)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path, {"step", "input_wait"})
    steps = sorted(st["step_num"] for name, st in events if name == "step")
    assert steps == [first, first + 1]
    waits = sorted(st["step"] for name, st in events if name == "input_wait")
    assert waits and set(waits) <= {first, first + 1, first + 2}


# ---------------------------------------------------------------------------
# FoldEngine: lifetime vs per-call counters (the inflated-ratio pin)
# ---------------------------------------------------------------------------

def _fold_engine(reg=None):
    from repro.serve import FoldEngine
    from repro.serve import fold_steps as fs
    cfg = _cfg()
    params = af2.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, FoldEngine(
        cfg, params, buckets=[fs.Bucket(cfg.n_res, cfg.n_seq,
                                        cfg.n_extra_seq)],
        micro_batch=2, max_recycle=2, tol=0.0, obs=reg)


def _fold_requests(cfg, n, base=0):
    from repro.data.protein import protein_sample
    from repro.serve import FoldRequest
    from repro.serve import fold_steps as fs
    reqs = []
    for i in range(n):
        smp = protein_sample(jax.random.PRNGKey(200 + base + i), cfg)
        feats = {k: np.asarray(smp[k]) for k in fs.REQUEST_FEATURE_KEYS}
        reqs.append(FoldRequest(rid=base + i, features=feats))
    return reqs


def test_fold_engine_lifetime_vs_per_call_counters():
    reg = MetricRegistry()
    cfg, eng = _fold_engine(reg)
    eng.run(_fold_requests(cfg, 2))
    first = dict(eng.last_stats)
    assert first["requests"] == 2 and first["call"] == "run"
    assert 0.0 < first["recycle_fraction"] <= 1.0
    life_after_first = dict(eng.stats)

    eng.run(_fold_requests(cfg, 2, base=10))
    second = dict(eng.last_stats)
    # per-call: the second window reports ONLY its own traffic...
    assert second["requests"] == 2
    assert second["recycles_budget"] == first["recycles_budget"]
    # ...while the lifetime view keeps accumulating (the old behavior,
    # now explicitly the lifetime series)
    assert eng.stats["requests"] == 4
    assert eng.stats["recycles_budget"] == 2 * life_after_first[
        "recycles_budget"]
    # a per-call ratio computed from last_stats does NOT inflate
    assert second["recycle_fraction"] == pytest.approx(
        second["recycles_run"] / second["recycles_budget"])
    # the registry's serve/* counters match the lifetime dict
    assert reg.counter("serve/requests").value == eng.stats["requests"]
    assert reg.counter("serve/steps").value == eng.stats["steps"]
    # one serve/call event per entry-point call
    assert len(reg.series("serve/call")) == 2
