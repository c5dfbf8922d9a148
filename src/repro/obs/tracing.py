"""Host spans: on the profiler's clock, and optionally as Chrome-trace JSON.

Every ``with trace_span("featurize", step=7):`` enters a
``jax.profiler.TraceAnnotation`` of that name and arguments, so the span
lands on the ``/host:CPU`` plane of any ``jax.profiler`` capture, on the
clock of the device ops beside it.  With no capture running an annotation
costs about a microsecond.  :func:`step_span` does the same for a training
step with a ``jax.profiler.StepTraceAnnotation`` (``step_num``), which
marks step boundaries for the profiler's step view.

A :class:`SpanTracer`, when one is active, also records each span as one
complete event ("ph":"X") per exit, with per-thread nesting depth tracked
so invariants (a child's interval lies inside its parent's) are testable.
Its timestamps come from a single ``perf_counter`` epoch per tracer,
converted to microseconds — the unit Chrome-trace expects.  The tracer is
either passed explicitly (``trace_span(name, tracer=t)``) or installed
process-wide with :func:`set_tracer` so deep call sites (worker threads
inside ``DataPipeline``) don't need plumbing.

An optional :class:`ProfileWindow` arms ``jax.profiler.trace`` over a step
interval ``A:B`` (``--profile-steps``) aligned to the same step ids as the
host spans.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation


class SpanTracer:
    """Collects nestable host spans; exports Chrome-trace JSON."""

    def __init__(self, *, pid: int = 1, process_name: str = "repro"):
        self.pid = pid
        self.process_name = process_name
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()
        self._local = threading.local()
        self.events: list = []          # finished spans, completion order
        self._tids: dict = {}           # thread ident -> small int
        self._tid_names: dict = {}      # small int -> thread name

    # -- time ----------------------------------------------------------------

    def now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    # -- thread bookkeeping --------------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = len(self._tids)
                self._tids[ident] = tid
                self._tid_names[tid] = threading.current_thread().name
            return tid

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    # -- spans ---------------------------------------------------------------

    @contextmanager
    def span(self, name: str, **args):
        tid = self._tid()
        stack = self._stack()
        depth = len(stack)
        t0 = self.now_us()
        stack.append(name)
        try:
            yield self
        finally:
            stack.pop()
            t1 = self.now_us()
            ev = {"name": name, "ph": "X", "ts": t0, "dur": t1 - t0,
                  "pid": self.pid, "tid": tid,
                  "args": {k: _arg(v) for k, v in args.items()}}
            ev["args"]["depth"] = depth
            with self._lock:
                self.events.append(ev)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker ("ph":"i") — step boundaries etc."""
        ev = {"name": name, "ph": "i", "ts": self.now_us(), "s": "t",
              "pid": self.pid, "tid": self._tid(),
              "args": {k: _arg(v) for k, v in args.items()}}
        with self._lock:
            self.events.append(ev)

    # -- export --------------------------------------------------------------

    def to_chrome_trace(self) -> dict:
        """Chrome-trace JSON object — loadable by Perfetto / chrome://tracing."""
        with self._lock:
            meta = [{"name": "process_name", "ph": "M", "pid": self.pid,
                     "tid": 0, "args": {"name": self.process_name}}]
            for tid in sorted(self._tid_names):
                meta.append({"name": "thread_name", "ph": "M",
                             "pid": self.pid, "tid": tid,
                             "args": {"name": self._tid_names[tid]}})
            return {"traceEvents": meta + list(self.events),
                    "displayTimeUnit": "ms"}

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)

    def spans(self, name: Optional[str] = None) -> list:
        with self._lock:
            return [e for e in self.events if e["ph"] == "X"
                    and (name is None or e["name"] == name)]


def _arg(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return str(v)


# -- module-global tracer (worker threads reach it without plumbing) ----------

_GLOBAL: Optional[SpanTracer] = None


def set_tracer(tracer: Optional[SpanTracer]) -> Optional[SpanTracer]:
    """Install ``tracer`` process-wide; returns the previous one."""
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = tracer
    return prev


def get_tracer() -> Optional[SpanTracer]:
    return _GLOBAL


def _span(annotation, name: str, tracer: Optional[SpanTracer], args: dict):
    t = tracer if tracer is not None else _GLOBAL
    return annotation if t is None else _both(annotation, t.span(name, **args))


@contextmanager
def _both(annotation, span):
    with annotation, span as t:
        yield t


def trace_span(name: str, *, tracer: Optional[SpanTracer] = None, **args):
    """A profiler annotation ``name``; also a span of ``tracer`` or of the
    global tracer, when either is set."""
    return _span(TraceAnnotation(name, **args), name, tracer, args)


def step_span(step: int, *, tracer: Optional[SpanTracer] = None, **args):
    """The span ``step`` of training step ``step``: a profiler step
    annotation (``step_num``), and a span like :func:`trace_span`'s."""
    return _span(StepTraceAnnotation("step", step_num=step, **args), "step",
                 tracer, dict(step=step, **args))


# -- jax.profiler capture window ---------------------------------------------

def parse_profile_steps(spec: str) -> Tuple[int, int]:
    """``"A:B"`` -> (A, B): capture begins entering step A, ends after
    step B-1 (half-open, like a Python slice)."""
    a, _, b = spec.partition(":")
    lo, hi = int(a), int(b)
    if hi <= lo:
        raise ValueError(f"--profile-steps {spec!r}: need A < B")
    return lo, hi


class ProfileWindow:
    """Arms ``jax.profiler.trace`` over a half-open step range.

    Call :meth:`maybe_start`/:meth:`maybe_stop` at each step boundary with
    the current step id; the device trace lands in ``logdir`` aligned to
    the same step ids as the host spans.  A capture that fails to start or
    stop raises: a run asked to trace that returns without its trace would
    pass for a traced one.
    """

    def __init__(self, lo: int, hi: int, logdir: str, log=print):
        self.lo, self.hi = lo, hi
        self.logdir = logdir
        self.log = log
        self.active = False

    def maybe_start(self, step: int) -> None:
        if self.active or step != self.lo:
            return
        import jax
        jax.profiler.start_trace(self.logdir)
        self.active = True
        self.log(f"[obs] jax.profiler capture ON at step {step} "
                 f"-> {self.logdir}")

    def maybe_stop(self, step: int) -> None:
        if not self.active or step + 1 != self.hi:
            return
        import jax
        self.active = False
        jax.profiler.stop_trace()
        self.log(f"[obs] jax.profiler capture OFF after step {step}")

    def close(self) -> None:
        if self.active:  # the run ended inside the window
            import jax
            self.active = False
            jax.profiler.stop_trace()
