"""Driver of a training cell.

Set-up builds one ``TrainRunner`` (the program's training loop around its
compiled step) for the cell's configuration and plan, gives it the
benchmark's weights for the seed, and drives it through its first steps
with the same call and feed as the window: ``run(1)``, then
``run(check_steps)``. The first step compiles. The program's loss at each
of these steps, its first gradient (from the optimizer's first moment
after one step), and its parameters' change and its EMA copy's change
over them are what the reference is compared with.

The window is ``run(check_steps + n)``: ``n`` whole steps, enough to fill
``seconds`` at the set-up's step time, ending in a loss the host has read.
With ``trace`` the window runs under the profiler. After the window, the
device memory is read, the program's state freed, and the reference takes
the same first steps from the same seed.

The host time of each step in the window (when its loss was recorded) and
the Python garbage collections that ran in it are logged to standard
error; they decide nothing.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import statistics
import shutil
import sys
import time

GIB = 1 << 30


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def program_config(model: dict):
    """The program's ``AlphaFold2Config`` with exactly the file's sizes."""
    from repro.core.config import (AlphaFold2Config, EvoformerConfig,
                                   StructureConfig)
    m = dict(model)
    return AlphaFold2Config(evoformer=EvoformerConfig(**m.pop("evoformer")),
                            extra=EvoformerConfig(**m.pop("extra")),
                            structure=StructureConfig(**m.pop("structure")),
                            **m)


def _same_sizes(run_cfg, model: dict) -> None:
    """The configuration the program runs, after the plan's choices, must
    be the file's: the reference reads the file."""
    got = dataclasses.asdict(run_cfg)
    for k, v in model.items():
        if got.get(k) != v:
            raise ValueError(f"the program runs {k}={got.get(k)!r}; the "
                             f"configuration file says {v!r}")


def memory_peak(devices) -> dict:
    """Peak device memory of the fullest chip: buffers the runtime
    allocated (``peak_bytes_in_use``) plus the scratch it reserved for
    programs' temporaries (``peak_bytes_reserved``)."""
    peaks = []
    for d in devices:
        ms = d.memory_stats() or {}
        peaks.append(ms.get("peak_bytes_in_use", 0)
                     + ms.get("peak_bytes_reserved", 0))
    return {"bytes": max(peaks), "per_chip": peaks}


class StepClock:
    """A sink of the program's metric registry: the host time at which the
    loss of each step was recorded."""

    def __init__(self):
        self.t = []

    def write(self, row: dict) -> None:
        if row["name"] == "train/loss":
            self.t.append(row["t"])

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class GcClock:
    """Python's garbage collections while it is on: count and seconds of
    each generation."""

    def __init__(self):
        self.count, self.seconds, self._t0 = [0, 0, 0], [0.0, 0.0, 0.0], 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.count[g] += 1
            self.seconds[g] += time.perf_counter() - self._t0

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def fresh_state(runner, make_params):
    """The runner's state as its constructor makes it, for the weights
    ``make_params()``: the keys are the runner's own; the optimizer state
    and the EMA copy come from the runner's optimizer and EMA, and any
    other part (such as an error-feedback buffer) starts at zero as there.
    The old state is let go first, so that two never share the device."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(lambda: runner.state)
    runner.state = None
    params = make_params()
    made = {"params": lambda: params,
            "opt": lambda: runner.optimizer.init(params),
            "ema": lambda: runner.ema.init(params)}
    return runner._place({
        k: made[k]() if k in made else jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), v)
        for k, v in shapes.items()})


def build_runner(sz: dict, tr: dict, workload: dict, seed: int, devices,
                 obs=None):
    """The program's ``TrainRunner`` for the cell: its configuration, plan
    and traffic."""
    from repro.parallel.plan import ParallelPlan
    from repro.train import optim
    from repro.train.trainer import TrainRunner
    opt = optim.adamw(
        optim.af2_lr_schedule(tr["lr"], warmup_steps=tr["warmup_steps"]),
        b1=tr["adam_b1"], b2=tr["adam_b2"], eps=tr["adam_eps"],
        per_sample_clip=tr["per_sample_clip"])
    runner = TrainRunner(program_config(sz), ParallelPlan(**workload["plan"]),
                         optimizer=opt, batch_size=tr["global_batch"],
                         seed=seed, n_recycle=tr["n_recycle"],
                         recycle_sample=False, ema_decay=tr["ema_decay"],
                         deterministic=not tr["dropout"], devices=devices,
                         obs=obs)
    _same_sizes(runner.cfg, sz)
    return runner


def weights_maker(runner):
    """``(make, shapes)``: the benchmark's weights for a seed, placed as the
    runner places its state, and the shapes of the runner's parameters."""
    import jax
    from jax.sharding import NamedSharding

    from bench import weights
    shapes = jax.eval_shape(lambda: runner.state["params"])
    return weights.maker(shapes, out_shardings=NamedSharding(
        runner.built.mesh, runner.built.state_spec)), shapes


def program_first_steps(runner, make, seed: int, tr: dict) -> tuple:
    """Give ``runner`` the seed's weights at step 0 and drive it through
    the compared steps with the window's own call and feed: ``run(1)``,
    then ``run(check_steps)``. Returns the program's readings (``loss`` per
    step; per-leaf norms of the first gradient, of the parameters' change
    and of the EMA copy's change) and the host seconds a step took."""
    import jax
    import jax.numpy as jnp

    from bench import reference as ref
    k_steps = tr["check_steps"]
    runner.seed, runner.step = seed, 0
    runner.state = fresh_state(runner, lambda: make(seed))
    runner.run(1)
    prog = {"grad": [float(x) / (1.0 - tr["adam_b1"])
                     for x in ref.leaf_norms(runner.state["opt"].mu)]}
    t0 = time.perf_counter()
    runner.run(k_steps)
    step_s = (time.perf_counter() - t0) / (k_steps - 1)
    p0 = make(seed)

    def change(tree):
        return [float(x) for x in ref.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, tree, p0))]
    prog["update"] = change(runner.state["params"])
    if "ema" in runner.state:
        prog["ema"] = change(runner.state["ema"])
    del p0
    prog["loss"] = [float(x) for x in runner.history["loss"][-k_steps:]]
    return prog, step_s


def reference_first_steps(sz: dict, tr: dict, workload: dict, seed: int,
                          shapes) -> dict:
    """The reference's readings of the same first steps, on one device."""
    from bench import data, weights
    from bench import reference as ref
    plan = workload["plan"]
    make = weights.maker(shapes)
    return ref.readings_of_steps(
        ref.Numerics("f32"), sz, tr, seed, tr["check_steps"],
        lambda: make(seed),
        lambda i: data.batch(seed, i, tr["global_batch"], sz),
        plan.get("pod", 1) * plan.get("data", 1))


def leaf_names(shapes) -> list:
    import jax
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def run(*, workload, config, traffic, seed, seconds, trace, devices,
        t_start, out_dir) -> dict:
    import jax

    from repro.obs import MetricRegistry

    from bench import compare, flops, trace_reduce

    sz, tr = config["model"], traffic
    batch_size, k_steps = tr["global_batch"], tr["check_steps"]
    clock = StepClock()
    runner = build_runner(sz, tr, workload, seed, devices,
                          obs=MetricRegistry(sinks=[clock],
                                             clock=time.perf_counter))
    log(f"runner built at {time.perf_counter() - t_start:.1f}s")
    make, shapes = weights_maker(runner)

    # the first steps, through the window's own call and feed
    prog, step_s = program_first_steps(runner, make, seed, tr)
    log(f"compared steps done at {time.perf_counter() - t_start:.1f}s")

    # the window
    n = max(1, math.ceil(seconds / step_s))
    log(f"set-up steps done at {time.perf_counter() - t_start:.1f}s, "
        f"{step_s:.3f}s a step; window of {n} steps")
    compiles0 = runner.train_compiles
    trace_dir = f"{out_dir}/trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # device ops and the runtime's host events; tracing every Python
        # call would slow the host the window measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    n_clock = len(clock.t)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    try:
        with GcClock() as gcs:
            if trace:
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
                    runner.run(k_steps + n)
            else:
                runner.run(k_steps + n)
    finally:
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
            log(f"trace written at {time.perf_counter() - t_start:.1f}s")
    ends = [t0] + clock.t[n_clock:]
    steps_s = [b - a for a, b in zip(ends, ends[1:])]
    log(f"window steps (s): {[round(x, 4) for x in steps_s]}; median "
        f"{statistics.median(steps_s):.4f}, slowest {max(steps_s):.4f}; "
        f"garbage collections by generation {gcs.count}, "
        f"{[round(x, 4) for x in gcs.seconds]} s")
    losses = [float(x) for x in runner.history["loss"][k_steps:]]
    data_report = runner.history["data"][-1]
    stall_s = data_report["stall_ms_per_step"] * data_report["steps"] / 1e3
    compiles = runner.train_compiles - compiles0
    mem = memory_peak(devices)
    log(f"window {window_s:.3f}s for {n} steps; memory per chip "
        f"{mem['per_chip']}")
    rec = {
        "window_s": window_s, "proteins_per_s": n * batch_size / window_s,
        "stall_s": stall_s, "compiles_in_window": compiles,
        "flops_per_protein": flops.train_step_per_protein(sz,
                                                          tr["n_recycle"]),
        "chips": len(devices), "device_kind": devices[0].device_kind,
        "memory_peak_bytes": mem["bytes"], "attempted": n,
        "failed": sum(not math.isfinite(x) for x in losses),
    }
    rec["end_to_end"] = {"proteins_per_s": rec["proteins_per_s"],
                         "hbm_peak_gib": mem["bytes"] / GIB,
                         "setup_s": setup_s}
    if trace:
        red = trace_reduce.reduce_file(trace_reduce.find_trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ids = {f"/device:TPU:{d.id}" for d in devices}
        planes = [v for k, v in red["devices"].items() if k in ids]
        rec["trace"] = {"planes": planes}
        rec["traced_window_s"] = red.get("window_s", window_s)
        rec["busy_s"] = (sum(p["busy_s"] for p in planes) / len(planes)
                         if planes else 0.0)
        rec["breakdown"] = {"device_ops": red.get("device_ops", []),
                            "idle_gaps": red.get("idle_gaps", [])}
        log(f"trace reduced at {time.perf_counter() - t_start:.1f}s")

    # free the program's state, then the reference's first steps
    del runner
    gc.collect()
    t_ref = time.perf_counter()
    r = reference_first_steps(sz, tr, workload, seed, shapes)
    log(f"reference {time.perf_counter() - t_ref:.1f}s; losses: program "
        f"{prog['loss']}, reference {r['loss']}")
    read = compare.readings(prog, r)
    names = leaf_names(shapes)
    log(f"readings {read}; leaves left out "
        f"{[names[i] for i in read['left_out']]}")
    rec["checks"] = compare.checks(read, workload["limits"])
    rec["correct"] = compare.passed(rec["checks"]) and rec["failed"] == 0
    return rec
