"""Helpers for the CPU tests: a copy of the benchmark with a small cell
added by data files alone, and a run of it with the look for a chip
skipped."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def tiny_model(**over) -> dict:
    """``af2_tiny``'s sizes as a configuration file's ``model`` dict."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.config import af2_tiny
    return dataclasses.asdict(af2_tiny(**over))


def copy_with_cell(dst: str, name: str, *, model: dict, traffic: dict,
                   chips: int = 1, plan=None, limits=None) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``dst`` and add one cell
    as data files (a configuration, a traffic mix, a workload); returns
    the copy's ``run.py``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    files = {
        f"configs/{name}.json": {"name": name, "source": "test",
                                 "reduced": [], "model": model},
        f"traffic/{name}.json": traffic,
        f"workloads/{name}.json": {
            "config": name, "traffic": name, "chips": chips,
            "plan": plan or {"data": 1}, "driver": "train", "why": "test",
            "limits": limits or TINY_LIMITS},
    }
    for rel, obj in files.items():
        with open(os.path.join(dst, "bench", rel), "w") as f:
            json.dump(obj, f)
    spec = json.load(open(os.path.join(dst, "BENCHMARK.json")))
    spec["workloads"].append({"name": name, "config": name, "traffic": name,
                              "chips": chips, "why": "test"})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return os.path.join(dst, "bench", "run.py")


def load_run(path: str):
    spec = importlib.util.spec_from_file_location("bench_copy_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_on_cpu(run_py: str, argv, n_devices: int = 1) -> dict:
    """The copy's ``run.run(argv)`` with the look for a chip replaced by
    the first ``n_devices`` CPU devices."""
    import jax
    mod = load_run(run_py)
    mod.require_chips = lambda n: jax.devices()[:n]
    mod.use_compile_cache = lambda: None
    return mod.run(argv)


# af2_tiny on the CPU, seeds 3000000007, 11 and 12: the program reads
# loss 3.1e-4, grad 0.021, update 0.018 (seed 3000000007); the float8
# control reads loss 2.8e-3 to 6.1e-3, grad 0.047 to 0.083, update 0.044
# to 0.066 (both variants). The EMA's change: the program reads 0.015 to
# 0.031 over seeds 3000000007, 11, 12, 77 and 2147483659 in both
# variants, the control 0.030 to 0.066 (under three times that), an EMA
# left unchanged 1.0
TINY_LIMITS = {"loss_gap": 1.2e-3, "grad_norm_gap": 0.04,
               "update_norm_gap": 0.035, "ema_norm_gap": 0.25}
TRAFFIC = {"global_batch": 1, "n_recycle": 2, "dropout": True, "lr": 1e-3,
           "warmup_steps": 100, "per_sample_clip": 0.1, "adam_b1": 0.9,
           "adam_b2": 0.999, "adam_eps": 1e-8, "ema_decay": 0.999,
           "check_steps": 3}


def fast_program_init(mp) -> None:
    """Jit the program's parameter init, which set-up runs eagerly: the
    benchmark replaces those weights with its own anyway, and compiling
    it once keeps the CPU tests short."""
    import jax
    from repro.core import model as af2
    mp.setattr(af2, "init_params",
               jax.jit(af2.init_params, static_argnums=1))


def cache_reference(mp, store: dict) -> None:
    """Compute each reference run once per process: runs of one cell and
    seed share it."""
    from bench import reference as ref
    inner = ref.readings_of_steps

    def cached(nx, sz, tr, seed, n_steps, make_params, make_batch, dp):
        key = json.dumps([nx.kind, sz, tr, seed, n_steps, dp],
                         sort_keys=True)
        if key not in store:
            store[key] = inner(nx, sz, tr, seed, n_steps, make_params,
                               make_batch, dp)
        return store[key]
    mp.setattr(ref, "readings_of_steps", cached)


def break_step(mp, fault: str) -> None:
    """Plant ``fault`` in the program's compiled train step:

    - ``state_unchanged``: the step returns the state it was given;
    - ``ema_unchanged``: the step returns the EMA copy it was given;
    - ``answer_altered``: the step's loss is 5% off where it is produced;
    - ``half_batch``: the step sees the first half of its batch only, and
      its loss and gradient are the means over that half;
    - ``no_exchange``: Branch Parallelism's exchange between chips is left
      out; each chip keeps its own branch and zeros for the other's.
    """
    import jax
    if fault == "no_exchange":
        from repro.parallel import branch

        def local_only(branches, *, axis="branch"):
            def run():
                idx = jax.lax.axis_index(axis)
                outs = []
                for i, fn in enumerate(branches):
                    shape = jax.eval_shape(fn)
                    zeros = lambda sh=shape: jax.tree_util.tree_map(
                        lambda s: jax.numpy.zeros(s.shape, s.dtype), sh)
                    outs.append(jax.lax.cond(idx == i, fn, zeros))
                return tuple(outs)
            return run
        mp.setattr(branch, "branch_parallel", local_only)
        return
    from repro.train import trainstep
    make = trainstep.make_af2_train_step

    def broken(*a, **kw):
        step, built = make(*a, **kw)

        def faulty(state, batch, rng, n_recycle_t=None):
            if fault == "half_batch":
                n = jax.tree_util.tree_leaves(batch)[0].shape[0] // 2
                batch = jax.tree_util.tree_map(lambda x: x[:n], batch)
            new_state, metrics = step(state, batch, rng, n_recycle_t)
            if fault == "state_unchanged":
                new_state = state
            if fault == "ema_unchanged":
                new_state = dict(new_state, ema=state["ema"])
            if fault == "answer_altered":
                metrics = dict(metrics, loss=metrics["loss"] * 1.05)
            return new_state, metrics
        return faulty, built
    mp.setattr(trainstep, "make_af2_train_step", broken)


def run_cell(tmp: str, name: str, *, traffic: dict, seed: int, plan=None,
             chips: int = 1, model_over=None, fault=None, store=None,
             limits=None) -> dict:
    """One run of a small cell on the CPU, optionally with a fault."""
    import pytest
    run_py = copy_with_cell(tmp, name, model=tiny_model(**(model_over or {})),
                            traffic=traffic, chips=chips, plan=plan,
                            limits=limits)
    with pytest.MonkeyPatch.context() as mp:
        fast_program_init(mp)
        cache_reference(mp, {} if store is None else store)
        if fault:
            break_step(mp, fault)
        return run_on_cpu(run_py, ["--workload", name, "--seed", str(seed),
                                   "--seconds", "1", "--trace", "0"],
                          n_devices=chips)
