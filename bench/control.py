#!/usr/bin/env python3
"""The upper readings of a cell's comparison, from the reference alone.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed it compares, by the cell's own numbers and limits, the
float32 reference with:

- ``control``: the reference computed one precision step lower (float8
  contractions instead of the configuration's bfloat16) in the program's
  place. It has to come out not correct.
- the faults a cell can have, planted in the reference put in the
  program's place: ``answer_altered`` (each step's loss 5% off),
  ``state_unchanged`` (neither the parameters nor their EMA copy move),
  ``ema_unchanged`` (the step leaves the EMA copy as it was), and, where
  the global batch holds two samples or more, ``half_batch`` (the step
  sees the first half of its batch and takes the means over it; with one
  sample per data-parallel chip this is also what chip 0 reads when the
  gradient exchange between the chips is left out).

Prints one JSON line per seed. The benchmark's runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def readings(workload: str, seed: int, bench_dir: str = BENCH) -> dict:
    import jax
    from repro.core import model as af2

    from bench import compare, data, weights
    from bench import reference as ref
    from bench.drivers.train import program_config

    def load(kind, name):
        with open(os.path.join(bench_dir, kind, f"{name}.json")) as f:
            return json.load(f)

    wl = load("workloads", workload)
    sz = load("configs", wl["config"])["model"]
    tr = load("traffic", wl["traffic"])
    dp = wl["plan"].get("pod", 1) * wl["plan"].get("data", 1)
    batch = tr["global_batch"]
    shapes = jax.eval_shape(
        lambda: af2.init_params(jax.random.PRNGKey(0), program_config(sz)))
    make = weights.maker(shapes)

    def run(kind, tr_, dp_):
        return ref.readings_of_steps(
            ref.Numerics(kind), sz, tr_, seed, tr_["check_steps"],
            lambda: make(seed),
            lambda i: jax.tree_util.tree_map(
                lambda x: x[:tr_["global_batch"]],
                data.batch(seed, i, batch, sz)), dp_)

    sound = run("f32", tr, dp)
    planted = {
        "control": run("fp8", tr, dp),
        "answer_altered": dict(sound, loss=[1.05 * x for x in sound["loss"]]),
        "state_unchanged": dict(sound, update=[0.0] * len(sound["update"]),
                                ema=[0.0] * len(sound["ema"])),
        "ema_unchanged": dict(sound, ema=[0.0] * len(sound["ema"])),
    }
    if batch >= 2:
        half = batch // 2
        planted["half_batch"] = run(
            "f32", dict(tr, global_batch=half), max(1, dp * half // batch))
    out = {"workload": workload, "seed": seed, "loss_f32": sound["loss"]}
    for name, p in planted.items():
        read = compare.readings(p, sound)
        out[name] = {"readings": read, "passed": compare.passed(
            compare.checks(read, wl["limits"]))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.run import require_chips, use_compile_cache
    use_compile_cache()
    require_chips(1)
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
