#!/usr/bin/env python3
"""The lower readings of a cell's comparison: the program on many seeds.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3

It builds the cell's ``TrainRunner`` once and, for each seed, gives it
that seed's weights at step 0 and drives it through the compared steps as
a run of the cell does (``bench/drivers/train.py``), then frees the
program and takes the reference's readings of each seed, and compares
them by the cell's own numbers and limits. One process serves every
seed, so the step is traced and loaded once and the reference too.

Prints one JSON line per seed. The benchmark's runs do not run it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import compare
    from bench.drivers import train
    from bench.run import load_json, require_chips, use_compile_cache

    wl = load_json("workloads", args.workload)
    sz = load_json("configs", wl["config"])["model"]
    tr = load_json("traffic", wl["traffic"])
    use_compile_cache()
    devices = require_chips(wl["chips"])
    t0 = time.perf_counter()
    runner = train.build_runner(sz, tr, wl, args.seeds[0], devices)
    make, shapes = train.weights_maker(runner)
    progs = {}
    for seed in args.seeds:
        progs[seed], _ = train.program_first_steps(runner, make, seed, tr)
        train.log(f"program seed {seed} at {time.perf_counter() - t0:.1f}s")
    del runner, make
    gc.collect()
    for seed in args.seeds:
        r = train.reference_first_steps(sz, tr, wl, seed, shapes)
        read = compare.readings(progs[seed], r)
        chk = compare.checks(read, wl["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": read,
                          "correct": compare.passed(chk)}), flush=True)
        train.log(f"reference seed {seed} at {time.perf_counter() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
