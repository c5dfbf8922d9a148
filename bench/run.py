#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is ``bench/workloads/<cell>.json``;
it names its configuration (``bench/configs/``), its traffic
(``bench/traffic/``) and its driver (``bench/drivers/<driver>.py``), which
runs set-up, the measured window and the check of what the window
produced. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, each read from the run's
record by ``bench/metrics/<metric>.py``. The last line of standard output
is one JSON object; the numbers compared with their limits are also the
last lines of standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no {kind[:-1]} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(spec: dict, workload: str, trace: bool) -> list:
    """The metrics this cell reports: end-to-end ones untraced, per-layer
    ones traced; a metric with a ``workloads`` list only in those cells."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in spec[key]
            if "workloads" not in m or workload in m["workloads"]]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` at the root of the checkout. Every
    program is cached, however small or quick to compile, and nothing is
    evicted: a cell's train step and reference are ~60 and ~100 MB, so a
    cap of a few hundred MB would make two cells evict each other and
    every run compile (and write) both again."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; exits non-zero where there are fewer."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: JAX finds no accelerator: {e}")
    if devices[0].platform != "tpu" or len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} TPU chip(s); JAX finds "
                         f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:n]


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def number(x):
    """A JSON-safe number: non-finite values become strings."""
    x = float(x)
    return x if math.isfinite(x) else str(x)


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = benchmark_spec()
    wl = load_json("workloads", args.workload)
    cfg = load_json("configs", wl["config"])
    traffic = load_json("traffic", wl["traffic"])
    metrics = cell_metrics(spec, args.workload, bool(args.trace))
    readers = {m["name"]: load_module("metrics", m["name"])
               for m in metrics} if args.trace else {}
    driver = load_module("drivers", wl["driver"])

    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    use_compile_cache()
    devices = require_chips(wl["chips"])

    rec = driver.run(workload=wl, config=cfg, traffic=traffic,
                     seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices,
                     t_start=T_START,
                     out_dir=os.path.join(ROOT, ".bench_out",
                                          f"{args.workload}.{args.seed}"))
    values = {}
    for m in metrics:
        v = (readers[m["name"]].compute(rec) if args.trace
             else rec["end_to_end"].get(m["name"]))
        if v is not None:
            values[m["name"]] = {"value": number(v), "unit": m["unit"]}
    device = dict(device_info(devices),
                  memory_peak_bytes=rec["memory_peak_bytes"])
    if args.trace:
        device.update(busy_s=rec["busy_s"], window_s=rec["traced_window_s"])
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": values, "device": device}
    if args.trace and rec.get("breakdown"):
        out["breakdown"] = rec["breakdown"]
    out["checks"] = {c["name"]: {"value": number(c["value"]),
                                 "limit": c["limit"]}
                     for c in rec["checks"]}
    return out


def main(argv=None) -> int:
    out = run(argv)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
