# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import json
import sys
import traceback


def compare_kernel_rows(baseline: list, fresh: list, tol: float = 0.10):
    """Regressions of previously-committed BENCH_kernels.json rows.

    A row regresses when its fresh ms exceeds the committed ms by more than
    ``tol``.  Rows new in this run (no committed counterpart) and rows that
    vanished (suite filtered out) are ignored — only a previously-committed
    row getting slower fails."""
    old = {(r["op"], r["shape"], r["impl"]): r["ms"] for r in baseline}
    out = []
    for r in fresh:
        key = (r["op"], r["shape"], r["impl"])
        if key in old and old[key] > 0 and r["ms"] > old[key] * (1 + tol):
            out.append((key, old[key], r["ms"]))
    return out


def compare_data_rows(baseline: list, fresh: list, tol: float = 0.10,
                      floor: float = 0.02):
    """Regressions of committed BENCH_data.json input-stall fractions.

    A scenario regresses when its fresh ``stall_fraction`` exceeds the
    committed one by more than ``tol`` relative AND ``floor`` absolute —
    the absolute floor keeps near-zero overlapped stalls (where 10% is
    sub-millisecond timing noise) from flapping the gate."""
    old = {r["scenario"]: r.get("stall_fraction") for r in baseline}
    out = []
    for r in fresh:
        prev = old.get(r["scenario"])
        cur = r.get("stall_fraction")
        if prev is None or cur is None:
            continue
        if cur > prev * (1 + tol) and cur - prev > floor:
            out.append((r["scenario"], prev, cur))
    return out


def compare_train_rows(baseline: list, fresh: list, tol: float = 0.10,
                       floor: float = 0.02):
    """Regressions of committed BENCH_train.json instrumentation overhead.

    The ``train_tiny_obs_overhead`` row's ``overhead_frac`` (instrumented
    vs default loop, DESIGN.md §14 budget) regresses when the fresh value
    exceeds the committed one by more than ``tol`` relative AND ``floor``
    absolute — the floor keeps near-zero overheads (where 10% relative is
    scheduler jitter on 20s CPU steps) from flapping the gate."""
    old = {r["scenario"]: r.get("overhead_frac") for r in baseline}
    out = []
    for r in fresh:
        prev = old.get(r["scenario"])
        cur = r.get("overhead_frac")
        if prev is None or cur is None:
            continue
        if cur > prev * (1 + tol) and cur - prev > floor:
            out.append((r["scenario"], prev, cur))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Run benchmark suites; positional names filter suites.")
    ap.add_argument("suites", nargs="*",
                    help="suite function names to run (default: all)")
    ap.add_argument("--compare", action="store_true",
                    help="diff fresh kernel rows against the committed "
                         "BENCH_kernels.json trajectory; fail (and keep the "
                         "committed file) on any >10%% regression of a "
                         "previously-committed row")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    from benchmarks import paper_tables, kernel_bench, fold_bench, train_bench
    from benchmarks import data_bench
    from benchmarks import common
    suites = (paper_tables.ALL + kernel_bench.ALL + fold_bench.ALL
              + train_bench.ALL + data_bench.ALL)
    if args.suites:
        wanted = set(args.suites)
        suites = [f for f in suites if f.__name__ in wanted]
    baseline = []
    if args.compare and common.KERNEL_JSON.exists():
        baseline = json.loads(common.KERNEL_JSON.read_text())
    data_baseline = []
    if args.compare and common.DATA_JSON.exists():
        data_baseline = json.loads(common.DATA_JSON.read_text())
    train_baseline = []
    if args.compare and common.TRAIN_JSON.exists():
        train_baseline = json.loads(common.TRAIN_JSON.read_text())
    failed = []
    for fn in suites:
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failed.append((fn.__name__, e))
            print(f"{fn.__name__},0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if args.compare and not failed:
        regressions = compare_kernel_rows(baseline, common.KERNEL_ROWS)
        if regressions:
            for (op, shape, impl), old_ms, new_ms in regressions:
                print(f"# REGRESSION {op}/{shape}/{impl}: "
                      f"{old_ms}ms -> {new_ms}ms "
                      f"({new_ms / old_ms - 1:+.0%})", file=sys.stderr)
            raise SystemExit(
                f"{len(regressions)} kernel row(s) regressed >10% vs the "
                "committed trajectory; BENCH_kernels.json left untouched")
        print(f"# compare: {len(common.KERNEL_ROWS)} fresh rows vs "
              f"{len(baseline)} committed, no >10% regressions",
              file=sys.stderr)
    if args.compare and not failed:
        data_reg = compare_data_rows(data_baseline, common.DATA_ROWS)
        if data_reg:
            for scenario, old_f, new_f in data_reg:
                print(f"# REGRESSION data/{scenario}: stall_fraction "
                      f"{old_f} -> {new_f}", file=sys.stderr)
            raise SystemExit(
                f"{len(data_reg)} data-pipeline row(s) regressed >10% vs "
                "the committed trajectory; BENCH_data.json left untouched")
        print(f"# compare: {len(common.DATA_ROWS)} fresh data rows vs "
              f"{len(data_baseline)} committed, no stall regressions",
              file=sys.stderr)
    if args.compare and not failed:
        train_reg = compare_train_rows(train_baseline, common.TRAIN_ROWS)
        if train_reg:
            for scenario, old_f, new_f in train_reg:
                print(f"# REGRESSION train/{scenario}: overhead_frac "
                      f"{old_f} -> {new_f}", file=sys.stderr)
            raise SystemExit(
                f"{len(train_reg)} training row(s) regressed >10% vs the "
                "committed trajectory; BENCH_train.json left untouched")
        print(f"# compare: {len(common.TRAIN_ROWS)} fresh train rows vs "
              f"{len(train_baseline)} committed, no overhead regressions",
              file=sys.stderr)
    if common.KERNEL_ROWS and not failed:
        # only a fully-green run may overwrite the committed trajectories —
        # a partial row set would read as kernels regressing out of existence
        common.write_kernel_json()
        print(f"# wrote {len(common.KERNEL_ROWS)} rows to "
              f"{common.KERNEL_JSON}", file=sys.stderr)
    if common.SERVE_ROWS and not failed:
        # same only-green gating for the fold-serving trajectory
        common.write_serve_json()
        print(f"# wrote {len(common.SERVE_ROWS)} rows to "
              f"{common.SERVE_JSON}", file=sys.stderr)
    if common.TRAIN_ROWS and not failed:
        # same only-green gating for the training-loop trajectory
        common.write_train_json()
        print(f"# wrote {len(common.TRAIN_ROWS)} rows to "
              f"{common.TRAIN_JSON}", file=sys.stderr)
    if common.DATA_ROWS and not failed:
        # same only-green gating for the input-pipeline trajectory
        common.write_data_json()
        print(f"# wrote {len(common.DATA_ROWS)} rows to "
              f"{common.DATA_JSON}", file=sys.stderr)
    if common.paper_rows() and not failed:
        # same only-green gating for the paper-table rows EXPERIMENTS.md
        # §Paper-claims cites
        common.write_paper_json()
        print(f"# wrote {len(common.paper_rows())} rows to "
              f"{common.PAPER_JSON}", file=sys.stderr)
    if failed:
        raise SystemExit(f"{len(failed)} benchmark(s) failed: "
                         f"{[n for n, _ in failed]}")


if __name__ == '__main__':
    main()
