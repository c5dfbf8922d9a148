"""BENCHMARK.json keeps the contract's form, and every cell's pieces are
found by name."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench.tests import harness

ROOT = harness.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_names_units_and_keys_follow_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert all(_line(w) for w in SPEC["command"])
    assert 1 <= SPEC["run_seconds"] <= 51
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/") and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(CELLS) // 2)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    names += CELLS
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_every_piece_of_a_cell_loads_by_name(cell):
    run = harness.load_run(os.path.join(ROOT, "bench", "run.py"))
    wl = run.load_json("workloads", cell)
    cfg = run.load_json("configs", wl["config"])
    assert run.load_json("traffic", wl["traffic"])["global_batch"] >= 1
    assert callable(run.load_module("drivers", wl["driver"]).run)
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        wl["config"], wl["traffic"], wl["chips"])
    listed = next(c for c in SPEC["configs"] if c["name"] == wl["config"])
    assert cfg["name"] == wl["config"]
    assert cfg["reduced"] == listed["reduced"]
    assert all(k in cfg["model"] for k in cfg["reduced"])
    for trace in (False, True):
        for m in run.cell_metrics(SPEC, cell, trace):
            if trace:
                assert callable(run.load_module("metrics", m["name"]).compute)


def test_a_cell_added_as_data_files_is_found_by_name(tmp_path):
    run_py = harness.copy_with_cell(
        str(tmp_path), "added_cell", model=harness.tiny_model(),
        traffic=harness.TRAFFIC)
    run = harness.load_run(run_py)
    assert run.load_json("workloads", "added_cell")["config"] == "added_cell"
    assert run.load_json("configs", "added_cell")["model"]["n_evoformer"] == 2
    names = [m["name"] for m in run.cell_metrics(run.benchmark_spec(),
                                                  "added_cell", True)]
    assert "step_mfu" in names and "collective_exposed_pct" not in names


PLAN_SCRIPT = """
import json, sys
sys.path[:0] = [{src!r}, {root!r}]
import jax
from bench.drivers.train import program_config
from repro.parallel.plan import ParallelPlan
wl = json.load(open({wl!r}))
cfg = json.load(open({cfg!r}))["model"]
built = ParallelPlan(**wl["plan"]).build(jax.devices()[:wl["chips"]],
                                         cfg=program_config(cfg))
print(json.dumps({{"devices": int(built.mesh.devices.size)}}))
"""


# the benchmark's cells, and Branch Parallelism x data parallelism on four
# chips for the model-1 configuration, whose cell waits for chip time
BP2DP2 = {"config": "af2_parallel_initial", "chips": 4,
          "plan": {"data": 2, "branch": 2}}


@pytest.mark.parametrize("cell", CELLS + ["bp2dp2"])
def test_each_cell_builds_its_plan_on_virtual_devices(cell, tmp_path):
    if cell == "bp2dp2":
        wl_path = str(tmp_path / "bp2dp2.json")
        json.dump(BP2DP2, open(wl_path, "w"))
    else:
        wl_path = os.path.join(ROOT, "bench", "workloads", f"{cell}.json")
    wl = json.load(open(wl_path))
    script = PLAN_SCRIPT.format(
        src=os.path.join(ROOT, "src"), root=ROOT, wl=wl_path,
        cfg=os.path.join(ROOT, "bench", "configs", f"{wl['config']}.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{wl['chips']}")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "devices": wl["chips"]}


@pytest.mark.parametrize("bare", [False, True], ids=["checkout", "bare"])
def test_run_exits_nonzero_with_no_result_without_a_tpu(tmp_path, bare):
    """Without a TPU (JAX held to the CPU here), and in a directory that
    holds only BENCHMARK.json and bench/, the command prints no result."""
    cwd = ROOT
    if bare:
        cwd = str(tmp_path)
        harness.copy_with_cell(cwd, "added_cell", model=harness.tiny_model(),
                               traffic=harness.TRAFFIC)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train_parallel_1chip",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout
