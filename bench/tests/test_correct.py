"""``correct`` on a small cell on the CPU: true for the program as it is,
false for the control (the reference in float8 in the program's place)
and for each fault a one-chip, batch-1 cell can have."""
import os
import shutil

import pytest

from bench import control
from bench.tests import harness

SEED = 3000000007
TRAFFIC = harness.TRAFFIC


@pytest.fixture(scope="module")
def store():
    return {}


@pytest.fixture(scope="module")
def sound(tmp_path_factory, store):
    return harness.run_cell(str(tmp_path_factory.mktemp("sound")), "tiny",
                            traffic=TRAFFIC, seed=SEED, store=store)


def test_the_program_as_it_is_is_correct(sound):
    assert sound["correct"] is True, sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] >= 1
    assert set(sound["metrics"]) == {"proteins_per_s", "hbm_peak_gib",
                                     "setup_s"}
    assert list(sound["checks"]) == ["loss_gap", "grad_norm_gap",
                                     "update_norm_gap", "ema_norm_gap"]


def test_the_control_and_faults_in_the_reference_are_not_correct(
        tmp_path, store):
    run_py = harness.copy_with_cell(str(tmp_path), "tiny",
                                    model=harness.tiny_model(),
                                    traffic=TRAFFIC)
    with pytest.MonkeyPatch.context() as mp:
        harness.cache_reference(mp, store)
        out = control.readings("tiny", SEED,
                               bench_dir=os.path.dirname(run_py))
    faults = ("control", "answer_altered", "state_unchanged",
              "ema_unchanged")
    assert set(out) >= set(faults)
    for name in faults:
        assert out[name]["passed"] is False, (name, out[name])
    assert out["state_unchanged"]["readings"]["update_norm_gap"] == 1.0
    for name in ("state_unchanged", "ema_unchanged"):
        assert out[name]["readings"]["ema_norm_gap"] == 1.0
    assert out["answer_altered"]["readings"]["loss_gap"] == \
        pytest.approx(0.05)


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "ema_unchanged"])
def test_a_broken_step_is_not_correct(tmp_path, store, sound, fault):
    out = harness.run_cell(str(tmp_path), "tiny", traffic=TRAFFIC, seed=SEED,
                           store=store, fault=fault)
    assert out["correct"] is False, out["checks"]
    if fault == "state_unchanged":
        assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)
    if fault in ("state_unchanged", "ema_unchanged"):
        assert out["checks"]["ema_norm_gap"]["value"] == pytest.approx(1.0)
    shutil.rmtree(tmp_path, ignore_errors=True)
