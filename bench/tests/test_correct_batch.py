"""``correct`` on a small cell with a batch of two on one CPU device: true
for the program as it is, false when the step leaves out half the batch
and takes the mean over the rest."""
import pytest

from bench.tests import harness

SEED = 2147483659
TRAFFIC = dict(harness.TRAFFIC, global_batch=2, n_recycle=1)


@pytest.fixture(scope="module")
def store():
    return {}


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_half_the_batch_left_out_is_not_correct(tmp_path, store, fault):
    out = harness.run_cell(str(tmp_path), "tiny_b2", traffic=TRAFFIC,
                           seed=SEED, store=store, fault=fault)
    assert out["correct"] is (fault is None), out["checks"]
