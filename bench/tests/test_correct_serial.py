"""``correct`` on a small cell in AlphaFold2's serial block order: the
reference follows the program in this order too."""
from bench.tests import harness


def test_the_serial_block_order_is_correct(tmp_path):
    out = harness.run_cell(str(tmp_path), "tiny_serial",
                           traffic=harness.TRAFFIC, seed=2999999999,
                           model_over={"variant": "af2"})
    assert out["correct"] is True, out["checks"]
