"""``correct`` on a small Branch Parallelism x data parallelism cell on
four virtual CPU devices: true for the program as it is, false when the
exchange between the branch chips is left out."""
import json
import os
import subprocess
import sys

from bench.tests import harness

SCRIPT = """
import json, sys, tempfile
sys.path[:0] = [{src!r}, {root!r}]
from bench.tests import harness
traffic = dict(harness.TRAFFIC, global_batch=2, n_recycle=1)
store, out = {{}}, {{}}
for fault in (None, "no_exchange"):
    r = harness.run_cell(tempfile.mkdtemp(dir={tmp!r}), "tiny_bp", chips=4,
                         plan={{"data": 2, "branch": 2}}, traffic=traffic,
                         seed=4000000001, store=store, fault=fault)
    out[str(fault)] = [r["correct"], r["checks"]]
print(json.dumps(out))
"""


def test_the_exchange_left_out_is_not_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SCRIPT.format(src=os.path.join(harness.ROOT, "src"),
                           root=harness.ROOT, tmp=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["None"][0] is True, res
    assert res["no_exchange"][0] is False, res
