"""End-to-end system behaviour: the public launchers actually train/serve."""
import os
import subprocess
import sys

import pytest

from tests.util import _repo_root

pytestmark = pytest.mark.slow


def _run(args, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", *args], capture_output=True, text=True,
        timeout=timeout, cwd=_repo_root(), env=env)
    assert proc.returncode == 0, (
        f"{args} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    return proc.stdout


def test_train_af2_tiny_end_to_end(tmp_path):
    out = _run(["repro.launch.train", "--af2", "tiny", "--steps", "3",
                "--batch", "2", "--ckpt-dir", str(tmp_path / "ck"),
                "--ckpt-every", "2"])
    assert "done: 3 steps" in out
    # no chip, no peak: MFU is not taken against a CPU time
    assert "MFU not measured (cpu)" in out
    # checkpoint written and resumable
    out2 = _run(["repro.launch.train", "--af2", "tiny", "--steps", "4",
                 "--batch", "2", "--ckpt-dir", str(tmp_path / "ck"),
                 "--resume"])
    assert "resumed from step" in out2


def test_train_af2_tiny_bp_on_fake_devices():
    out = _run(["repro.launch.train", "--af2", "tiny", "--steps", "2",
                "--batch", "4", "--devices", "4", "--bp", "2"])
    assert "done: 2 steps" in out
    assert "'branch': 2" in out


def test_train_lm_smoke():
    out = _run(["repro.launch.train", "--arch", "mamba2-2.7b", "--smoke",
                "--steps", "3", "--batch", "2", "--seq", "32"])
    assert "loss" in out and "done" in out


def test_serve_smoke():
    out = _run(["repro.launch.serve", "--arch", "glm4-9b", "--smoke",
                "--requests", "3", "--slots", "2", "--max-new", "4",
                "--prompt-len", "8", "--max-len", "32"])
    assert "served 3 requests" in out


def test_print_tpu_env_appends_to_existing_args():
    """The emitted line keeps what $LIBTPU_INIT_ARGS already holds."""
    from repro.launch.train import TPU_ASYNC_COLLECTIVE_FLAGS
    line = _run(["repro.launch.train", "--print-tpu-env"])
    script = 'LIBTPU_INIT_ARGS="--keep_me=1"\n' + line + \
        'printf %s "$LIBTPU_INIT_ARGS"'
    got = subprocess.run(["bash", "-c", script], capture_output=True,
                         text=True, check=True).stdout
    assert got == " ".join(("--keep_me=1",) + TPU_ASYNC_COLLECTIVE_FLAGS)
    alone = subprocess.run(["bash", "-c", "unset LIBTPU_INIT_ARGS\n" + line
                            + 'printf %s "$LIBTPU_INIT_ARGS"'],
                           capture_output=True, text=True, check=True).stdout
    assert alone == " ".join(TPU_ASYNC_COLLECTIVE_FLAGS)
