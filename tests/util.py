"""Shared test helpers."""
import subprocess
import sys
import textwrap

from repro.nn.layers import randomize  # noqa: F401  (re-exported for tests)


def run_subprocess(code: str, *, devices: int = 8, timeout: int = 560) -> str:
    """Run test code in a fresh interpreter with N fake XLA host devices
    (the main pytest process must keep seeing exactly 1 device)."""
    prologue = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, {str('src')!r})
    """)
    proc = subprocess.run(
        [sys.executable, "-c", prologue + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, cwd=_repo_root())
    assert proc.returncode == 0, (
        f"subprocess failed:\nSTDOUT:\n{proc.stdout[-3000:]}\n"
        f"STDERR:\n{proc.stderr[-3000:]}")
    return proc.stdout


def _repo_root():
    import pathlib
    return str(pathlib.Path(__file__).resolve().parents[1])


# The jaxpr-walking helpers delegate to the static analyzer's shared
# traversal (src/repro/analysis/static/jaxpr_walk.py) so tests and the lint
# CLI agree on what "an intermediate" is.

def iter_eqn_avals(closed_jaxpr):
    """All output avals of all eqns, recursing into sub-jaxprs (scan/map
    bodies) — shared by the peak-intermediate memory assertions."""
    from repro.analysis.static.jaxpr_walk import iter_out_avals
    for aval, _eqn, _path in iter_out_avals(closed_jaxpr):
        yield aval


def count_prims(closed_jaxpr, names):
    """Occurrences of each primitive name, recursing into sub-jaxprs
    (scan/cond/shard_map bodies) — used to pin collective counts."""
    from repro.analysis.static.jaxpr_walk import count_primitives
    return count_primitives(closed_jaxpr, names)


def max_eqn_elems(closed_jaxpr) -> int:
    """Largest eqn-output aval, in elements."""
    from repro.analysis.static.jaxpr_walk import peak_eqn_elems
    return peak_eqn_elems(closed_jaxpr)
