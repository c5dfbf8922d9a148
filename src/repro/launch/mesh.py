"""Production mesh (spec-fixed shapes) + logical refactorings.

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state.  The physical mesh is (data, model) = (16, 16) per pod;
multi-pod prepends a pod axis (2, 16, 16).  Logical views:

* LM archs: 'model' = tensor/expert parallel, 'pod' folds into data-parallel.
* AlphaFold2: the 'model' axis factors into ('branch', 'dap') according to a
  ``repro.parallel.plan.ParallelPlan`` — ``plan.build(mesh)`` performs the
  refactoring (the paper's BP=2 x DAP=8 hybrid, §4.3, is
  ``ParallelPlan.for_mesh(mesh, branch=2, dap=8)``).
"""
from __future__ import annotations

import os

from repro.parallel.mesh_utils import make_mesh, refactor_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def production_mesh_from_env(multi_pod: bool = False,
                             env: str = "REPRO_DRYRUN_MESH"):
    """Production mesh, overridable via e.g. REPRO_DRYRUN_MESH='4x4[x2]' for
    the small-mesh self-test (tests/test_dryrun_small.py)."""
    override = os.environ.get(env)
    if override:
        dims = tuple(int(x) for x in override.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        return make_mesh(dims, axes)
    return make_production_mesh(multi_pod=multi_pod)


def af2_logical_mesh(mesh, *, bp: int = 2, dap: int = 8):
    """(…, data, model) -> (…, data, branch, dap) with branch*dap = model.

    Kept for direct use; ``ParallelPlan.build`` performs the same
    refactoring as part of building the full execution plan.
    """
    model = mesh.shape["model"]
    if bp * dap != model:
        raise ValueError(f"bp({bp}) * dap({dap}) != model axis ({model})")
    split = [("branch", bp), ("dap", dap)] if bp > 1 else [("dap", dap)]
    if dap == 1 and bp > 1:
        split = [("branch", bp)]
    return refactor_mesh(mesh, {"model": split})


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
