from repro.parallel.branch import (  # noqa: F401
    branch_parallel, bp_evoformer_block, bp_dap_evoformer_block)
from repro.parallel.mesh_utils import (  # noqa: F401
    refactor_mesh, rename_mesh, axis_size, make_mesh, smap, local_slice)
from repro.parallel.plan import (  # noqa: F401
    ParallelPlan, BuiltPlan, PlanError, auto_plan)
from repro.parallel.grad_sync import (  # noqa: F401
    psum_tree, pmean_tree, compressed_psum_tree, zeros_error_state)
from repro.parallel import dap  # noqa: F401
