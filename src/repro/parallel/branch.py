"""Branch Parallelism (paper §4.2, Fig. 4) as a composable shard_map pattern.

The paper's BP assigns each dependency-free branch of a block to a device
group.  GPU frameworks realize this as MPMD (different code per rank) with
NCCL broadcast/all-reduce.  The TPU/XLA-native encoding used here is SPMD:

* a ``branch`` mesh axis of extent = number of branches;
* each device selects its branch with ``lax.cond(axis_index('branch')==i)``
  (XLA compiles a conditional; each core executes exactly one arm);
* the exchange is a single ``lax.psum`` over ``branch`` per output tensor —
  the non-owner arm contributes zeros, so the psum *is* the paper's
  broadcast; its AD transpose reproduces the paper's backward
  broadcast+all-reduce schedule for free.

BP deliberately does NOT split activations ("the same computational
intensity is retained", §4.2) — both devices hold replicated inputs.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core import evoformer as evo
from repro.core.config import EvoformerConfig


def branch_parallel(branches: Sequence[Callable], *, axis: str = "branch"):
    """Generalized BP combinator.

    ``branches`` are thunks (argument-closed callables).  Returns the tuple of
    every branch's output, replicated across the ``axis`` — device i computes
    only ``branches[i]`` and receives the others via the exchange psum.
    Must run inside ``shard_map`` with an ``axis`` mesh axis of matching size.
    """
    def run():
        idx = jax.lax.axis_index(axis)
        outs = []
        for i, fn in enumerate(branches):
            shape = jax.eval_shape(fn)
            zeros = lambda sh=shape: jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), sh)
            outs.append(jax.lax.cond(idx == i, fn, zeros))
        # one fused exchange for all branches (paper: broadcast per tensor)
        with jax.named_scope("bp_exchange"):
            return jax.lax.psum(tuple(outs), axis)
    return run


def _reject_masks(masks):
    if masks is not None:
        raise ValueError(
            "Branch Parallelism is a training layout; padded-bucket masks "
            "are an inference feature — inference plans fold the branch "
            "extent into data parallelism (ParallelPlan.for_inference), so "
            "route masked folds through a serial or dap block_fn")


def bp_evoformer_block(p, cfg: EvoformerConfig, msa, z, *, rng=None,
                       deterministic: bool = True, axis: str = "branch",
                       masks=None):
    """Branch-parallel Parallel-Evoformer block (Fig. 4).

    Device(branch=0): MSA stack + outer-product mean.
    Device(branch=1): pair stack.
    Exchange at block end; ``z_out = pair_branch(z) + OPM(msa_out)`` lands via
    the same psum (branch-0 contributes the OPM term, branch-1 the pair term).
    """
    _reject_masks(masks)
    if cfg.variant != "parallel":
        raise ValueError(
            "Branch Parallelism requires the 'parallel' Evoformer variant "
            f"(got {cfg.variant!r}): serial variants have a cross-branch "
            "dependency inside the block (paper §4.1)")
    rngs = (None, None) if rng is None else tuple(jax.random.split(rng))

    def branch_msa():
        msa_out = evo.msa_branch(p, cfg, msa, z, rng=rngs[0],
                                 deterministic=deterministic)
        with jax.named_scope("opm"):
            opm = evo.opm_apply(p["opm"], cfg, msa_out)
        return msa_out, opm.astype(z.dtype)

    def branch_pair():
        return evo.pair_branch(p, cfg, z, rng=rngs[1],
                               deterministic=deterministic).astype(z.dtype)

    (msa_out, opm), z_pair = branch_parallel(
        [branch_msa, branch_pair], axis=axis)()
    with jax.named_scope("opm"):
        return msa_out, z_pair + opm


def bp_dap_evoformer_block(p, cfg: EvoformerConfig, msa_l, z_l, *, rng=None,
                           deterministic: bool = True, n_seq_total: int = None,
                           branch_axis: str = "branch", dap_axis: str = "dap",
                           masks=None):
    """Hybrid BP x DAP block (paper §4.3, Table 6).

    Inputs are DAP shards (replicated across ``branch``).  Branch 0 runs the
    DAP MSA stack + OPM over its own ``dap`` sub-axis; branch 1 the DAP pair
    stack.  All devices with equal branch coordinate execute the same cond
    arm, so the DAP collectives inside each arm are well-formed (their
    replica groups only span devices that take that arm).
    """
    from repro.parallel import dap as dap_lib
    _reject_masks(masks)
    if cfg.variant != "parallel":
        raise ValueError("hybrid BP x DAP requires the 'parallel' variant")
    rngs = (None, None) if rng is None else tuple(jax.random.split(rng))

    def branch_msa():
        msa_out = dap_lib.dap_msa_branch(p, cfg, msa_l, z_l, rng=rngs[0],
                                         deterministic=deterministic,
                                         axis_name=dap_axis)
        opm = dap_lib.dap_outer_product_mean(p["opm"], msa_out, n_seq_total,
                                             dap_axis,
                                             row_chunk=cfg.opm_chunk,
                                             opm_impl=cfg.opm_impl)
        return msa_out, opm.astype(z_l.dtype)

    def branch_pair():
        return dap_lib.dap_pair_branch(p, cfg, z_l, rng=rngs[1],
                                       deterministic=deterministic,
                                       axis_name=dap_axis).astype(z_l.dtype)

    (msa_out, opm), z_pair = branch_parallel(
        [branch_msa, branch_pair], axis=branch_axis)()
    return msa_out, z_pair + opm
