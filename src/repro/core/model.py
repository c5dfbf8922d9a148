"""Full AlphaFold2 model: embedder -> extra-MSA stack -> 48x Evoformer ->
structure module -> heads, with recycling.  Single-protein functions; the
training step vmaps over the per-device batch (paper: 1 protein per device).

Branch Parallelism plugs in at the Evoformer stack: ``evoformer_stack`` takes
a ``block_fn`` so the BP-wrapped block (repro.parallel.branch) is a drop-in.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import evoformer as evo
from repro.core import heads as heads_lib
from repro.core import structure as struct
from repro.core.config import AlphaFold2Config
from repro.nn import layers as nn

Params = dict


# ---------------------------------------------------------------------------
# Input embedder (Algorithm 3) + recycling embedder (Algorithm 32)
# ---------------------------------------------------------------------------

def embedder_init(key, cfg: AlphaFold2Config) -> Params:
    ks = nn.split_keys(key, 8)
    rel_dim = 2 * cfg.max_relative_idx + 1
    return {
        "msa_proj": nn.dense_init(ks[0], cfg.msa_feat_dim, cfg.c_m),
        "target_msa": nn.dense_init(ks[1], cfg.target_feat_dim, cfg.c_m),
        "target_left": nn.dense_init(ks[2], cfg.target_feat_dim, cfg.c_z),
        "target_right": nn.dense_init(ks[3], cfg.target_feat_dim, cfg.c_z),
        "relpos": nn.dense_init(ks[4], rel_dim, cfg.c_z),
        "extra_msa_proj": nn.dense_init(ks[5], cfg.msa_feat_dim, cfg.extra.c_m),
        # recycling
        "rec_msa_ln": nn.layernorm_init(cfg.c_m),
        "rec_z_ln": nn.layernorm_init(cfg.c_z),
        "rec_dist": nn.dense_init(ks[6], 15, cfg.c_z),
        # single repr projection for the structure module
        "single_proj": nn.dense_init(ks[7], cfg.c_m, cfg.structure.c_s),
    }


def embed_inputs(p: Params, cfg: AlphaFold2Config, batch: dict, dtype=jnp.bfloat16):
    """batch: msa_feat (s, r, f_m), target_feat (r, f_t), residue_index (r,)."""
    tf = batch["target_feat"].astype(dtype)
    msa = nn.dense(p["msa_proj"], batch["msa_feat"].astype(dtype))
    msa = msa + nn.dense(p["target_msa"], tf)[None]
    left = nn.dense(p["target_left"], tf)
    right = nn.dense(p["target_right"], tf)
    z = left[:, None] + right[None, :]
    ri = batch["residue_index"]
    rel = jnp.clip(ri[:, None] - ri[None, :], -cfg.max_relative_idx,
                   cfg.max_relative_idx) + cfg.max_relative_idx
    z = z + nn.dense(p["relpos"], jax.nn.one_hot(rel, 2 * cfg.max_relative_idx + 1,
                                                 dtype=dtype))
    extra = nn.dense(p["extra_msa_proj"], batch["extra_msa_feat"].astype(dtype))
    return msa, z, extra


def recycle_distance_bins(x: jnp.ndarray) -> jnp.ndarray:
    """CA coords (r, 3) -> binned distance map (r, r) int32.

    THE recycling discretization (15 bins, edges 3.375..21.375): consumed by
    the recycling embedder AND by ``predict``'s early-exit convergence test —
    one definition so they can never drift apart.
    """
    d = jnp.sqrt(jnp.sum(jnp.square(x[:, None] - x[None, :]), -1) + 1e-8)
    edges = jnp.linspace(3.375, 21.375, 14)
    return jnp.sum(d[..., None] > edges, -1).astype(jnp.int32)


def embed_recycle(p: Params, cfg: AlphaFold2Config, msa, z, prev):
    """Add recycled first-row MSA, pair rep, and binned CA-distance embedding."""
    prev_msa0, prev_z, prev_x = prev
    msa = msa.at[0].add(nn.layernorm(p["rec_msa_ln"], prev_msa0).astype(msa.dtype))
    z = z + nn.layernorm(p["rec_z_ln"], prev_z).astype(z.dtype)
    bins = jax.nn.one_hot(recycle_distance_bins(prev_x), 15, dtype=z.dtype)
    z = z + nn.dense(p["rec_dist"], bins)
    return msa, z


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def stack_init(key, cfg_block, n_blocks: int, *, scan: bool) -> Params:
    keys = jax.random.split(key, n_blocks)
    if scan:
        return jax.vmap(lambda k: evo.evoformer_block_init(k, cfg_block))(keys)
    return [evo.evoformer_block_init(k, cfg_block) for k in keys]


BlockFn = Callable[..., tuple]


def evoformer_stack(params, cfg_block, n_blocks: int, msa, z, *, scan: bool,
                    remat: bool, block_fn: Optional[BlockFn] = None,
                    rng=None, deterministic: bool = True,
                    masks: Optional[evo.EvoMasks] = None):
    """Apply n_blocks Evoformer blocks (scan over stacked params).

    Overlap protocol (communication-overlapped DAP, DESIGN.md §3): a
    block_fn exposing a ``prefetch_init`` attribute opts into a
    double-buffered prefetch carry.  The stack seeds it once at entry
    (``prefetch_init(msa, z)`` — one extra gather per stack), then each
    block consumes the carried operand and returns the next one as a third
    output — so the gather for block k+1 is issued inside block k's body,
    a full block of compute ahead of its consumer.  The scan carry is what
    makes this double-buffered: the prefetched tensor materializes at the
    iteration boundary, and XLA's async-collective pipelining hoists the
    gather's start across the loop back-edge.  (The LAST block's issue
    gather is the stack's exit ``all_gather`` arriving one op early.)
    """
    fn = block_fn or evo.evoformer_block
    prefetch_init = getattr(fn, "prefetch_init", None)

    # masks only reach the block when present (inference) — training-path
    # block_fns predating the masks kwarg keep working unchanged
    mask_kw = {} if masks is None else {"masks": masks}

    if prefetch_init is None:
        def one_block(carry, xs):
            msa, z = carry
            block_params, key = xs
            m, zz = fn(block_params, cfg_block, msa, z, rng=key,
                       deterministic=deterministic, **mask_kw)
            return (m.astype(msa.dtype), zz.astype(z.dtype)), None
        carry0 = (msa, z)
    else:
        def one_block(carry, xs):
            msa, z, pf = carry
            block_params, key = xs
            m, zz, pf = fn(block_params, cfg_block, msa, z, rng=key,
                           deterministic=deterministic, prefetch=pf,
                           **mask_kw)
            return (m.astype(msa.dtype), zz.astype(z.dtype),
                    pf.astype(z.dtype)), None
        carry0 = (msa, z, prefetch_init(msa, z))

    if remat == "dots":
        # §Perf H3 iteration 3: selective remat — matmul outputs are saved,
        # pointwise/LN/gating recomputed: less bwd traffic than full-block
        # remat, far less live memory than no remat.
        one_block = jax.checkpoint(
            one_block, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat:
        one_block = jax.checkpoint(one_block)

    if scan:
        if rng is not None:
            keys = jax.random.split(rng, n_blocks)
            carry, _ = jax.lax.scan(
                lambda c, xs: one_block(c, xs), carry0, (params, keys))
        else:
            carry, _ = jax.lax.scan(
                lambda c, bp: one_block(c, (bp, None)), carry0, params)
        return carry[0], carry[1]

    carry = carry0
    for i, bp in enumerate(params):
        key = jax.random.fold_in(rng, i) if rng is not None else None
        carry, _ = one_block(carry, (bp, key))
    return carry[0], carry[1]


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init_params(key, cfg: AlphaFold2Config) -> Params:
    ks = nn.split_keys(key, 5)
    return {
        "embedder": embedder_init(ks[0], cfg),
        "extra_stack": stack_init(ks[1], cfg.extra, cfg.n_extra_msa_blocks,
                                  scan=cfg.scan_blocks),
        "evoformer": stack_init(ks[2], cfg.evoformer, cfg.n_evoformer,
                                scan=cfg.scan_blocks),
        "structure": struct.structure_module_init(ks[3], cfg.structure),
        "heads": heads_lib.heads_init(ks[4], cfg),
    }


def trunk_masks(batch) -> Optional[dict]:
    """Extract padded-bucket validity masks from an inference batch.

    Returns ``{"res", "msa_rows", "extra_rows"}`` (each possibly None) or
    None when the batch carries no row mask at all — the training fast path.
    ``res_mask`` alone does NOT trigger masking (training batches carry it
    for the losses); inference batches opt in by carrying the row masks
    (``serve.fold_steps.pad_to_bucket`` always adds all three).
    """
    if not any(k in batch for k in ("msa_row_mask", "extra_row_mask")):
        return None
    return {"res": batch.get("res_mask"),
            "msa_rows": batch.get("msa_row_mask"),
            "extra_rows": batch.get("extra_row_mask")}


def run_trunk(params, cfg: AlphaFold2Config, batch, prev, *, block_fn=None,
              stack_io=None, rng=None, deterministic=True, dtype=jnp.bfloat16,
              masks: Optional[dict] = None):
    """One recycling iteration of the trunk: returns (msa, z, single).

    ``stack_io`` = (pre, post): applied around each Evoformer stack — DAP
    uses it to shard (msa, z) at stack entry and all_gather at exit.

    ``masks`` = {"res": (r,), "msa_rows": (s,), "extra_rows": (se,)} validity
    masks for padded-bucket inference (see :func:`trunk_masks`); each stack
    receives its own row mask.  Masked axes are consumed at FULL extent in
    every layout (DAP shards queries, never keys), so the same masks work
    for serial and dap block_fns.
    """
    with jax.named_scope("embed"):
        msa, z, extra = embed_inputs(params["embedder"], cfg, batch, dtype)
        msa, z = embed_recycle(params["embedder"], cfg, msa, z, prev)
    pre, post = stack_io or ((lambda m, zz: (m, zz)),) * 2
    extra_masks = main_masks = None
    if masks is not None:
        ones = lambda n: jnp.ones((n,), jnp.float32)
        res = masks.get("res")
        res = ones(z.shape[0]) if res is None else res
        rows = masks.get("extra_rows")
        extra_masks = evo.EvoMasks(
            ones(extra.shape[0]) if rows is None else rows, res)
        rows = masks.get("msa_rows")
        main_masks = evo.EvoMasks(
            ones(msa.shape[0]) if rows is None else rows, res)
    k1 = k2 = None
    if rng is not None:
        rng, k1, k2 = jax.random.split(rng, 3)
    extra_l, z_l = pre(extra, z)
    with jax.named_scope("extra_stack"):
        _, z_l = evoformer_stack(params["extra_stack"], cfg.extra,
                                 cfg.n_extra_msa_blocks, extra_l, z_l,
                                 scan=cfg.scan_blocks,
                                 remat=(False if cfg.remat == "none"
                                        else cfg.remat),
                                 block_fn=block_fn, rng=k1,
                                 deterministic=deterministic,
                                 masks=extra_masks)
    msa_l = pre(msa, z)[0]        # z stays sharded between the two stacks
    with jax.named_scope("evoformer"):
        msa_l, z_l = evoformer_stack(params["evoformer"], cfg.evoformer,
                                     cfg.n_evoformer, msa_l, z_l,
                                     scan=cfg.scan_blocks,
                                     remat=(False if cfg.remat == "none"
                                            else cfg.remat),
                                     block_fn=block_fn, rng=k2,
                                     deterministic=deterministic,
                                     masks=main_masks)
    msa, z = post(msa_l, z_l)
    single = nn.dense(params["embedder"]["single_proj"], msa[0])
    return msa, z, single


def cycle_rng(rng, i):
    """Per-recycle-cycle dropout key: ``fold_in`` the cycle index.

    Every cycle re-runs the same trunk, so passing one rng through would
    draw IDENTICAL dropout masks in all no-grad cycles and the grad cycle —
    the grad cycle's masks would be the very masks the recycled features
    were computed under, correlated noise instead of regularization.
    ``i`` may be traced (the stochastic-recycling fori_loop index).
    """
    return None if rng is None else jax.random.fold_in(rng, i)


def forward(params, cfg: AlphaFold2Config, batch, *, n_recycle=1,
            block_fn=None, stack_io=None, rng=None,
            deterministic: bool = True, dtype=jnp.bfloat16) -> dict:
    """Full forward with ``n_recycle`` trunk passes (grad on the last only).

    ``n_recycle`` is a static Python int OR a traced int32 scalar — the
    stochastic-recycling training path (DESIGN.md §11) draws it per step on
    the host and feeds it in as a step argument, so the no-grad ``fori_loop``
    lowers to a dynamic-trip-count while_loop and ONE compiled step serves
    every draw.  Dropout decorrelates across cycles via :func:`cycle_rng`.
    """
    # AMP: fp32 master params -> compute dtype once at entry (paper §5.1)
    params = nn.Policy(compute_dtype=dtype).cast(params)
    r, c_m, c_z = cfg.n_res, cfg.c_m, cfg.c_z
    prev = (jnp.zeros((r, c_m), dtype), jnp.zeros((r, r, c_z), dtype),
            jnp.zeros((r, 3), jnp.float32))

    def cycle(p, prev, key, stop_grad):
        msa, z, single = run_trunk(p, cfg, batch, prev, block_fn=block_fn,
                                   stack_io=stack_io, rng=key,
                                   deterministic=deterministic, dtype=dtype)
        (rots, trans), traj, s_final = struct.structure_module(
            p["structure"], cfg.structure, single, z)
        out = {"msa": msa, "z": z, "single": single, "s_final": s_final,
               "rots": rots, "trans": trans, "traj": traj}
        new_prev = (msa[0], z, trans)
        if stop_grad:
            new_prev = jax.tree_util.tree_map(jax.lax.stop_gradient, new_prev)
        return out, new_prev

    # n_recycle - 1 no-grad iterations (lax loop keeps HLO size constant).
    # The loop closes over DETACHED params: with a traced bound the loop is
    # a while_loop, which has no transpose rule — detaching every
    # differentiated input up front keeps autodiff from ever looking inside
    # (the recycled features are stop_gradient'ed anyway).
    static = isinstance(n_recycle, int)
    if not static or n_recycle > 1:
        frozen = jax.tree_util.tree_map(jax.lax.stop_gradient, params)

        def body(i, prev):
            _, new_prev = cycle(frozen, prev, cycle_rng(rng, i), True)
            return new_prev
        with jax.named_scope("recycle"):
            prev = jax.lax.stop_gradient(
                jax.lax.fori_loop(0, n_recycle - 1, body, prev))
    out, _ = cycle(params, prev, cycle_rng(rng, n_recycle - 1), False)
    return out


def fold_pair_mask(batch):
    """(pair_mask (B, r, r), pair_count (B,)) for the convergence test —
    padded residues never vote on whether a sample converged."""
    bsz, r = batch["target_feat"].shape[:2]
    res_mask = batch.get("res_mask")
    if res_mask is not None:
        pair_mask = (res_mask[:, :, None] * res_mask[:, None, :]
                     ).astype(jnp.float32)
    else:
        pair_mask = jnp.ones((bsz, r, r), jnp.float32)
    return pair_mask, jnp.maximum(jnp.sum(pair_mask, (1, 2)), 1.0)


def fold_carry_init(cfg: AlphaFold2Config, bsz: int, r: int, dtype):
    """Zero recycling carry: (prev (msa0, z, x), s_final)."""
    prev = (jnp.zeros((bsz, r, cfg.c_m), dtype),
            jnp.zeros((bsz, r, r, cfg.c_z), dtype),
            jnp.zeros((bsz, r, 3), jnp.float32))
    return prev, jnp.zeros((bsz, r, cfg.structure.c_s), dtype)


def fold_cycle(params, cfg: AlphaFold2Config, batch, prev, sf, conv, n_rec, *,
               tol: float, pair_mask, pair_count, block_fn=None,
               stack_io=None, dtype=jnp.bfloat16, active=None):
    """ONE batched recycling cycle with per-sample freeze semantics.

    THE cycle definition — shared by :func:`predict`'s while_loop body and
    the continuous-batching serving step (``serve.fold_steps.
    make_recycle_step``), so stepwise serving and whole-fold inference can
    never drift apart.  ``params`` must already be cast to the compute
    dtype.  ``active`` (B,) bool marks occupied batch slots in the serving
    path: an inactive slot behaves exactly like a frozen (converged) one —
    its carry never updates, its recycle counter never advances, and it can
    never converge — which is what makes mid-flight admission safe (the
    scheduler's invariant: admitting into a free slot cannot change any
    in-flight sample's state or budget, because per-slot math is
    independent under vmap).  ``active=None`` is the predict() fast path
    (every slot live).
    """
    def one_cycle(sample, prev_s):
        msa, z, single = run_trunk(params, cfg, sample, prev_s,
                                   block_fn=block_fn, stack_io=stack_io,
                                   rng=None, deterministic=True, dtype=dtype,
                                   masks=trunk_masks(sample))
        (_, trans), _, s_final = struct.structure_module(
            params["structure"], cfg.structure, single, z,
            sample.get("res_mask"))
        return (msa[0], z, trans), s_final

    new_prev, new_sf = jax.vmap(one_cycle)(batch, prev)
    old_bins = jax.vmap(recycle_distance_bins)(prev[2])
    new_bins = jax.vmap(recycle_distance_bins)(new_prev[2])
    frac = jnp.sum((old_bins != new_bins) * pair_mask, (1, 2)) / pair_count
    keep = conv if active is None else (conv | ~active)

    def sel(old, new):
        return jnp.where(keep.reshape(-1, *([1] * (new.ndim - 1))), old, new)
    prev = jax.tree_util.tree_map(sel, prev, new_prev)
    sf = sel(sf, new_sf)
    n_rec = n_rec + jnp.where(keep, 0, 1)
    conv = conv | ((frac < tol) & ~keep)
    return prev, sf, conv, n_rec


def fold_heads(params, cfg: AlphaFold2Config, z, s_final) -> dict:
    """Confidence heads over a batched carry (params already cast)."""
    plddt_logits = jax.vmap(
        lambda s: heads_lib.plddt_logits(params["heads"], s))(s_final)
    disto_logits = jax.vmap(
        lambda zz: heads_lib.distogram_logits(params["heads"], zz))(z)
    return {
        "plddt": heads_lib.plddt_from_logits(plddt_logits),
        "contact_probs": heads_lib.contact_probs_from_distogram(disto_logits),
        "plddt_logits": plddt_logits,
        "distogram_logits": disto_logits,
    }


def predict(params, cfg: AlphaFold2Config, batch, *, max_recycle: int,
            tol: float = 0.0, block_fn=None, stack_io=None,
            dtype=jnp.bfloat16) -> dict:
    """Batched inference with adaptive early-exit recycling (DESIGN.md §10).

    ``batch``: per-sample features with a leading batch axis (B, ...) —
    msa_feat, extra_msa_feat, target_feat, residue_index, plus (padded
    buckets) res_mask / msa_row_mask / extra_row_mask validity masks.

    Runs trunk + structure cycles inside one ``lax.while_loop``.  After each
    cycle the recycled CA-distance maps are re-binned with the SAME 15-bin
    discretization the recycling embedder consumes; a sample converges when
    fewer than ``tol`` of its valid residue pairs changed bin — recycling
    past that point feeds the trunk a (near-)identical recycling embedding,
    so further cycles are wasted FLOPs (ParaFold's observation: serving is
    scheduling-bound, not model-bound).  Converged samples FREEZE in place —
    their carried state stops updating while unconverged batchmates keep
    recycling — and the loop exits early once every sample froze.

    ``tol=0.0`` can never converge (strict ``<``): exactly ``max_recycle``
    cycles run, reproducing ``forward(n_recycle=max_recycle)``.

    Returns: coords (B, r, 3) fp32; plddt (B, r) in [0, 100]; contact_probs
    (B, r, r); the raw plddt/distogram logits; n_recycles (B,) cycles each
    sample actually consumed; converged (B,) bool.
    """
    if max_recycle < 1:
        raise ValueError(f"max_recycle must be >= 1, got {max_recycle}")
    params = nn.Policy(compute_dtype=dtype).cast(params)
    bsz, r = batch["target_feat"].shape[:2]
    prev0, sf0 = fold_carry_init(cfg, bsz, r, dtype)
    pair_mask, pair_count = fold_pair_mask(batch)

    def cond(state):
        i, _, _, conv, _ = state
        return (i < max_recycle) & ~jnp.all(conv)

    def body(state):
        i, prev, sf, conv, n_rec = state
        prev, sf, conv, n_rec = fold_cycle(
            params, cfg, batch, prev, sf, conv, n_rec, tol=tol,
            pair_mask=pair_mask, pair_count=pair_count, block_fn=block_fn,
            stack_io=stack_io, dtype=dtype)
        return i + 1, prev, sf, conv, n_rec

    state0 = (jnp.zeros((), jnp.int32), prev0, sf0,
              jnp.zeros((bsz,), bool), jnp.zeros((bsz,), jnp.int32))
    _, prev, s_final, conv, n_rec = jax.lax.while_loop(cond, body, state0)
    _, z, coords = prev
    out = fold_heads(params, cfg, z, s_final)
    out.update(coords=coords, n_recycles=n_rec, converged=conv)
    return out


def loss_fn(params, cfg: AlphaFold2Config, batch, *, n_recycle=1,
            block_fn=None, stack_io=None, rng=None,
            deterministic: bool = True) -> tuple:
    out = forward(params, cfg, batch, n_recycle=n_recycle, block_fn=block_fn,
                  stack_io=stack_io, rng=rng, deterministic=deterministic)
    with jax.named_scope("loss"):
        return _heads_and_losses(params, cfg, batch, out)


def _heads_and_losses(params, cfg: AlphaFold2Config, batch, out) -> tuple:
    res_mask = batch["res_mask"].astype(jnp.float32)
    rots_traj, trans_traj = out["traj"]
    l_fape = heads_lib.fape_loss(rots_traj, trans_traj, batch["true_rots"],
                                 batch["true_trans"], res_mask)
    l_dist = heads_lib.distogram_loss(
        heads_lib.distogram_logits(params["heads"], out["z"]),
        batch["true_trans"], res_mask, n_bins=cfg.n_distogram_bins)
    l_msa = heads_lib.masked_msa_loss(
        heads_lib.masked_msa_logits(params["heads"], out["msa"]),
        batch["true_msa"], batch["msa_mask_positions"].astype(jnp.float32))
    l_plddt = heads_lib.plddt_loss(
        heads_lib.plddt_logits(params["heads"], out["s_final"]),
        out["trans"], batch["true_trans"], res_mask, n_bins=cfg.n_plddt_bins)
    total = 0.5 * l_fape + 0.3 * l_dist + 2.0 * l_msa + 0.01 * l_plddt
    metrics = {"loss": total, "fape": l_fape, "distogram": l_dist,
               "masked_msa": l_msa, "plddt": l_plddt}
    return total, metrics
