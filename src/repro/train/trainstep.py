"""Train-step factory for the LM zoo (GSPMD path) and AF2 (shard_map path).

LM: pjit with param/optimizer shardings from the model's partition rules;
activations constrained at layer boundaries; optional microbatch gradient
accumulation (lax.scan over microbatches — constant HLO size, enables
compute/gradient-reduce overlap by XLA's latency-hiding scheduler).

AF2: one shard_map over the full logical mesh (pod, data, branch, dap) —
explicit BP/DAP collectives inside, psum gradient reduction over (pod, data),
optional int8 error-feedback compression on the pod hop (grad_sync).  The
entire layout (mesh axes, block_fn, stack_io, gradient reduction) comes from
one ``repro.parallel.plan.ParallelPlan`` — no loose (bp, dap, ...) flags.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.partition import make_param_specs
from repro.train.optim import Optimizer, OptState


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes from dims they don't divide (e.g. batch=1 decode)."""
    out = []
    for i, names in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        if names is None:
            out.append(None)
            continue
        names_t = names if isinstance(names, tuple) else (names,)
        total = 1
        keep = []
        for n in names_t:
            ext = mesh.shape[n]
            if shape[i] % (total * ext) == 0:
                keep.append(n)
                total *= ext
        out.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*out)


def sanitize_spec_tree(tree_of_shapes, tree_of_specs, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s, sp: sanitize_spec(sp, s.shape, mesh), tree_of_shapes,
        tree_of_specs, is_leaf=lambda x: isinstance(x, P))


def shardings_for(tree_of_shapes, rules, mesh: Mesh):
    """ShapeDtypeStruct tree + rules -> NamedSharding tree (sanitized)."""
    specs = make_param_specs(tree_of_shapes, rules)
    specs = sanitize_spec_tree(tree_of_shapes, specs, mesh)
    return jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# LM train step (GSPMD)
# ---------------------------------------------------------------------------

def make_lm_train_step(model, cfg, optimizer: Optimizer, mesh: Mesh, *,
                       data_axes=("data",), microbatch: Optional[int] = None):
    """Returns (train_step, state_shardings_fn, batch_sharding).

    state = {'params': ..., 'opt': OptState}; batch = model-specific dict with
    leading global-batch dim sharded over ``data_axes``.
    """
    data_spec = P(data_axes if len(data_axes) > 1 else data_axes[0])

    def constrain(x, spec: P | None = None):
        if spec is None:
            spec = P(data_spec[0], *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def loss_fn(params, batch):
        return model.loss(params, cfg, batch, constrain=constrain)

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        if microbatch and microbatch > 1:
            def micro(c, mb):
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                acc_l, acc_g = c
                return (acc_l + l,
                        jax.tree_util.tree_map(jnp.add, acc_g, g)), None
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape(microbatch, x.shape[0] // microbatch,
                                    *x.shape[1:]), batch)
            (loss_sum, grads), _ = jax.lax.scan(
                micro, (jnp.zeros((), jnp.float32), zeros), mbs)
            loss = loss_sum / microbatch
            grads = jax.tree_util.tree_map(lambda g: g / microbatch, grads)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_opt = optimizer.update(grads, opt, params)
        return {"params": new_params, "opt": new_opt}, {"loss": loss}

    def state_shardings(params_shapes, opt_shapes=None):
        rules = model.partition_rules(cfg)
        specs = sanitize_spec_tree(
            params_shapes, make_param_specs(params_shapes, rules), mesh)
        pshard = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))
        scalar = NamedSharding(mesh, P())
        if opt_shapes is None:
            return {"params": pshard,
                    "opt": OptState(step=scalar, mu=pshard, nu=pshard)}
        mu = _opt_branch_shardings(params_shapes, specs, opt_shapes.mu, mesh)
        nu = _opt_branch_shardings(params_shapes, specs, opt_shapes.nu, mesh)
        return {"params": pshard,
                "opt": OptState(step=scalar, mu=mu, nu=nu)}

    return train_step, state_shardings, NamedSharding(mesh, data_spec)


def _opt_branch_shardings(params_shapes, pspecs, branch_shapes, mesh):
    """Shardings for one optimizer-state branch whose leaves mirror params
    but may be lower-rank (Adafactor factored v: (row, col) tuples) or
    scalars — the param spec is fitted to each leaf's shape."""
    flat_p, treedef = jax.tree_util.tree_flatten(params_shapes)
    flat_spec = treedef.flatten_up_to(pspecs)
    flat_b = treedef.flatten_up_to(branch_shapes)

    def fit(pshape, spec, leaf):
        sp = tuple(spec) + (None,) * (len(pshape) - len(spec))
        def one(x):
            if x.shape == tuple(pshape):
                return NamedSharding(mesh, P(*sp))
            if len(x.shape) == 0:
                return NamedSharding(mesh, P())
            if x.shape == tuple(pshape[:-1]):           # row factor
                return NamedSharding(mesh, P(*sp[:-1]))
            if x.shape == tuple(pshape[:-2]) + (pshape[-1],):  # col factor
                return NamedSharding(mesh, P(*sp[:-2], sp[-1]))
            return NamedSharding(mesh, P())
        if isinstance(leaf, tuple):
            return tuple(one(x) for x in leaf)
        return one(leaf)

    out = [fit(p.shape, sp, b) for p, sp, b in zip(flat_p, flat_spec, flat_b)]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# AF2 train step (shard_map over the plan's logical mesh)
# ---------------------------------------------------------------------------

def make_af2_train_step(cfg, optimizer: Optimizer, plan, *,
                        n_recycle: int = 1, deterministic: bool = True,
                        devices=None, ema=None):
    """Paper-faithful AF2 distributed training step, laid out by a
    ``ParallelPlan`` (repro.parallel.plan — the single source of truth for
    mesh axes, block_fn, stack_io and gradient reduction).

    ``plan`` is a ``ParallelPlan`` (built here against ``devices``, default
    all local devices) or an already-``BuiltPlan``.  Batch: (global_batch,
    ...) sharded over the plan's DP axes; params replicated (pure DP over
    93M params, as in the paper); BP/DAP act inside the per-protein
    computation via the plan's block_fn/stack_io; gradient completion and
    reduction via the plan's grad_sync (DESIGN.md §2).

    The returned step is ``train_step(state, batch, rng, n_recycle=None)``:
    the optional last argument is a traced int32 recycle count (stochastic
    recycling, DESIGN.md §11) overriding the factory's static ``n_recycle``
    — ONE compiled step serves every draw because the bound only feeds
    ``forward``'s fori_loop.

    ``optimizer.per_sample_clip`` moves gradient clipping INSIDE the
    per-protein scan (AF2 suppl. 1.11.3 clips each sample at 0.1 before
    accumulation); without it the batch gradient is clipped at update time.
    ``ema`` (repro.train.optim.Ema) makes the step carry ``state['ema']``
    — eval-time parameters updated after every optimizer step.

    Returns ``(train_step, built)`` — ``built.mesh`` / ``built.batch_spec``
    are what launchers need for sharding and logging.
    """
    from jax.sharding import PartitionSpec as P
    from repro.core import model as af2
    from repro.parallel.mesh_utils import smap
    from repro.parallel.plan import (BuiltPlan, ParallelPlan,
                                     complete_partial_grads)
    from repro.train.optim import clip_by_global_norm

    if isinstance(plan, ParallelPlan):
        built = plan.build(devices, cfg=cfg)
    elif isinstance(plan, BuiltPlan):
        built = plan
    else:
        raise TypeError(
            f"make_af2_train_step expects a ParallelPlan or BuiltPlan, got "
            f"{type(plan).__name__}: construct one with ParallelPlan(...), "
            "ParallelPlan.from_flags(...) or auto_plan(...)")
    mesh, dp_axes = built.mesh, built.dp_axes
    per_sample_clip = getattr(optimizer, "per_sample_clip", None)

    def per_protein_loss(params, sample, rng, n_rec):
        return af2.loss_fn(
            params, cfg, sample, n_recycle=n_rec,
            block_fn=built.block_fn, stack_io=built.stack_io, rng=rng,
            deterministic=deterministic)

    def step_body(state, batch, rng, n_rec):
        params, opt, err = state["params"], state["opt"], state.get("err")
        # decorrelate dropout across DP shards
        dp_idx = jnp.zeros((), jnp.int32)
        for a in dp_axes:
            dp_idx = dp_idx * mesh.shape[a] + jax.lax.axis_index(a)
        rng = jax.random.fold_in(rng, dp_idx)
        n_local = jax.tree_util.tree_leaves(batch)[0].shape[0]
        rngs = jax.random.split(rng, n_local)

        if per_sample_clip is None:
            def local_loss(params):
                # local shard of the global batch: proteins scanned
                # sequentially (paper: 1 protein per device group; scan =
                # grad accumulation)
                def one(c, sample_rng):
                    sample, r = sample_rng
                    l, m = per_protein_loss(params, sample, r, n_rec)
                    return c + l, m
                total, metrics = jax.lax.scan(
                    one, jnp.zeros((), jnp.float32), (batch, rngs))
                metrics = jax.tree_util.tree_map(jnp.mean, metrics)
                return total / n_local, metrics

            (loss, metrics), grads = jax.value_and_grad(
                local_loss, has_aux=True)(params)
        else:
            # per-sample clipping (AF2 suppl. 1.11.3): each protein's
            # gradient is clipped to per_sample_clip global norm BEFORE
            # accumulation — the same scan, but value_and_grad moves inside
            # so every sample's gradient exists on its own for one moment.
            # Under BP/DAP the per-shard grad is PARTIAL (DESIGN.md §2) and
            # its norm is NOT the sample's norm, so the completing psum
            # moves inside the scan too (grad_sync then skips it) — the
            # clip measures the true sample gradient on every layout.
            def one(carry, sample_rng):
                sample, r = sample_rng
                acc_l, acc_g = carry
                (l, m), g = jax.value_and_grad(
                    per_protein_loss, has_aux=True)(params, sample, r, n_rec)
                with jax.named_scope("grad_sync"):
                    g = complete_partial_grads(g, built.sync_axes)
                with jax.named_scope("clip"):
                    g, _ = clip_by_global_norm(g, per_sample_clip)
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g)
                return (acc_l + l, acc_g), m
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (total, grads), metrics = jax.lax.scan(
                one, (jnp.zeros((), jnp.float32), zeros), (batch, rngs))
            loss = total / n_local
            grads = jax.tree_util.tree_map(lambda g: g / n_local, grads)
            metrics = jax.tree_util.tree_map(jnp.mean, metrics)

        with jax.named_scope("grad_sync"):
            grads, err = built.grad_sync(grads, err,
                                         completed=per_sample_clip is not None)
            if dp_axes:
                loss = jax.lax.pmean(loss, dp_axes)
                metrics = jax.lax.pmean(metrics, dp_axes)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, opt, params)
        out = {"params": new_params, "opt": new_opt}
        if ema is not None:
            with jax.named_scope("ema"):
                out["ema"] = ema.update(state["ema"], new_params)
        if err is not None:
            out["err"] = err
        metrics = dict(metrics)
        metrics["loss"] = loss
        return out, metrics

    # shard_map wrapper: batch sharded over dp axes on dim 0, rest replicated
    batch_spec, state_spec = built.batch_spec, built.state_spec

    def train_step(state, batch, rng, n_recycle_t=None):
        batch_specs = jax.tree_util.tree_map(lambda _: batch_spec, batch)
        state_specs = jax.tree_util.tree_map(lambda _: state_spec, state)
        if n_recycle_t is None:
            # static path: the factory's Python-int bound stays a closure
            # constant, so ``forward`` keeps its unrolled/scan recycling —
            # no dead dynamic while_loop in the HLO of legacy callers
            fn = smap(lambda s, b, r: step_body(s, b, r, n_recycle), mesh,
                      in_specs=(state_specs, batch_specs, state_spec),
                      out_specs=(state_specs, state_spec))
            return fn(state, batch, rng)
        nr = jnp.asarray(n_recycle_t, jnp.int32)
        fn = smap(step_body, mesh,
                  in_specs=(state_specs, batch_specs, state_spec, P()),
                  out_specs=(state_specs, state_spec))
        return fn(state, batch, rng, nr)

    return train_step, built
