"""bench/flops.py counts the model's contractions: it equals the FLOPs of
every dot in the program's forward at af2_tiny, and XLA's cost analysis
of the same forward, which adds elementwise work, is at least that."""
import dataclasses
import math

import jax
import pytest

from bench import flops


def dot_flops(jaxpr, mult=1):
    """2 x multiply-adds of every dot_general, loops unrolled by count."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            k = math.prod(eqn.invars[0].aval.shape[d] for d in lc)
            total += 2 * math.prod(eqn.outvars[0].aval.shape) * k * mult
        inner_mult = mult * (eqn.params.get("length", 1)
                             if eqn.primitive.name == "scan" else 1)
        for sub in eqn.params.values():
            for s in (sub if isinstance(sub, (list, tuple)) else [sub]):
                inner = getattr(s, "jaxpr", s)
                if hasattr(inner, "eqns"):
                    total += dot_flops(inner, inner_mult)
    return total


@pytest.mark.parametrize("variant", ["parallel", "af2"])
def test_forward_count_matches_the_programs_dots(variant):
    from repro.core import model as af2
    from repro.core.config import af2_tiny
    from repro.data.protein import protein_batch
    cfg = af2_tiny(variant=variant, scan_blocks=False, remat="none")
    sz = dataclasses.asdict(cfg)
    params = af2.init_params(jax.random.PRNGKey(0), cfg)
    batch = jax.tree_util.tree_map(lambda x: x[0],
                                   protein_batch(0, 0, 1, cfg))

    def loss(p, b):
        return af2.loss_fn(p, cfg, b, n_recycle=1)[0]

    assert dot_flops(jax.make_jaxpr(loss)(params, batch).jaxpr) == \
        flops.forward(sz)
    cost = jax.jit(loss).lower(params, batch).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["flops"] >= flops.forward(sz)


def test_train_step_counts_recycles_and_backward():
    from repro.core.config import af2_initial
    sz = dataclasses.asdict(af2_initial())
    f = flops.train_step_per_protein(sz, 3)
    assert f == 2 * flops.cycle(sz) + 3 * flops.forward(sz)
    # the main stack does most of the forward work at the initial crop
    main = sz["n_evoformer"] * flops.evoformer_block(
        sz["evoformer"], sz["n_seq"], sz["n_res"])
    assert 0.8 < main / flops.forward(sz) < 0.95
