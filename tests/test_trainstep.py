"""LM train-step factory: loss descends, microbatch == full batch."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import dense
from repro.models.lmconfig import LMConfig
from repro.train.optim import adamw, sgd
from repro.train.trainstep import make_lm_train_step, sanitize_spec


def _setup(microbatch=None):
    cfg = LMConfig(arch_id="t", family="dense", n_layer=2, d_model=32,
                   n_head=2, n_kv_head=2, d_ff=64, vocab=67,
                   scan_layers=True, remat="none", attention_chunk=8)
    from repro.parallel.mesh_utils import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = sgd(0.1)
    step, state_sh, batch_sh = make_lm_train_step(
        dense, cfg, opt, mesh, microbatch=microbatch)
    params = dense.init_params(jax.random.PRNGKey(0), cfg)
    state = {"params": params, "opt": opt.init(params)}
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    return cfg, step, state, batch


def test_loss_decreases():
    cfg, step, state, batch = _setup()
    fn = jax.jit(step)
    losses = []
    for _ in range(8):
        state, m = fn(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_microbatch_equals_full_batch():
    _, step_full, state_f, batch = _setup()
    _, step_micro, state_m, _ = _setup(microbatch=2)
    sf, mf = jax.jit(step_full)(state_f, batch)
    sm, mm = jax.jit(step_micro)(state_m, batch)
    np.testing.assert_allclose(float(mf["loss"]), float(mm["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(sf["params"]),
                    jax.tree_util.tree_leaves(sm["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_sanitize_spec_drops_indivisible():
    from jax.sharding import PartitionSpec as P
    from repro.parallel.mesh_utils import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    assert sanitize_spec(P("data", "model"), (32, 48), FakeMesh()) == \
        P("data", "model")
    assert sanitize_spec(P("data", None), (1, 5), FakeMesh()) == P(None, None)
    assert sanitize_spec(P(("data", "model"),), (256,), FakeMesh()) == \
        P(("data", "model"))
    # 64 and 16 divide only the first factor of (data=16, model=16)
    assert sanitize_spec(P(("data", "model"),), (64,), FakeMesh()) == P("data")
    assert sanitize_spec(P(("data", "model"),), (16,), FakeMesh()) == P("data")


def test_af2_model_flops_sane():
    from repro.analysis.roofline import af2_model_flops
    from repro.core.config import af2_initial, af2_finetune
    f_init = af2_model_flops(af2_initial())
    f_ft = af2_model_flops(af2_finetune())
    assert f_ft > 2 * f_init  # fine-tuning shapes are much bigger
    assert 1e12 < f_init < 1e16
