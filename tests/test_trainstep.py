"""LM train-step factory: loss descends, microbatch == full batch; the AF2
step's named scopes reach its HLO."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


# ---------------------------------------------------------------------------
# AF2 step: named scopes in the HLO's op_name metadata
# ---------------------------------------------------------------------------

SUB_OP_SCOPES = ("msa_row_attn", "msa_col_attn", "msa_transition", "opm",
                 "tri_mult_out", "tri_mult_in", "tri_attn_start",
                 "tri_attn_end", "pair_transition")
# scopes whose ops the gradient runs backward through, and those it does not
# (recycling is a no-grad loop; the update runs after the gradient)
BACKWARD_SCOPES = SUB_OP_SCOPES + ("extra_stack", "evoformer", "embed",
                                   "structure", "loss")
FORWARD_ONLY_SCOPES = ("recycle", "clip", "grad_sync", "optimizer", "ema")

_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_LOOP = re.compile(r"\b(?:body|condition|true_computation|"
                   r"false_computation)=%?([\w.\-]+)")
_CALL = re.compile(r" call\(.*\bto_apply=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SEGMENT = re.compile(r"^((?:[\w.\-]+\()*)([^()]*)\)*$")


def op_name_paths(hlo: str) -> set:
    """Every instruction's op_name in lowered HLO text, prefixed with the
    op_names of the calls, loops and conditionals that reach it: inside a
    called computation the names are relative, and XLA joins them so when
    it inlines the call."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        if cur is None or " = " not in line:
            continue
        m = _OP_NAME.search(line)
        callees = _LOOP.findall(line) + _CALL.findall(line)
        for group in _BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        cur.append((m.group(1) if m else "", callees))
    out, todo = set(), [(entry, "")]
    while todo:
        comp, prefix = todo.pop()
        for name, callees in comps[comp]:
            path = "/".join(p for p in (prefix, name) if p)
            out.add(path)
            todo.extend((c, path) for c in callees)
    return out


def named_scopes(path: str) -> set:
    """The ``jax.named_scope`` names on an op_name path: each segment with
    its transformations (``transpose(jvp(...))``) taken off; a segment a
    ``jit`` wraps names a function (``jnp.clip`` is ``jit(clip)``)."""
    out = set()
    for seg in path.split("/"):
        m = _SEGMENT.match(seg)
        if m and not m.group(1).endswith(("jit(", "pjit(")):
            out.add(m.group(2))
    return out


def _lowered_af2_step_hlo(variant: str) -> str:
    from repro.core import model as af2
    from repro.core.config import af2_tiny
    from repro.data.protein import protein_batch
    from repro.parallel.plan import ParallelPlan
    from repro.train.optim import ema
    from repro.train.trainstep import make_af2_train_step
    cfg = af2_tiny(variant=variant, n_evoformer=1, n_res=8, n_seq=4,
                   n_extra_seq=6)
    opt, avg = adamw(1e-3, per_sample_clip=0.1), ema(0.999)
    step, _ = make_af2_train_step(cfg, opt, ParallelPlan(),
                                  deterministic=False, ema=avg)

    def state():
        p = af2.init_params(jax.random.PRNGKey(0), cfg)
        return {"params": p, "opt": opt.init(p), "ema": avg.init(p)}
    batch = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        protein_batch(0, 0, 1, cfg))
    return (jax.jit(step)
            .lower(jax.eval_shape(state), batch,
                   jax.ShapeDtypeStruct((2,), jnp.uint32), 2)
            .as_text(dialect="hlo", debug_info=True))


@pytest.mark.parametrize("variant", ["parallel", "af2"])
def test_af2_step_hlo_carries_named_scopes(variant):
    """Each part of the step is a ``jax.named_scope`` that reaches the
    op_name of its ops, which a device profile reports as ``tf_op``; the
    gradient's ops carry their forward scope under ``transpose(``."""
    forward, backward = set(), set()
    for path in op_name_paths(_lowered_af2_step_hlo(variant)):
        (backward if "transpose(" in path else forward).update(
            named_scopes(path))
    missing = [s for s in BACKWARD_SCOPES + FORWARD_ONLY_SCOPES
               if s not in forward]
    assert not missing, missing
    missing = [s for s in BACKWARD_SCOPES if s not in backward]
    assert not missing, missing
    assert not backward & set(FORWARD_ONLY_SCOPES), (
        backward & set(FORWARD_ONLY_SCOPES))


def test_bp_block_hlo_carries_exchange_and_opm_scopes():
    """Branch Parallelism's exchange psum is ``bp_exchange``; its OPM, run
    on the MSA branch and added after the exchange, is ``opm``. Lowered on
    a one-device ``branch`` axis: the scopes do not depend on its extent."""
    from jax.sharding import PartitionSpec as P

    from repro.core.config import af2_tiny
    from repro.core.evoformer import evoformer_block_init
    from repro.parallel.branch import bp_evoformer_block
    from repro.parallel.mesh_utils import make_mesh, smap
    cfg = af2_tiny(n_res=8, n_seq=4).evoformer
    p = jax.eval_shape(lambda: evoformer_block_init(jax.random.PRNGKey(0),
                                                    cfg))
    msa = jax.ShapeDtypeStruct((4, 8, cfg.c_m), jnp.float32)
    z = jax.ShapeDtypeStruct((8, 8, cfg.c_z), jnp.float32)
    block = smap(lambda p, m, z: bp_evoformer_block(p, cfg, m, z),
                 make_mesh((1,), ("branch",)),
                 in_specs=(P(), P(), P()), out_specs=(P(), P()))
    hlo = jax.jit(block).lower(p, msa, z).as_text(dialect="hlo",
                                                  debug_info=True)
    scopes = set().union(*map(named_scopes, op_name_paths(hlo)))
    assert {"bp_exchange", "opm", "msa_row_attn", "tri_mult_out"} <= scopes
