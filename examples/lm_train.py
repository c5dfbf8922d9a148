"""Train a ~100M-parameter dense LM end-to-end on the synthetic token
pipeline — the framework's GSPMD training path at a CPU-runnable scale.

  PYTHONPATH=src python examples/lm_train.py --steps 200
(defaults are sized so a few hundred steps complete on a single CPU;
the identical code path drives the 110B assigned config on the pod.)
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.data.loader import ShardedLoader
from repro.data.tokens import token_batch
from repro.models import dense
from repro.models.lmconfig import LMConfig
from repro.parallel.mesh_utils import make_mesh
from repro.train.checkpoint import CheckpointManager, StepWatchdog
from repro.train.optim import adamw, warmup_cosine
from repro.train.trainstep import make_lm_train_step

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=128)
ap.add_argument("--d-model", type=int, default=640)
ap.add_argument("--layers", type=int, default=10)
ap.add_argument("--vocab", type=int, default=32000)
ap.add_argument("--ckpt-dir", default="")
args = ap.parse_args()

cfg = LMConfig(arch_id="lm100m", family="dense", n_layer=args.layers,
               d_model=args.d_model, n_head=args.d_model // 64,
               n_kv_head=max(2, args.d_model // 128), d_ff=4 * args.d_model,
               vocab=args.vocab, scan_layers=True, remat="none",
               attention_chunk=128)
model = dense
mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
opt = adamw(warmup_cosine(3e-4, 20, args.steps), clip_norm=1.0)
step_fn, _, _ = make_lm_train_step(model, cfg, opt, mesh)

params = model.init_params(jax.random.PRNGKey(0), cfg)
n = sum(x.size for x in jax.tree_util.tree_leaves(params))
print(f"params: {n:,} (~{n/1e6:.0f}M)")
state = {"params": params, "opt": opt.init(params)}
fn = jax.jit(step_fn, donate_argnums=(0,))

mgr = CheckpointManager(args.ckpt_dir, keep=2) if args.ckpt_dir else None
wd = StepWatchdog()


def make_batch(step):
    b = token_batch(0, step, args.batch, args.seq, cfg.vocab)
    return {"tokens": jnp.asarray(b["tokens"]),
            "labels": jnp.asarray(b["labels"])}


loader = ShardedLoader(make_batch)
t0 = time.time()
try:
    for step, batch in loader:
        if step >= args.steps:
            break
        wd.start_step()
        state, m = fn(state, batch)
        wd.end_step(step)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"({args.batch * args.seq / max(wd.ema or 1, 1e-9):,.0f} tok/s)")
        if mgr and step and step % 100 == 0:
            mgr.save(step, state)
finally:
    loader.close()
if mgr:
    mgr.save(args.steps, state)
    mgr.wait()
print(f"trained {args.steps} steps in {time.time()-t0:.0f}s")
