import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct stand-ins (no allocation), record memory/cost/collective
analysis for EXPERIMENTS.md §Dry-run and §Roofline.

The two lines above MUST run before any jax import (device count locks on
first init) — do not move them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--probes]
  PYTHONPATH=src python -m repro.launch.dryrun --af2 initial --bp 2 --dap 8
Results cached as JSON under experiments/dryrun/.
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as cfglib
from repro.analysis.hlo import parse_hlo_collectives
from repro.analysis.roofline import HW, model_flops, roofline_terms
from repro.launch.mesh import production_mesh_from_env, dp_axes_of
from repro.models import get_model
from repro.serve.steps import cache_partition_rules
from repro.train.optim import adamw, adafactor_like
from repro.train.trainstep import (make_lm_train_step, shardings_for,
                                   sanitize_spec_tree)
from repro.nn.partition import make_param_specs

OUT_DIR = pathlib.Path(os.environ.get(
    "REPRO_DRYRUN_OUT",
    pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"))


def _mesh(multi_pod: bool):
    """Production mesh, overridable via REPRO_DRYRUN_MESH='4x4[x2]' for the
    small-mesh self-test (tests/test_dryrun_small.py)."""
    return production_mesh_from_env(multi_pod)


# ---------------------------------------------------------------------------
# shape/sharding construction
# ---------------------------------------------------------------------------

def batch_shapes(cfg, shape, *, for_prefill=False):
    """ShapeDtypeStructs for the training / prefill request batch."""
    b, s = shape.global_batch, shape.seq_len
    front = {}
    text_len = s
    if cfg.family == "audio":
        front["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
    if cfg.family == "vlm":
        front["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
        text_len = s - cfg.n_frontend_tokens  # backbone seq == assigned seq
    out = {"tokens": jax.ShapeDtypeStruct((b, text_len), jnp.int32), **front}
    if not for_prefill:
        out["labels"] = jax.ShapeDtypeStruct((b, text_len), jnp.int32)
    return out


def tree_shapes(f):
    return jax.eval_shape(f)


def to_sharded(shapes, specs, mesh):
    specs = sanitize_spec_tree(shapes, specs, mesh)
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        shapes, specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def batch_specs_tree(shapes, data_axes):
    spec = P(data_axes if len(data_axes) > 1 else data_axes[0])
    return jax.tree_util.tree_map(lambda s: spec, shapes,
                                  is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# ---------------------------------------------------------------------------
# analysis of a compiled artifact
# ---------------------------------------------------------------------------

def analyse(lowered, compiled, n_devices) -> dict:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    colls = parse_hlo_collectives(compiled.as_text())
    return {
        "per_device_flops": float(ca.get("flops", 0.0)),
        "per_device_bytes": float(ca.get("bytes accessed", 0.0)),
        "collectives": colls,
        "collective_bytes_static": sum(v["bytes"] for v in colls.values()),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_estimate": ma.argument_size_in_bytes
            + ma.temp_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "n_devices": n_devices,
    }


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def build_lm_step(cfg, shape, mesh, *, optimizer=None):
    """Returns (jitted_fn, example_args(ShapeDtypeStructs))."""
    model = get_model(cfg)
    data_axes = dp_axes_of(mesh)
    if shape.kind == "train":
        optimizer = optimizer or adafactor_like(1e-4, clip_norm=1.0)
        step, state_shardings, _ = make_lm_train_step(
            model, cfg, optimizer, mesh, data_axes=data_axes)
        key = jax.random.PRNGKey(0)
        pshapes = tree_shapes(lambda: model.init_params(key, cfg))
        oshapes = tree_shapes(lambda: optimizer.init(
            jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), pshapes)))
        # build sharded ShapeDtypeStructs
        shd = state_shardings(pshapes, oshapes)
        state = {
            "params": jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                pshapes, shd["params"]),
            "opt": jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                oshapes, shd["opt"]),
        }
        bshapes = batch_shapes(cfg, shape)
        bsh = to_sharded(bshapes, batch_specs_tree(bshapes, data_axes), mesh)
        fn = jax.jit(step, donate_argnums=(0,))
        return fn, (state, bsh)

    # serving cells
    from repro.serve.steps import decode_mesh_plan, cache_partition_rules_2d
    tp_axis = "model"
    if shape.kind == "decode" and cfg.factored_decode:
        mesh, tp_axis, data_axes = decode_mesh_plan(cfg, mesh)
    key = jax.random.PRNGKey(0)
    pshapes = tree_shapes(lambda: model.init_params(key, cfg))
    prules = model.partition_rules(cfg, tp_axis=tp_axis)
    pspecs = make_param_specs(pshapes, prules)
    params = to_sharded(pshapes, pspecs, mesh)
    cache_len = shape.seq_len + 1
    cshapes = tree_shapes(lambda: model.init_cache(cfg, shape.global_batch,
                                                   cache_len))
    crules = (cache_partition_rules_2d(cfg, data_axes=tuple(data_axes))
              if isinstance(tp_axis, tuple) else cache_partition_rules(cfg))
    cspecs = make_param_specs(cshapes, crules)
    cache = to_sharded(cshapes, cspecs, mesh)
    data_axis = data_axes if len(data_axes) > 1 else data_axes[0]

    if shape.kind == "prefill":
        bshapes = batch_shapes(cfg, shape, for_prefill=True)
        bsh = to_sharded(bshapes, batch_specs_tree(bshapes, data_axes), mesh)
        if cfg.family in ("audio", "vlm"):
            fn = jax.jit(lambda p, b, c: get_model(cfg).prefill(p, cfg, b, c),
                         donate_argnums=(2,))
            return fn, (params, bsh, cache)
        fn = jax.jit(lambda p, t, c: get_model(cfg).prefill(p, cfg, t, c),
                     donate_argnums=(2,))
        return fn, (params, bsh["tokens"], cache)

    # decode: one token for the whole batch
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32,
                               sharding=NamedSharding(mesh, sanitize_spec_tree(
                                   jax.ShapeDtypeStruct((shape.global_batch, 1),
                                                        jnp.int32),
                                   P(data_axis, None), mesh)))
    fn = jax.jit(lambda p, t, c: get_model(cfg).decode_step(p, cfg, t, c),
                 donate_argnums=(2,))
    return fn, (params, tok, cache)


def run_lm_cell(arch, shape_name, multi_pod, *, probes=True,
                result_suffix="", cfg_override=None) -> dict:
    cfg = cfg_override or cfglib.get_config(arch)
    shape = cfglib.SHAPES[shape_name]
    mesh = _mesh(multi_pod)
    n_dev = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi_pod" if multi_pod else "single_pod",
           "devices": n_dev, "status": "ok"}
    t0 = time.time()
    fn, args = build_lm_step(cfg, shape, mesh)
    lowered = fn.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)
    rec["full"] = analyse(lowered, compiled, n_dev)

    if probes and shape.kind in ("train", "prefill", "decode"):
        rec["probe"] = probe_per_layer(cfg, shape, mesh)
        rec["roofline"] = derive_roofline(cfg, shape, rec, n_dev)
    return rec


def probe_per_layer(cfg, shape, mesh, l1=2, l2=4) -> dict:
    """Reduced-depth UNROLLED lowerings -> per-layer cost extrapolation
    (scan bodies are counted once by cost_analysis; DESIGN.md §7)."""
    if cfg.family == "hybrid":
        every = cfg.shared_attn_every
        l1, l2 = every, 2 * every
    out = {}
    for name, nl in (("l1", l1), ("l2", l2)):
        over = {"n_layer": nl, "scan_layers": False}
        if cfg.family == "audio":
            over["n_enc_layer"] = nl
        c = dataclasses.replace(cfg, **over)
        fn, args = build_lm_step(c, shape, mesh)
        compiled = fn.lower(*args).compile()
        out[name] = analyse(None, compiled, mesh.devices.size)
        out[name]["n_layer"] = nl
    per_layer = {}
    for k in ("per_device_flops", "per_device_bytes", "collective_bytes_static"):
        d = (out["l2"][k] - out["l1"][k]) / (l2 - l1)
        per_layer[k] = d
    n_full = cfg.n_layer
    out["extrapolated"] = {
        k: out["l1"][k] + per_layer[k] * (n_full - l1)
        for k in per_layer}
    out["per_layer"] = per_layer
    return out


def derive_roofline(cfg, shape, rec, n_dev) -> dict:
    ex = rec["probe"]["extrapolated"]
    total_flops = ex["per_device_flops"] * n_dev
    total_bytes = ex["per_device_bytes"] * n_dev
    total_coll = ex["collective_bytes_static"] * n_dev
    terms = roofline_terms(total_flops=total_flops, total_bytes=total_bytes,
                           total_collective_bytes=total_coll, chips=n_dev)
    mf = model_flops(cfg, shape.kind, shape.seq_len, shape.global_batch)
    terms["model_flops"] = mf
    terms["hlo_flops_global"] = total_flops
    terms["useful_flops_ratio"] = mf / total_flops if total_flops else 0.0
    return terms


# ---------------------------------------------------------------------------
# AF2 cells (paper model, BP x DAP x DP logical mesh)
# ---------------------------------------------------------------------------

def run_af2_cell(process: str, multi_pod: bool, *, bp=2, dap=8,
                 global_batch=128, variant="parallel", n_recycle=1,
                 remat="block", suffix="") -> dict:
    from repro.core.config import af2_initial, af2_finetune
    from repro.core import model as af2
    from repro.parallel.plan import ParallelPlan
    from repro.train.trainstep import make_af2_train_step
    from repro.data.protein import protein_sample

    cfg = (af2_initial if process == "initial" else af2_finetune)()
    base = _mesh(multi_pod)
    plan = ParallelPlan.for_mesh(base, branch=bp, dap=max(dap, 1),
                                 variant=variant, remat=remat)
    cfg = plan.apply_to(cfg)
    built = plan.build(base, cfg=cfg)
    mesh = built.mesh
    n_dev = mesh.devices.size
    opt = adamw(1e-3, clip_norm=0.1)
    step, _ = make_af2_train_step(cfg, opt, built, n_recycle=n_recycle)
    key = jax.random.PRNGKey(0)
    pshapes = tree_shapes(lambda: af2.init_params(key, cfg))
    oshapes = tree_shapes(lambda: opt.init(jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), pshapes)))
    sshapes = tree_shapes(lambda: protein_sample(key, cfg))
    bshapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((global_batch,) + s.shape, s.dtype),
        sshapes)
    rep = NamedSharding(mesh, P())
    bsh = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=NamedSharding(mesh, built.batch_spec)),
        bshapes)
    state = {
        "params": jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            pshapes),
        "opt": jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            oshapes),
    }
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)

    rec = {"arch": f"af2-{process}", "shape": f"bp{bp}_dap{dap}_b{global_batch}",
           "variant": variant,
           "mesh": "multi_pod" if multi_pod else "single_pod",
           "devices": n_dev, "status": "ok"}
    t0 = time.time()
    lowered = jax.jit(step, donate_argnums=(0,)).lower(state, bsh, rng)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)
    rec["full"] = analyse(lowered, compiled, n_dev)

    # per-block probe: unrolled 1 vs 2 evoformer blocks
    probes = {}
    for name, nb in (("l1", 1), ("l2", 2)):
        c2 = dataclasses.replace(cfg, n_evoformer=nb, n_extra_msa_blocks=1,
                                 scan_blocks=False)
        step2, _ = make_af2_train_step(c2, opt, built, n_recycle=n_recycle)
        p2 = tree_shapes(lambda: af2.init_params(key, c2))
        o2 = tree_shapes(lambda: opt.init(jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), p2)))
        st2 = {
            "params": jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), p2),
            "opt": jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), o2),
        }
        compiled2 = jax.jit(step2, donate_argnums=(0,)).lower(
            st2, bsh, rng).compile()
        probes[name] = analyse(None, compiled2, n_dev)
    per_block = {k: probes["l2"][k] - probes["l1"][k]
                 for k in ("per_device_flops", "per_device_bytes",
                           "collective_bytes_static")}
    n_blocks = cfg.n_evoformer + cfg.n_extra_msa_blocks
    probes["extrapolated"] = {
        k: probes["l1"][k] + per_block[k] * (n_blocks - 2)
        for k in per_block}
    rec["probe"] = probes
    ex = probes["extrapolated"]
    terms = roofline_terms(
        total_flops=ex["per_device_flops"] * n_dev,
        total_bytes=ex["per_device_bytes"] * n_dev,
        total_collective_bytes=ex["collective_bytes_static"] * n_dev,
        chips=n_dev)
    from repro.analysis.roofline import af2_model_flops
    terms["model_flops"] = 3.0 * af2_model_flops(cfg) * global_batch
    terms["hlo_flops_global"] = ex["per_device_flops"] * n_dev
    terms["useful_flops_ratio"] = (terms["model_flops"] /
                                   terms["hlo_flops_global"]
                                   if terms["hlo_flops_global"] else 0.0)
    rec["roofline"] = terms
    return rec


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def cell_path(arch, shape, mesh_kind, suffix=""):
    safe = arch.replace("/", "_").replace(".", "_")
    return OUT_DIR / f"{safe}__{shape}__{mesh_kind}{suffix}.json"


def run_and_save(arch, shape_name, multi_pod, *, probes=True, force=False,
                 suffix="", cfg_override=None):
    mesh_kind = "multi_pod" if multi_pod else "single_pod"
    path = cell_path(arch, shape_name, mesh_kind, suffix)
    if path.exists() and not force:
        print(f"[skip cached] {path.name}")
        return json.loads(path.read_text())
    print(f"[run] {arch} x {shape_name} x {mesh_kind}", flush=True)
    try:
        rec = run_lm_cell(arch, shape_name, multi_pod, probes=probes,
                          cfg_override=cfg_override)
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1, default=str))
    ok = rec.get("status") == "ok"
    print(f"[{'ok' if ok else 'FAIL'}] {path.name}"
          + ("" if ok else f" :: {rec.get('error')}"), flush=True)
    return rec


OPT_OVERRIDES = {
    # §Perf hillclimbs: named optimization sets applied over the baseline cfg
    "moe_sorted": {"moe_dispatch": "sorted"},
    "uniform_decode": {"uniform_decode": True},
    "factored_decode": {"factored_decode": True, "uniform_decode": True},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--opt", action="append", default=[],
                    choices=list(OPT_OVERRIDES),
                    help="apply named optimization(s), suffix output files")
    ap.add_argument("--af2", choices=["initial", "finetune"])
    ap.add_argument("--bp", type=int, default=2)
    ap.add_argument("--dap", type=int, default=8)
    ap.add_argument("--variant", default="parallel")
    ap.add_argument("--af2-remat", default="block", choices=["block", "none", "dots"])
    ap.add_argument("--ln-bf16", action="store_true",
                    help="§Perf: LN output in compute dtype (bf16 io)")
    args = ap.parse_args()

    if args.ln_bf16:
        from repro.nn import layers as _nl
        _nl.set_ln_fp32_io(False)

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    if args.af2:
        for mp in meshes:
            mesh_kind = "multi_pod" if mp else "single_pod"
            rsuf = "" if args.af2_remat == "block" else f"_remat-{args.af2_remat}"
            rsuf += "_lnbf16" if args.ln_bf16 else ""
            path = cell_path(f"af2-{args.af2}",
                             f"bp{args.bp}_dap{args.dap}", mesh_kind,
                             f"_{args.variant}{rsuf}")
            if path.exists() and not args.force:
                print(f"[skip cached] {path.name}")
                continue
            try:
                rec = run_af2_cell(args.af2, mp, bp=args.bp, dap=args.dap,
                                   variant=args.variant, remat=args.af2_remat)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": f"af2-{args.af2}", "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
            OUT_DIR.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(rec, indent=1, default=str))
            print(f"[{rec.get('status')}] {path.name}", flush=True)
        return

    if args.all:
        for arch in cfglib.ARCH_IDS:
            for shape in cfglib.arch_shapes(arch):
                for mp in meshes:
                    run_and_save(arch, shape, mp, probes=not args.no_probes,
                                 force=args.force)
        return

    assert args.arch and args.shape
    cfg_override = None
    suffix = ""
    if args.opt:
        over = {}
        for name in args.opt:
            over.update(OPT_OVERRIDES[name])
        cfg_override = dataclasses.replace(cfglib.get_config(args.arch), **over)
        suffix = "_opt_" + "-".join(sorted(args.opt))
    for mp in meshes:
        run_and_save(args.arch, args.shape, mp, probes=not args.no_probes,
                     force=args.force, suffix=suffix, cfg_override=cfg_override)


if __name__ == "__main__":
    main()
