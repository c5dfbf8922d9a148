"""ParallelPlan: the single declarative source of truth for how an AF2 train
step is laid out across devices (DESIGN.md §1).

The paper's headline result is a *combination* of strategies — Parallel
Evoformer + Branch Parallelism, hybridized with DAP (§4.3, Table 6) — and the
winning combination depends on shape and device count.  A ``ParallelPlan``
names one point of that matrix:

    pod x data        data-parallel extents (gradient pmean axes)
    branch            Branch Parallelism extent (1 or 2, paper §4.2)
    dap               Dynamic Axial Parallelism extent (FastFold, §3.2)
    variant / attention_impl / opm_impl / tri_mult_impl / remat
                      Evoformer implementation choices (None = keep cfg's)
    compress_pod_grads int8 error-feedback on the cross-pod gradient hop

``plan.build(devices_or_mesh, cfg=cfg)`` validates the plan and returns a
``BuiltPlan`` — mesh, block_fn, stack_io, grad_sync, batch/state specs — the
ONLY thing ``make_af2_train_step`` and the launchers consume.  ``auto_plan``
picks the DP x BP x DAP split from the roofline per-block cost model
(``repro.analysis.roofline.estimate_block_time``), reproducing the paper's
Table 5/6 preferences: BP at initial-training shapes, BP x DAP at
fine-tuning shapes, serial DP whenever the batch can cover every device.

Plans serialize (``to_dict``/``from_dict``); ``CheckpointManager`` records
the plan + mesh fingerprint in checkpoint metadata and refuses restores
under a silently-different plan (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

_VARIANTS = ("af2", "multimer", "parallel")
_ATTENTION_IMPLS = ("reference", "chunked", "pallas", "evo_pallas")
_OPM_IMPLS = ("fused", "naive")
_TRI_MULT_IMPLS = ("reference", "chunked", "pallas")
_REMATS = ("none", "block", "dots")

# params whose gradients are PARTIAL across branch/dap devices and need the
# completing psum (see BuiltPlan.grad_sync and DESIGN.md §2): the stacks
# themselves plus everything UPSTREAM of them (the embedder — each device's
# backward only carries its cond arm's / activation shard's cotangent back
# to the stack inputs).  'single_proj' is the exception inside the embedder
# tree: it consumes the post-exchange (replicated) stack output, so its grad
# is already complete — psumming it would multiply it by the group size.
PARTIAL_GRAD_KEYS = ("evoformer", "extra_stack", "embedder")
COMPLETE_EMBEDDER_KEYS = ("single_proj",)


class PlanError(ValueError):
    """A ParallelPlan that cannot run; the message says how to fix it."""


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    pod: int = 1
    data: int = 1
    branch: int = 1
    dap: int = 1
    # Evoformer implementation selection; None = inherit from the config
    variant: Optional[str] = None
    attention_impl: Optional[str] = None
    opm_impl: Optional[str] = None
    tri_mult_impl: Optional[str] = None
    remat: Optional[str] = None
    compress_pod_grads: bool = False
    # communication-overlapped DAP (double-buffered prefetch carry through
    # the stack scan; DESIGN.md §3).  None = auto: ON whenever dap>1 on a
    # pure-DAP group with the 'parallel' variant (the only variant whose
    # branches both consume the block-input pair rep — the prefetch
    # invariant).  The BP x DAP hybrid keeps the sync schedule (the cond-arm
    # structure precludes a shared carry), as do serial variants.
    overlap_dap: Optional[bool] = None

    # -- derived ------------------------------------------------------------

    @property
    def n_devices(self) -> int:
        return self.pod * self.data * self.branch * self.dap

    @property
    def group(self) -> int:
        """Devices cooperating on one protein (the model-parallel extent)."""
        return self.branch * self.dap

    def describe(self) -> str:
        parts = [f"dp={self.pod * self.data}"
                 + (f" (pod={self.pod} x data={self.data})" if self.pod > 1
                    else "")]
        parts.append(f"bp={self.branch}")
        parts.append(f"dap={self.dap}")
        for k in ("variant", "attention_impl", "opm_impl", "tri_mult_impl",
                  "remat"):
            v = getattr(self, k)
            if v is not None:
                parts.append(f"{k}={v}")
        if self.compress_pod_grads:
            parts.append("compress_pod_grads")
        if self.overlap_dap is not None:
            parts.append(f"overlap_dap={'on' if self.overlap_dap else 'off'}")
        return f"ParallelPlan[{' '.join(parts)}] ({self.n_devices} devices)"

    # -- construction helpers ------------------------------------------------

    @classmethod
    def from_flags(cls, n_devices: int, *, bp: int = 1, dap: int = 1,
                   pod: int = 1, **kw) -> "ParallelPlan":
        """The legacy ``(--bp, --dap)`` CLI surface: whatever the model
        extents don't use becomes data parallelism."""
        group = bp * dap * pod
        if group <= 0 or n_devices % group:
            raise PlanError(
                f"pod({pod}) x bp({bp}) x dap({dap}) = {group} does not "
                f"divide the {n_devices} available devices; pick extents "
                f"whose product divides the device count")
        return cls(pod=pod, data=n_devices // group, branch=bp, dap=dap, **kw)

    @classmethod
    def for_mesh(cls, mesh, *, branch: int = 1, dap: int = 1,
                 **kw) -> "ParallelPlan":
        """Plan matching an existing production mesh: pod/data extents are
        read off the mesh; its 'model' axis must factor as branch x dap
        (``build(mesh)`` performs the refactoring)."""
        shape = dict(mesh.shape)
        return cls(pod=shape.get("pod", 1), data=shape.get("data", 1),
                   branch=branch, dap=dap, **kw)

    def for_inference(self) -> "ParallelPlan":
        """Derive the inference layout from a training plan (DESIGN.md §10).

        Inference has no backward pass, so two of the plan's dimensions
        change meaning:

        * ``branch`` folds into ``data`` — BP's win is overlapping two
          dependency-free branch *gradients*; a forward-only branch split
          just halves per-device utilization, while the same two devices
          double fold throughput as data parallelism.  ``pod`` likewise
          collapses into plain data parallelism (there is no cross-pod
          gradient hop to compress).
        * ``remat='none'`` — rematerialization trades compute for backward
          liveness; with no backward it is pure waste.
        * ``dap`` KEEPS its extent: sharding activations is exactly what
          long-protein buckets need (the (r, r) pair rep is the memory
          wall either way).  ``overlap_dap`` carries over unchanged — with
          ``branch`` folded away the long-bucket data x dap route
          auto-resolves overlap ON, hiding the per-block gathers behind
          the forward compute exactly as in training.

        The result still ``build()``s into the standard BuiltPlan; its
        grad_sync is simply never called by the serving step.
        """
        return dataclasses.replace(
            self, pod=1, data=self.pod * self.data * self.branch, branch=1,
            remat="none", compress_pod_grads=False)

    # -- config interaction --------------------------------------------------

    def apply_to(self, cfg):
        """Return ``cfg`` with this plan's non-None implementation choices
        applied to both Evoformer stacks (and the model-level remat)."""
        evo_over = {k: v for k, v in (
            ("variant", self.variant),
            ("attention_impl", self.attention_impl),
            ("opm_impl", self.opm_impl),
            ("tri_mult_impl", self.tri_mult_impl)) if v is not None}
        over = {}
        if evo_over:
            over["evoformer"] = dataclasses.replace(cfg.evoformer, **evo_over)
            over["extra"] = dataclasses.replace(cfg.extra, **evo_over)
        if self.remat is not None:
            over["remat"] = self.remat
        return dataclasses.replace(cfg, **over) if over else cfg

    def _effective_variant(self, cfg=None) -> Optional[str]:
        if self.variant is not None:
            return self.variant
        return cfg.evoformer.variant if cfg is not None else None

    def resolve_overlap(self, cfg=None) -> bool:
        """The overlapped-DAP decision actually built (DESIGN.md §3).

        Explicit ``overlap_dap`` wins; None auto-resolves to ON for a
        pure-DAP group (dap>1, branch==1) running the 'parallel' variant —
        the prefetch carry's invariant needs both branches to consume the
        block-input pair rep.  With no config in hand (variant unknowable)
        auto resolves OFF: the sync schedule is always correct.
        """
        if self.overlap_dap is not None:
            return self.overlap_dap
        return (self.dap > 1 and self.branch == 1
                and self._effective_variant(cfg) == "parallel")

    # -- validation ----------------------------------------------------------

    def validate(self, cfg=None) -> "ParallelPlan":
        for k in ("pod", "data", "branch", "dap"):
            v = getattr(self, k)
            if not isinstance(v, int) or v < 1:
                raise PlanError(f"plan.{k} must be a positive int, got {v!r}")
        if self.branch not in (1, 2):
            raise PlanError(
                f"plan.branch must be 1 or 2, got {self.branch}: the "
                "Parallel Evoformer block has exactly two dependency-free "
                "branches (MSA+OPM and pair, paper §4.2)")
        variant = self._effective_variant(cfg)
        if self.branch > 1 and variant not in (None, "parallel"):
            raise PlanError(
                f"branch parallelism (branch={self.branch}) requires the "
                f"'parallel' Evoformer variant, got {variant!r}: serial "
                "variants have a cross-branch dependency inside the block "
                "(paper §4.1) — set plan.variant='parallel'")
        for field, allowed in (("variant", _VARIANTS),
                               ("attention_impl", _ATTENTION_IMPLS),
                               ("opm_impl", _OPM_IMPLS),
                               ("tri_mult_impl", _TRI_MULT_IMPLS),
                               ("remat", _REMATS)):
            v = getattr(self, field)
            if v is not None and v not in allowed:
                raise PlanError(f"plan.{field}={v!r} is not one of {allowed}")
        if self.compress_pod_grads and self.pod == 1:
            raise PlanError(
                "compress_pod_grads targets the cross-pod gradient hop but "
                "the plan has pod=1 — set pod>1 (e.g. --pods 2) or drop "
                "compression")
        if self.overlap_dap:
            if self.dap < 2:
                raise PlanError(
                    "overlap_dap=True overlaps DAP's collectives with "
                    f"compute, but the plan has dap={self.dap} (no DAP "
                    "collectives to overlap) — raise dap or leave "
                    "overlap_dap=None")
            if self.branch > 1:
                raise PlanError(
                    f"overlap_dap=True is not supported under the BP x DAP "
                    f"hybrid (branch={self.branch}): the cond-arm branch "
                    "dispatch precludes the shared prefetch carry — leave "
                    "overlap_dap=None (the hybrid keeps the sync schedule)")
            if variant not in (None, "parallel"):
                raise PlanError(
                    f"overlap_dap=True requires the 'parallel' Evoformer "
                    f"variant, got {variant!r}: only the parallel block "
                    "feeds BOTH branches the block-input pair rep, the "
                    "invariant the prefetched gather relies on — set "
                    "plan.variant='parallel' or leave overlap_dap=None")
        if cfg is not None and self.dap > 1:
            for name, extent in (("n_seq", cfg.n_seq),
                                 ("n_extra_seq", cfg.n_extra_seq),
                                 ("n_res", cfg.n_res)):
                if extent % self.dap:
                    ok = [d for d in range(2, extent + 1)
                          if cfg.n_seq % d == 0 and cfg.n_extra_seq % d == 0
                          and cfg.n_res % d == 0][:6]
                    raise PlanError(
                        f"dap={self.dap} does not divide cfg.{name}="
                        f"{extent}; DAP shards must be equal on every "
                        f"device (feasible dap extents for this config: "
                        f"{ok or 'none'})")
        return self

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ParallelPlan":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise PlanError(f"unknown ParallelPlan fields {sorted(unknown)} "
                            f"(known: {sorted(known)})")
        return cls(**d)

    # -- build ---------------------------------------------------------------

    def build(self, devices=None, *, cfg=None) -> "BuiltPlan":
        """Materialize the plan: ``devices`` may be None (all local devices),
        a device sequence (a fresh mesh is built), or an existing Mesh whose
        'model' axis is refactored into branch x dap."""
        from jax.sharding import Mesh
        self.validate(cfg)
        if isinstance(devices, Mesh):
            mesh = self._adapt_mesh(devices)
        else:
            if devices is None:
                import jax
                devices = jax.devices()
            mesh = self._make_mesh(devices)
        return _build(self, mesh, cfg)

    def _make_mesh(self, devices: Sequence):
        from repro.parallel.mesh_utils import make_mesh
        n = self.n_devices
        if len(devices) != n:
            raise PlanError(
                f"plan covers {n} devices (pod={self.pod} data={self.data} "
                f"branch={self.branch} dap={self.dap}) but {len(devices)} "
                f"were given; fix the extents (ParallelPlan.from_flags "
                f"derives data from the device count) or pass "
                f"devices[:{n}] explicitly")
        axes = [("pod", self.pod), ("data", self.data),
                ("branch", self.branch), ("dap", self.dap)]
        axes = [(name, ext) for name, ext in axes
                if ext > 1 or name == "data"]
        names = tuple(a for a, _ in axes)
        shape = tuple(e for _, e in axes)
        # jax.make_mesh orders devices for ICI locality (the trailing dap
        # axis carries ~13 collectives per block — it must sit on adjacent
        # chips); a raw Mesh(devices.reshape(...)) would keep enumeration
        # order
        return make_mesh(shape, names, devices=list(devices))

    def _adapt_mesh(self, mesh):
        """Fit the plan onto a production mesh (pod?, data, model): the
        'model' axis factors into (branch, dap); a model axis with no model
        parallelism in the plan stays as an inert replicated axis."""
        from repro.parallel.mesh_utils import refactor_mesh
        for name in ("pod", "data"):
            extent = mesh.shape.get(name, 1) if name in mesh.axis_names else 1
            if extent != getattr(self, name):
                raise PlanError(
                    f"plan.{name}={getattr(self, name)} but the mesh has "
                    f"{name} extent {extent}; use ParallelPlan.for_mesh to "
                    "derive DP extents from the mesh")
        if "model" in mesh.axis_names:
            model = mesh.shape["model"]
            if self.group == 1:
                return mesh  # model axis idle: everything replicated over it
            if self.group != model:
                raise PlanError(
                    f"branch({self.branch}) x dap({self.dap}) = {self.group} "
                    f"!= mesh 'model' axis extent {model}; the logical "
                    "refactoring must cover the physical axis exactly")
            split = [(n, e) for n, e in (("branch", self.branch),
                                         ("dap", self.dap)) if e > 1]
            return refactor_mesh(mesh, {"model": split})
        for name in ("branch", "dap"):
            extent = mesh.shape.get(name, 1) if name in mesh.axis_names else 1
            if extent != getattr(self, name):
                raise PlanError(
                    f"plan.{name}={getattr(self, name)} but the mesh has "
                    f"{name} extent {extent}")
        return mesh

    def fingerprint(self, mesh) -> dict:
        """Mesh identity recorded in checkpoint metadata: enough to detect a
        changed topology without pinning exact device objects."""
        flat = mesh.devices.reshape(-1)
        return {"n_devices": int(flat.size),
                "axes": {k: int(v) for k, v in mesh.shape.items()},
                "platform": getattr(flat[0], "platform", "unknown")}


# ---------------------------------------------------------------------------
# BuiltPlan: what the train step actually consumes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BuiltPlan:
    plan: ParallelPlan
    mesh: object                    # jax.sharding.Mesh
    dp_axes: tuple                  # gradient/loss pmean axes
    sync_axes: tuple                # partial-grad psum axes (branch/dap)
    batch_spec: object              # PartitionSpec for dim 0 of the batch
    state_spec: object              # PartitionSpec for params/opt (replicated)
    block_fn: Optional[object]      # Evoformer block override (None = serial)
    stack_io: Optional[tuple]       # (pre, post) around each stack (DAP)
    grad_sync: object               # (grads, err) -> (grads, err), in shard_map

    def metadata(self) -> dict:
        return {"plan": self.plan.to_dict(),
                "mesh_fingerprint": self.plan.fingerprint(self.mesh)}


def _region_exit_fn(factor: float):
    """Identity on (msa, z) whose VJP scales cotangents by ``factor``.

    Applied at the exit of the branch/dap-parallel region (the Evoformer
    stacks) when gradients are taken INSIDE shard_map (DESIGN.md §2): the
    replicated downstream (structure module, heads, loss) produces the FULL
    cotangent on every device of the group, while the collective transposes
    inside the region (psum -> psum, all_gather -> psum_scatter) assume
    partial cotangents that SUM to the true one across the group.  Scaling
    by 1/group_size at the boundary converts conventions; without it every
    exchange crossing multiplies upstream gradients by the group size
    (masked by Adam's scale invariance, caught by the SGD-based plan-matrix
    equivalence test)."""
    import jax

    @jax.custom_vjp
    def region_exit(msa, z):
        return msa, z

    def fwd(msa, z):
        return (msa, z), None

    def bwd(_, ct):
        cm, cz = ct
        return cm * factor, cz * factor

    region_exit.defvjp(fwd, bwd)
    return region_exit


def complete_partial_grads(grads, sync_axes):
    """psum the PARTIAL gradient subtrees over the branch/dap axes
    (DESIGN.md §2): the stacks and everything upstream of them, minus the
    post-exchange ``single_proj``.  Shared by ``BuiltPlan.grad_sync`` (the
    once-per-step batched completion) and the per-sample clipping path in
    ``make_af2_train_step`` (which must measure the norm of the COMPLETED
    sample gradient — a shard's partial-grad norm is not it)."""
    import jax
    if not sync_axes:
        return grads
    grads = dict(grads)
    partial = {k: grads[k] for k in PARTIAL_GRAD_KEYS if k != "embedder"}
    emb = dict(grads["embedder"])
    complete_emb = {k: emb.pop(k) for k in COMPLETE_EMBEDDER_KEYS}
    partial["embedder"] = emb
    partial = jax.lax.psum(partial, sync_axes)
    partial["embedder"].update(complete_emb)
    grads.update(partial)
    return grads


def _build(plan: ParallelPlan, mesh, cfg=None) -> BuiltPlan:
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.parallel import branch as bp_lib
    from repro.parallel import dap as dap_lib
    from repro.parallel import grad_sync as gs_lib

    axis_names = mesh.axis_names
    dp_axes = tuple(a for a in ("pod", "data") if a in axis_names)
    have_branch = plan.branch > 1 and "branch" in axis_names
    have_dap = plan.dap > 1 and "dap" in axis_names

    block_fn = None
    if have_branch and have_dap:
        def block_fn(p, c, m, z, rng=None, deterministic=True, masks=None):
            # n_seq_total=None: derived per-stack from the shard shape x dap
            # extent — the main and extra stacks have different row counts
            return bp_lib.bp_dap_evoformer_block(
                p, c, m, z, rng=rng, deterministic=deterministic, masks=masks)
    elif have_branch:
        def block_fn(p, c, m, z, rng=None, deterministic=True, masks=None):
            return bp_lib.bp_evoformer_block(
                p, c, m, z, rng=rng, deterministic=deterministic, masks=masks)
    elif have_dap:
        # overlap carries the prefetch protocol (block_fn.prefetch_init +
        # the extra prefetch carry through the stack scan, DESIGN.md §3)
        block_fn = dap_lib.make_dap_block_fn(
            overlap=plan.resolve_overlap(cfg))

    sync_axes = ((("branch",) if have_branch else ()) +
                 (("dap",) if have_dap else ()))
    group = (plan.branch if have_branch else 1) * \
        (plan.dap if have_dap else 1)
    stack_io = None
    if group > 1:
        exit_fn = _region_exit_fn(1.0 / group)
        if have_dap:
            def pre(m, z):
                return dap_lib.shard_inputs(m, z)

            def post(m, z):
                return exit_fn(*dap_lib.unshard_outputs(m, z))
        else:
            def pre(m, z):
                return m, z
            post = exit_fn
        stack_io = (pre, post)

    compress = plan.compress_pod_grads and "pod" in axis_names
    npods = mesh.shape.get("pod", 1) if "pod" in axis_names else 1

    def grad_sync(grads, err=None, *, completed=False):
        """Complete + reduce gradients (inside shard_map; DESIGN.md §2):
        grads of the Evoformer stacks AND of everything upstream of them
        (embedder) are PARTIAL across branch/dap devices (each device
        backpropped only its cond arm / activation shard) — psum over
        ``sync_axes`` completes them; grads of post-exchange consumers
        (single_proj / structure / heads) are already identical and stay
        untouched; every grad then pmeans over the DP axes, optionally
        int8-error-feedback-compressed on the pod hop.

        ``completed=True`` skips the completing psum — the per-sample
        clipping path already completed each sample's gradient inside its
        scan (re-psumming would multiply by the group size)."""
        if not completed:
            grads = complete_partial_grads(grads, sync_axes)
        if compress and err is not None:
            inner = tuple(a for a in dp_axes if a != "pod")
            if inner:
                grads = jax.lax.pmean(grads, inner)
            grads, err = gs_lib.compressed_psum_tree(grads, "pod", err)
            grads = jax.tree_util.tree_map(lambda g: g / npods, grads)
        elif dp_axes:
            grads = jax.lax.pmean(grads, dp_axes)
        return grads, err

    batch_spec = (P(dp_axes if len(dp_axes) > 1 else dp_axes[0])
                  if dp_axes else P())
    return BuiltPlan(plan=plan, mesh=mesh, dp_axes=dp_axes,
                     sync_axes=sync_axes, batch_spec=batch_spec,
                     state_spec=P(), block_fn=block_fn, stack_io=stack_io,
                     grad_sync=grad_sync)


# ---------------------------------------------------------------------------
# auto_plan: pick the split from the roofline cost model
# ---------------------------------------------------------------------------

def _divisors(n: int):
    return [d for d in range(1, n + 1) if n % d == 0]


def auto_plan(n_devices: int, cfg, *, global_batch: int = 128, pod: int = 1,
              hw=None, **plan_kw) -> ParallelPlan:
    """Choose the DP x BP x DAP split for ``n_devices`` and a model config.

    Strategy (paper §4 + Table 5/6): data parallelism is free — the batch is
    the limit (convergence caps it; paper: 128).  The per-protein group is
    therefore the SMALLEST extent that lets every device participate
    (``n_devices / dp <= global_batch``); within a group, the (bp, dap)
    factorization minimizing the roofline per-block time wins —
    ``analysis.roofline.estimate_block_time`` prefers BP at
    initial-training shapes and BP x DAP hybrids at fine-tuning shapes.
    """
    from repro.analysis.roofline import HW, estimate_block_time
    hw = hw or HW()
    if n_devices < 1:
        raise PlanError(f"n_devices must be >= 1, got {n_devices}")
    if pod < 1 or n_devices % pod:
        raise PlanError(f"pod={pod} does not divide n_devices={n_devices}")
    per_pod = n_devices // pod
    variant = plan_kw.get("variant") or cfg.evoformer.variant
    want_overlap = plan_kw.get("overlap_dap")
    infeasible = []
    for group in _divisors(per_pod):
        dp = pod * (per_pod // group)
        if dp > global_batch or global_batch % dp:
            continue
        cands = []
        for bp in (2, 1):
            if group % bp:
                continue
            dap = group // bp
            if bp > 1 and variant != "parallel":
                infeasible.append(f"bp={bp} (variant={variant!r})")
                continue
            if bp > 1 and want_overlap:
                # explicit overlap_dap=True excludes the hybrid (validate
                # would reject it: no prefetch carry across cond arms)
                infeasible.append(f"bp={bp} (overlap_dap=True)")
                continue
            if any(extent % dap for extent in
                   (cfg.n_seq, cfg.n_extra_seq, cfg.n_res)):
                infeasible.append(f"dap={dap} (indivisible shapes)")
                continue
            # score each candidate under the schedule it would actually
            # build: the overlapped comm model for pure-DAP 'parallel'
            # groups, the sync additive model otherwise
            ov = (want_overlap if want_overlap is not None else
                  (bp == 1 and dap > 1 and variant == "parallel"))
            t = estimate_block_time(cfg, bp=bp, dap=dap, hw=hw, overlap=ov)
            cands.append((t, bp, dap))
        if not cands:
            continue
        _, bp, dap = min(cands)
        return ParallelPlan(pod=pod, data=per_pod // group, branch=bp,
                            dap=dap, **plan_kw).validate(cfg)
    raise PlanError(
        f"no feasible plan for {n_devices} devices, global_batch="
        f"{global_batch}, pod={pod}"
        + (f" (rejected: {sorted(set(infeasible))})" if infeasible else "")
        + "; lower the device count, raise the batch, or pick extents "
        "explicitly with ParallelPlan.from_flags")
