"""Checkpointing + fault tolerance, built from scratch (no orbax).

* Atomic: write to ``<dir>/tmp.<step>``, fsync, rename to ``step_<n>`` —
  a crash mid-write never corrupts the latest checkpoint.
* Keep-N garbage collection.
* Async: serialization happens on a worker thread; ``wait()`` barriers.
* Elastic restore: checkpoints store full (unsharded) arrays + the pytree
  structure; ``restore`` re-shards onto ANY target mesh — restart with a
  shrunk/grown pod count (node failures, elastic scaling) just works.
* Preemption hook: SIGTERM triggers a final synchronous save.

Format: one ``.npz`` per checkpoint holding flattened leaves keyed by index
+ a msgpack/JSON manifest with paths, dtypes, shapes and the step number.
93M-param AF2 fp32+Adam ≈ 1.1 GB — single-file-per-host is fine; larger LMs
would extend to per-shard files via the same manifest (documented).
"""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import signal
import threading
import time
from typing import Any, Callable, Optional

import jax
import numpy as np


def _flatten_with_names(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names, leaves = [], []
    for path, leaf in flat:
        names.append(jax.tree_util.keystr(path))
        leaves.append(leaf)
    return names, leaves, treedef


_NATIVE = {np.dtype(d) for d in
           ("float64", "float32", "float16", "int64", "int32", "int16",
            "int8", "uint64", "uint32", "uint16", "uint8", "bool")}


def _encode(arr: np.ndarray) -> np.ndarray:
    """npz can't hold ml_dtypes (bf16/fp8): store a uint8 view; the logical
    dtype lives in the manifest and is restored with ``_decode``."""
    if arr.dtype in _NATIVE:
        return arr
    return np.ascontiguousarray(arr).view(np.uint8)


def _decode(arr: np.ndarray, dtype: str, shape) -> np.ndarray:
    if np.dtype(arr.dtype) in _NATIVE and arr.dtype == dtype:
        return arr
    import ml_dtypes  # ships with jax
    dt = np.dtype(getattr(ml_dtypes, dtype, dtype))
    return arr.view(dt).reshape(shape)


class PlanMismatchError(ValueError):
    """Checkpoint was written under a different ParallelPlan/mesh than the
    one restoring it; the message lists the differing fields."""


def _diff_meta(stored: dict, current: dict, prefix="") -> list:
    out = []
    for k in sorted(set(stored) | set(current)):
        a, b = stored.get(k), current.get(k)
        if isinstance(a, dict) and isinstance(b, dict):
            out.extend(_diff_meta(a, b, prefix=f"{prefix}{k}."))
        elif a != b:
            out.append(f"{prefix}{k}: checkpoint={a!r} current={b!r}")
    return out


def check_plan_meta(stored: Optional[dict], current: Optional[dict], *,
                    adapt: bool = False):
    """Compare stored vs current plan metadata (see BuiltPlan.metadata).

    Plan field mismatches are fatal unless ``adapt=True`` — silently
    training on under a different BP/DAP/compression layout than the run
    that wrote the checkpoint is almost never intended.  Mesh-fingerprint
    mismatches alone (device count / topology) are always allowed: the
    checkpoint format is mesh-agnostic and re-shards on restore (the
    elastic-restart path)."""
    if not stored or not current or adapt:
        return
    diffs = _diff_meta(stored.get("plan", {}), current.get("plan", {}))
    if diffs:
        raise PlanMismatchError(
            "checkpoint was written under a different ParallelPlan:\n  "
            + "\n  ".join(diffs)
            + "\npass adapt_plan=True (launcher: --adapt-plan) to restore "
            "anyway — arrays are mesh-agnostic and re-shard, but optimizer "
            "dynamics and dropout streams may differ across layouts")


def save_checkpoint(directory, step: int, tree, *,
                    meta: Optional[dict] = None) -> pathlib.Path:
    directory = pathlib.Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    tmp = directory / f"tmp.{step}.{os.getpid()}"
    final = directory / f"step_{step:010d}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    names, leaves, _ = _flatten_with_names(tree)
    logical = [np.asarray(leaf) for leaf in leaves]
    arrays = {f"a{i}": _encode(a) for i, a in enumerate(logical)}
    np.savez(tmp / "arrays.npz", **arrays)
    manifest = {
        "step": step,
        "names": names,
        "dtypes": [str(a.dtype) for a in logical],
        "shapes": [list(a.shape) for a in logical],
        "time": time.time(),
        "meta": meta or {},
    }
    with open(tmp / "manifest.json", "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(directory) -> Optional[int]:
    directory = pathlib.Path(directory)
    if not directory.exists():
        return None
    steps = [int(m.group(1)) for p in directory.iterdir()
             if (m := re.fullmatch(r"step_(\d+)", p.name))]
    return max(steps) if steps else None


def checkpoint_meta(directory, step: Optional[int] = None) -> dict:
    """The ``meta`` dict recorded at save time (plan + mesh fingerprint)."""
    directory = pathlib.Path(directory)
    step = step if step is not None else latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoints in {directory}")
    manifest = json.loads(
        (directory / f"step_{step:010d}" / "manifest.json").read_text())
    return manifest.get("meta", {})


def restore_checkpoint(directory, tree_like, *, step: Optional[int] = None,
                       shardings=None, expect_meta: Optional[dict] = None,
                       adapt_plan: bool = False):
    """Restore into the structure of ``tree_like``; optionally re-shard each
    leaf with ``shardings`` (a matching pytree of Sharding) — this is the
    elastic-reshape path: the checkpoint is mesh-agnostic.

    ``expect_meta`` (see ``BuiltPlan.metadata``) cross-checks the stored
    ParallelPlan; a mismatch raises ``PlanMismatchError`` unless
    ``adapt_plan=True``."""
    directory = pathlib.Path(directory)
    step = step if step is not None else latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoints in {directory}")
    path = directory / f"step_{step:010d}"
    manifest = json.loads((path / "manifest.json").read_text())
    check_plan_meta(manifest.get("meta"), expect_meta, adapt=adapt_plan)
    data = np.load(path / "arrays.npz")
    names, leaves, treedef = _flatten_with_names(tree_like)
    if names != manifest["names"]:
        raise ValueError("checkpoint structure mismatch: "
                         f"{set(names) ^ set(manifest['names'])}")
    out = []
    shard_flat = (jax.tree_util.tree_flatten(shardings)[0]
                  if shardings is not None else [None] * len(leaves))
    for i, (leaf, sh) in enumerate(zip(leaves, shard_flat)):
        arr = _decode(data[f"a{i}"], manifest["dtypes"][i],
                      tuple(manifest["shapes"][i]))
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out), step


class CheckpointManager:
    """Keep-N async checkpoint manager with preemption handling."""

    def __init__(self, directory, *, keep: int = 3, async_save: bool = True,
                 install_sigterm: bool = False,
                 plan_meta: Optional[dict] = None, obs=None):
        self.directory = pathlib.Path(directory)
        self.keep = keep
        self.async_save = async_save
        # BuiltPlan.metadata() of the run writing/reading these checkpoints:
        # stamped into every save, cross-checked on every restore
        self.plan_meta = plan_meta
        # obs MetricRegistry (DESIGN.md §14): save/restore timings land in
        # ckpt/* series — the snapshot cost on the training thread and the
        # serialization cost on the worker are separate observables
        self.obs = obs
        self._thread: Optional[threading.Thread] = None
        self._last_state = None
        self._lock = threading.Lock()
        if install_sigterm:
            signal.signal(signal.SIGTERM, self._on_sigterm)

    def _on_sigterm(self, signum, frame):  # pragma: no cover - signal path
        with self._lock:
            if self._last_state is not None:
                step, tree = self._last_state
                save_checkpoint(self.directory, step, tree,
                                meta=self.plan_meta)
        raise SystemExit(143)

    def _record(self, name: str, dt: float, step: int):
        if self.obs is not None:
            self.obs.record(name, dt, step=step)

    def save(self, step: int, tree):
        # snapshot to host memory NOW (donated buffers may be reused)
        t0 = time.perf_counter()
        host_tree = jax.tree_util.tree_map(np.asarray, tree)
        self._record("ckpt/snapshot_s", time.perf_counter() - t0, step)
        with self._lock:
            self._last_state = (step, host_tree)
        if self.async_save:
            self.wait()
            self._thread = threading.Thread(
                target=self._save_and_gc, args=(step, host_tree), daemon=True)
            self._thread.start()
        else:
            self._save_and_gc(step, host_tree)

    def _save_and_gc(self, step, tree):
        t0 = time.perf_counter()
        save_checkpoint(self.directory, step, tree, meta=self.plan_meta)
        steps = sorted(int(m.group(1)) for p in self.directory.iterdir()
                       if (m := re.fullmatch(r"step_(\d+)", p.name)))
        for s in steps[:-self.keep]:
            shutil.rmtree(self.directory / f"step_{s:010d}", ignore_errors=True)
        self._record("ckpt/save_s", time.perf_counter() - t0, step)

    def wait(self):
        if self._thread is not None and self._thread.is_alive():
            self._thread.join()

    def restore_latest(self, tree_like, shardings=None, *,
                       adapt_plan: bool = False):
        t0 = time.perf_counter()
        out = restore_checkpoint(self.directory, tree_like,
                                 shardings=shardings,
                                 expect_meta=self.plan_meta,
                                 adapt_plan=adapt_plan)
        self._record("ckpt/restore_s", time.perf_counter() - t0, out[1])
        return out


class StepWatchdog:
    """Straggler/hang detection for synchronous SPMD training.

    Tracks an EMA of step wall-time; flags steps slower than
    ``threshold x EMA`` and calls ``on_straggler`` (e.g. log, mark host,
    request checkpoint+restart with a shrunk mesh — the elastic restore
    path).  On real pods this runs per-host; the coordinator aggregates.
    """

    def __init__(self, *, threshold: float = 2.0, decay: float = 0.9,
                 on_straggler: Optional[Callable[[int, float, float], Any]] = None):
        self.threshold = threshold
        self.decay = decay
        self.ema: Optional[float] = None
        self.last_s = 0.0           # the last step's own seconds
        self.flagged: list[tuple[int, float]] = []
        self.on_straggler = on_straggler
        self._t0: Optional[float] = None

    def start_step(self):
        self._t0 = time.perf_counter()

    def end_step(self, step: int) -> bool:
        dt = self.last_s = time.perf_counter() - self._t0
        is_straggler = False
        if self.ema is not None and dt > self.threshold * self.ema:
            is_straggler = True
            self.flagged.append((step, dt))
            if self.on_straggler:
                self.on_straggler(step, dt, self.ema)
            # do not poison the EMA with the outlier
        else:
            self.ema = dt if self.ema is None else (
                self.decay * self.ema + (1 - self.decay) * dt)
        return is_straggler
