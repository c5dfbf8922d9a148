"""Roofline-vs-measured attribution: is the plan delivering its prediction?

``attribution_report`` confronts a measured per-step wall time with
``analysis.roofline.predict_step_time`` for the active ``ParallelPlan`` and
derives the run-health scalars the paper's analysis turns on:

* ``predicted_step_s`` vs ``measured_step_s`` (+ their ratio — >1 means the
  run is slower than the cost model that picked the plan; a drifting ratio
  is a regression with a location, not a vibe);
* ``achieved_flops`` — model-FLOP/s actually sustained;
* ``mfu`` — achieved / (n_devices x the peak of ``device_kind``, from
  ``analysis.roofline.DEVICE_PEAKS``); ``None`` on the host CPU, which
  has no peak to measure against;
* ``goodput`` — the fraction of wall time that is neither input stall nor
  eval/checkpoint overhead (the ScaleFold framing: time not spent training
  is the bottleneck inventory).

Everything here is plain arithmetic over floats — no jax, importable
anywhere (benchmarks, launchers, tests).
"""
from __future__ import annotations

from typing import Optional

from repro.analysis.roofline import HW, device_peaks, predict_step_time


def attribution_report(cfg, plan, *, device_kind: str, global_batch: int,
                       n_recycle: float, measured_step_s: float,
                       stall_fraction: float = 0.0,
                       overhead_s: float = 0.0,
                       wall_s: Optional[float] = None,
                       hw: HW = HW(), elt: int = 2,
                       step: Optional[int] = None) -> dict:
    """Build one attribution row (plain dict, JSON-ready).

    ``measured_step_s`` is the mean train-step wall time over the window
    being attributed; ``overhead_s``/``wall_s`` price eval + checkpoint
    time against total window wall time for goodput; ``stall_fraction`` is
    the DataPipeline input-stall share of that window.  ``hw`` prices the
    prediction (a model); ``device_kind`` names the device the time was
    measured on, whose published peak is the MFU denominator.
    """
    peaks = device_peaks(device_kind)
    pred = predict_step_time(
        cfg, bp=plan.branch, dap=plan.dap, pod=plan.pod, data=plan.data,
        global_batch=global_batch, n_recycle=n_recycle, hw=hw, elt=elt,
        overlap=getattr(plan, "overlap_dap", None))
    measured = float(measured_step_s)
    flops = pred["model_flops_per_step"]
    achieved = flops / measured if measured > 0 else 0.0
    n_dev = pred["n_devices"]
    mfu = (achieved / (n_dev * peaks.peak_flops)
           if peaks is not None and n_dev > 0 else None)
    overhead_frac = (overhead_s / wall_s) if wall_s and wall_s > 0 else 0.0
    goodput = max(0.0, 1.0 - float(stall_fraction) - overhead_frac)
    return {
        "step": step,
        "measured_step_s": measured,
        "predicted_step_s": pred["predicted_step_s"],
        "measured_over_predicted": (
            measured / pred["predicted_step_s"]
            if pred["predicted_step_s"] > 0 else float("inf")),
        "model_flops_per_step": flops,
        "achieved_flops": achieved,
        "mfu": mfu,
        "goodput": goodput,
        "stall_fraction": float(stall_fraction),
        "overhead_fraction": overhead_frac,
        "n_devices": n_dev,
        "device_kind": device_kind,
        "plan": plan.describe() if hasattr(plan, "describe") else str(plan),
        "global_batch": global_batch,
        "n_recycle": float(n_recycle),
    }


def describe_attribution(rep: dict) -> str:
    """One-line human rendering for launcher logs."""
    mfu = ("not measured" if rep["mfu"] is None
           else f"{rep['mfu'] * 100:.3f}%")
    return (f"attribution[step {rep.get('step')}]: "
            f"measured {rep['measured_step_s'] * 1e3:.1f} ms/step vs "
            f"predicted {rep['predicted_step_s'] * 1e3:.3f} ms "
            f"(x{rep['measured_over_predicted']:.1f}); "
            f"{rep['achieved_flops'] / 1e12:.4f} TFLOP/s achieved, "
            f"MFU {mfu} ({rep['device_kind']}), "
            f"goodput {rep['goodput'] * 100:.1f}%, "
            f"stall {rep['stall_fraction'] * 100:.1f}%")
