"""Fold a profiler trace (``.xplane.pb``) by the program's named scopes,
and split each chip's idle time by the host's ``step`` spans.

The program wraps each part of its train step in a ``jax.named_scope``
(``SCOPES``: the Evoformer's sub-ops, the two stacks, the structure
module, the loss, the optimizer, ...). XLA keeps the scope path in each
instruction's ``op_name``; the profiler stores it as the ``tf_op`` stat of
the instruction's event metadata on each TPU plane. ``ProfileData``
exposes only an event's own stats, so ``op_names`` reads that metadata
from the protobuf's wire format itself.

A scope is a segment of the ``tf_op`` path, with the transformations
round it taken off (``transpose(jvp(msa_row_attn))`` is ``msa_row_attn``).
An op that no scope covers is ``(unscoped)``.
An op counts toward every scope on its path; it is backward when the path
holds ``transpose(``, forward otherwise (recomputation under a transpose
counts as backward). Busy time and the window are ``trace_reduce``'s, so
``idle_in_step_s + idle_between_steps_s`` is the window less its busy
time. The host's ``step`` spans are the program's
``jax.profiler.StepTraceAnnotation`` round each training step (its
dispatch, the device's run and the read of its loss); the rest of the
window is the loop between steps (waiting for input, bookkeeping).

    python3 bench/trace_scopes.py <trace.xplane.pb or its directory>

prints the reduction as JSON.
"""
from __future__ import annotations

import json
import os
import re
import sys

if __package__ in (None, ""):       # run as a script from the checkout
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as tr  # noqa: E402

SUB_OPS = ("msa_row_attn", "msa_col_attn", "msa_transition", "opm",
           "tri_mult_out", "tri_mult_in", "tri_attn_start", "tri_attn_end",
           "pair_transition")
SCOPES = SUB_OPS + ("extra_stack", "evoformer", "embed", "recycle",
                    "structure", "loss", "clip", "grad_sync", "optimizer",
                    "ema", "bp_exchange")
UNSCOPED = "(unscoped)"
STEP_EVENT = "step"
_SEGMENT = re.compile(r"^((?:[\w.\-]+\()*)([^()]*)\)*$")


# -- the protobuf wire format ------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i=0, end=None):
    """``(field number, value)`` of a message: an int for a varint, a
    ``(start, end)`` slice for a length-delimited field; fixed-width
    fields are skipped."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif kind == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif kind == 1:
            i += 8
        elif kind == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, span):
    """``(key, value slice)`` of one protobuf map entry."""
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_op_names(buf, span, stat_name: str) -> tuple[str, dict]:
    """``(plane name, {event name: value of stat_name})`` of one XPlane."""
    name, events, stat_ids = "", [], {}
    for f, v in _fields(buf, *span):
        if f == 2:                                  # XPlane.name
            name = _text(buf, v)
        elif f == 4:                                # event_metadata map
            events.append(_map_entries(buf, v)[1])
        elif f == 5:                                # stat_metadata map
            sid, meta = _map_entries(buf, v)
            for g, w in _fields(buf, *meta):
                if g == 2:                          # XStatMetadata.name
                    stat_ids[sid] = _text(buf, w)
    if not name.startswith("/device:TPU:"):
        return name, {}
    want = {sid for sid, s in stat_ids.items() if s == stat_name}
    out = {}
    for meta in events:
        ev_name, value = "", None
        for f, v in _fields(buf, *meta):
            if f == 2:                              # XEventMetadata.name
                ev_name = _text(buf, v)
            elif f == 5:                            # XEventMetadata.stats
                sid, val = None, None
                for g, w in _fields(buf, *v):
                    if g == 1:
                        sid = w
                    elif g == 5:                    # str_value
                        val = _text(buf, w)
                    elif g == 7:                    # ref_value: a stat name
                        val = stat_ids.get(w)
                if sid in want and val is not None:
                    value = val.rpartition(":")[0] if ":" in val else val
        if value is not None:
            out[ev_name] = value
    return name, out


def op_names(data: bytes, stat_name: str = "tf_op") -> dict:
    """``{TPU plane name: {event name: tf_op}}`` from a serialized
    XSpace: each XLA op's ``op_name``, as its event metadata holds it."""
    buf = memoryview(data)
    out = {}
    for f, v in _fields(buf):
        if f == 1:                                  # XSpace.planes
            name, names = _plane_op_names(buf, v, stat_name)
            if name.startswith("/device:TPU:"):
                out[name] = names
    return out


# -- scopes and idle ---------------------------------------------------------

def scopes_of(tf_op: str) -> set:
    """The program scopes on an op's path. A segment that a ``jit`` wraps
    names a function, not a scope (``jnp.clip`` is ``jit(clip)``)."""
    out = set()
    for seg in tf_op.split("/"):
        m = _SEGMENT.match(seg)
        if (m and m.group(2) in SCOPES
                and not m.group(1).endswith(("jit(", "pjit("))):
            out.add(m.group(2))
    return out


def is_backward(tf_op: str) -> bool:
    return "transpose(" in tf_op


def fold(ops, names: dict, lo: float, hi: float) -> dict:
    """Seconds of device time in ``[lo, hi)`` per scope, forward and
    backward. ``ops`` are ``(start_ns, end_ns, event name)``; control-flow
    ops (``trace_reduce.CONTAINERS``) are left out, as in busy time."""
    out, parsed = {}, {}
    for s, e, text in ops:
        t = min(e, hi) - max(s, lo)
        if t <= 0:
            continue
        if text not in parsed:
            name, opcode = tr.parse_op(text)
            tf_op = names.get(text, names.get(name, ""))
            parsed[text] = (None if opcode in tr.CONTAINERS else
                            (scopes_of(tf_op) or {UNSCOPED},
                             "bwd_s" if is_backward(tf_op) else "fwd_s"))
        if parsed[text] is None:
            continue
        scopes, way = parsed[text]
        for sc in scopes:
            row = out.setdefault(sc, {"fwd_s": 0.0, "bwd_s": 0.0})
            row[way] += t * 1e-9
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi)`` outside ``busy`` (merged, inside it)."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def split_idle(busy, steps, lo: float, hi: float) -> tuple[float, float]:
    """Seconds of ``[lo, hi)`` outside ``busy`` (merged intervals), inside
    and outside ``steps`` (merged intervals)."""
    idle = gaps(busy, lo, hi)
    inside = sum(tr.measure(tr.clip(steps, a, b)) for a, b in idle)
    return inside * 1e-9, (tr.measure(idle) - inside) * 1e-9


def reduce_xspace(xspace, names_by_plane: dict) -> dict:
    """Per TPU plane: device seconds per scope and the idle split; the
    host ``step`` spans in the window with their step numbers; the longest
    idle gaps inside steps, each with its step number and what the host
    was doing."""
    host = tr._host_events(xspace)
    step_spans = []
    for plane in xspace.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == STEP_EVENT and ev.duration_ns > 0:
                    step_spans.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns,
                                       dict(ev.stats).get("step_num")))
    planes = {}
    for plane in xspace.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    planes[plane.name] = [(ev.start_ns, ev.start_ns +
                                           ev.duration_ns, ev.name)
                                          for ev in line.events]
    if not planes or not any(planes.values()):
        return {"planes": {}}
    marks = [(s, e) for s, e, name in host if name == tr.WINDOW_EVENT]
    if marks:
        lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    else:
        lo = min(s for ops in planes.values() for s, _, _ in ops)
        hi = max(e for ops in planes.values() for _, e, _ in ops)
    in_window = [(s, e, n) for s, e, n in step_spans if e > lo and s < hi]
    steps_u = tr.clip(tr.union([(s, e) for s, e, _ in in_window]), lo, hi)
    out, in_step = {}, []
    for pname in sorted(planes):
        ops = planes[pname]
        parsed = {text: tr.parse_op(text) for _, _, text in ops}
        busy = tr.clip(tr.union(
            [(s, e) for s, e, text in ops
             if parsed[text][1] not in tr.CONTAINERS]), lo, hi)
        idle_in, idle_out = split_idle(busy, steps_u, lo, hi)
        out[pname] = {"busy_s": tr.measure(busy) * 1e-9,
                      "idle_in_step_s": idle_in,
                      "idle_between_steps_s": idle_out,
                      "scopes": fold(ops, names_by_plane.get(pname, {}),
                                     lo, hi)}
        for a, b in gaps(busy, lo, hi):
            for s, e, num in in_window:
                if s < b and e > a:
                    in_step.append((min(b, e) - max(a, s), num, a, b))
    in_step.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) * 1e-9,
        "steps": sorted(n for _, _, n in in_window if n is not None),
        "planes": out,
        "idle_gaps_in_steps": [
            [num, g * 1e-9, tr._host_activity(host, a, b)]
            for g, num, a, b in in_step[:tr.TOP]],
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    return reduce_xspace(ProfileData.from_serialized_xspace(data),
                         op_names(data))


def per_protein_ms(red: dict, proteins: int) -> dict:
    """Every scope's device milliseconds per protein trained in the
    window, summed over the chips, forward and backward."""
    total = {}
    for p in red["planes"].values():
        for sc, row in p["scopes"].items():
            t = total.setdefault(sc, {"fwd_ms": 0.0, "bwd_ms": 0.0})
            t["fwd_ms"] += 1e3 * row["fwd_s"] / proteins
            t["bwd_ms"] += 1e3 * row["bwd_s"] / proteins
    return dict(sorted(total.items(),
                       key=lambda kv: -(kv[1]["fwd_ms"] + kv[1]["bwd_ms"])))


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="an .xplane.pb, or a directory holding one")
    ap.add_argument("--batch", type=int, default=1,
                    help="proteins a step trains (the global batch)")
    args = ap.parse_args(argv)
    path = (tr.find_trace(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    red = reduce_file(path)
    red["scopes_ms_per_protein"] = per_protein_ms(
        red, max(1, len(red.get("steps", []))) * args.batch)
    print(json.dumps(red))
    return 0


if __name__ == "__main__":
    sys.exit(main())
