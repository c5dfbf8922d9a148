"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: busy and idle time per TPU, the time a collective ran with no
compute beside it, the device operations that took most time, and the
longest idle gaps named by what the host was doing.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, without the control-flow ops that span them
(``CONTAINERS``); an event's name is the HLO instruction text
(``%name = <shape> <opcode>(...)``). Host events are the ``/host:CPU``
plane's. The window is the host event named ``WINDOW_EVENT``, which the
driver wraps round the traced steps; without it, the span of the device
operations.
"""
from __future__ import annotations

import collections
import glob
import os

WINDOW_EVENT = "bench_window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
# control flow: their events span the ops of their bodies, which the line
# also holds, so they are neither busy time of their own nor compute
CONTAINERS = ("while", "conditional", "call")
TOP = 10


def parse_op(text: str) -> tuple[str, str]:
    """``(instruction name, opcode)`` of an HLO instruction's text."""
    lhs, sep, rhs = text.partition(" = ")
    name = lhs.strip().lstrip("%")
    if not sep:
        return name, ""
    i = 0
    if rhs.startswith("("):                      # tuple shape: skip it
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.find(" ")
    rest = rhs[i:].lstrip()
    return name, rest.split("(", 1)[0].strip()


def is_collective(name: str, opcode: str) -> bool:
    return any(opcode.startswith(c) or name.startswith(c)
               for c in COLLECTIVES)


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def minus(a, b) -> float:
    """Measure of ``a`` outside ``b`` (both merged)."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _host_events(xspace):
    out = []
    for plane in xspace.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def _host_activity(host, lo, hi):
    """Name of what the host was doing in ``[lo, hi)``: the shortest host
    event covering at least half of it, else the one overlapping most."""
    best, best_cover = None, None
    gap = hi - lo
    for s, e, name in host:
        if name == WINDOW_EVENT:
            continue
        ov = min(e, hi) - max(s, lo)
        if ov <= 0:
            continue
        if ov >= 0.5 * gap:
            if best_cover is None or e - s < best_cover[0]:
                best_cover = (e - s, name)
        elif best is None or ov > best[0]:
            best = (ov, name)
    if best_cover is not None:
        return best_cover[1]
    return best[1] if best is not None else "(no host event)"


def reduce_xspace(xspace) -> dict:
    """Everything the per-layer metrics and the breakdown read; times in
    seconds. ``devices`` maps each TPU plane to its busy, collective and
    exposed-collective seconds inside the window."""
    host = _host_events(xspace)
    planes = {}
    for plane in xspace.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                planes[plane.name] = [(ev.start_ns, ev.start_ns +
                                       ev.duration_ns, ev.name)
                                      for ev in line.events]
    if not planes or not any(planes.values()):
        return {"devices": {}}
    marks = [(s, e) for s, e, name in host if name == WINDOW_EVENT]
    if marks:
        lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    else:
        lo = min(s for ops in planes.values() for s, _, _ in ops)
        hi = max(e for ops in planes.values() for _, e, _ in ops)
    devices, op_time, gaps, parsed = {}, collections.Counter(), [], {}
    for pname in sorted(planes):
        coll, comp, ops = [], [], []
        for s, e, text in planes[pname]:
            if text not in parsed:          # loop bodies repeat each op
                parsed[text] = parse_op(text)
            name, opcode = parsed[text]
            if opcode in CONTAINERS:
                continue
            ops.append((s, e))
            (coll if is_collective(name, opcode) else comp).append((s, e))
            op_time[name] += (min(e, hi) - max(s, lo)) if e > lo and s < hi \
                else 0.0
        busy = clip(union(ops), lo, hi)
        coll_u = clip(union(coll), lo, hi)
        devices[pname] = {
            "busy_s": measure(busy) * 1e-9,
            "collective_s": measure(coll_u) * 1e-9,
            "exposed_collective_s": minus(coll_u, clip(union(comp), lo, hi))
            * 1e-9,
            "n_ops": len(ops),
        }
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    n_dev = len(devices)
    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": devices,
        "device_ops": [[name, t * 1e-9 / n_dev]
                       for name, t in op_time.most_common(TOP)],
        "idle_gaps": [[_host_activity(host, a, b), g * 1e-9]
                      for g, a, b in gaps[:TOP]],
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_xspace(ProfileData.from_file(path))


def find_trace(logdir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``logdir``."""
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(found)}")
    return found[0]
