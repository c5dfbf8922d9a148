"""Shared jaxpr traversal (DESIGN.md §15).

Every jaxpr-level analysis pass — and the jaxpr assertions in the test
suite — walks programs through these utilities, so "recurse into scan /
while / cond / pjit / shard_map bodies" is implemented exactly once.
``tests/util.py``'s ``max_eqn_elems`` / ``count_prims`` delegate here.
"""
from __future__ import annotations

from collections import Counter
from typing import Iterable, Iterator, Tuple

import numpy as np


def _subjaxpr_items(eqn):
    """(kind_name, core.Jaxpr) pairs hiding inside an eqn's params."""
    from jax.extend import core
    for key, val in eqn.params.items():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for it in items:
            if isinstance(it, core.ClosedJaxpr):
                yield key, it.jaxpr
            elif isinstance(it, core.Jaxpr):
                yield key, it


def iter_eqns(closed_jaxpr, *, path: str = "") -> Iterator[Tuple[object, str]]:
    """Yield ``(eqn, path)`` for every eqn, recursing into sub-jaxprs
    (scan/while/cond/pjit/shard_map/remat bodies).  ``path`` is a
    '/'-joined trail of the enclosing call primitives, e.g.
    ``"shard_map/scan/pjit"`` — enough to say *where* a finding lives."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def walk(jaxpr, path):
        for eqn in jaxpr.eqns:
            yield eqn, path
            sub_path = f"{path}/{eqn.primitive.name}" if path \
                else eqn.primitive.name
            for _, sub in _subjaxpr_items(eqn):
                yield from walk(sub, sub_path)

    yield from walk(jaxpr, path)


def iter_out_avals(closed_jaxpr) -> Iterator[Tuple[object, object, str]]:
    """``(aval, eqn, path)`` for every eqn output, recursing."""
    for eqn, path in iter_eqns(closed_jaxpr):
        for var in eqn.outvars:
            yield var.aval, eqn, path


def aval_elems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape)) if shape else 1


def peak_eqn_elems(closed_jaxpr) -> int:
    """Largest eqn-output aval, in elements (the jaxpr-level proxy for peak
    intermediate memory used by the fusion/materialization guarantees)."""
    return max((aval_elems(a) for a, _, _ in iter_out_avals(closed_jaxpr)
                if getattr(a, "shape", None) is not None), default=0)


def count_primitives(closed_jaxpr, names: Iterable[str]) -> dict:
    """Occurrences of each primitive name, recursing into sub-jaxprs."""
    names = set(names)
    counts = Counter({n: 0 for n in names})
    for eqn, _ in iter_eqns(closed_jaxpr):
        if eqn.primitive.name in names:
            counts[eqn.primitive.name] += 1
    return dict(counts)


# ---------------------------------------------------------------------------
# Collective accounting per mesh axis
# ---------------------------------------------------------------------------

COLLECTIVE_PRIMS = ("psum", "all_gather", "all_to_all", "psum_scatter",
                    "reduce_scatter", "ppermute", "pmax", "pmin")


def eqn_axis_names(eqn) -> tuple:
    """Mesh axis names a collective eqn reduces/gathers over (named axes
    only; positional ints are dropped)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def collective_axis_counts(closed_jaxpr) -> Counter:
    """``Counter[(prim_name, axis_name)]`` over the whole program — the raw
    material of the gradient-completion audit (one eqn over several axes
    counts once per axis)."""
    counts: Counter = Counter()
    for eqn, _ in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMS:
            continue
        for axis in eqn_axis_names(eqn):
            counts[(name, axis)] += 1
    return counts
