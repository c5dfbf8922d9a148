"""Model weights made from the seed, in one jitted call on the device.

The benchmark, not the program, makes the weights, so that its reference
and the program train from the same ones. Leaves follow the AF2
initialisation (suppl. 1.11.4) in spirit, with noise on every leaf so that
no part of a block is invisible at the first step: layers that write into
a residual stream or a gate (AF2 initialises them to zero) get small
normal weights, gate biases start near 1 (open), LayerNorm scales near 1,
every other matrix is normal with fan-in scaling, clipped at two standard
deviations. All leaves are cut from one normal draw, so the program that
makes them stays small.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SALT = 0x5EED
NOISE = 0.02
SMALL_W = ("gate", "out", "a_gate", "b_gate", "w2", "w3", "backbone_update")
OPEN_GATES = ("gate", "a_gate", "b_gate")


def _names(path):
    return [getattr(k, "key", getattr(k, "name", str(k))) for k in path]


def _leaf(names, sd, z):
    """Leaf ``names[-1]`` of shape ``sd`` from standard normals ``z``."""
    last = names[-1]
    parent = names[-2] if len(names) > 1 else ""
    if last == "w" and parent not in SMALL_W:
        return jnp.clip(z, -2.0, 2.0) / math.sqrt(sd.shape[-2])
    if last == "scale" or (last == "b" and parent in OPEN_GATES):
        return 1.0 + NOISE * z
    return NOISE * z


def maker(shapes, out_shardings=None):
    """``make(seed) -> params`` for a pytree of ``ShapeDtypeStruct``s whose
    dict keys name the leaves; one jitted program."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sizes = [math.prod(sd.shape) for _, sd in flat]

    def make(seed):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), SALT)
        z = jax.random.normal(key, (sum(sizes),), jnp.float32)
        leaves, at = [], 0
        for (path, sd), n in zip(flat, sizes):
            leaf = _leaf(_names(path), sd, z[at:at + n].reshape(sd.shape))
            leaves.append(leaf.astype(sd.dtype))
            at += n
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(make, out_shardings=out_shardings)
    return lambda seed: fn(jnp.asarray(seed % (1 << 32), jnp.uint32))
