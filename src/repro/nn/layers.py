"""Basic layers as pure functions over parameter pytrees.

Conventions
-----------
* ``*_init(key, ...) -> params`` builds a (nested) dict of ``jnp.ndarray``.
* The matching apply function takes ``(params, x, ...)``.
* Parameters are stored in ``param_dtype`` (fp32 master copies by default) and
  cast to ``compute_dtype`` at use via :class:`Policy` — the paper's AMP recipe
  (fp32 params, bf16 intermediate activations).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

Params = Any  # nested dict pytree of arrays


@dataclasses.dataclass(frozen=True)
class Policy:
    """Mixed-precision policy (paper §5.1: fp32 params, bf16 activations)."""

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16

    def cast(self, params: Params) -> Params:
        """Cast floating-point leaves to the compute dtype."""
        def _c(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(self.compute_dtype)
            return x
        return jax.tree_util.tree_map(_c, params)


F32 = Policy(compute_dtype=jnp.float32)
BF16 = Policy()


def randomize(params: Params, key, scale: float = 0.02) -> Params:
    """Add ``scale`` x N(0, 1) noise to every leaf.  AF2 zero-inits its
    residual outputs, so at init most of a block is invisible to a
    comparison of two implementations; this makes it visible."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + scale * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# Linear / dense
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, *, use_bias: bool = True,
               scale: float | str = 1.0, dtype=jnp.float32) -> Params:
    """Lecun-normal (fan-in) dense init; ``scale='zeros'`` for AF2 final layers."""
    if scale == "zeros":
        w = jnp.zeros((in_dim, out_dim), dtype)
    else:
        std = float(scale) / (in_dim ** 0.5)
        w = std * jax.random.truncated_normal(key, -2.0, 2.0, (in_dim, out_dim)).astype(dtype)
    p = {"w": w}
    if use_bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def dense(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ params["w"]
    if "b" in params:
        b = params["b"]
        # add in f32: the bias gradient is then summed over every leading
        # row in f32, not in the compute dtype — a bf16 sum of that many
        # terms cancels badly, and differs with how the rows are split
        # across devices (DAP shards them)
        out_dtype = jnp.result_type(y.dtype, b.dtype)
        y = (y.astype(jnp.float32) + b.astype(jnp.float32)).astype(out_dtype)
    return y


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def layernorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


# §Perf H3 iteration 2 (AF2 is LayerNorm-bandwidth-bound): statistics stay
# fp32 (a reduction — numerically critical) but the normalized output is
# produced in the compute dtype directly, saving one fp32 round-trip of the
# full activation per LN.  Static at trace time; default faithful (fp32 io).
LN_FP32_IO = True


def set_ln_fp32_io(value: bool) -> None:
    global LN_FP32_IO
    LN_FP32_IO = value


def layernorm(params: Params, x: jnp.ndarray, *, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    if LN_FP32_IO:
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + \
            params["bias"].astype(jnp.float32)
        return y.astype(dt)
    inv = jax.lax.rsqrt(var + eps).astype(dt)
    y = (x - mu.astype(dt)) * inv
    return y * params["scale"].astype(dt) + params["bias"].astype(dt)


def rmsnorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, *, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    y = y * params["scale"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, dim: int, *, dtype=jnp.float32) -> Params:
    return {"table": jax.random.normal(key, (vocab, dim)).astype(dtype) * (dim ** -0.5)}


def embedding_lookup(params: Params, ids: jnp.ndarray, *, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    return params["table"].astype(compute_dtype)[ids]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, dim: int, hidden: int, *, use_bias: bool = False,
                dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, dim, hidden, use_bias=use_bias, dtype=dtype),
        "w_up": dense_init(k2, dim, hidden, use_bias=use_bias, dtype=dtype),
        "w_down": dense_init(k3, hidden, dim, use_bias=use_bias, dtype=dtype),
    }


def swiglu(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return dense(params["w_down"], jax.nn.silu(dense(params["w_gate"], x)) * dense(params["w_up"], x))


def gelu_mlp_init(key, dim: int, hidden: int, *, out_dim: int | None = None,
                  use_bias: bool = True, dtype=jnp.float32,
                  final_scale: float | str = 1.0) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "w_in": dense_init(k1, dim, hidden, use_bias=use_bias, dtype=dtype),
        "w_out": dense_init(k2, hidden, out_dim or dim, use_bias=use_bias,
                            dtype=dtype, scale=final_scale),
    }


def gelu_mlp(params: Params, x: jnp.ndarray,
             act: Callable[[jnp.ndarray], jnp.ndarray] = jax.nn.gelu) -> jnp.ndarray:
    return dense(params["w_out"], act(dense(params["w_in"], x)))


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def split_keys(key, n: int):
    return list(jax.random.split(key, n))
