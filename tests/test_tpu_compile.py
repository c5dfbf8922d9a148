"""Compile the Pallas kernels at AF2 model-1 widths for a described v5e.

Nothing runs: the TPU compiler that ships with jaxlib lowers each kernel
for a chip that is described, not attached.  That catches what the
interpret-mode tests cannot — block shapes Mosaic refuses, and kernels
that need more VMEM than they declare.  The topology is described inside
a fixture (never at import), so test collection is the same in every
pytest-xdist worker and only the worker that runs this file loads libtpu.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fk
from repro.kernels import triangle as tk

# (lead rows, sequence, heads, head channels, biased) — af2_initial:
# MSA row attention (s 128, r 256, 8 heads, pair bias) and triangle
# attention (r 256, 4 heads, pair bias)
ATTENTION_SHAPES = {
    "msa_row": (128, 256, 8, 32),
    "triangle": (256, 256, 4, 32),
}
C_Z = C_MUL = 128
R = 256
# bf16 is what training feeds the kernels; f32 inputs contract at HIGHEST
# precision with smaller tiles (chip_smoke.py checks both on the chip)
DTYPES = [jnp.bfloat16, jnp.float32]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no libtpu"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo      # Mosaic kernel, not the interpreter
    return hlo


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", sorted(ATTENTION_SHAPES))
def test_evo_attention_fwd_with_residuals_compiles(one_chip, name, dtype):
    L, s, h, c = ATTENTION_SHAPES[name]
    x = _spec(one_chip, (L, s, h, c), dtype)
    bias = _spec(one_chip, (h, s, s), dtype)
    _compile(lambda q, k, v, b, g: fk.evo_attention_fwd(
        q, k, v, b, g, interpret=False, return_residuals=True),
        x, x, x, bias, x)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", sorted(ATTENTION_SHAPES))
def test_evo_attention_bwd_compiles(one_chip, name, dtype):
    L, s, h, c = ATTENTION_SHAPES[name]
    x = _spec(one_chip, (L, s, h, c), dtype)
    bias = _spec(one_chip, (h, s, s), dtype)
    lse = _spec(one_chip, (L * h, 1, s), jnp.float32)
    _compile(lambda q, k, v, b, g, o, lse, do: fk.evo_attention_bwd(
        q, k, v, b, g, o, lse, do, interpret=False),
        x, x, x, bias, x, x, lse, x)


def _tri_weights(sharding, dtype):
    c2 = 2 * C_MUL
    shapes = [(C_Z, c2), (c2,), (C_Z, c2), (c2,), (C_MUL,), (C_MUL,),
              (C_MUL, C_Z), (C_Z,), (C_Z, C_Z), (C_Z,)]
    return [_spec(sharding, s, dtype) for s in shapes]


@pytest.mark.parametrize("dtype", DTYPES)
def test_triangle_mult_fwd_compiles(one_chip, dtype):
    x = _spec(one_chip, (R, R, C_Z), dtype)
    _compile(lambda *a: tk.triangle_mult_fwd(
        *a, interpret=False, return_residuals=True),
        x, x, x, *_tri_weights(one_chip, dtype))


@pytest.mark.parametrize("dtype", DTYPES)
def test_triangle_mult_bwd_epilogue_compiles(one_chip, dtype):
    s = _spec(one_chip, (R, R, C_MUL), jnp.float32)
    x = _spec(one_chip, (R, R, C_Z), dtype)
    _compile(lambda s, xg, dy, *w: tk.triangle_mult_bwd_epilogue(
        s, xg, dy, *w, interpret=False),
        s, x, x, *_tri_weights(one_chip, dtype)[4:])


@pytest.mark.parametrize("dtype", DTYPES)
def test_triangle_mult_bwd_dx_compiles(one_chip, dtype):
    ds = _spec(one_chip, (R, R, C_MUL), jnp.float32)
    x = _spec(one_chip, (R, R, C_Z), dtype)
    _compile(lambda ds, xl, xs, *w: tk.triangle_mult_bwd_dx(
        ds, xl, xs, *w, interpret=False),
        ds, x, x, *_tri_weights(one_chip, dtype)[:4])
