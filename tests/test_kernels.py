"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

FA_CASES = [
    # (b, s, t, h, kv, d, causal, dtype, tol)
    (1, 128, 128, 4, 2, 64, True, jnp.float32, 2e-4),
    (2, 256, 256, 4, 4, 32, True, jnp.float32, 2e-4),
    (1, 128, 128, 2, 1, 128, False, jnp.float32, 2e-4),
    (1, 128, 128, 4, 2, 64, True, jnp.bfloat16, 3e-2),
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_vs_ref(case):
    b, s, t, h, kv, d, causal, dtype, tol = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kv, d)).astype(dtype)
    out = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal))(q, k, v)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_grads():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    g1 = jax.grad(lambda q: ops.flash_attention(q, k, v, True).sum())(q)
    g2 = jax.grad(lambda q: ref.flash_attention_ref(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-3)


EVO_CASES = [
    (8, 128, 4, 32, jnp.float32, 2e-4),
    (4, 256, 2, 16, jnp.float32, 2e-4),
    (2, 128, 8, 64, jnp.bfloat16, 3e-2),
]


@pytest.mark.parametrize("case", EVO_CASES)
def test_evo_attention_vs_ref(case):
    L, s, h, c, dtype, tol = case
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (L, s, h, c)).astype(dtype)
    k = jax.random.normal(ks[1], (L, s, h, c)).astype(dtype)
    v = jax.random.normal(ks[2], (L, s, h, c)).astype(dtype)
    bias = jax.random.normal(ks[3], (h, s, s)).astype(dtype)
    gate = jax.random.normal(ks[4], (L, s, h, c)).astype(dtype)
    out = jax.jit(ops.evo_attention)(q, k, v, bias, gate)
    expect = ref.evo_attention_ref(q, k, v, bias, gate)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_evo_attention_bias_grad():
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    L, s, h, c = 4, 128, 2, 32
    q, k, v, gate = (jax.random.normal(kk, (L, s, h, c)) for kk in ks[:4])
    bias = jax.random.normal(ks[4], (h, s, s))
    g1 = jax.grad(lambda b: ops.evo_attention(q, k, v, b, gate).sum())(bias)
    g2 = jax.grad(lambda b: ref.evo_attention_ref(q, k, v, b, gate).sum())(bias)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-3)


def _chunked_vjp_evo(q, k, v, bias, gate):
    """The old fallback VJP path: chunked-XLA attention + external gating."""
    from repro.nn.attention import attention_chunked
    o = attention_chunked(q, k, v, bias=bias, chunk_size=32)
    return jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype) * o


def test_evo_flash_backward_matches_chunked_vjp():
    """All five gradients (q/k/v/bias/gate) from the Pallas flash backward
    kernels vs the chunked-XLA VJP, on MXU-aligned shapes."""
    L, s, h, c = 4, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q, k, v, gate = (jax.random.normal(kk, (L, s, h, c)) for kk in ks[:4])
    bias = jax.random.normal(ks[4], (h, s, s))
    w = jnp.cos(jnp.arange(c))  # non-uniform cotangent

    def loss(fn):
        return lambda *args: (fn(*args) * w).sum()

    g_flash = jax.jit(jax.grad(loss(ops.evo_attention),
                               argnums=(0, 1, 2, 3, 4)))(q, k, v, bias, gate)
    g_ref = jax.grad(loss(_chunked_vjp_evo),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, bias, gate)
    for name, a, b in zip("q k v bias gate".split(), g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=f"d{name}")


def test_evo_flash_backward_nogate():
    from repro.nn.attention import attention_reference
    L, s, h, c = 2, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    q, k, v = (jax.random.normal(kk, (L, s, h, c)) for kk in ks[:3])
    bias = jax.random.normal(ks[3], (h, s, s))
    g1 = jax.jit(jax.grad(lambda q, k, v, b: ops.evo_attention_nogate(
        q, k, v, b).sum(), argnums=(0, 1, 2, 3)))(q, k, v, bias)
    g2 = jax.grad(lambda q, k, v, b: attention_reference(
        q, k, v, bias=b).sum(), argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_evo_attention_nobias_gated():
    """Gated attention with the bias add compiled out (MSA column attention
    under evo_pallas): fwd + all gradients vs the gated reference."""
    from repro.nn.attention import attention_reference
    L, s, h, c = 2, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(15), 4)
    q, k, v, gate = (jax.random.normal(kk, (L, s, h, c)) for kk in ks)

    def gated_ref(q, k, v, gate):
        o = attention_reference(q, k, v)
        return jax.nn.sigmoid(gate) * o

    out = jax.jit(ops.evo_attention_nobias)(q, k, v, gate)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gated_ref(q, k, v, gate)),
                               rtol=2e-5, atol=2e-5)
    g1 = jax.jit(jax.grad(lambda *a: ops.evo_attention_nobias(*a).sum(),
                          argnums=(0, 1, 2, 3)))(q, k, v, gate)
    g2 = jax.grad(lambda *a: gated_ref(*a).sum(),
                  argnums=(0, 1, 2, 3))(q, k, v, gate)
    for name, a, b in zip("q k v gate".split(), g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=f"d{name}")


def test_evo_block_size_always_divides():
    """Regression: a non-power-of-two block request must degrade to a valid
    divisor, never to a grid that under-covers the sequence (NaN rows)."""
    from repro.kernels.flash_attention import evo_block_size, evo_attention_fwd
    for s in (8, 12, 96, 128, 250, 384):
        for cap in (1, 7, 32, 96, 128):
            b = evo_block_size(s, cap)
            assert s % b == 0 and 1 <= b <= max(cap, 1), (s, cap, b)
    ks = jax.random.split(jax.random.PRNGKey(16), 5)
    L, s, h, c = 2, 128, 2, 16
    q, k, v, gate = (jax.random.normal(kk, (L, s, h, c)) for kk in ks[:4])
    bias = jax.random.normal(ks[4], (h, s, s))
    a = evo_attention_fwd(q, k, v, bias, gate, block_q=96, block_k=96,
                          interpret=True)
    b = evo_attention_fwd(q, k, v, bias, gate, interpret=True)
    assert np.isfinite(np.asarray(a)).all()
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


def test_evo_vjp_no_longer_calls_attention_chunked(monkeypatch):
    """The evo_attention VJP must be flash-native: poisoning the chunked-XLA
    path must not affect it (while flash_attention's LM bwd still uses it)."""
    def boom(*a, **kw):
        raise AssertionError("evo_attention VJP called attention_chunked")

    monkeypatch.setattr(ops, "attention_chunked", boom)
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    L, s, h, c = 2, 64, 2, 16
    q, k, v, gate = (jax.random.normal(kk, (L, s, h, c)) for kk in ks[:4])
    bias = jax.random.normal(ks[4], (h, s, s))
    g = jax.grad(lambda q: ops.evo_attention(q, k, v, bias, gate).sum())(q)
    assert np.isfinite(np.asarray(g)).all()
    gn = jax.grad(lambda q: ops.evo_attention_nogate(q, k, v, bias).sum())(q)
    assert np.isfinite(np.asarray(gn)).all()


def test_evo_fwd_residuals_lse():
    """Residual-mode forward must agree with the plain forward and emit the
    correct per-row log-sum-exp."""
    from repro.kernels import flash_attention as fk
    L, s, h, c = 2, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(14), 5)
    q, k, v, gate = (jax.random.normal(kk, (L, s, h, c)) for kk in ks[:4])
    bias = jax.random.normal(ks[4], (h, s, s))
    out0 = fk.evo_attention_fwd(q, k, v, bias, gate, interpret=True)
    out1, lse = fk.evo_attention_fwd(q, k, v, bias, gate, interpret=True,
                                     return_residuals=True)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1))
    scale = c ** -0.5
    logits = (jnp.einsum("lshc,lthc->lhst", q, k) * scale +
              bias[None]).astype(jnp.float32)
    lse_ref = jax.scipy.special.logsumexp(logits, axis=-1)   # (L, h, s)
    np.testing.assert_allclose(np.asarray(lse.reshape(L, h, s)),
                               np.asarray(lse_ref), rtol=1e-5, atol=1e-5)


def test_kernel_blocking_invariance():
    """Output must not depend on block sizes (pure tiling parameter)."""
    from repro.kernels.flash_attention import flash_attention_fwd
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    a = flash_attention_fwd(q, k, v, causal=True, block_q=128, block_k=128,
                            interpret=True)
    b = flash_attention_fwd(q, k, v, causal=True, block_q=64, block_k=32,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
