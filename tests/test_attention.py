"""Chunked flash-style attention vs naive reference (+ hypothesis sweep)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.nn.attention import (attention_chunked, attention_reference,
                                decode_attention)
from repro.nn.rope import apply_rope


CASES = [
    dict(lead=(2,), s=16, t=16, h=8, kv=8, d=32, causal=False, bias=False, cs=8),
    dict(lead=(2,), s=16, t=16, h=8, kv=2, d=32, causal=True, bias=False, cs=5),
    dict(lead=(1, 3), s=7, t=13, h=4, kv=4, d=16, causal=False, bias=True, cs=4),
    dict(lead=(2,), s=9, t=9, h=6, kv=2, d=8, causal=True, bias=True, cs=16),
]


@pytest.mark.parametrize("case", CASES)
def test_chunked_matches_reference(case):
    k0 = jax.random.PRNGKey(0)
    ks = jax.random.split(k0, 4)
    q = jax.random.normal(ks[0], (*case["lead"], case["s"], case["h"], case["d"]))
    k = jax.random.normal(ks[1], (*case["lead"], case["t"], case["kv"], case["d"]))
    v = jax.random.normal(ks[2], (*case["lead"], case["t"], case["kv"], case["d"]))
    bias = (jax.random.normal(ks[3], (case["h"], case["s"], case["t"]))
            if case["bias"] else None)
    ref = attention_reference(q, k, v, causal=case["causal"], bias=bias)
    chk = attention_chunked(q, k, v, causal=case["causal"], bias=bias,
                            chunk_size=case["cs"])
    np.testing.assert_allclose(np.asarray(ref), np.asarray(chk),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", CASES[:2])
def test_chunked_gradients_match(case):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (*case["lead"], case["s"], case["h"], case["d"]))
    k = jax.random.normal(ks[1], (*case["lead"], case["t"], case["kv"], case["d"]))
    v = jax.random.normal(ks[2], (*case["lead"], case["t"], case["kv"], case["d"]))
    g1 = jax.grad(lambda q: attention_reference(
        q, k, v, causal=case["causal"]).sum())(q)
    g2 = jax.grad(lambda q: attention_chunked(
        q, k, v, causal=case["causal"], chunk_size=case["cs"]).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=12, deadline=None)
@given(s=st.integers(1, 12), t=st.integers(1, 12),
       kv=st.sampled_from([1, 2]), g=st.sampled_from([1, 3]),
       d=st.sampled_from([4, 8]), cs=st.integers(1, 8),
       causal=st.booleans())
def test_chunked_property(s, t, kv, g, d, cs, causal):
    ks = jax.random.split(jax.random.PRNGKey(s * 100 + t), 3)
    q = jax.random.normal(ks[0], (s, kv * g, d))
    k = jax.random.normal(ks[1], (t, kv, d))
    v = jax.random.normal(ks[2], (t, kv, d))
    if causal and s > t:
        return  # undefined offsets in this harness
    ref = attention_reference(q, k, v, causal=causal,
                              q_offset=t - s if causal else 0)
    chk = attention_chunked(q, k, v, causal=causal, chunk_size=cs,
                            q_offset=t - s if causal else 0)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(chk),
                               rtol=3e-5, atol=3e-5)


def test_softmax_rows_sum_to_one_under_mask():
    # fully-masked rows must produce zeros, not NaN
    q = jnp.ones((4, 2, 8))
    k = jnp.ones((6, 2, 8))
    v = jnp.ones((6, 2, 8))
    mask = jnp.zeros((6,), bool)  # nothing visible
    out = attention_chunked(q, k, v, mask=mask, chunk_size=3)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_decode_matches_masked_reference():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q1 = jax.random.normal(ks[0], (3, 1, 4, 16))
    kc = jax.random.normal(ks[1], (3, 12, 2, 16))
    vc = jax.random.normal(ks[2], (3, 12, 2, 16))
    lengths = jnp.array([5, 12, 1])
    out = decode_attention(q1, kc, vc, lengths=lengths)
    for i, L in enumerate([5, 12, 1]):
        ref = attention_reference(q1[i:i+1], kc[i:i+1, :L], vc[i:i+1, :L])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   rtol=1e-5, atol=1e-5)


def test_pallas_impl_biased_noncausal_routes_to_evo_kernel():
    """Regression: ``attention(..., impl='pallas', bias=...)`` used to forward
    bias= to kops.flash_attention, which doesn't accept it (TypeError)."""
    from repro.nn.attention import attention
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    L, s, h, d = 2, 32, 2, 16
    q = jax.random.normal(ks[0], (L, s, h, d))
    k = jax.random.normal(ks[1], (L, s, h, d))
    v = jax.random.normal(ks[2], (L, s, h, d))
    bias = jax.random.normal(ks[3], (h, s, s))
    out = attention(q, k, v, impl="pallas", bias=bias)
    ref = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # and it is differentiable (flash backward, not a crash)
    g = jax.grad(lambda b: attention(q, k, v, impl="pallas", bias=b).sum())(bias)
    gr = jax.grad(lambda b: attention_reference(q, k, v, bias=b).sum())(bias)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-4, atol=1e-4)


def test_pallas_impl_default_is_noncausal():
    """Pin the dispatch default: impl='pallas' without causal= computes
    bidirectional attention, consistent with 'reference'/'chunked' (the old
    dispatch inherited kops.flash_attention's causal=True default)."""
    from repro.nn.attention import attention
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    out = attention(q, k, v, impl="pallas")
    ref = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pallas_impl_unsupported_combinations_raise_clearly():
    from repro.nn.attention import attention
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (2, 32, 2, 16))
    k = jax.random.normal(ks[1], (2, 32, 2, 16))
    v = jax.random.normal(ks[2], (2, 32, 2, 16))
    bias = jnp.zeros((2, 32, 32))
    with pytest.raises(ValueError, match="mask"):
        attention(q, k, v, impl="pallas", mask=jnp.ones((32,), bool))
    with pytest.raises(ValueError, match="causal"):
        attention(q, k, v, impl="pallas", bias=bias, causal=True)
    with pytest.raises(ValueError, match="q_offset"):
        attention(q, k, v, impl="pallas", causal=True, q_offset=4)
    with pytest.raises(ValueError, match="broadcastable"):
        attention(q, k, v, impl="pallas", bias=jnp.zeros((1, 1, 32)))


def test_chunked_bias_is_not_broadcast_upfront():
    """Regression: the bias used to be broadcast to the full
    (lead, h, s, t) fp32 tensor before chunking, defeating the memory
    saving.  No intermediate may reach that size."""
    lead, h, s, t, chunk = 16, 4, 32, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (lead, s, h, 8))
    k = jax.random.normal(ks[1], (lead, t, 1, 8))
    v = jax.random.normal(ks[2], (lead, t, 1, 8))
    bias = jax.random.normal(ks[3], (h, s, t))
    full_broadcast = lead * h * s * t
    from tests.util import max_eqn_elems
    jaxpr = jax.make_jaxpr(lambda q, k, v, b: attention_chunked(
        q, k, v, bias=b, chunk_size=chunk))(q, k, v, bias)
    biggest = max_eqn_elems(jaxpr)
    assert biggest < full_broadcast, (
        f"an intermediate of {biggest} elems >= the full bias broadcast "
        f"({full_broadcast}) — lazy T-chunking regressed")
    # numerics unchanged (also covers the bias.shape[-1]==1 broadcast path)
    out = attention_chunked(q, k, v, bias=bias, chunk_size=chunk)
    ref = attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    b1 = bias[..., :1]
    out1 = attention_chunked(q, k, v, bias=b1, chunk_size=chunk)
    ref1 = attention_reference(q, k, v, bias=b1)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref1),
                               rtol=2e-5, atol=2e-5)


def _eq_against_reduce_max(closed_jaxpr) -> int:
    """Count `eq` eqns comparing against a reduce_max output (through
    shape-only ops): the location mask of jnp.max's VJP."""
    from jax.extend.core import Var
    from repro.analysis.static.jaxpr_walk import iter_eqns
    eqns = [e for e, _ in iter_eqns(closed_jaxpr)]
    producer = {v: e for e in eqns for v in e.outvars}
    passthrough = {"broadcast_in_dim", "reshape", "convert_element_type",
                   "squeeze", "expand_dims"}

    def from_max(v):
        e = producer.get(v) if isinstance(v, Var) else None
        while e is not None and e.primitive.name in passthrough:
            v = e.invars[0]
            e = producer.get(v) if isinstance(v, Var) else None
        return e is not None and e.primitive.name == "reduce_max"

    return sum(any(from_max(v) for v in e.invars)
               for e in eqns if e.primitive.name == "eq")


def test_chunked_grad_does_not_differentiate_running_max():
    """The running max cancels out of the output, so no gradient may flow
    through it.  jnp.max's VJP divides by the count of logits equal to the
    max; when recomputed logits differ in the last bit from the ones the
    max saw (a TPU can fuse the two apart), that count is 0 and dq/dk
    become 0/0 = NaN.  The gradients must still be exact."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (2, 12, 4, 8))
    k = jax.random.normal(ks[1], (2, 12, 4, 8))
    v = jax.random.normal(ks[2], (2, 12, 4, 8))
    bias = jax.random.normal(ks[3], (4, 12, 12))

    def loss(fn, q, k, v, b):
        return (fn(q, k, v, bias=b) ** 2).sum()

    grad = jax.grad(partial(loss, partial(attention_chunked, chunk_size=4)),
                    argnums=(0, 1, 2, 3))
    assert _eq_against_reduce_max(jax.make_jaxpr(grad)(q, k, v, bias)) == 0
    # the check sees the pattern where it exists
    ref_loss = lambda q: (jnp.max(q, axis=-1) ** 2).sum()
    assert _eq_against_reduce_max(jax.make_jaxpr(jax.grad(ref_loss))(q)) == 1
    want = jax.grad(partial(loss, attention_reference),
                    argnums=(0, 1, 2, 3))(q, k, v, bias)
    for g, w in zip(grad(q, k, v, bias), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_rope_preserves_norm_and_relative_phase():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 10, 4, 32))
    xr = apply_rope(x, jnp.arange(10))
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(xr), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(5), (1, 1, 32))
    def dot(i, j):
        qr = apply_rope(q[None], jnp.array([[i]]))[0, 0, 0]
        kr = apply_rope(k[None], jnp.array([[j]]))[0, 0, 0]
        return float(jnp.dot(qr, kr))
    assert abs(dot(3, 1) - dot(7, 5)) < 1e-4
    assert abs(dot(3, 1) - dot(4, 1)) > 1e-6  # actually varies with distance
