"""Pallas TPU flash attention — LM prefill + AF2 Evoformer hot paths.

TPU-native tiling: the grid walks (batch x kv_head x q_group, q_blocks);
each program holds a (block_q, D) query tile in VMEM and streams K/V tiles
of (block_k, D) from HBM->VMEM, maintaining online-softmax (m, l, acc) in
fp32 VREGs.  Causal blocks beyond the diagonal are skipped via the grid
index map (no wasted MXU work).  D and block sizes are chosen
MXU/lane-aligned (multiples of 128).

The Evoformer kernel (``evo_attention_fwd``) fuses the pair bias add and the
sigmoid gate multiply into the attention epilogue, and has a flash-native
backward: the forward optionally emits per-row log-sum-exp residuals
(lse = m + log l) and the ``_evo_bwd_*`` kernels recompute probability tiles
from them on the fly — dq/dbias/dgate in one kernel (the dbias head
reduction over MSA rows accumulates in VMEM across the innermost grid axis),
dk/dv in a second.  No (S, S) score matrix and no chunked-XLA recompute.

Validated in interpret mode on CPU against ``ref.flash_attention_ref`` /
``ref.evo_attention_ref``; on TPU the same pallas_calls lower to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def contract_precision(dtype) -> Optional[jax.lax.Precision]:
    """Precision of the in-kernel contractions for inputs of ``dtype``.

    Mosaic, like XLA on a TPU, rounds f32 operands to a single bf16 MXU
    pass unless told otherwise; the interpreter does not.  Kernels whose
    inputs are f32 therefore ask for f32 contractions, and bf16 inputs keep
    the single pass.  Accumulation is f32 either way."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def mxu_dot(precision):
    """``dot_general`` accumulating in f32 at ``precision``."""
    return functools.partial(jax.lax.dot_general,
                             preferred_element_type=jnp.float32,
                             precision=precision)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, block_k: int,
                  causal: bool, q_block: int, seq_k: int, precision):
    dot = mxu_dot(precision)
    qi = pl.program_id(1)
    q = q_ref[...]                                  # (block_q, D)
    m = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l = jnp.zeros((q.shape[0],), jnp.float32)
    acc = jnp.zeros((q.shape[0], q.shape[1]), jnp.float32)

    n_kb = seq_k // block_k
    if causal:
        # only blocks up to the diagonal contribute
        last = (qi + 1) * q_block
        n_needed = (last + block_k - 1) // block_k
    else:
        n_needed = n_kb

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = dot(
            q, k, (((1,), (1,)), ((), ()))) * scale   # (bq, bk)
        if causal:
            qpos = qi * q_block + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(qpos >= kpos, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_needed, body, (m, l, acc))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool) -> jnp.ndarray:
    """q (B,S,H,D); k/v (B,T,KV,D) with H = KV*G. Forward only."""
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)

    # layout: fold heads into the lead dim; kv head shared across its group
    qh = q.reshape(b, s, kv, g, d).transpose(0, 2, 3, 1, 4)  # (B,KV,G,S,D)
    qh = qh.reshape(b * kv * g, s, d)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(b * kv, t, d), g, axis=0)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(b * kv, t, d), g, axis=0)

    grid = (b * kv * g, s // block_q)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_k=block_k,
                          causal=causal, q_block=block_q, seq_k=t,
                          precision=contract_precision(q.dtype)),
        out_shape=jax.ShapeDtypeStruct((b * kv * g, s, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(b, kv, g, s, d).transpose(0, 3, 1, 2, 4).reshape(
        b, s, h, d)


def evo_block_size(s: int, cap: int = 128) -> int:
    """Largest power-of-two divisor of ``s``, capped at ``cap``.

    ``cap`` is rounded down to a power of two first, so the result always
    divides ``s`` — a non-power-of-two block request can therefore never
    produce a grid that under-covers the sequence.
    """
    cap = 1 << (max(1, cap).bit_length() - 1)
    return max(1, min(cap, s & -s))


def evo_supported(s: int, min_block: int = 8) -> bool:
    """Whether the fused Evoformer kernel tiles ``s`` efficiently.

    Lengths whose largest power-of-two divisor is below ``min_block`` would
    degrade to near-rowwise blocks (and break MXU/lane alignment on TPU);
    callers should fall back to the chunked XLA path for them.
    """
    return evo_block_size(s) >= min(min_block, s)


def _evo_kernel(q_ref, k_ref, v_ref, bias_ref, gate_ref, o_ref, *rest,
                scale: float, block_k: int, seq_k: int, biased: bool,
                gated: bool, precision):
    dot = mxu_dot(precision)
    q = q_ref[...]                                   # (block_q, C)
    m = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l = jnp.zeros((q.shape[0],), jnp.float32)
    acc = jnp.zeros((q.shape[0], q.shape[1]), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        ks = k_ref[pl.ds(kb * block_k, block_k), :]
        vs = v_ref[pl.ds(kb * block_k, block_k), :]
        s = dot(
            q, ks, (((1,), (1,)), ((), ()))) * scale
        if biased:
            bs = bias_ref[:, pl.ds(kb * block_k, block_k)]
            s = s + bs.astype(jnp.float32)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + dot(
            p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, seq_k // block_k, body, (m, l, acc))
    l_safe = jnp.maximum(l, 1e-30)
    o = acc / l_safe[:, None]
    if gated:
        o = o * jax.nn.sigmoid(gate_ref[...].astype(jnp.float32))
    o_ref[...] = o.astype(o_ref.dtype)
    if rest:  # residual mode: per-row log-sum-exp for the flash backward,
        # stored lane-major as a (1, block_q) row of the (L*H, 1, S) array
        rest[0][...] = (m + jnp.log(l_safe)).reshape(1, -1)


def _dummy_operand(dtype):
    """Placeholder for a compiled-out kernel input: a single element with a
    (1, 1)-block spec, so the pipeline DMAs one element instead of streaming
    an unused full-size operand."""
    return (jnp.zeros((1, 1, 1), dtype),
            pl.BlockSpec((None, 1, 1), lambda *_: (0, 0, 0)))


def evo_attention_fwd(q, k, v, bias, gate, *, scale: Optional[float] = None,
                      block_q: int = 128, block_k: int = 128,
                      interpret: bool, return_residuals: bool = False):
    """AF2 fused gated bias attention (paper hot path — Evoformer row/triangle
    attention is 62-78%% of step time, Table 2).

    q/k/v/gate: (L, S, H, C); bias (H, S, S). The sigmoid gate multiply is
    fused into the kernel epilogue (one fewer HBM round-trip of the (L,S,H,C)
    attention output).  ``gate`` holds pre-sigmoid logits; ``bias=None`` /
    ``gate=None`` compile the bias add / gate epilogue out of the kernel
    entirely (no dummy operand traffic).  With ``return_residuals=True`` also
    returns the (L*H, 1, S) fp32 log-sum-exp rows consumed by
    :func:`evo_attention_bwd`: the sequence rides the lane dim, so a
    (1, block_q) block meets Mosaic's (8, 128) block-tiling rule.
    """
    lrows, s, h, c = q.shape
    biased, gated = bias is not None, gate is not None
    scale = scale if scale is not None else c ** -0.5
    block_q = evo_block_size(s, block_q)
    block_k = evo_block_size(s, block_k)

    qh = q.transpose(0, 2, 1, 3).reshape(lrows * h, s, c)
    kh = k.transpose(0, 2, 1, 3).reshape(lrows * h, s, c)
    vh = v.transpose(0, 2, 1, 3).reshape(lrows * h, s, c)

    if biased:
        # bias is shared across MSA rows: indexed by head only (i % h) —
        # no (L,h,S,S) broadcast ever materializes in HBM
        bias_spec = pl.BlockSpec((None, block_q, s), lambda i, j: (i % h, j, 0))
    else:
        bias, bias_spec = _dummy_operand(q.dtype)
    if gated:
        gh = gate.transpose(0, 2, 1, 3).reshape(lrows * h, s, c)
        gate_spec = pl.BlockSpec((None, block_q, c), lambda i, j: (i, j, 0))
    else:
        gh, gate_spec = _dummy_operand(q.dtype)

    out_shape = [jax.ShapeDtypeStruct((lrows * h, s, c), q.dtype)]
    out_specs = [pl.BlockSpec((None, block_q, c), lambda i, j: (i, j, 0))]
    if return_residuals:
        out_shape.append(
            jax.ShapeDtypeStruct((lrows * h, 1, s), jnp.float32))
        out_specs.append(
            pl.BlockSpec((None, 1, block_q), lambda i, j: (i, 0, j)))

    grid = (lrows * h, s // block_q)
    res = pl.pallas_call(
        functools.partial(_evo_kernel, scale=scale, block_k=block_k, seq_k=s,
                          biased=biased, gated=gated,
                          precision=contract_precision(q.dtype)),
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, c), lambda i, j: (i, 0, 0)),
            bias_spec,
            gate_spec,
        ],
        out_specs=out_specs,
        interpret=interpret,
    )(qh, kh, vh, bias, gh)
    out = res[0].reshape(lrows, h, s, c).transpose(0, 2, 1, 3)
    if return_residuals:
        return out, res[1]
    return out


def _evo_bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, gate_ref, out_ref,
                       do_ref, lse_ref, dq_ref, dgate_ref, dbias_ref, *,
                       scale: float, block_k: int, seq_k: int, biased: bool,
                       gated: bool, precision):
    """dq + dgate for one (head, q-block, lead-row) program; dbias accumulates
    across the innermost lead-row grid axis (the head reduction over MSA
    rows), so the (H, S, S) bias gradient is built without recomputation."""
    dot = mxu_dot(precision)
    li = pl.program_id(2)
    q = q_ref[...]                                       # (bq, C)
    do = do_ref[...].astype(jnp.float32)
    out = out_ref[...].astype(jnp.float32)
    lse = lse_ref[...].reshape(-1)                       # (bq,)
    if gated:
        sig = jax.nn.sigmoid(gate_ref[...].astype(jnp.float32))
        # out = sig * o_raw, so o_raw*sig == out: no division needed
        dgate_ref[...] = (do * out * (1.0 - sig)).astype(dgate_ref.dtype)
        do_raw = do * sig
    else:
        do_raw = do
    delta = jnp.sum(do * out, axis=1)                    # rowsum(do_raw*o_raw)

    if biased:
        @pl.when(li == 0)
        def _init():
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

    def body(kb, dq):
        ksl = pl.ds(kb * block_k, block_k)
        ks = k_ref[ksl, :]
        vs = v_ref[ksl, :]
        s = dot(
            q, ks, (((1,), (1,)), ((), ()))) * scale
        if biased:
            s = s + bias_ref[:, ksl].astype(jnp.float32)
        p = jnp.exp(s - lse[:, None])                    # (bq, bk)
        dp = dot(
            do_raw.astype(vs.dtype), vs, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None])                   # (bq, bk) fp32
        if biased:
            dbias_ref[:, ksl] = dbias_ref[:, ksl] + ds
        return dq + dot(
            ds.astype(ks.dtype), ks, (((1,), (0,)), ((), ()))) * scale

    dq = jnp.zeros((q.shape[0], q.shape[1]), jnp.float32)
    dq = jax.lax.fori_loop(0, seq_k // block_k, body, dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _evo_bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, gate_ref, out_ref,
                        do_ref, lse_ref, dk_ref, dv_ref, *,
                        scale: float, block_q: int, seq_q: int, biased: bool,
                        gated: bool, precision):
    """dk + dv for one (lead-row*head, k-block) program, streaming q-blocks."""
    dot = mxu_dot(precision)
    k = k_ref[...]                                       # (bk, C)
    v = v_ref[...]

    def body(jq, carry):
        dk, dv = carry
        qsl = pl.ds(jq * block_q, block_q)
        q = q_ref[qsl, :]
        do = do_ref[qsl, :].astype(jnp.float32)
        out = out_ref[qsl, :].astype(jnp.float32)
        lse = lse_ref[:, qsl].reshape(-1)                # (bq,)
        if gated:
            sig = jax.nn.sigmoid(gate_ref[qsl, :].astype(jnp.float32))
            do_raw = do * sig
        else:
            do_raw = do
        delta = jnp.sum(do * out, axis=1)                # (bq,)
        s = dot(
            q, k, (((1,), (1,)), ((), ()))) * scale
        if biased:
            s = s + bias_ref[qsl, :].astype(jnp.float32)
        p = jnp.exp(s - lse[:, None])                    # (bq, bk)
        dv = dv + dot(
            p.astype(do_raw.dtype), do_raw, (((0,), (0,)), ((), ())))
        dp = dot(
            do_raw.astype(v.dtype), v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None])
        dk = dk + dot(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ()))) * scale
        return dk, dv

    dk0 = jnp.zeros((k.shape[0], k.shape[1]), jnp.float32)
    dv0 = jnp.zeros((v.shape[0], v.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, seq_q // block_q, body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def evo_attention_bwd(q, k, v, bias, gate, out, lse, do, *,
                      scale: Optional[float] = None,
                      block_q: int = 128, block_k: int = 128,
                      interpret: bool):
    """Flash backward for :func:`evo_attention_fwd`.

    Consumes the saved fwd output + (L*H, 1, S) log-sum-exp residuals; never
    materializes an (S, S) probability matrix and never recomputes the
    forward softmax outside the tile being processed.  Returns
    ``(dq, dk, dv, dbias, dgate)`` in the public (L, S, H, C) / (H, S, S)
    layouts; ``dbias`` / ``dgate`` are None when ``bias`` / ``gate`` is None
    (the corresponding loads/stores are compiled out of the kernels).
    """
    lrows, s, h, c = q.shape
    biased, gated = bias is not None, gate is not None
    scale = scale if scale is not None else c ** -0.5
    block_q = evo_block_size(s, block_q)
    block_k = evo_block_size(s, block_k)

    def heads_first(x):
        return x.transpose(0, 2, 1, 3).reshape(lrows * h, s, c)

    qh, kh, vh = heads_first(q), heads_first(k), heads_first(v)
    oh, doh = heads_first(out), heads_first(do)

    row_spec = pl.BlockSpec((None, s, c), lambda hh, j, li, H=h: (li * H + hh, 0, 0))
    blk_spec = pl.BlockSpec((None, block_q, c),
                            lambda hh, j, li, H=h: (li * H + hh, j, 0))
    if biased:
        bias_in, bias_spec = bias, pl.BlockSpec(
            (None, block_q, s), lambda hh, j, li: (hh, j, 0))
        dbias_shape = jax.ShapeDtypeStruct((h, s, s), jnp.float32)
        dbias_spec = pl.BlockSpec((None, block_q, s), lambda hh, j, li: (hh, j, 0))
    else:
        bias_in, bias_spec = _dummy_operand(q.dtype)
        dbias_shape = jax.ShapeDtypeStruct((1, 1, 1), jnp.float32)
        dbias_spec = pl.BlockSpec((None, 1, 1), lambda *_: (0, 0, 0))
    if gated:
        gh, gate_spec = heads_first(gate), blk_spec
        dgate_shape = jax.ShapeDtypeStruct((lrows * h, s, c), gate.dtype)
        dgate_spec = blk_spec
    else:
        gh, gate_spec = _dummy_operand(q.dtype)
        dgate_shape = jax.ShapeDtypeStruct((1, 1, 1), q.dtype)
        dgate_spec = pl.BlockSpec((None, 1, 1), lambda *_: (0, 0, 0))

    # dq/dgate per (head, q-block, lead-row); lead-row innermost so the dbias
    # output block (head, q-block) is revisited consecutively and accumulates
    # in VMEM across the whole MSA-row reduction.
    dq, dgate, dbias = pl.pallas_call(
        functools.partial(_evo_bwd_dq_kernel, scale=scale, block_k=block_k,
                          seq_k=s, biased=biased, gated=gated,
                          precision=contract_precision(q.dtype)),
        out_shape=[
            jax.ShapeDtypeStruct((lrows * h, s, c), q.dtype),
            dgate_shape,
            dbias_shape,
        ],
        grid=(h, s // block_q, lrows),
        in_specs=[
            blk_spec,                                              # q
            row_spec,                                              # k
            row_spec,                                              # v
            bias_spec,
            gate_spec,
            blk_spec,                                              # out
            blk_spec,                                              # do
            pl.BlockSpec((None, 1, block_q),
                         lambda hh, j, li, H=h: (li * H + hh, 0, j)),  # lse
        ],
        out_specs=[blk_spec, dgate_spec, dbias_spec],
        interpret=interpret,
    )(qh, kh, vh, bias_in, gh, oh, doh, lse)

    full_spec = pl.BlockSpec((None, s, c), lambda i, kb: (i, 0, 0))
    if biased:
        bias_spec_kv = pl.BlockSpec((None, s, block_k),
                                    lambda i, kb, H=h: (i % H, 0, kb))
    else:
        bias_spec_kv = pl.BlockSpec((None, 1, 1), lambda *_: (0, 0, 0))
    gate_spec_kv = (full_spec if gated
                    else pl.BlockSpec((None, 1, 1), lambda *_: (0, 0, 0)))
    dk, dv = pl.pallas_call(
        functools.partial(_evo_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          seq_q=s, biased=biased, gated=gated,
                          precision=contract_precision(q.dtype)),
        out_shape=[
            jax.ShapeDtypeStruct((lrows * h, s, c), k.dtype),
            jax.ShapeDtypeStruct((lrows * h, s, c), v.dtype),
        ],
        grid=(lrows * h, s // block_k),
        in_specs=[
            full_spec,                                             # q
            pl.BlockSpec((None, block_k, c), lambda i, kb: (i, kb, 0)),
            pl.BlockSpec((None, block_k, c), lambda i, kb: (i, kb, 0)),
            bias_spec_kv,
            gate_spec_kv,
            full_spec,                                             # out
            full_spec,                                             # do
            pl.BlockSpec((None, 1, s), lambda i, kb: (i, 0, 0)),   # lse
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, c), lambda i, kb: (i, kb, 0)),
            pl.BlockSpec((None, block_k, c), lambda i, kb: (i, kb, 0)),
        ],
        interpret=interpret,
    )(qh, kh, vh, bias_in, gh, oh, doh, lse)

    def heads_last(x):
        return x.reshape(lrows, h, s, c).transpose(0, 2, 1, 3)

    return (heads_last(dq), heads_last(dk), heads_last(dv),
            dbias.astype(bias.dtype) if biased else None,
            heads_last(dgate) if gated else None)
