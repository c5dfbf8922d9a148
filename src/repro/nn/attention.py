"""Attention: naive reference and memory-efficient chunked (flash-style) paths.

Pure-JAX implementations used by every model; the Pallas TPU kernels in
``repro.kernels`` are drop-in replacements for the hot paths.

Impl selection matrix (see also ROADMAP.md §Attention impl selection):

* ``'reference'`` — naive O(S*T) softmax; the numerical oracle.  Materializes
  the full (..., H, S, T) score matrix; only for tests/tiny shapes.
* ``'chunked'``   — flash-style online-softmax scan over KV chunks, pure XLA.
  The default everywhere: it is what the multi-pod dry-run lowers (Pallas TPU
  kernels cannot compile on the CPU dry-run backend) and the fallback for
  shapes/features the kernels don't cover.  Bias is chunked lazily along T —
  never broadcast to the full (lead, H, S, T) fp32 tensor.
* ``'pallas'``    — fused Pallas kernels (interpret mode on CPU — a
  correctness harness; Mosaic on TPU).  Causal/plain GQA calls hit the LM
  flash kernel; biased non-causal self-attention calls are routed to the
  Evoformer kernel (``evo_attention_nogate``).  ``mask=`` is rejected with a
  clear error rather than silently crashing in the kernel.
* ``'evo_pallas'`` (EvoformerConfig only, handled in
  ``core.evoformer.gated_attention``) — the fully fused AF2 hot path: one
  kernel does bias add + softmax + sigmoid gating with a flash-native
  backward (``kernels.ops.evo_attention``), so the (L, S, H, C) attention
  output never round-trips HBM before gating.

Layout conventions: ``q``: (..., S, H, D); ``k``/``v``: (..., T, KV, D) with
``H = KV * G`` (grouped-query attention).  Masks/bias broadcast to
(..., H, S, T).  Softmax statistics in fp32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _expand_gqa(q: jnp.ndarray, kv_heads: int) -> jnp.ndarray:
    """(..., S, H, D) -> (..., S, KV, G, D)."""
    *lead, s, h, d = q.shape
    assert h % kv_heads == 0, f"{h} q heads not divisible by {kv_heads} kv heads"
    return q.reshape(*lead, s, kv_heads, h // kv_heads, d)


def attention_reference(q, k, v, *, causal: bool = False,
                        bias: Optional[jnp.ndarray] = None,
                        mask: Optional[jnp.ndarray] = None,
                        q_offset: int = 0,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Naive O(S*T) attention. Oracle for the chunked path and Pallas kernels."""
    *_, s, h, d = q.shape
    t, kv = k.shape[-3], k.shape[-2]
    scale = scale if scale is not None else d ** -0.5
    qg = _expand_gqa(q, kv)  # (..., S, KV, G, D)
    logits = jnp.einsum("...skgd,...tkd->...kgst", qg, k).astype(jnp.float32) * scale
    lead = logits.shape[:-4]
    logits = logits.reshape(*lead, h, s, t)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    if causal:
        qpos = jnp.arange(s) + q_offset
        cmask = qpos[:, None] >= jnp.arange(t)[None, :]
        logits = jnp.where(cmask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = probs.reshape(*lead, kv, h // kv, s, t).astype(v.dtype)
    out = jnp.einsum("...kgst,...tkd->...skgd", probs, v)
    return out.reshape(*lead, s, h, d)


def attention_chunked(q, k, v, *, causal: bool = False,
                      bias: Optional[jnp.ndarray] = None,
                      mask: Optional[jnp.ndarray] = None,
                      q_offset: int = 0,
                      scale: Optional[float] = None,
                      chunk_size: int = 1024) -> jnp.ndarray:
    """Flash-style online-softmax attention, scanning KV chunks.

    Never materializes the (S, T) score matrix; peak temp is O(S * chunk).
    Matches :func:`attention_reference` to fp32-accumulation tolerance.
    ``mask`` may be 1-D (T,) key-validity or broadcastable to (..., H, S, T);
    large dense masks defeat the memory saving — prefer ``causal``/1-D forms.
    """
    *lead, s, h, d = q.shape
    t0, kv = k.shape[-3], k.shape[-2]
    g = h // kv
    scale = scale if scale is not None else d ** -0.5
    chunk_size = min(chunk_size, t0)
    t = t0
    if t % chunk_size != 0:
        pad = chunk_size - t % chunk_size
        k = jnp.pad(k, [(0, 0)] * (k.ndim - 3) + [(0, pad), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 3) + [(0, pad), (0, 0), (0, 0)])
        t = t + pad
    n_chunks = t // chunk_size
    key_valid = jnp.arange(t) < t0  # (T,)
    if mask is not None and mask.ndim == 1:
        key_valid = key_valid & jnp.pad(mask, (0, t - t0), constant_values=False)
        mask = None

    qg = (_expand_gqa(q, kv) * jnp.asarray(scale, q.dtype))  # (..., S, KV, G, D)

    def chunked_axis(x, axis):  # split axis into (n_chunks, chunk) & move front
        x = x.reshape(*x.shape[:axis], n_chunks, chunk_size, *x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    kc = chunked_axis(k, k.ndim - 3)
    vc = chunked_axis(v, v.ndim - 3)
    vk = key_valid.reshape(n_chunks, chunk_size)
    xs = {"idx": jnp.arange(n_chunks), "k": kc, "v": vc, "kv_valid": vk}
    bias_bcast = None
    if bias is not None:
        # chunk the bias lazily along T on its OWN shape — broadcasting to
        # the full (lead, h, s, t) fp32 tensor up front would defeat the
        # memory saving (it is as large as the score matrix we avoid)
        bf = bias.astype(jnp.float32)
        if bf.shape[-1] == 1:
            bias_bcast = bf            # T-broadcast bias: same every chunk
        else:
            if bf.shape[-1] != t0:
                raise ValueError(
                    f"bias trailing dim {bf.shape[-1]} must be 1 or match "
                    f"the key length {t0} (bias shape {bias.shape})")
            bf = jnp.pad(bf, [(0, 0)] * (bf.ndim - 1) + [(0, t - t0)])
            xs["bias"] = chunked_axis(bf, bf.ndim - 1)
    if mask is not None:
        mfull = jnp.broadcast_to(mask, (*lead, h, s, t0))
        mfull = jnp.pad(mfull, [(0, 0)] * (mfull.ndim - 1) + [(0, t - t0)],
                        constant_values=False)
        xs["mask"] = chunked_axis(mfull, mfull.ndim - 1)

    qpos = jnp.arange(s) + q_offset

    def body(carry, x):
        m, l, acc = carry
        logits = jnp.einsum("...skgd,...tkd->...kgst", qg, x["k"]).astype(jnp.float32)
        logits = logits.reshape(*lead, h, s, chunk_size)
        if "bias" in x:
            logits = logits + x["bias"]
        elif bias_bcast is not None:
            logits = logits + bias_bcast
        valid = x["kv_valid"]  # (chunk,)
        if causal:
            kpos = x["idx"] * chunk_size + jnp.arange(chunk_size)
            valid = valid & (qpos[:, None] >= kpos[None, :])  # (s, chunk)
        if "mask" in x:
            valid = valid & x["mask"]
        logits = jnp.where(valid, logits, NEG_INF)
        # the output does not depend on the running max (it cancels between
        # acc and l), so no gradient flows through it.  Differentiating
        # jnp.max instead divides by the count of entries equal to the max;
        # on a TPU the recomputed logits can differ in the last bit from the
        # ones the max was taken over (another fusion, excess precision),
        # the count is 0, and 0/0 poisons dq and dk with NaN.
        m_new = jax.lax.stop_gradient(
            jnp.maximum(m, jnp.max(logits, axis=-1)))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(jnp.broadcast_to(valid, p.shape), p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pg = p.reshape(*lead, kv, g, s, chunk_size).astype(x["v"].dtype)
        upd = jnp.einsum("...kgst,...tkd->...kgsd", pg, x["v"]).astype(jnp.float32)
        acc_new = acc * corr.reshape(*lead, kv, g, s, 1) + upd
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((*lead, h, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((*lead, h, s), jnp.float32)
    acc0 = jnp.zeros((*lead, kv, g, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), xs)
    out = acc / jnp.maximum(l.reshape(*lead, kv, g, s)[..., None], 1e-30)
    out = jnp.moveaxis(out, -2, -4)               # (..., S, KV, G, D)
    return out.reshape(*lead, s, h, d).astype(q.dtype)


def attention(q, k, v, *, impl: str = "chunked", chunk_size: int = 1024, **kw):
    """Dispatch: 'reference' | 'chunked' | 'pallas' (TPU kernels).

    ``impl='pallas'``: causal/plain GQA goes to the LM flash kernel; biased
    non-causal self-attention goes to the Evoformer kernel.  Unsupported
    combinations raise ``ValueError`` instead of crashing inside the kernel.
    """
    if impl == "reference":
        return attention_reference(q, k, v, **kw)
    if impl == "chunked":
        return attention_chunked(q, k, v, chunk_size=chunk_size, **kw)
    if impl == "pallas":
        from repro.kernels import ops as kops
        bias = kw.pop("bias", None)
        mask = kw.pop("mask", None)
        causal = kw.pop("causal", False)
        q_offset = kw.pop("q_offset", 0)
        scale = kw.pop("scale", None)
        if kw:
            raise TypeError(
                f"impl='pallas' got unsupported kwargs {sorted(kw)}")
        if mask is not None:
            raise ValueError(
                "impl='pallas' does not support mask=; use impl='chunked' "
                "or fold the mask into an additive bias")
        if q_offset:
            raise ValueError("impl='pallas' does not support q_offset=")
        if bias is not None:
            if causal:
                raise ValueError(
                    "impl='pallas' supports bias= only for non-causal "
                    "self-attention (the Evoformer kernel); causal+bias "
                    "needs impl='chunked'")
            *lead, s, h, d = q.shape
            if k.shape != q.shape or v.shape != q.shape:
                raise ValueError(
                    "impl='pallas' with bias= requires self-attention with "
                    f"h == kv heads; got q {q.shape} vs k {k.shape}")
            if bias.shape != (h, s, s):
                raise ValueError(
                    f"impl='pallas' bias must be (h, s, s)=({h}, {s}, {s}); "
                    f"got {bias.shape} — broadcastable biases need "
                    "impl='chunked'")
            from repro.kernels.flash_attention import evo_supported
            if not evo_supported(s):
                raise ValueError(
                    f"impl='pallas' would tile length {s} into degenerate "
                    "(< 8-row) blocks; use impl='chunked' for this shape")
            flat = lambda x: x.reshape(-1, s, h, d)
            out = kops.evo_attention_nogate(flat(q), flat(k), flat(v), bias,
                                            scale)
            return out.reshape(*lead, s, h, d)
        return kops.flash_attention(q, k, v, causal, scale)
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(q1, k_cache, v_cache, *, lengths=None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token decode: q1 (..., 1, H, D) vs (..., T, KV, D) cache.

    ``lengths`` (...,) marks how many cache slots are filled per sequence.
    """
    mask = None
    if lengths is not None:
        t = k_cache.shape[-3]
        mask = jnp.arange(t) < lengths[..., None]      # (..., T)
        mask = mask[..., None, None, :]                # (..., 1, 1, T) over (H, S)
    return attention_reference(q1, k_cache, v_cache, mask=mask, scale=scale)
