"""Jit'd public wrappers around the Pallas kernels.

Forward = Pallas kernel: Mosaic on TPU, the interpreter elsewhere
(``interpret_mode``).

Backward:

* ``evo_attention`` / ``evo_attention_nogate`` are flash-native end to end:
  the forward emits per-row log-sum-exp residuals and the ``custom_vjp``
  consumes them with dedicated Pallas dq/dbias/dgate and dk/dv kernels
  (``flash_attention.evo_attention_bwd``) — no chunked-XLA recompute, no
  (S, S) probability matrix, and the bias head-reduction over MSA rows
  happens inside the dq kernel's VMEM accumulator.
* the LM ``flash_attention`` keeps the memory-efficient chunked-XLA VJP
  (same asymptotics as a flash backward; a dedicated causal-GQA bwd kernel
  is a further TPU optimization).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fk
from repro.kernels import triangle as tk
from repro.nn.attention import attention_chunked


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in the interpreter: exactly when the
    default backend is not a TPU.  The one place this is decided — every
    kernel entry takes ``interpret`` as a required argument, so no caller
    can reach the interpreter on the chip by omission."""
    return jax.default_backend() != "tpu"


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None):
    return fk.flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                  interpret=interpret_mode())


def _fa_fwd(q, k, v, causal, scale):
    return flash_attention(q, k, v, causal, scale), (q, k, v)


def _fa_bwd(causal, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: attention_chunked(q, k, v, causal=causal, scale=scale),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def evo_attention(q, k, v, bias, gate, scale: Optional[float] = None):
    """Fused AF2 gated-bias attention: sigmoid(gate) * attn(q,k,v;bias).

    q/k/v/gate: (L, S, H, C) with pre-sigmoid gate logits; bias (H, S, S)
    shared across the L lead rows.  Differentiable in all five tensor args
    via the flash backward kernels.
    """
    return fk.evo_attention_fwd(q, k, v, bias, gate, scale=scale,
                                interpret=interpret_mode())


def _ea_fwd(q, k, v, bias, gate, scale):
    out, lse = fk.evo_attention_fwd(q, k, v, bias, gate, scale=scale,
                                    interpret=interpret_mode(),
                                    return_residuals=True)
    return out, (q, k, v, bias, gate, out, lse)


def _ea_bwd(scale, res, g):
    q, k, v, bias, gate, out, lse = res
    dq, dk, dv, dbias, dgate = fk.evo_attention_bwd(
        q, k, v, bias, gate, out, lse, g, scale=scale,
        interpret=interpret_mode())
    return dq, dk, dv, dbias, dgate


evo_attention.defvjp(_ea_fwd, _ea_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def evo_attention_nogate(q, k, v, bias, scale: Optional[float] = None):
    """Biased (non-causal) attention on the Evoformer kernel, no gate fusion.

    The target of ``attention(..., impl='pallas', bias=...)`` dispatch: same
    tiling and flash backward as :func:`evo_attention`, with the sigmoid-gate
    epilogue compiled out.
    """
    return fk.evo_attention_fwd(q, k, v, bias, None, scale=scale,
                                interpret=interpret_mode())


def _eang_fwd(q, k, v, bias, scale):
    out, lse = fk.evo_attention_fwd(q, k, v, bias, None, scale=scale,
                                    interpret=interpret_mode(),
                                    return_residuals=True)
    return out, (q, k, v, bias, out, lse)


def _eang_bwd(scale, res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv, dbias, _ = fk.evo_attention_bwd(
        q, k, v, bias, None, out, lse, g, scale=scale,
        interpret=interpret_mode())
    return dq, dk, dv, dbias


evo_attention_nogate.defvjp(_eang_fwd, _eang_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def evo_attention_nobias(q, k, v, gate, scale: Optional[float] = None):
    """Gated attention with NO pair bias (e.g. MSA column attention under
    ``evo_pallas``): the bias add is compiled out of the kernel — no zeros
    bias is materialized or streamed."""
    return fk.evo_attention_fwd(q, k, v, None, gate, scale=scale,
                                interpret=interpret_mode())


def _eanb_fwd(q, k, v, gate, scale):
    out, lse = fk.evo_attention_fwd(q, k, v, None, gate, scale=scale,
                                    interpret=interpret_mode(),
                                    return_residuals=True)
    return out, (q, k, v, gate, out, lse)


def _eanb_bwd(scale, res, g):
    q, k, v, gate, out, lse = res
    dq, dk, dv, _, dgate = fk.evo_attention_bwd(
        q, k, v, None, gate, out, lse, g, scale=scale,
        interpret=interpret_mode())
    return dq, dk, dv, dgate


evo_attention_nobias.defvjp(_eanb_fwd, _eanb_bwd)


@jax.custom_vjp
def triangle_mult(xa, xb, xg, w_a, b_a, w_b, b_b, ln_s, ln_b, w_o, b_o,
                  w_g, b_g):
    """Fused AF2 triangle-multiplicative update (Algorithms 11/12).

    xa/xb (r_i, r_k, c_z) / (r_j, r_k, c_z): gated-projection sources with
    the contracted axis k on axis 1 — the caller orients them for
    outgoing/incoming and DAP sharding (see ``kernels.triangle``); xg
    (r_i, r_j, c_z) is the gate source in output orientation.  w_a/w_b are
    packed [value | gate] (c_z, 2·c_hidden) projections.  The gated
    projection pair, the pre-LN contraction and the pre-gate output never
    round-trip HBM in the forward; the VJP is Pallas-native, consuming the
    fp32 contraction residual (no chunked-XLA recompute of the O(r³) op).
    """
    return tk.triangle_mult_fwd(xa, xb, xg, w_a, b_a, w_b, b_b, ln_s, ln_b,
                                w_o, b_o, w_g, b_g, interpret=interpret_mode())


def _tm_fwd(xa, xb, xg, w_a, b_a, w_b, b_b, ln_s, ln_b, w_o, b_o, w_g, b_g):
    out, s = tk.triangle_mult_fwd(
        xa, xb, xg, w_a, b_a, w_b, b_b, ln_s, ln_b, w_o, b_o, w_g, b_g,
        interpret=interpret_mode(), return_residuals=True)
    return out, (xa, xb, xg, w_a, b_a, w_b, b_b, ln_s, ln_b, w_o, b_o,
                 w_g, b_g, s)


def _tm_bwd(res, dy):
    xa, xb, xg, w_a, b_a, w_b, b_b, ln_s, ln_b, w_o, b_o, w_g, b_g, s = res
    interpret = interpret_mode()
    ds, dxg, dln_s, dln_b, dw_o, db_o, dw_g, db_g = \
        tk.triangle_mult_bwd_epilogue(s, xg, dy, ln_s, ln_b, w_o, b_o,
                                      w_g, b_g, interpret=interpret)
    dxa, dw_a, db_a = tk.triangle_mult_bwd_dx(
        ds, xa, xb, w_a, b_a, w_b, b_b, interpret=interpret)
    dxb, dw_b, db_b = tk.triangle_mult_bwd_dx(
        ds.swapaxes(0, 1), xb, xa, w_b, b_b, w_a, b_a, interpret=interpret)
    cast = lambda g, p: g.astype(p.dtype)
    return (dxa, dxb, dxg, cast(dw_a, w_a), cast(db_a, b_a),
            cast(dw_b, w_b), cast(db_b, b_b), cast(dln_s, ln_s),
            cast(dln_b, ln_b), cast(dw_o, w_o), cast(db_o, b_o),
            cast(dw_g, w_g), cast(db_g, b_g))


triangle_mult.defvjp(_tm_fwd, _tm_bwd)


def triangle_mult_masked(xa, xb, xg, k_mask, w_a, b_a, w_b, b_b, ln_s, ln_b,
                         w_o, b_o, w_g, b_g):
    """Forward-only masked triangle mult (padded-bucket inference).

    Same fused kernel as :func:`triangle_mult` plus a streamed (r_k,)
    k-validity operand that zeroes padded residues' contraction terms
    in-kernel.  The fold serving path never differentiates, so no custom
    VJP is wired — training always folds full buckets (``k_mask=None``)
    and keeps the Pallas backward.
    """
    return tk.triangle_mult_fwd(xa, xb, xg, w_a, b_a, w_b, b_b, ln_s, ln_b,
                                w_o, b_o, w_g, b_g, k_mask=k_mask,
                                interpret=interpret_mode())
