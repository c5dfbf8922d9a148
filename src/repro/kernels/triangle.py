"""Pallas fused triangle-multiplicative update (AF2 Algorithms 11/12).

The last heavyweight Evoformer op without a fused path after the attention/
OPM kernels of PR 1: per op the reference runs 2 layernorms, 6 denses, 3
sigmoid gates and an (r, r, c)·k-contraction with every intermediate
round-tripping HBM.  This kernel computes, for one (i-block, j-block) output
tile (DESIGN.md §9):

    a[i,k,:] = sigmoid(x_a[i,k]·W_ag + b_ag) * (x_a[i,k]·W_av + b_av)
    b[j,k,:] = sigmoid(x_b[j,k]·W_bg + b_bg) * (x_b[j,k]·W_bv + b_bv)
    s[i,j,:] = Σ_k a[i,k,:] ⊙ b[j,k,:]          (fp32 VMEM accumulator)
    y[i,j,:] = sigmoid(x_g[i,j]·W_g + b_g) ⊙ (LN(s)·W_o + b_o)

streaming k in blocks: the gated-projection pair (two (r, r, c) tensors —
"the (r, r, 2c) intermediate") and the pre-gate output LN(s)·W_o never exist
in HBM.  'Outgoing' vs 'incoming' (and DAP sharding) are pure operand
orientation handled by the caller: ``x_a``/``x_b`` are the (possibly
transposed / gathered) gated-projection sources with k on axis 1, ``x_g``
the gate source in output orientation — the kernel itself is direction- and
shard-agnostic (rectangular r_i × r_j × r_k extents are supported).

The k-contraction is a c-batched (block_i × block_k)·(block_k × block_j)
matmul (channels ride the Mosaic batch dimension), accumulated in fp32.

Backward (custom_vjp in ``kernels.ops``): residual mode additionally emits
the fp32 pre-LN contraction ``s`` — the only intermediate whose recompute
costs O(r³); everything else is recomputed per tile from the inputs, flash-
attention-style.  Two kernels consume it:

* ``triangle_mult_bwd_epilogue`` — grid (i, j): LN/out-proj/gate backward,
  emitting ds plus the six epilogue weight grads accumulated in VMEM across
  the whole grid (constant-index output blocks);
* ``triangle_mult_bwd_dx`` — grid (p, k, q), run once per operand side:
  d a[p,k] = Σ_q ds[p,q] ⊙ b[q,k] with the streamed operand's gated
  projection recomputed per (q, k) tile, fused immediately into that side's
  projection backward (dx plus dW/db accumulated in VMEM) — the a/b tensors
  and their cotangents never exist in HBM in the backward either.

Validated in interpret mode on CPU against the fp32-accumulating reference
(tests/test_triangle.py); on TPU the same pallas_calls lower to Mosaic
(tests/test_tpu_compile.py compiles them for a described v5e).  Both
contractions stream their reduced axis through the grid — k in the
forward, q in ``triangle_mult_bwd_dx`` — into an fp32 VMEM scratch
accumulator, so a program holds (block, block_k, c_z) operand tiles and
its VMEM does not grow with r.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (contract_precision, evo_block_size,
                                           mxu_dot)

LN_EPS = 1e-5
# Each kernel declares its own scoped-VMEM budget (Mosaic's default is
# 16 MiB; v5e has 128 MiB): at c_z = c_mul = 128 the default blocks need
# 14-21 MiB of pipeline buffers, scratch and in-kernel temporaries.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=48 * 1024 * 1024)


def _tiles(dtype):
    """Default (row, k) tile extents for inputs of ``dtype``: 64 rows x 128
    k for bf16, and the same bytes for wider types — f32 contractions run at
    HIGHEST precision (``contract_precision``), whose split operands need
    the room."""
    n = jnp.dtype(dtype).itemsize
    return 128 // n, 256 // n


def _proj_gated(xs, w_ref, b_ref, c: int, dot):
    """Gated projection of a (rows, bk, c_z) tile: packed weights are
    [value | gate] along the output dim -> (rows, bk, c) fp32."""
    h = dot(xs, w_ref[...], (((2,), (0,)), ((), ())))
    h = h + b_ref[...].astype(jnp.float32)[None]
    return jax.nn.sigmoid(h[..., c:]) * h[..., :c]


def _tri_fwd_kernel(xa_ref, xb_ref, xg_ref, wa_ref, ba_ref, wb_ref, bb_ref,
                    lns_ref, lnb_ref, wo_ref, bo_ref, wg_ref, bg_ref,
                    *rest, c_hidden: int, masked: bool, precision):
    """One (i-block, j-block, k-block) program: the k-block's gated
    projections accumulate into the fp32 VMEM scratch ``acc``; the last
    k-block runs the LN/out-proj/gate epilogue and writes the tile."""
    if masked:
        kmask_ref, *rest = rest
    o_ref, *res, acc_ref = rest
    c = c_hidden
    dot = mxu_dot(precision)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _proj_gated(xa_ref[...], wa_ref, ba_ref, c, dot)      # (bi,bk,c)
    b = _proj_gated(xb_ref[...], wb_ref, bb_ref, c, dot)      # (bj,bk,c)
    if masked:
        # padded-bucket residues: zero their k terms — the gated
        # projection of a padded (nonzero) input row is not zero
        a = a * kmask_ref[...].astype(jnp.float32)[None]      # (1,bk,1)
    # s[c,i,j] += Σ_k a[i,k,c]·b[j,k,c]: c-batched MXU matmul
    acc_ref[...] += dot(
        jnp.transpose(a, (2, 0, 1)), jnp.transpose(b, (2, 0, 1)),
        (((2,), (2,)), ((0,), (0,))))

    @pl.when(kb == pl.num_programs(2) - 1)
    def _epilogue():
        s = jnp.transpose(acc_ref[...], (1, 2, 0))            # (bi,bj,c) f32
        mu = jnp.mean(s, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True)
        nhat = (s - mu) * jax.lax.rsqrt(var + LN_EPS)
        n = nhat * lns_ref[...].astype(jnp.float32)[None] \
            + lnb_ref[...].astype(jnp.float32)[None]
        u = dot(n, wo_ref[...], (((2,), (0,)), ((), ())))
        u = u + bo_ref[...].astype(jnp.float32)[None]
        zg = dot(
            xg_ref[...].astype(jnp.float32), wg_ref[...],
            (((2,), (0,)), ((), ())))
        zg = zg + bg_ref[...].astype(jnp.float32)[None]
        o_ref[...] = (jax.nn.sigmoid(zg) * u).astype(o_ref.dtype)
        if res:  # residual mode: pre-LN contraction for the backward
            res[0][...] = s


def _const_spec(arr_or_shape):
    """Whole-array block revisited by every program (weights / accumulated
    weight grads): constant index map, so the block pins in VMEM."""
    shape = getattr(arr_or_shape, "shape", arr_or_shape)
    return pl.BlockSpec(tuple(shape), lambda *_: (0,) * len(shape))


def _weight_operands(w_a, b_a, w_b, b_b, ln_s, ln_b, w_o, b_o, w_g, b_g):
    """1-D params are lifted to (1, n) — Mosaic wants >=2D operands."""
    ops = [w_a, b_a.reshape(1, -1), w_b, b_b.reshape(1, -1),
           ln_s.reshape(1, -1), ln_b.reshape(1, -1),
           w_o, b_o.reshape(1, -1), w_g, b_g.reshape(1, -1)]
    return ops, [_const_spec(o) for o in ops]


def triangle_mult_fwd(xa, xb, xg, w_a, b_a, w_b, b_b, ln_s, ln_b, w_o, b_o,
                      w_g, b_g, *, k_mask=None, block_i: int = None,
                      block_j: int = None, block_k: int = None,
                      interpret: bool,
                      return_residuals: bool = False):
    """Fused triangle-mult forward.

    xa (r_i, r_k, c_z) / xb (r_j, r_k, c_z): gated-projection sources, k on
    axis 1 (caller orients for outgoing/incoming/DAP); xg (r_i, r_j, c_z):
    gate source in output orientation.  w_a/w_b are the packed
    [value | gate] (c_z, 2c) projections.  Returns (r_i, r_j, c_z); with
    ``return_residuals`` also the fp32 (r_i, r_j, c) pre-LN contraction.
    ``k_mask`` (r_k,) zeroes masked residues' k-contraction terms in-kernel
    (padded-bucket inference; see ``kernels.ops.triangle_mult_masked``).
    """
    r_i, r_k, _ = xa.shape
    r_j = xb.shape[0]
    c = w_a.shape[1] // 2
    c_z = w_o.shape[1]
    rows, ks = _tiles(xa.dtype)
    bi = evo_block_size(r_i, block_i or rows)
    bj = evo_block_size(r_j, block_j or rows)
    bk = evo_block_size(r_k, block_k or ks)

    w_ops, w_specs = _weight_operands(w_a, b_a, w_b, b_b, ln_s, ln_b,
                                      w_o, b_o, w_g, b_g)
    in_specs = [
        pl.BlockSpec((bi, bk, xa.shape[2]), lambda i, j, k: (i, k, 0)),
        pl.BlockSpec((bj, bk, xb.shape[2]), lambda i, j, k: (j, k, 0)),
        pl.BlockSpec((bi, bj, xg.shape[2]), lambda i, j, k: (i, j, 0)),
    ] + w_specs
    mask_ops = []
    if k_mask is not None:
        # a (r_k, 1) column: its (bk, 1) block tiles legally for any bk
        mask_ops = [k_mask.astype(jnp.float32).reshape(r_k, 1)]
        in_specs.append(pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)))
    out_shape = [jax.ShapeDtypeStruct((r_i, r_j, c_z), xg.dtype)]
    out_specs = [pl.BlockSpec((bi, bj, c_z), lambda i, j, k: (i, j, 0))]
    if return_residuals:
        out_shape.append(jax.ShapeDtypeStruct((r_i, r_j, c), jnp.float32))
        out_specs.append(
            pl.BlockSpec((bi, bj, c), lambda i, j, k: (i, j, 0)))

    res = pl.pallas_call(
        functools.partial(_tri_fwd_kernel, c_hidden=c,
                          masked=k_mask is not None,
                          precision=contract_precision(xa.dtype)),
        out_shape=out_shape,
        grid=(r_i // bi, r_j // bj, r_k // bk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((c, bi, bj), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(xa, xb, xg, *w_ops, *mask_ops)
    return tuple(res) if return_residuals else res[0]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _tri_bwd_epi_kernel(s_ref, xg_ref, dy_ref, lns_ref, lnb_ref, wo_ref,
                        bo_ref, wg_ref, bg_ref,
                        ds_ref, dxg_ref, dlns_ref, dlnb_ref, dwo_ref,
                        dbo_ref, dwg_ref, dbg_ref, *, precision):
    """Epilogue backward for one (i-block, j-block) tile; the six epilogue
    param grads accumulate in VMEM across the whole grid (constant-index
    output blocks, zeroed by the first program)."""
    dot = mxu_dot(precision)
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _init():
        for ref in (dlns_ref, dlnb_ref, dwo_ref, dbo_ref, dwg_ref, dbg_ref):
            ref[...] = jnp.zeros_like(ref)

    s = s_ref[...]                                            # (bi,bj,c) f32
    gam = lns_ref[...].astype(jnp.float32)                    # (1,c)
    mu = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + LN_EPS)
    nhat = (s - mu) * rstd
    n = nhat * gam[None] + lnb_ref[...].astype(jnp.float32)[None]
    u = dot(n, wo_ref[...], (((2,), (0,)), ((), ())))
    u = u + bo_ref[...].astype(jnp.float32)[None]
    xg = xg_ref[...].astype(jnp.float32)
    zg = dot(xg, wg_ref[...], (((2,), (0,)), ((), ())))
    zg = zg + bg_ref[...].astype(jnp.float32)[None]
    g = jax.nn.sigmoid(zg)
    dy = dy_ref[...].astype(jnp.float32)

    du = dy * g
    dzg = dy * u * g * (1.0 - g)
    dxg_ref[...] = dot(
        dzg, wg_ref[...], (((2,), (1,)), ((), ()))).astype(dxg_ref.dtype)
    dn = dot(du, wo_ref[...], (((2,), (1,)), ((), ())))
    dnh = dn * gam[None]
    ds = rstd * (dnh - jnp.mean(dnh, axis=-1, keepdims=True)
                 - nhat * jnp.mean(dnh * nhat, axis=-1, keepdims=True))
    ds_ref[...] = ds

    flat = lambda t: t.reshape(-1, t.shape[-1])
    mm = lambda a, b: dot(            # aᵀ·b over tile rows
        a, b, (((0,), (0,)), ((), ())))
    dlns_ref[...] = dlns_ref[...] + jnp.sum(flat(dn * nhat), 0)[None]
    dlnb_ref[...] = dlnb_ref[...] + jnp.sum(flat(dn), 0)[None]
    dwo_ref[...] = dwo_ref[...] + mm(flat(n), flat(du))
    dbo_ref[...] = dbo_ref[...] + jnp.sum(flat(du), 0)[None]
    dwg_ref[...] = dwg_ref[...] + mm(flat(xg), flat(dzg))
    dbg_ref[...] = dbg_ref[...] + jnp.sum(flat(dzg), 0)[None]


def triangle_mult_bwd_epilogue(s, xg, dy, ln_s, ln_b, w_o, b_o, w_g, b_g, *,
                               block_i: int = None, block_j: int = None,
                               interpret: bool):
    """LN + out-proj + gate backward from the saved fp32 contraction ``s``.

    Returns ``(ds, dxg, dln_s, dln_b, dw_o, db_o, dw_g, db_g)``; all param
    grads fp32 (cast to the params' dtype by the custom_vjp wrapper)."""
    r_i, r_j, c = s.shape
    c_z = xg.shape[2]
    rows, _ = _tiles(xg.dtype)
    bi = evo_block_size(r_i, block_i or rows)
    bj = evo_block_size(r_j, block_j or rows)
    blk = lambda d: pl.BlockSpec((bi, bj, d), lambda i, j: (i, j, 0))
    w_ops = [ln_s.reshape(1, -1), ln_b.reshape(1, -1),
             w_o, b_o.reshape(1, -1), w_g, b_g.reshape(1, -1)]
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    ds, dxg, dlns, dlnb, dwo, dbo, dwg, dbg = pl.pallas_call(
        functools.partial(_tri_bwd_epi_kernel,
                          precision=contract_precision(xg.dtype)),
        out_shape=[f32((r_i, r_j, c)),
                   jax.ShapeDtypeStruct((r_i, r_j, c_z), xg.dtype),
                   f32((1, c)), f32((1, c)), f32((c, c_z)), f32((1, c_z)),
                   f32((c_z, c_z)), f32((1, c_z))],
        grid=(r_i // bi, r_j // bj),
        in_specs=[blk(c), blk(c_z), blk(c_z)] + [_const_spec(o) for o in w_ops],
        out_specs=[blk(c), blk(c_z)] + [
            _const_spec(sh) for sh in
            ((1, c), (1, c), (c, c_z), (1, c_z), (c_z, c_z), (1, c_z))],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(s, xg, dy, *w_ops)
    return (ds, dxg, dlns.reshape(-1), dlnb.reshape(-1), dwo,
            dbo.reshape(-1), dwg, dbg.reshape(-1))


def _tri_bwd_dx_kernel(ds_ref, xloc_ref, xstr_ref, wloc_ref, bloc_ref,
                       wstr_ref, bstr_ref,
                       dx_ref, dwloc_ref, dbloc_ref, dacc_ref, *,
                       c_hidden: int, precision):
    """One (p-block, k-block, q-block) program of the contraction backward:
    recomputes the streamed side's gated projection for the (q, k) tile and
    accumulates the local side's cotangent in the fp32 VMEM scratch
    ``dacc``; the last q-block pushes it through the local side's gated
    projection (dx out; dW/db accumulated in VMEM across the grid)."""
    c = c_hidden
    dot = mxu_dot(precision)
    qb = pl.program_id(2)
    first = ((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (qb == 0))

    @pl.when(first)
    def _init_w():
        dwloc_ref[...] = jnp.zeros_like(dwloc_ref)
        dbloc_ref[...] = jnp.zeros_like(dbloc_ref)

    @pl.when(qb == 0)
    def _init_acc():
        dacc_ref[...] = jnp.zeros_like(dacc_ref)

    strv = _proj_gated(xstr_ref[...], wstr_ref, bstr_ref, c, dot)
    # dloc[c,p,k] += Σ_q ds[p,q,c]·str[q,k,c]
    dacc_ref[...] += dot(
        jnp.transpose(ds_ref[...], (2, 0, 1)), jnp.transpose(strv, (2, 0, 1)),
        (((2,), (1,)), ((0,), (0,))))

    @pl.when(qb == pl.num_programs(2) - 1)
    def _local_proj_bwd():
        dloc = jnp.transpose(dacc_ref[...], (1, 2, 0))        # (bp,bk,c)
        xl = xloc_ref[...]                                    # (bp,bk,cz)
        bp_, bk = xl.shape[0], xl.shape[1]
        h = dot(xl, wloc_ref[...], (((2,), (0,)), ((), ())))
        h = h + bloc_ref[...].astype(jnp.float32)[None]
        val, sg = h[..., :c], jax.nn.sigmoid(h[..., c:])
        dh = jnp.concatenate([dloc * sg, dloc * val * sg * (1.0 - sg)],
                             axis=-1)
        dx_ref[...] = dot(
            dh, wloc_ref[...], (((2,), (1,)), ((), ()))).astype(dx_ref.dtype)
        xl2 = xl.reshape(bp_ * bk, -1).astype(jnp.float32)
        dh2 = dh.reshape(bp_ * bk, -1)
        dwloc_ref[...] += dot(
            xl2, dh2, (((0,), (0,)), ((), ())))
        dbloc_ref[...] += jnp.sum(dh2, 0)[None]


def triangle_mult_bwd_dx(ds, x_loc, x_str, w_loc, b_loc, w_str, b_str, *,
                         block_p: int = None, block_q: int = None,
                         block_k: int = None, interpret: bool):
    """Contraction + projection backward for ONE operand side.

    ``ds`` (r_p, r_q, c) is the saved-contraction cotangent with the LOCAL
    side's rows leading (pass ``ds.swapaxes(0, 1)`` with swapped operands /
    weights for the other side); x_loc (r_p, r_k, c_z) is the local
    projection source, x_str (r_q, r_k, c_z) the streamed one.  Returns
    ``(dx_loc, dw_loc, db_loc)`` with the weight grads in fp32.
    """
    r_p, r_q, c = ds.shape
    r_k = x_loc.shape[1]
    rows, ks = _tiles(x_loc.dtype)
    bp_ = evo_block_size(r_p, block_p or rows)
    bq = evo_block_size(r_q, block_q or rows)
    bk = evo_block_size(r_k, block_k or ks)
    c_z = x_loc.shape[2]
    w_ops = [w_loc, b_loc.reshape(1, -1), w_str, b_str.reshape(1, -1)]
    dx, dw, db = pl.pallas_call(
        functools.partial(_tri_bwd_dx_kernel, c_hidden=c,
                          precision=contract_precision(x_loc.dtype)),
        out_shape=[jax.ShapeDtypeStruct((r_p, r_k, c_z), x_loc.dtype),
                   jax.ShapeDtypeStruct(w_loc.shape, jnp.float32),
                   jax.ShapeDtypeStruct((1, w_loc.shape[1]), jnp.float32)],
        grid=(r_p // bp_, r_k // bk, r_q // bq),
        in_specs=[
            pl.BlockSpec((bp_, bq, c), lambda p, k, q: (p, q, 0)),    # ds
            pl.BlockSpec((bp_, bk, c_z), lambda p, k, q: (p, k, 0)),  # x_loc
            pl.BlockSpec((bq, bk, c_z), lambda p, k, q: (q, k, 0)),   # x_str
        ] + [_const_spec(o) for o in w_ops],
        out_specs=[
            pl.BlockSpec((bp_, bk, c_z), lambda p, k, q: (p, k, 0)),
            _const_spec(w_loc),
            _const_spec((1, w_loc.shape[1])),
        ],
        scratch_shapes=[pltpu.VMEM((c, bp_, bk), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(ds, x_loc, x_str, *w_ops)
    return dx, dw, db.reshape(-1)
