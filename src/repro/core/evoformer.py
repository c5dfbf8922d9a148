"""Evoformer: MSA stack, pair stack, outer-product mean, and the three block
variants of paper Fig. 1:

* ``af2``      — serial (Fig 1a): MSA stack -> OPM -> pair stack.
* ``multimer`` — OPM first (Fig 1b): OPM -> {MSA stack, pair stack}.
* ``parallel`` — OPM last (Fig 1c, the paper's contribution): the MSA branch
  and the pair branch are fully independent; all cross-communication happens
  at the end of the block.  This is the property Branch Parallelism exploits.

All functions operate on one protein: ``msa`` (s, r, c_m), ``pair`` (r, r, c_z).
Batching is vmapped at the model level (paper: 1 protein per device).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.config import EvoformerConfig
from repro.nn.attention import attention
from repro.nn import layers as nn

Params = dict


class EvoMasks(NamedTuple):
    """Validity masks for a padded protein (inference buckets, DESIGN.md §10).

    ``rows`` (s,): valid MSA rows of THIS stack (main vs extra differ);
    ``res`` (r,): valid residues.  1.0 = real, 0.0 = bucket padding.  A
    NamedTuple so it crosses jit/vmap boundaries as a pytree; ``None``
    anywhere means "everything valid" (the training path pays zero cost).
    """
    rows: jnp.ndarray
    res: jnp.ndarray


def mask_bias(key_mask: jnp.ndarray) -> jnp.ndarray:
    """(S,) validity -> (S,) additive attention bias: 0 valid / -1e9 padded.

    Folded into the (h, S, S) pair bias so EVERY attention impl — reference,
    chunked, pallas, evo_pallas — masks padded keys through the one code path
    it already has (the fused kernels take the bias add in-kernel; no masked
    kernel variants needed)."""
    return (key_mask.astype(jnp.float32) - 1.0) * 1e9


# ---------------------------------------------------------------------------
# Dropout with shared axes (AF2 row-/column-wise dropout)
# ---------------------------------------------------------------------------

def shared_dropout(key, x, rate: float, *, shared_axis: int,
                   deterministic: bool) -> jnp.ndarray:
    if deterministic or rate == 0.0:
        return x
    shape = list(x.shape)
    shape[shared_axis] = 1
    keep = jax.random.bernoulli(key, 1.0 - rate, shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated attention (AF2 suppl. Algorithm 7) — used by MSA row/col + triangle att
# ---------------------------------------------------------------------------

def gated_attention_init(key, c_in: int, c_hidden: int, n_head: int,
                         *, c_bias_in: Optional[int] = None) -> Params:
    ks = nn.split_keys(key, 6)
    hc = n_head * c_hidden
    p = {
        "ln": nn.layernorm_init(c_in),
        "q": nn.dense_init(ks[0], c_in, hc, use_bias=False),
        "k": nn.dense_init(ks[1], c_in, hc, use_bias=False),
        "v": nn.dense_init(ks[2], c_in, hc, use_bias=False),
        "gate": nn.dense_init(ks[3], c_in, hc, scale="zeros"),
        "out": nn.dense_init(ks[4], hc, c_in, scale="zeros"),
    }
    # AF2 gating init: sigmoid(0 + 1) ~ open gate
    p["gate"]["b"] = jnp.ones_like(p["gate"]["b"])
    if c_bias_in is not None:
        p["bias_ln"] = nn.layernorm_init(c_bias_in)
        p["bias_proj"] = nn.dense_init(ks[5], c_bias_in, n_head, use_bias=False)
    return p


def project_attention_bias(p: Params, bias_input: jnp.ndarray) -> jnp.ndarray:
    """(S, S', c_z) -> (h, S, S') attention bias (LN + headwise projection)."""
    zb = nn.layernorm(p["bias_ln"], bias_input)
    return jnp.moveaxis(nn.dense(p["bias_proj"], zb), -1, -3)


def gated_attention(p: Params, x: jnp.ndarray, *, n_head: int, c_hidden: int,
                    bias_input: Optional[jnp.ndarray] = None,
                    bias: Optional[jnp.ndarray] = None,
                    key_mask: Optional[jnp.ndarray] = None,
                    attention_impl: str = "chunked",
                    attention_chunk: int = 256) -> jnp.ndarray:
    """x: (..., L, S, c) — attention along S independently for each leading L.

    ``bias_input`` projects a pair rep to the bias internally; alternatively a
    precomputed ``bias`` (h, S, S) can be passed (DAP gathers it sharded).
    ``key_mask`` (S,) marks valid keys (padded-bucket inference): it is folded
    into the additive bias, so all impls (incl. the fused kernels) honor it.
    """
    h = nn.layernorm(p["ln"], x)
    *lead, s, _ = x.shape
    q = nn.dense(p["q"], h).reshape(*lead, s, n_head, c_hidden)
    k = nn.dense(p["k"], h).reshape(*lead, s, n_head, c_hidden)
    v = nn.dense(p["v"], h).reshape(*lead, s, n_head, c_hidden)
    if bias_input is not None:
        assert bias is None
        bias = project_attention_bias(p, bias_input)       # (h, S, S)
    if key_mask is not None:
        mb = mask_bias(key_mask)                           # (S,) 0 / -1e9
        base = 0.0 if bias is None else bias.astype(jnp.float32)
        # materialize (h, S, S): the Pallas kernels require an exact-shape
        # bias operand, and the chunked path T-chunks it lazily anyway
        bias = jnp.broadcast_to(base + mb, (n_head, s, s))
    if attention_impl == "evo_pallas":
        from repro.kernels.flash_attention import evo_supported
        if not evo_supported(s):
            # poorly factorable length: the kernel would tile near-rowwise,
            # so the chunked XLA path below is the faster exact fallback
            attention_impl = "chunked"
    if attention_impl == "evo_pallas":
        # Fused Pallas hot path: bias add + softmax + sigmoid gate in one
        # kernel — the (L, S, H, C) attention output never round-trips HBM
        # before gating.  The gate dense stays outside (it is a GEMM); its
        # pre-sigmoid logits feed the kernel epilogue.
        from repro.kernels import ops as kops
        gate = nn.dense(p["gate"], h).reshape(*lead, s, n_head, c_hidden)
        flat = lambda t: t.reshape(-1, s, n_head, c_hidden)
        if bias is None:  # e.g. MSA column attention: no pair bias —
            # the bias add is compiled out of the kernel entirely
            o = kops.evo_attention_nobias(flat(q), flat(k), flat(v), flat(gate))
        else:
            o = kops.evo_attention(flat(q), flat(k), flat(v), bias, flat(gate))
        o = o.reshape(*lead, s, n_head * c_hidden).astype(x.dtype)
        return nn.dense(p["out"], o)
    o = attention(q, k, v, bias=bias, impl=attention_impl,
                  chunk_size=attention_chunk)
    g = jax.nn.sigmoid(nn.dense(p["gate"], h))
    o = (g * o.reshape(*lead, s, n_head * c_hidden)).astype(x.dtype)
    return nn.dense(p["out"], o)


def global_attention_init(key, c_in: int, c_hidden: int, n_head: int) -> Params:
    ks = nn.split_keys(key, 5)
    hc = n_head * c_hidden
    p = {
        "ln": nn.layernorm_init(c_in),
        "q": nn.dense_init(ks[0], c_in, hc, use_bias=False),
        "k": nn.dense_init(ks[1], c_in, c_hidden, use_bias=False),
        "v": nn.dense_init(ks[2], c_in, c_hidden, use_bias=False),
        "gate": nn.dense_init(ks[3], c_in, hc, scale="zeros"),
        "out": nn.dense_init(ks[4], hc, c_in, scale="zeros"),
    }
    p["gate"]["b"] = jnp.ones_like(p["gate"]["b"])
    return p


def global_attention(p: Params, x: jnp.ndarray, *, n_head: int,
                     c_hidden: int,
                     key_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Global (mean-query) attention along S: x (..., L, S, c) -> same.

    Extra-MSA column attention (AF2 Algorithm 19): one averaged query per
    column, shared K/V heads; O(L*S) not O(L*S^2).  ``key_mask`` (S,) drops
    padded rows from BOTH the averaged query and the softmax (a padded row
    would otherwise shift the mean query of every valid column).
    """
    h = nn.layernorm(p["ln"], x)
    *lead, s, _ = x.shape
    if key_mask is not None:
        km = key_mask.astype(h.dtype)
        q_avg = (jnp.sum(h * km[:, None], axis=-2)
                 / jnp.maximum(jnp.sum(km), 1.0).astype(h.dtype))
    else:
        q_avg = jnp.mean(h, axis=-2)                                # (..., c)
    q = nn.dense(p["q"], q_avg).reshape(*lead, n_head, c_hidden)
    q = q * (c_hidden ** -0.5)
    k = nn.dense(p["k"], h)                                         # (..., S, c_h)
    v = nn.dense(p["v"], h)
    logits = jnp.einsum("...hc,...sc->...hs", q, k).astype(jnp.float32)
    if key_mask is not None:
        logits = logits + mask_bias(key_mask)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    o = jnp.einsum("...hs,...sc->...hc", w, v,
                   preferred_element_type=jnp.float32).astype(v.dtype)
    g = jax.nn.sigmoid(nn.dense(p["gate"], h))                      # (..., S, h*c)
    o = g * o.reshape(*lead, 1, n_head * c_hidden)
    return nn.dense(p["out"], o.astype(x.dtype))


# ---------------------------------------------------------------------------
# Transition (Algorithm 9/15)
# ---------------------------------------------------------------------------

def transition_init(key, c: int, factor: int) -> Params:
    ks = nn.split_keys(key, 2)
    return {
        "ln": nn.layernorm_init(c),
        "w1": nn.dense_init(ks[0], c, factor * c),
        "w2": nn.dense_init(ks[1], factor * c, c, scale="zeros"),
    }


def transition(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = nn.layernorm(p["ln"], x)
    return nn.dense(p["w2"], jax.nn.relu(nn.dense(p["w1"], h)))


# ---------------------------------------------------------------------------
# Outer product mean (Algorithm 10) — the cross-branch communication
# ---------------------------------------------------------------------------

def opm_init(key, c_m: int, c_hidden: int, c_z: int) -> Params:
    ks = nn.split_keys(key, 3)
    return {
        "ln": nn.layernorm_init(c_m),
        "a": nn.dense_init(ks[0], c_m, c_hidden),
        "b": nn.dense_init(ks[1], c_m, c_hidden),
        "out": nn.dense_init(ks[2], c_hidden * c_hidden, c_z, scale="zeros"),
    }


def _mask_opm_operands(a, b, row_mask, n_rows: int):
    """Zero padded MSA rows of the OPM operands and return the matching mean
    denominator (the number of VALID rows, not the padded row count)."""
    if row_mask is None:
        return a, b, float(n_rows)
    rm = row_mask.astype(a.dtype)[:, None, None]
    denom = jnp.maximum(jnp.sum(row_mask.astype(jnp.float32)), 1.0)
    return a * rm, b * rm, denom


def outer_product_mean(p: Params, msa: jnp.ndarray,
                       row_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """msa (s, r, c_m) -> pair update (r, r, c_z).  Naive oracle: materializes
    the full (r, r, c_hidden^2) outer-product tensor before projecting."""
    h = nn.layernorm(p["ln"], msa)
    a = nn.dense(p["a"], h)                                   # (s, r, c)
    b = nn.dense(p["b"], h)
    a, b, denom = _mask_opm_operands(a, b, row_mask, msa.shape[0])
    outer = jnp.einsum("sic,sjd->ijcd", a, b) / denom
    outer = outer.reshape(*outer.shape[:2], -1)
    return nn.dense(p["out"], outer.astype(msa.dtype))


def opm_contract(a: jnp.ndarray, b: jnp.ndarray, w: jnp.ndarray,
                 b_out: jnp.ndarray, denom: float, out_dtype,
                 row_chunk: int = 32) -> jnp.ndarray:
    """Fused OPM contraction: ``out[i,j] = ((Σ_s a[s,i] ⊗ b[s,j])/denom) · W``.

    a (s, r_i, c); b (s, r_j, d); w (c*d, c_z).  The (r_i, r_j, c*d)
    outer-product tensor is never materialized — residue-row chunks of the
    outer product are contracted directly against the output projection, so
    the peak temp is (row_chunk, r_j, c*d).  Shared by the serial and DAP
    (i-sharded) OPM paths.
    """
    s, r_i, c = a.shape
    d = b.shape[-1]
    wr = w.reshape(c, d, w.shape[-1])
    rc = min(row_chunk, r_i)
    pad = (-r_i) % rc
    a_p = jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a
    chunks = jnp.moveaxis(a_p.reshape(s, (r_i + pad) // rc, rc, c), 1, 0)

    def one_chunk(a_c):                                       # (s, rc, c)
        # fp32 accumulation over s (AMP policy: bf16 sums over thousands of
        # MSA rows lose mantissa exactly where the signal is a mean)
        outer = jnp.einsum("sic,sjd->ijcd", a_c, b,
                           preferred_element_type=jnp.float32) / denom
        return jnp.einsum("ijcd,cdz->ijz", outer.astype(out_dtype), wr)

    # checkpoint: without it AD saves each chunk's (rc, r_j, c, d) outer
    # tensor as a stacked residual for the w-gradient — the full (r, r, c*d)
    # this impl exists to avoid, just split across the ys of the scan
    out = jax.lax.map(jax.checkpoint(one_chunk), chunks)      # (n, rc, r_j, z)
    out = out.reshape(-1, b.shape[1], wr.shape[-1])[:r_i]
    # bias added in f32, as in nn.dense: its gradient sums all r_i x r_j
    # positions, and DAP splits the r_i rows across devices
    return (out.astype(jnp.float32) + b_out.astype(jnp.float32)).astype(
        jnp.result_type(out.dtype, b_out.dtype))


def outer_product_mean_fused(p: Params, msa: jnp.ndarray, *,
                             row_chunk: int = 32,
                             row_mask: Optional[jnp.ndarray] = None
                             ) -> jnp.ndarray:
    """Fused OPM: numerically matches :func:`outer_product_mean` but the
    (r, r, c_hidden^2) intermediate never exists (see :func:`opm_contract`)."""
    h = nn.layernorm(p["ln"], msa)
    a = nn.dense(p["a"], h)                                   # (s, r, c)
    b = nn.dense(p["b"], h)
    a, b, denom = _mask_opm_operands(a, b, row_mask, msa.shape[0])
    return opm_contract(a, b, p["out"]["w"], p["out"]["b"],
                        denom, msa.dtype, row_chunk=row_chunk)


def opm_apply(p: Params, cfg: EvoformerConfig, msa: jnp.ndarray,
              row_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """OPM dispatch on ``cfg.opm_impl`` ('fused' | 'naive')."""
    if cfg.opm_impl == "fused":
        return outer_product_mean_fused(p, msa, row_chunk=cfg.opm_chunk,
                                        row_mask=row_mask)
    if cfg.opm_impl == "naive":
        return outer_product_mean(p, msa, row_mask=row_mask)
    raise ValueError(f"unknown opm impl {cfg.opm_impl!r}")


# ---------------------------------------------------------------------------
# Triangle multiplicative update (Algorithms 11/12)
# ---------------------------------------------------------------------------

def triangle_mult_init(key, c_z: int, c_hidden: int) -> Params:
    ks = nn.split_keys(key, 6)
    p = {
        "ln_in": nn.layernorm_init(c_z),
        "a": nn.dense_init(ks[0], c_z, c_hidden),
        "a_gate": nn.dense_init(ks[1], c_z, c_hidden, scale="zeros"),
        "b": nn.dense_init(ks[2], c_z, c_hidden),
        "b_gate": nn.dense_init(ks[3], c_z, c_hidden, scale="zeros"),
        "ln_out": nn.layernorm_init(c_hidden),
        "out": nn.dense_init(ks[4], c_hidden, c_z, scale="zeros"),
        "gate": nn.dense_init(ks[5], c_z, c_z, scale="zeros"),
    }
    for g in ("a_gate", "b_gate", "gate"):
        p[g]["b"] = jnp.ones_like(p[g]["b"])
    return p


def triangle_mult(p: Params, z: jnp.ndarray, *, outgoing: bool,
                  k_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Reference (oracle) triangle-multiplicative update.

    The k-contraction accumulates in fp32 (``preferred_element_type``): under
    the AMP policy a/b are bf16 and a bf16 accumulation over r >= 128 terms
    loses ~half the mantissa — the reference must stay a valid numerical
    oracle for the chunked/Pallas impls (pinned by tests/test_triangle.py).

    ``k_mask`` (r,) zeroes padded residues' contributions to the
    k-contraction (the gated projection of a padded-but-nonzero pair entry
    is NOT zero — sigmoid(gate_bias)·proj_bias survives any input).
    """
    x = nn.layernorm(p["ln_in"], z)
    a = jax.nn.sigmoid(nn.dense(p["a_gate"], x)) * nn.dense(p["a"], x)
    b = jax.nn.sigmoid(nn.dense(p["b_gate"], x)) * nn.dense(p["b"], x)
    if k_mask is not None:
        km = k_mask.astype(a.dtype)
        # the contracted axis is k: axis 1 for outgoing (ik), 0 for incoming
        a = a * (km[None, :, None] if outgoing else km[:, None, None])
    if outgoing:
        o = jnp.einsum("ikc,jkc->ijc", a, b,   # 'outgoing' edges
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("kic,kjc->ijc", a, b,   # 'incoming' edges
                       preferred_element_type=jnp.float32)
    o = nn.dense(p["out"], nn.layernorm(p["ln_out"], o.astype(z.dtype)))
    g = jax.nn.sigmoid(nn.dense(p["gate"], x))
    return (g * o).astype(z.dtype)


def _tri_mult_packed_weights(p: Params):
    """[value | gate] packing of the a/b projections for the Pallas kernel."""
    w_a = jnp.concatenate([p["a"]["w"], p["a_gate"]["w"]], axis=1)
    b_a = jnp.concatenate([p["a"]["b"], p["a_gate"]["b"]])
    w_b = jnp.concatenate([p["b"]["w"], p["b_gate"]["w"]], axis=1)
    b_b = jnp.concatenate([p["b"]["b"], p["b_gate"]["b"]])
    return w_a, b_a, w_b, b_b


def _tri_mult_onepass(p: Params, xa: jnp.ndarray, xb: jnp.ndarray,
                      xg: jnp.ndarray, *, outgoing: bool, out_dtype,
                      k_mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The chunked impl when c_hidden <= c_z: each gated operand projected
    once, one fp32 k-contraction, then the out-LN/out-proj/gate epilogue.
    Its largest tensors are the fp32 contraction (r_i, r_j, c_hidden) and
    the reps themselves.  Incoming edges contract over axis 0 as given:
    swapping the rep first costs a transpose copy of it on a TPU."""
    a = jax.nn.sigmoid(nn.dense(p["a_gate"], xa)) * nn.dense(p["a"], xa)
    if k_mask is not None:
        km = k_mask.astype(a.dtype)
        a = a * (km[None, :, None] if outgoing else km[:, None, None])
    b = jax.nn.sigmoid(nn.dense(p["b_gate"], xb)) * nn.dense(p["b"], xb)
    o = jnp.einsum("ikc,jkc->ijc" if outgoing else "kic,kjc->ijc", a, b,
                   preferred_element_type=jnp.float32)
    o = nn.dense(p["out"], nn.layernorm(p["ln_out"], o.astype(out_dtype)))
    g = jax.nn.sigmoid(nn.dense(p["gate"], xg))
    return (g * o).astype(out_dtype)


def triangle_mult_fused(p: Params, xa: jnp.ndarray, xb: jnp.ndarray,
                        xg: jnp.ndarray, *, impl: str, chunk: int = 64,
                        out_dtype=None, outgoing: bool = True,
                        k_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Fused triangle-mult core shared by the serial and DAP paths.

    Operands are already LN'd so that ``o[i,j,c] = sum_k a(xa[i,k])·
    b(xb[j,k])`` (outgoing) or ``sum_k a(xa[k,i])·b(xb[k,j])`` (incoming,
    ``outgoing=False``), which also covers DAP row-sharding (i is a shard
    of rows, xb gathered — see ``parallel.dap.dap_triangle_mult``).  The
    Pallas kernel and the slab path take incoming edges as outgoing ones on
    the transposed operands.

    impl='pallas': the Pallas kernel (``kernels.triangle``) — nothing
    between xa/xb and the gated output touches HBM.  impl='chunked': XLA.
    When c_hidden <= c_z each gated projection holds no more elements than
    the rep passed in, so it runs in one pass (``_tri_mult_onepass``): a and
    b projected once over the whole operand, one fp32 k-contraction, one
    epilogue.  When c_hidden > c_z, i-rows are processed in ``chunk`` slabs,
    each running a k-chunked fp32 online accumulation followed immediately
    by its out-LN/out-proj/gate epilogue, so no full (r, r, c_hidden)
    projection is ever materialized; ``chunk`` is that slab path's row and
    k tile and means nothing to the one-pass path.  Neither path builds the
    (r, r, 2·c_hidden) gated-projection pair (jaxpr-pinned by
    tests/test_triangle.py).

    ``k_mask`` (r_k,) additionally drops padded-bucket residues from the
    k-contraction (inference; both impls honor it — the Pallas kernel takes
    it as a streamed operand via the forward-only masked entry point).
    """
    out_dtype = out_dtype or xg.dtype
    if impl == "chunked" and p["a"]["w"].shape[1] <= xa.shape[-1]:
        with jax.named_scope("tri_mult_onepass"):
            return _tri_mult_onepass(p, xa, xb, xg, outgoing=outgoing,
                                     out_dtype=out_dtype, k_mask=k_mask)
    if not outgoing:
        xa, xb = xa.swapaxes(0, 1), xb.swapaxes(0, 1)
    if impl == "pallas":
        from repro.kernels import ops as kops
        w_a, b_a, w_b, b_b = _tri_mult_packed_weights(p)
        packed = (w_a, b_a, w_b, b_b,
                  p["ln_out"]["scale"], p["ln_out"]["bias"],
                  p["out"]["w"], p["out"]["b"],
                  p["gate"]["w"], p["gate"]["b"])
        if k_mask is None:
            y = kops.triangle_mult(xa, xb, xg, *packed)
        else:
            y = kops.triangle_mult_masked(xa, xb, xg, k_mask, *packed)
        return y.astype(out_dtype)
    if impl != "chunked":
        raise ValueError(f"unknown tri_mult impl {impl!r}")

    r_i, r_k, _ = xa.shape
    kc = max(1, min(chunk, r_k))
    ic = max(1, min(chunk, r_i))
    kpad, ipad = (-r_k) % kc, (-r_i) % ic
    n_k = (r_k + kpad) // kc
    pad_k = lambda t: (jnp.pad(t, ((0, 0), (0, kpad), (0, 0)))
                       if kpad else t)
    # padded k columns project to sigmoid(b_gate)*b_val != 0: mask them out
    # (chunk padding always; bucket padding when a k_mask is given)
    k_valid = jnp.arange(n_k * kc).reshape(n_k, kc) < r_k
    if k_mask is not None:
        km = k_mask.astype(bool)
        if kpad:
            km = jnp.pad(km, (0, kpad), constant_values=False)
        k_valid = k_valid & km.reshape(n_k, kc)
    k_valid = k_valid[..., None]

    def gated(pa, pg, t):
        return jax.nn.sigmoid(nn.dense(pg, t)) * nn.dense(pa, t)

    xb_k = jnp.moveaxis(pad_k(xb).reshape(xb.shape[0], n_k, kc, -1), 1, 0)

    xa_p = pad_k(xa)
    xg_p = xg
    if ipad:
        xa_p = jnp.pad(xa_p, ((0, ipad), (0, 0), (0, 0)))
        xg_p = jnp.pad(xg, ((0, ipad), (0, 0), (0, 0)))
    n_i = (r_i + ipad) // ic
    xa_c = xa_p.reshape(n_i, ic, r_k + kpad, xa.shape[2])
    xg_c = xg_p.reshape(n_i, ic, *xg.shape[1:])

    def one_row_slab(inp):
        xa_s, xg_s = inp                                  # (ic, r_k+p, c_z)
        xa_k = jnp.moveaxis(xa_s.reshape(ic, n_k, kc, -1), 1, 0)

        def k_step(acc, kin):
            xak, xbk, valid = kin
            a = gated(p["a"], p["a_gate"], xak) * valid   # (ic, kc, c)
            b = gated(p["b"], p["b_gate"], xbk)           # (r_j, kc, c)
            return acc + jnp.einsum("ikc,jkc->ijc", a, b,
                                    preferred_element_type=jnp.float32), None

        c_hidden = p["a"]["w"].shape[1]
        acc0 = jnp.zeros((ic, xb.shape[0], c_hidden), jnp.float32)
        acc, _ = jax.lax.scan(k_step, acc0, (xa_k, xb_k, k_valid))
        o = nn.dense(p["out"], nn.layernorm(p["ln_out"],
                                            acc.astype(out_dtype)))
        g = jax.nn.sigmoid(nn.dense(p["gate"], xg_s))
        return (g * o).astype(out_dtype)

    out = jax.lax.map(one_row_slab, (xa_c, xg_c))         # (n_i, ic, r_j, z)
    return out.reshape(-1, *out.shape[2:])[:r_i]


def tri_mult_supported(r_i: int, r_j: int, r_k: int) -> bool:
    """Whether the Pallas triangle kernel tiles these extents efficiently
    (same power-of-two-divisor criterion as the attention kernel)."""
    from repro.kernels.flash_attention import evo_supported
    return all(evo_supported(n) for n in (r_i, r_j, r_k))


def tri_mult_apply(p: Params, cfg: EvoformerConfig, z: jnp.ndarray, *,
                   outgoing: bool,
                   k_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Triangle-mult dispatch on ``cfg.tri_mult_impl``
    ('reference' | 'chunked' | 'pallas').  ``k_mask`` (r,) marks valid
    residues on the contracted axis (padded-bucket inference)."""
    impl = cfg.tri_mult_impl
    if impl == "pallas" and not tri_mult_supported(*z.shape[:2], z.shape[0]):
        impl = "chunked"  # poorly factorable r: near-rowwise tiles — fall back
    if impl == "reference":
        return triangle_mult(p, z, outgoing=outgoing, k_mask=k_mask)
    if impl not in ("chunked", "pallas"):
        raise ValueError(f"unknown tri_mult impl {impl!r}")
    x = nn.layernorm(p["ln_in"], z)
    return triangle_mult_fused(p, x, x, x, impl=impl,
                               chunk=cfg.tri_mult_chunk, out_dtype=z.dtype,
                               outgoing=outgoing, k_mask=k_mask)


# ---------------------------------------------------------------------------
# Evoformer block: branches + variants
# ---------------------------------------------------------------------------

def evoformer_block_init(key, cfg: EvoformerConfig) -> Params:
    ks = nn.split_keys(key, 9)
    col_attn = (global_attention_init(ks[1], cfg.c_m, cfg.c_hidden_att, cfg.n_head_msa)
                if cfg.global_column_attn else
                gated_attention_init(ks[1], cfg.c_m, cfg.c_hidden_att, cfg.n_head_msa))
    return {
        "row_attn": gated_attention_init(ks[0], cfg.c_m, cfg.c_hidden_att,
                                         cfg.n_head_msa, c_bias_in=cfg.c_z),
        "col_attn": col_attn,
        "msa_trans": transition_init(ks[2], cfg.c_m, cfg.transition_factor),
        "opm": opm_init(ks[3], cfg.c_m, cfg.c_hidden_opm, cfg.c_z),
        "tri_mul_out": triangle_mult_init(ks[4], cfg.c_z, cfg.c_hidden_mul),
        "tri_mul_in": triangle_mult_init(ks[5], cfg.c_z, cfg.c_hidden_mul),
        "tri_att_start": gated_attention_init(ks[6], cfg.c_z, cfg.c_hidden_pair_att,
                                              cfg.n_head_pair, c_bias_in=cfg.c_z),
        "tri_att_end": gated_attention_init(ks[7], cfg.c_z, cfg.c_hidden_pair_att,
                                            cfg.n_head_pair, c_bias_in=cfg.c_z),
        "pair_trans": transition_init(ks[8], cfg.c_z, cfg.transition_factor),
    }


def msa_branch(p: Params, cfg: EvoformerConfig, msa: jnp.ndarray,
               z_bias_src: jnp.ndarray, *, rng=None,
               deterministic: bool = True,
               masks: Optional[EvoMasks] = None) -> jnp.ndarray:
    """Row attention (pair-biased) -> column attention -> transition.

    ``masks`` (padded-bucket inference): row attention masks padded residue
    KEYS (along r); column attention masks padded MSA-row keys (along s).
    """
    kw = dict(attention_impl=cfg_attention_impl(cfg),
              attention_chunk=cfg_attention_chunk(cfg))
    res_mask = rows_mask = None
    if masks is not None:
        rows_mask, res_mask = masks.rows, masks.res
    # named scopes (``jax.named_scope``) put each sub-op's name, with its
    # dropout and residual add, into the op_name of every XLA op it lowers
    # to: a profile of the step can then be read by sub-op
    with jax.named_scope("msa_row_attn"):
        upd = gated_attention(p["row_attn"], msa, n_head=cfg.n_head_msa,
                              c_hidden=cfg.c_hidden_att,
                              bias_input=z_bias_src, key_mask=res_mask, **kw)
        if rng is not None:
            rng, k = jax.random.split(rng)
            upd = shared_dropout(k, upd, cfg.dropout_msa, shared_axis=0,
                                 deterministic=deterministic)
        msa = msa + upd
    with jax.named_scope("msa_col_attn"):
        if cfg.global_column_attn:
            col = global_attention(p["col_attn"], msa.swapaxes(0, 1),
                                   n_head=cfg.n_head_msa,
                                   c_hidden=cfg.c_hidden_att,
                                   key_mask=rows_mask)
        else:
            col = gated_attention(p["col_attn"], msa.swapaxes(0, 1),
                                  n_head=cfg.n_head_msa,
                                  c_hidden=cfg.c_hidden_att,
                                  key_mask=rows_mask, **kw)
        msa = msa + col.swapaxes(0, 1)
    with jax.named_scope("msa_transition"):
        msa = msa + transition(p["msa_trans"], msa)
    return msa


def pair_branch(p: Params, cfg: EvoformerConfig, z: jnp.ndarray, *, rng=None,
                deterministic: bool = True,
                masks: Optional[EvoMasks] = None) -> jnp.ndarray:
    """Triangle updates + triangle attention + transition.

    ``masks.res`` masks the triangle-mult k-contractions and the triangle
    attention keys (both directions) against padded-bucket residues.
    """
    kw = dict(attention_impl=cfg_attention_impl(cfg),
              attention_chunk=cfg_attention_chunk(cfg))
    res_mask = masks.res if masks is not None else None

    def drop(key_idx, x, shared_axis):
        if rng is None:
            return x
        k = jax.random.fold_in(rng, key_idx)
        return shared_dropout(k, x, cfg.dropout_pair, shared_axis=shared_axis,
                              deterministic=deterministic)

    with jax.named_scope("tri_mult_out"):
        z = z + drop(0, tri_mult_apply(p["tri_mul_out"], cfg, z,
                                       outgoing=True, k_mask=res_mask), 0)
    with jax.named_scope("tri_mult_in"):
        z = z + drop(1, tri_mult_apply(p["tri_mul_in"], cfg, z,
                                       outgoing=False, k_mask=res_mask), 0)
    with jax.named_scope("tri_attn_start"):
        z = z + drop(2, gated_attention(
            p["tri_att_start"], z, n_head=cfg.n_head_pair,
            c_hidden=cfg.c_hidden_pair_att, bias_input=z, key_mask=res_mask,
            **kw), 0)
    with jax.named_scope("tri_attn_end"):
        zt = z.swapaxes(0, 1)
        att_end = gated_attention(p["tri_att_end"], zt,
                                  n_head=cfg.n_head_pair,
                                  c_hidden=cfg.c_hidden_pair_att,
                                  bias_input=zt, key_mask=res_mask, **kw)
        z = z + drop(3, att_end.swapaxes(0, 1), 1)
    with jax.named_scope("pair_transition"):
        z = z + transition(p["pair_trans"], z)
    return z


def evoformer_block(p: Params, cfg: EvoformerConfig, msa: jnp.ndarray,
                    z: jnp.ndarray, *, rng=None, deterministic: bool = True,
                    masks: Optional[EvoMasks] = None):
    """Dispatch on cfg.variant (paper Fig 1a/1b/1c).

    ``masks`` (padded-bucket inference, DESIGN.md §10): residue/row validity
    threaded into every op that mixes across positions — attention keys,
    OPM row sum, triangle k-contraction.  ``None`` = training fast path.
    """
    rngs = (None, None) if rng is None else tuple(jax.random.split(rng))
    row_mask = masks.rows if masks is not None else None
    if cfg.variant == "af2":
        msa_out = msa_branch(p, cfg, msa, z, rng=rngs[0],
                             deterministic=deterministic, masks=masks)
        with jax.named_scope("opm"):
            z = z + opm_apply(p["opm"], cfg, msa_out, row_mask=row_mask)
        z_out = pair_branch(p, cfg, z, rng=rngs[1], deterministic=deterministic,
                            masks=masks)
        return msa_out, z_out
    if cfg.variant == "multimer":
        with jax.named_scope("opm"):
            z = z + opm_apply(p["opm"], cfg, msa, row_mask=row_mask)
        msa_out = msa_branch(p, cfg, msa, z, rng=rngs[0],
                             deterministic=deterministic, masks=masks)
        z_out = pair_branch(p, cfg, z, rng=rngs[1], deterministic=deterministic,
                            masks=masks)
        return msa_out, z_out
    if cfg.variant == "parallel":
        # Paper Fig 1c / Fig 4: both branches read only block inputs; the OPM
        # (computed from the MSA branch output) lands at the end of the block.
        msa_out = msa_branch(p, cfg, msa, z, rng=rngs[0],
                             deterministic=deterministic, masks=masks)
        z_out = pair_branch(p, cfg, z, rng=rngs[1], deterministic=deterministic,
                            masks=masks)
        with jax.named_scope("opm"):
            z_out = z_out + opm_apply(p["opm"], cfg, msa_out,
                                      row_mask=row_mask)
        return msa_out, z_out
    raise ValueError(f"unknown Evoformer variant {cfg.variant!r}")


def cfg_attention_impl(cfg: EvoformerConfig) -> str:
    return cfg.attention_impl


def cfg_attention_chunk(cfg: EvoformerConfig) -> int:
    return cfg.attention_chunk
