"""Dynamic Axial Parallelism (FastFold; paper §3.2/§4.3 baseline + hybrid).

DAP shards the *activations* along an axial dimension across a ``dap`` mesh
axis — MSA rep over its row axis ``s``, pair rep over its first residue axis
``i`` — and re-shards with collectives whenever an op needs the other axis:

* row attention / transitions / triangle-start attention: local;
* column attention / triangle-end attention: ``all_to_all`` transpose;
* triangle multiplications: ``all_gather`` of the contracted operand;
* attention biases from the pair rep: project locally, ``all_gather`` heads;
* outer-product mean: ``all_to_all`` to residue shards + ``all_gather`` of
  the right operand.

These are exactly the collectives the paper counts against DAP (Table 5):
at initial-training shapes the activations are small, so the extra
communication + lost per-op intensity make DAP *slower* than serial — which
our roofline reproduces — while at fine-tuning shapes DAP wins back.

All functions run inside ``shard_map``; ``msa_l`` is (s/d, r, c_m) and
``z_l`` is (r/d, r, c_z).

Communication-overlapped schedule (``make_dap_block_fn(overlap=True)``,
FastFold's duplex idiom; DESIGN.md §3): the 'parallel' variant's branches
both consume the BLOCK-INPUT pair rep, so the block can carry
``z_full == all_gather(z_l)`` prefetched during the PREVIOUS block's
compute.  Consuming it replaces two head-of-block gathers (row-attention
bias, tri-mult-out operand) with replicated per-position math — bitwise
identical, because LayerNorm/dense commute elementwise with
gather-as-concat — and the single replacement gather (of the block's output
``z_l``) is issued at the body's end, a full block of compute ahead of its
consumer, where XLA's async-collective pipelining (see
``launch.train --print-tpu-env``) hides it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import evoformer as evo
from repro.core.config import EvoformerConfig
from repro.nn import layers as nn

AXIS = "dap"


def _all_gather(x, axis_name=AXIS, axis=0):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def _transpose_shards(x, axis_name=AXIS):
    """(a/d, b, ...) -> (a, b/d, ...): all_to_all re-shard."""
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=0,
                              tiled=True)


def _untranspose_shards(x, axis_name=AXIS):
    """(a, b/d, ...) -> (a/d, b, ...)."""
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=1,
                              tiled=True)


# ---------------------------------------------------------------------------
# MSA branch under DAP
# ---------------------------------------------------------------------------

def dap_msa_branch(p, cfg: EvoformerConfig, msa_l, z_l, *, rng=None,
                   deterministic: bool = True, axis_name: str = AXIS,
                   masks=None, z_full=None):
    """``masks`` (``evo.EvoMasks``, padded-bucket inference): DAP shards the
    QUERY axes only — every masked (key) axis is consumed at full extent, so
    the full-length masks thread straight through (DESIGN.md §10).

    ``z_full`` (overlap schedule): the prefetched ``all_gather(z_l)`` from
    the previous block's issue phase.  When present, the row-attention bias
    is projected from it directly (per-position LN+dense on the gathered
    tensor == gather of the per-shard projection, bitwise) — no collective
    on this block's critical path."""
    kw = dict(attention_impl=cfg.attention_impl,
              attention_chunk=cfg.attention_chunk)
    res_mask = rows_mask = None
    if masks is not None:
        rows_mask, res_mask = masks.rows, masks.res
    if z_full is not None:
        bias = evo.project_attention_bias(p["row_attn"], z_full)  # (h, r, r)
    else:
        # row attention: local over s-shard; bias gathered over the i-shard
        bias_l = evo.project_attention_bias(p["row_attn"], z_l)  # (h, r/d, r)
        bias = _all_gather(bias_l, axis_name, axis=1)            # (h, r, r)
    upd = evo.gated_attention(p["row_attn"], msa_l, n_head=cfg.n_head_msa,
                              c_hidden=cfg.c_hidden_att, bias=bias,
                              key_mask=res_mask, **kw)
    if rng is not None:
        rng, k = jax.random.split(rng)
        upd = evo.shared_dropout(k, upd, cfg.dropout_msa, shared_axis=0,
                                 deterministic=deterministic)
    msa_l = msa_l + upd
    # column attention: re-shard to residue shards, attend over full s
    msa_r = _transpose_shards(msa_l, axis_name)                # (s, r/d, c)
    if cfg.global_column_attn:
        col = evo.global_attention(p["col_attn"], msa_r.swapaxes(0, 1),
                                   n_head=cfg.n_head_msa,
                                   c_hidden=cfg.c_hidden_att,
                                   key_mask=rows_mask)
    else:
        col = evo.gated_attention(p["col_attn"], msa_r.swapaxes(0, 1),
                                  n_head=cfg.n_head_msa,
                                  c_hidden=cfg.c_hidden_att,
                                  key_mask=rows_mask, **kw)
    msa_r = msa_r + col.swapaxes(0, 1)
    msa_l = _untranspose_shards(msa_r, axis_name)              # (s/d, r, c)
    msa_l = msa_l + evo.transition(p["msa_trans"], msa_l)
    return msa_l


def dap_outer_product_mean(p, msa_l, n_seq_total: int = None,
                           axis_name: str = AXIS,
                           row_chunk: int = 32, opm_impl: str = "fused",
                           row_mask=None):
    """OPM with s-sharded MSA -> i-sharded pair update (r/d, r, c_z).

    ``n_seq_total`` is the OPM mean denominator — the stack's TOTAL row
    count.  The default (None) derives it from the local shard shape x the
    dap extent, which is correct for every stack (the main Evoformer sees
    n_seq rows, the extra-MSA stack n_extra_seq; a fixed cfg.n_seq would be
    8x off on the extra stack at initial-training shapes).

    ``row_mask`` (s, full extent) zeroes padded MSA rows after the shards
    are re-gathered to full s, and replaces the denominator by the VALID
    row count (padded-bucket inference).

    With ``opm_impl='fused'`` (the default) uses the fused row-chunked
    contraction (``evo.opm_contract``): even on the local i-shard the
    (r/d, r, c^2) outer tensor is never materialized.
    """
    if n_seq_total is None:
        n_seq_total = msa_l.shape[0] * jax.lax.axis_size(axis_name)
    h = nn.layernorm(p["ln"], msa_l)
    a = nn.dense(p["a"], h)                                    # (s/d, r, c)
    b = nn.dense(p["b"], h)
    a_i = _transpose_shards(a, axis_name)                      # (s, r/d, c)
    b_full = _all_gather(_transpose_shards(b, axis_name),      # (s, r, c)
                         axis_name, axis=1)
    # same masking rule as the serial OPM — one definition, no drift
    a_i, b_full, n_seq_total = evo._mask_opm_operands(
        a_i, b_full, row_mask, n_seq_total)
    if opm_impl == "naive":
        outer = jnp.einsum("sic,sjd->ijcd", a_i, b_full) / n_seq_total
        outer = outer.reshape(*outer.shape[:2], -1)
        return nn.dense(p["out"], outer.astype(msa_l.dtype))
    if opm_impl != "fused":
        raise ValueError(f"unknown opm impl {opm_impl!r}")
    # n_seq_total is already a denominator here: float, or the traced
    # valid-row count when masked (see _mask_opm_operands)
    return evo.opm_contract(a_i, b_full, p["out"]["w"], p["out"]["b"],
                            n_seq_total, msa_l.dtype, row_chunk=row_chunk)


# ---------------------------------------------------------------------------
# Pair branch under DAP
# ---------------------------------------------------------------------------

def dap_triangle_mult(p, z_l, *, outgoing: bool, axis_name: str = AXIS,
                      impl: str = "reference", chunk: int = 64, k_mask=None,
                      z_full=None):
    """Triangle mult on an i-sharded pair rep (z_l (r/d, r, c_z)).

    ``k_mask`` (r, full extent) drops padded residues from the
    k-contraction; in every orientation below the contracted axis is full
    length, so the same full mask applies everywhere.

    impl='reference' keeps the original schedule (project locally, gather /
    re-shard the PROJECTED operands).  The fused impls ('chunked'/'pallas')
    instead gather the LN'd pair rep itself and hand the kernel the
    DAP-oriented operand triple — the gathered tensor is (r, r, c_z) instead
    of (r, r, c_mul) (identical bytes at paper shapes, c_z == c_mul == 128),
    and the projections happen inside the fused core on the gathered rows,
    so the kernel runs unchanged on row-sharded tiles (DESIGN.md §9).

    ``z_full`` (overlap schedule; only valid when ``z_l`` IS the block-input
    pair rep, i.e. the tri-mult-out of the 'parallel' variant): the
    prefetched full pair rep.  The gathered operand is then computed from it
    by replicated per-position math instead of an ``all_gather`` —
    LayerNorm/projections commute elementwise with gather-as-concat, so the
    result is bitwise identical to the sync schedule.
    """
    if impl not in ("reference", "chunked", "pallas"):
        raise ValueError(f"unknown tri_mult impl {impl!r}")
    if impl in ("chunked", "pallas"):
        x_l = nn.layernorm(p["ln_in"], z_l)                    # (r/d, r, cz)
        if z_full is not None:
            x_full = nn.layernorm(p["ln_in"], z_full)          # (r, r, cz)
        else:
            x_full = _all_gather(x_l, axis_name, axis=0)       # (r, r, cz)
        if outgoing:
            # out[i_l, j] = sum_k a(x[i_l, k]) b(x[j, k])
            xa, xb = x_l, x_full
        else:
            # out[i_l, j] = sum_k a(x[k, i_l]) b(x[k, j]): the gathered rep
            # already holds every element — slice this device's i-columns
            # out of it locally (no extra all_to_all)
            lo = jax.lax.axis_index(axis_name) * z_l.shape[0]
            xa = jax.lax.dynamic_slice_in_dim(
                x_full, lo, z_l.shape[0], axis=1)
            xb = x_full
        if impl == "pallas" and not evo.tri_mult_supported(
                z_l.shape[0], *x_full.shape[:2]):
            impl = "chunked"
        return evo.triangle_mult_fused(p, xa, xb, x_l, impl=impl,
                                       chunk=chunk, out_dtype=z_l.dtype,
                                       outgoing=outgoing, k_mask=k_mask)
    x = nn.layernorm(p["ln_in"], z_l)
    a = jax.nn.sigmoid(nn.dense(p["a_gate"], x)) * nn.dense(p["a"], x)
    b = jax.nn.sigmoid(nn.dense(p["b_gate"], x)) * nn.dense(p["b"], x)
    if outgoing:
        # out[i_l, j] = sum_k a[i_l, k] b[j, k]: gather b rows — or, under
        # the overlap schedule, project b from the prefetched full rep
        if z_full is not None:
            xf = nn.layernorm(p["ln_in"], z_full)
            b_full = jax.nn.sigmoid(nn.dense(p["b_gate"], xf)) * \
                nn.dense(p["b"], xf)                           # (r, r, c)
        else:
            b_full = _all_gather(b, axis_name, axis=0)         # (r, r, c)
        if k_mask is not None:
            a = a * k_mask.astype(a.dtype)[None, :, None]
        o = jnp.einsum("ikc,jkc->ijc", a, b_full,
                       preferred_element_type=jnp.float32)
    else:
        # out[i_l, j] = sum_k a[k, i_l] b[k, j]: k is the sharded axis ->
        # re-shard a to (k, i_l), gather b to (k, r)
        a_col = _transpose_shards(a, axis_name)                # (r, r/d, c)
        b_full = _all_gather(b, axis_name, axis=0)             # (r, r, c)
        if k_mask is not None:
            a_col = a_col * k_mask.astype(a_col.dtype)[:, None, None]
        o = jnp.einsum("kic,kjc->ijc", a_col, b_full,
                       preferred_element_type=jnp.float32)
    o = nn.dense(p["out"], nn.layernorm(p["ln_out"], o.astype(z_l.dtype)))
    g = jax.nn.sigmoid(nn.dense(p["gate"], x))
    return (g * o).astype(z_l.dtype)


def dap_pair_branch(p, cfg: EvoformerConfig, z_l, *, rng=None,
                    deterministic: bool = True, axis_name: str = AXIS,
                    masks=None, z_full=None):
    """``z_full`` (overlap schedule): prefetched gather of the BLOCK-INPUT
    pair rep, consumed by the first triangle mult (whose input is exactly
    the block input under the 'parallel' variant)."""
    kw = dict(attention_impl=cfg.attention_impl,
              attention_chunk=cfg.attention_chunk)
    res_mask = masks.res if masks is not None else None

    def drop(key_idx, x, shared_axis):
        if rng is None:
            return x
        k = jax.random.fold_in(rng, key_idx)
        return evo.shared_dropout(k, x, cfg.dropout_pair, shared_axis=shared_axis,
                                  deterministic=deterministic)

    tri_kw = dict(axis_name=axis_name, impl=cfg.tri_mult_impl,
                  chunk=cfg.tri_mult_chunk, k_mask=res_mask)
    z_l = z_l + drop(0, dap_triangle_mult(p["tri_mul_out"], z_l,
                                          outgoing=True, z_full=z_full,
                                          **tri_kw), 0)
    z_l = z_l + drop(1, dap_triangle_mult(p["tri_mul_in"], z_l,
                                          outgoing=False, **tri_kw), 0)
    # starting-node attention: rows local, bias gathered
    bias = _all_gather(evo.project_attention_bias(p["tri_att_start"], z_l),
                       axis_name, axis=1)                      # (h, r, r)
    att = evo.gated_attention(p["tri_att_start"], z_l, n_head=cfg.n_head_pair,
                              c_hidden=cfg.c_hidden_pair_att, bias=bias,
                              key_mask=res_mask, **kw)
    z_l = z_l + drop(2, att, 0)
    # ending-node attention.  The bias is projected from the PRE-transpose
    # shard and gathered over i — elementwise-identical to projecting the
    # transposed shard (LN/dense are per-position), but this way the bias
    # gather does not serially depend on the all_to_all: both collectives
    # are in flight together (the issue half of the duplex schedule)
    bias_t = _all_gather(evo.project_attention_bias(p["tri_att_end"], z_l),
                         axis_name, axis=1).swapaxes(1, 2)     # (h, r[j], r[i])
    zt_l = _transpose_shards(z_l, axis_name).swapaxes(0, 1)    # (r/d[j], r[i], c)
    att_t = evo.gated_attention(p["tri_att_end"], zt_l, n_head=cfg.n_head_pair,
                                c_hidden=cfg.c_hidden_pair_att, bias=bias_t,
                                key_mask=res_mask, **kw)
    zt_l = zt_l + drop(3, att_t, 0)
    z_l = _untranspose_shards(zt_l.swapaxes(0, 1), axis_name)
    z_l = z_l + evo.transition(p["pair_trans"], z_l)
    return z_l


# ---------------------------------------------------------------------------
# DAP Evoformer block (all three variants) + stack wrappers
# ---------------------------------------------------------------------------

def dap_evoformer_block(p, cfg: EvoformerConfig, msa_l, z_l, *, rng=None,
                        deterministic: bool = True, n_seq_total: int = None,
                        axis_name: str = AXIS, masks=None):
    rngs = (None, None) if rng is None else tuple(jax.random.split(rng))
    row_mask = masks.rows if masks is not None else None
    opm = lambda m: dap_outer_product_mean(p["opm"], m, n_seq_total, axis_name,
                                           row_chunk=cfg.opm_chunk,
                                           opm_impl=cfg.opm_impl,
                                           row_mask=row_mask)
    if cfg.variant == "af2":
        msa_l = dap_msa_branch(p, cfg, msa_l, z_l, rng=rngs[0],
                               deterministic=deterministic, axis_name=axis_name,
                               masks=masks)
        z_l = z_l + opm(msa_l)
        z_l = dap_pair_branch(p, cfg, z_l, rng=rngs[1],
                              deterministic=deterministic, axis_name=axis_name,
                              masks=masks)
        return msa_l, z_l
    if cfg.variant == "multimer":
        z_l = z_l + opm(msa_l)
        msa_l = dap_msa_branch(p, cfg, msa_l, z_l, rng=rngs[0],
                               deterministic=deterministic, axis_name=axis_name,
                               masks=masks)
        z_l = dap_pair_branch(p, cfg, z_l, rng=rngs[1],
                              deterministic=deterministic, axis_name=axis_name,
                              masks=masks)
        return msa_l, z_l
    if cfg.variant == "parallel":
        msa_out = dap_msa_branch(p, cfg, msa_l, z_l, rng=rngs[0],
                                 deterministic=deterministic, axis_name=axis_name,
                                 masks=masks)
        z_out = dap_pair_branch(p, cfg, z_l, rng=rngs[1],
                                deterministic=deterministic, axis_name=axis_name,
                                masks=masks)
        return msa_out, z_out + opm(msa_out)
    raise ValueError(cfg.variant)


def dap_evoformer_block_overlap(p, cfg: EvoformerConfig, msa_l, z_l, z_full,
                                *, rng=None, deterministic: bool = True,
                                n_seq_total: int = None,
                                axis_name: str = AXIS, masks=None):
    """Communication-overlapped 'parallel'-variant block (DESIGN.md §3).

    Consume phase: ``z_full`` (the prefetched ``all_gather`` of this block's
    input pair rep, issued by the PREVIOUS block) feeds the row-attention
    bias and the tri-mult-out gathered operand as replicated per-position
    math — the two head-of-block gathers of the sync schedule disappear.
    Issue phase: the gather of the block's OUTPUT pair rep starts at the
    body's end, a full block of compute ahead of its consumer.  Net: one
    fewer collective per block, and the remaining prefetch gather sits where
    XLA's async-collective pipelining can hide it (the
    ``--print-tpu-env`` preset).  Bitwise-identical to the sync schedule:
    every replaced collective is a gather of a per-position map's output,
    and per-position maps commute with gather-as-concat.

    Only the 'parallel' variant qualifies: its MSA and pair branches both
    consume the BLOCK-INPUT pair rep (af2/multimer feed the pair branch a
    mid-block ``z``, for which no prefetch can exist).
    """
    if cfg.variant != "parallel":
        raise ValueError(
            f"the overlapped DAP schedule requires the 'parallel' Evoformer "
            f"variant (both branches consume the block-input pair rep); got "
            f"variant={cfg.variant!r} — use overlap_dap=False or "
            "variant='parallel'")
    rngs = (None, None) if rng is None else tuple(jax.random.split(rng))
    row_mask = masks.rows if masks is not None else None
    msa_out = dap_msa_branch(p, cfg, msa_l, z_l, rng=rngs[0],
                             deterministic=deterministic, axis_name=axis_name,
                             masks=masks, z_full=z_full)
    z_out = dap_pair_branch(p, cfg, z_l, rng=rngs[1],
                            deterministic=deterministic, axis_name=axis_name,
                            masks=masks, z_full=z_full)
    z_out = z_out + dap_outer_product_mean(
        p["opm"], msa_out, n_seq_total, axis_name, row_chunk=cfg.opm_chunk,
        opm_impl=cfg.opm_impl, row_mask=row_mask)
    z_full_next = _all_gather(z_out, axis_name, 0)             # issue half
    return msa_out, z_out, z_full_next


def shard_inputs(msa, z, axis_name: str = AXIS):
    """Slice full (replicated) reps into this device's DAP shards."""
    from repro.parallel.mesh_utils import local_slice
    return local_slice(msa, axis_name, 0), local_slice(z, axis_name, 0)


def unshard_outputs(msa_l, z_l, axis_name: str = AXIS):
    return _all_gather(msa_l, axis_name, 0), _all_gather(z_l, axis_name, 0)


def make_dap_block_fn(n_seq_total: int = None, axis_name: str = AXIS,
                      overlap: bool = False):
    """Adapter matching the ``block_fn`` signature of ``evoformer_stack``.

    With ``overlap=True`` the returned block_fn follows the stack's
    prefetch-carry protocol: it exposes ``prefetch_init`` (the stack-entry
    seed gather) and takes/returns the double-buffered ``prefetch`` operand
    (``z_full == all_gather(z_l)``) alongside (msa, z).
    """
    if not overlap:
        def block_fn(p, cfg, msa_l, z_l, *, rng=None, deterministic=True,
                     masks=None):
            return dap_evoformer_block(p, cfg, msa_l, z_l, rng=rng,
                                       deterministic=deterministic,
                                       n_seq_total=n_seq_total,
                                       axis_name=axis_name, masks=masks)
        return block_fn

    def block_fn(p, cfg, msa_l, z_l, *, rng=None, deterministic=True,
                 masks=None, prefetch=None):
        return dap_evoformer_block_overlap(
            p, cfg, msa_l, z_l, prefetch, rng=rng,
            deterministic=deterministic, n_seq_total=n_seq_total,
            axis_name=axis_name, masks=masks)

    block_fn.prefetch_init = lambda msa_l, z_l: _all_gather(
        z_l, axis_name, 0)
    return block_fn
