"""Roofline terms from dry-run artifacts, priced with published chip peaks."""
from __future__ import annotations

import dataclasses
from typing import Optional


# Published peaks of one chip, keyed by ``jax.Device.device_kind``: bf16
# FLOP/s and HBM B/s.  A measured time is divided only by the peaks of the
# kind it was measured on; a device kind that is not here is an error,
# never a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9},
}
# the chip the roofline *model* (predictions, paper tables) is priced for
MODEL_KIND = "TPU v5 lite"


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = DEVICE_PEAKS[MODEL_KIND]["peak_flops"]
    hbm_bw: float = DEVICE_PEAKS[MODEL_KIND]["hbm_bw"]
    ici_bw: float = 50e9            # B/s per link
    # per-collective dispatch/sync overhead (DAP issues ~13 collectives per
    # Evoformer block vs BP's single fused psum — at initial-training shapes
    # this latency term is what sinks DAP, per the paper's Table 5)
    coll_launch: float = 20e-6
    # rows below which a sharded matmul under-utilizes the MXU pipeline
    # (2 double-buffered 128-row tiles); sharding an axis past this loses
    # per-op intensity (paper §4.2: BP "retains the same computational
    # intensity", DAP does not)
    tile_rows: float = 256.0
    # fraction of DAP's collective time the overlapped schedule hides behind
    # compute (communication-overlapped DAP, DESIGN.md §3): 1.0 would be the
    # ideal max(compute, comm) composition, 0.0 the sync sum.  0.5 reflects
    # that only the prefetch gather is issued a full block early — the
    # intra-block transposes/gathers rely on the async-collective scheduler
    # finding shorter-range slack (the --print-tpu-env preset)
    overlap_eff: float = 0.5


def device_peaks(device_kind: str) -> Optional[HW]:
    """Peaks of one ``device_kind`` chip.  ``None`` for the host CPU, which
    has no peak a utilization could be taken against; an accelerator kind
    missing from ``DEVICE_PEAKS`` raises."""
    if device_kind == "cpu":
        return None
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"with their source to DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)})")
    return HW(**DEVICE_PEAKS[device_kind])


def roofline_terms(*, total_flops: float, total_bytes: float,
                   total_collective_bytes: float, chips: int,
                   hw: HW = HW()) -> dict:
    """All inputs are GLOBAL (across chips); terms are seconds."""
    compute = total_flops / (chips * hw.peak_flops)
    memory = total_bytes / (chips * hw.hbm_bw)
    collective = total_collective_bytes / (chips * hw.ici_bw)
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    dom = max(terms, key=terms.get)
    bound = max(compute, memory, collective)
    terms.update({
        "dominant": dom.replace("_s", ""),
        "step_lower_bound_s": bound,
        "roofline_fraction": compute / bound if bound > 0 else 0.0,
    })
    return terms


def model_flops(cfg, shape_kind: str, seq_len: int, global_batch: int) -> float:
    """MODEL_FLOPS: 6·N·D for dense training (2·N·D fwd-only for prefill,
    2·N_active per token for decode); MoE uses active params."""
    n_active = active_params(cfg)
    tokens = seq_len * global_batch
    if shape_kind == "train":
        return 6.0 * n_active * tokens
    if shape_kind == "prefill":
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * global_batch


def active_params(cfg) -> float:
    """Parameter count touched per token (MoE: top-k + shared only)."""
    d, v = cfg.d_model, cfg.vocab
    emb = v * d * (1 if cfg.tie_embeddings else 2)
    if cfg.family in ("dense", "vlm"):
        att = d * (cfg.n_head + 2 * cfg.n_kv_head) * cfg.d_head + \
            cfg.n_head * cfg.d_head * d
        ffn = 3 * d * cfg.d_ff
        n = cfg.n_layer * (att + ffn) + emb
        if cfg.family == "vlm":
            n += cfg.frontend_dim * d + d * d
        return n
    if cfg.family == "moe":
        att = d * (cfg.n_head + 2 * cfg.n_kv_head) * cfg.d_head + \
            cfg.n_head * cfg.d_head * d
        routed = 3 * d * cfg.moe_d_ff * cfg.top_k
        shared = 3 * d * (cfg.shared_d_ff or 0)
        return cfg.n_layer * (att + routed + shared + d * cfg.n_experts) + emb
    if cfg.family == "ssm":
        di, n_s, h = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
        blk = 2 * d * di + 2 * d * n_s + d * h + di * d
        return cfg.n_layer * blk + emb
    if cfg.family == "hybrid":
        di, n_s, h = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
        blk = 2 * d * di + 2 * d * n_s + d * h + di * d
        shared_blk = 2 * d * d + d * (cfg.n_head + 2 * cfg.n_kv_head) * \
            cfg.d_head + cfg.n_head * cfg.d_head * d + 3 * d * cfg.d_ff
        n_inv = (cfg.n_layer + cfg.shared_attn_every - 1) // cfg.shared_attn_every
        # shared weights counted once for params but ACTIVE at each invocation
        return cfg.n_layer * blk + n_inv * shared_blk + emb
    if cfg.family == "audio":
        att = 2 * (d * (cfg.n_head + 2 * cfg.n_kv_head) * cfg.d_head +
                   cfg.n_head * cfg.d_head * d)   # self + cross
        ffn = 2 * d * cfg.d_ff
        dec = cfg.n_layer * (att + ffn)
        enc = cfg.n_enc_layer * (att / 2 + ffn)
        return dec + enc + v * d
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# AF2 per-block costs under (BP, DAP) splits — consumed by
# repro.parallel.plan.auto_plan and benchmarks/paper_tables.py (DESIGN.md §2)
# ---------------------------------------------------------------------------

def tri_mult_flops(cfg) -> float:
    """Fwd FLOPs of the two triangle-multiplicative updates of one block:
    gated a/b projections + output gate (~3 z->c_mul-sized GEMMs), the
    r-contraction, and the output projection."""
    e = cfg.evoformer
    r, z, c_mul = cfg.n_res, e.c_z, e.c_hidden_mul
    return 2 * (2 * r * r * z * c_mul * 3 + 2 * r ** 3 * c_mul +
                2 * r * r * c_mul * z)


def tri_mult_hbm_bytes(cfg, impl: str = None, *, dap: int = 1,
                       elt: int = 2) -> float:
    """Per-device fwd HBM bytes of the two triangle mults of one block, by
    ``tri_mult_impl`` (None = the config's).  Coarse activation-traffic
    counts (weights and cache effects ignored), ``area`` = this device's
    (r/dap)·r output positions:

    * ``reference``: the LN'd input round-trips for 5 projections, the
      (r, r, 2c) gated pair + the pre-gate output + epilogue tensors all
      write+read HBM — ~(8·c_z + 6·c_mul) elements per position;
    * ``chunked``: the gated pair never materializes; the fp32 product
      round-trips once when c_mul <= c_z (the one-pass path), else once
      per k-chunk of the slab path;
    * ``pallas``: only the LN'd input (the xb operand streamed once per
      i-block row), the gate source and the output touch HBM — the kernel's
      arithmetic intensity is what ``auto_plan`` sees.
    """
    e = cfg.evoformer
    impl = impl or e.tri_mult_impl
    r, z, c_mul = cfg.n_res, e.c_z, e.c_hidden_mul
    area = (r // max(dap, 1)) * r
    if impl == "reference":
        per_op = elt * area * (8 * z + 6 * c_mul)
    elif impl == "chunked":
        n_k = 1 if c_mul <= z else -(-r // max(1, e.tri_mult_chunk))
        per_op = elt * area * 6 * z + 4 * area * c_mul * 2 * n_k
    elif impl == "pallas":
        n_i = -(-r // min(r, 128))        # xb streamed once per i-block
        per_op = elt * area * z * (3 + n_i)
    else:
        raise ValueError(f"unknown tri_mult impl {impl!r}")
    return 2.0 * per_op


def evo_branch_flops(cfg) -> tuple:
    """(msa_branch + OPM, pair_branch) fwd FLOPs for one main-Evoformer block.

    These are the two dependency-free branches of the *parallel* variant —
    BP's load balance is ``max(f_msa, f_pair) / (f_msa + f_pair)`` (paper
    §4.2 'approximate amount of computation')."""
    e = cfg.evoformer
    s, r, m, z = cfg.n_seq, cfg.n_res, e.c_m, e.c_z
    ha = e.n_head_msa * e.c_hidden_att
    row = 2 * s * r * m * ha * 4 + 2 * s * r * r * ha * 2
    col = 2 * s * r * m * ha * 4 + 2 * r * s * s * ha * 2
    mtrans = 2 * s * r * m * 4 * m * 2
    opm = (2 * s * r * m * e.c_hidden_opm * 2 +
           2 * r * r * s * e.c_hidden_opm ** 2 +
           2 * r * r * e.c_hidden_opm ** 2 * z)
    msa_branch = row + col + mtrans + opm
    tri_mul = tri_mult_flops(cfg)
    hp = e.n_head_pair * e.c_hidden_pair_att
    tri_att = 2 * (2 * r * r * z * hp * 4 + 2 * r ** 3 * hp * 2)
    ptrans = 2 * r * r * z * 4 * z * 2
    pair_branch = tri_mul + tri_att + ptrans
    return msa_branch, pair_branch


def dap_comm_bytes(cfg, dap: int, *, elt: int = 2,
                   overlap: bool = False) -> tuple:
    """(msa_branch, pair_branch) per-device fwd collective bytes for one
    block at DAP extent ``dap`` — the schedule of repro.parallel.dap:
    tiled all_gathers receive (d-1)/d of the FULL tensor, all_to_alls move
    (d-1)/d of a 1/d shard.  ``elt`` is the activation element size in
    bytes (2 = bf16, 4 = fp32) and scales EVERY leg, including the OPM
    all_to_alls.

    ``overlap=True`` prices the communication-overlapped schedule
    (DESIGN.md §3): the row-attention bias gather and the tri-mult-out
    operand gather are replaced by ONE prefetch gather of the (r, r, c_z)
    block-output pair rep, issued a block ahead of its consumer."""
    if dap <= 1:
        return 0.0, 0.0
    e = cfg.evoformer
    s, r, d = cfg.n_seq, cfg.n_res, dap
    gather = (d - 1) / d
    a2a = (d - 1) / (d * d)
    bias_gather = 0.0 if overlap else e.n_head_msa * r * r * gather
    msa = (bias_gather                            # row-attn bias gather
           + 2 * s * r * e.c_m * a2a              # col-attn transpose + back
           + s * r * e.c_hidden_opm * a2a         # OPM: a -> residue shards
           + s * r * e.c_hidden_opm * (a2a + gather)) * elt  # OPM: b full
    # sync: two tri-mult operand gathers; overlap: tri-mult-in's gather plus
    # the (r, r, c_z) prefetch gather replacing tri-mult-out's
    tri_gathers = ((r * r * e.c_hidden_mul + r * r * e.c_z) if overlap
                   else 2 * r * r * e.c_hidden_mul) * gather
    pair = (tri_gathers
            + r * r * e.c_hidden_mul * a2a        # tri-mult-in a transpose
            + 2 * e.n_head_pair * r * r * gather  # tri-att bias gathers (x2)
            + 2 * r * r * e.c_z * a2a) * elt      # end-att transpose + back
    return msa, pair


# DAP collectives per block fwd (the repro.parallel.dap schedule): under the
# BP x DAP hybrid each device only issues its own branch's share.  The
# overlapped schedule drops the row-attn bias gather (consumed from the
# prefetch) and swaps tri-mult-out's gather for the block-end prefetch
# issue: 6+7=13 dispatches -> 5+7=12.
_N_DAP_COLLECTIVES_MSA = 6
_N_DAP_COLLECTIVES_PAIR = 7
_N_DAP_COLLECTIVES_MSA_OVERLAP = 5
_N_DAP_COLLECTIVES_PAIR_OVERLAP = 7


def bp_exchange_bytes(cfg, dap: int = 1, *, elt: int = 2) -> float:
    """Per-device fwd bytes of BP's single block-end psum: msa_out (s,r,c_m)
    + OPM and pair contributions (2x (r,r,c_z)), DAP-sharded if hybrid.
    A 2-participant allreduce moves 2(n-1)/n = 1x the payload."""
    e = cfg.evoformer
    payload = (cfg.n_seq * cfg.n_res * e.c_m +
               2 * cfg.n_res * cfg.n_res * e.c_z) / max(dap, 1)
    return payload * elt


def estimate_block_time(cfg, *, bp: int = 1, dap: int = 1, hw: HW = HW(),
                        fwd_bwd: bool = True, elt: int = 2,
                        overlap: bool = None) -> float:
    """Roofline seconds for one main-Evoformer block per device under a
    (BP, DAP) split.  Captures the three effects that decide the paper's
    Table 5/6 preferences:

    * DAP divides branch FLOPs by ``dap`` but loses per-op intensity once the
      sharded axis drops below a tile (``hw.tile_rows``) — BP keeps full
      shapes ("the same computational intensity is retained", §4.2);
    * DAP pays ~13 collectives/block (bytes + ``coll_launch`` each); BP pays
      one fused psum whose payload shrinks 1/dap under the hybrid;
    * BP=2 runs the two branches concurrently: time is the max branch.

    The pair branch additionally carries the triangle-mult HBM term
    (``tri_mult_hbm_bytes``, keyed on ``cfg.evoformer.tri_mult_impl``):
    the op's intensity differs ~4x between the reference and the fused
    Pallas path, and at fine-tune shapes the pair branch is what bounds the
    block — this is how ``auto_plan`` sees a kernel-impl change.  Memory is
    overlapped with compute (``max``), the classic roofline composition.

    ``elt`` is the activation element size in bytes (2 = bf16 AMP, 4 =
    fp32), plumbed through every byte term — comm bytes, BP's exchange, the
    triangle-mult HBM traffic.

    ``overlap`` prices the communication-overlapped DAP schedule
    (DESIGN.md §3, ``ParallelPlan.overlap_dap``): instead of ADDING comm
    time to compute, the two partially MAX-compose,

        t = eff * max(C, M) + (1 - eff) * (C + M),   eff = hw.overlap_eff

    (eff=1 is the ideal roofline max, eff=0 degenerates to the sync sum),
    over the overlapped schedule's smaller collective budget
    (``dap_comm_bytes(..., overlap=True)``, 12 dispatches instead of 13).
    None auto-resolves like the plan layer: ON for a pure-DAP split of the
    'parallel' variant, OFF for the hybrid (no carry across cond arms) and
    serial variants.

    ``fwd_bwd`` scales compute x3 and communication x2 (backward re-runs the
    collective schedule once; matmul backward is ~2x forward FLOPs)."""
    if overlap is None:
        overlap = (dap > 1 and bp == 1
                   and cfg.evoformer.variant == "parallel")
    f_msa, f_pair = evo_branch_flops(cfg)
    d = max(dap, 1)
    eff_msa = min(1.0, (cfg.n_seq / d) / hw.tile_rows)
    eff_pair = min(1.0, (cfg.n_res / d) / hw.tile_rows)
    t_msa = f_msa / d / (hw.peak_flops * eff_msa)
    t_pair = max(f_pair / d / (hw.peak_flops * eff_pair),
                 tri_mult_hbm_bytes(cfg, dap=d, elt=elt) / hw.hbm_bw)
    b_msa, b_pair = dap_comm_bytes(cfg, d, elt=elt, overlap=overlap)
    kc, kb = (3.0, 2.0) if fwd_bwd else (1.0, 1.0)
    n_msa = (_N_DAP_COLLECTIVES_MSA_OVERLAP if overlap
             else _N_DAP_COLLECTIVES_MSA)
    n_pair = (_N_DAP_COLLECTIVES_PAIR_OVERLAP if overlap
              else _N_DAP_COLLECTIVES_PAIR)
    a_msa = (n_msa * hw.coll_launch) if d > 1 else 0.0
    a_pair = (n_pair * hw.coll_launch) if d > 1 else 0.0
    c_msa = b_msa / hw.ici_bw + a_msa
    c_pair = b_pair / hw.ici_bw + a_pair
    if bp > 1:
        t = max(kc * t_msa + kb * c_msa, kc * t_pair + kb * c_pair) + \
            kb * (bp_exchange_bytes(cfg, d, elt=elt) / hw.ici_bw +
                  hw.coll_launch)
    elif overlap and d > 1:
        comp = kc * (t_msa + t_pair)
        comm = kb * (c_msa + c_pair)
        t = hw.overlap_eff * max(comp, comm) + \
            (1.0 - hw.overlap_eff) * (comp + comm)
    else:
        t = kc * (t_msa + t_pair) + kb * (c_msa + c_pair)
    return t


def predict_step_time(cfg, *, bp: int = 1, dap: int = 1, pod: int = 1,
                      data: int = 1, global_batch: int = 1,
                      n_recycle: float = 1.0, hw: HW = HW(), elt: int = 2,
                      overlap: bool = None) -> dict:
    """Roofline prediction for one full train step under a ParallelPlan.

    Extends the per-block model (``estimate_block_time``) to a whole step:
    the main-stack block time is extrapolated to the full trunk (extra-MSA
    stack + structure module) by the analytic FLOPs ratio
    ``af2_model_flops / main-stack FLOPs``, recycling runs ``n_recycle``
    forward passes of which only the last carries a backward, and each
    data-parallel group steps over its local batch.  This is the number the
    attribution report (obs layer) confronts with the measured step time —
    the same cost model ``auto_plan`` ranks plans with, now continuously
    validated against reality.
    """
    d_groups = max(pod, 1) * max(data, 1)
    local_batch = global_batch / d_groups
    t_fb = estimate_block_time(cfg, bp=bp, dap=dap, hw=hw, fwd_bwd=True,
                               elt=elt, overlap=overlap)
    t_f = estimate_block_time(cfg, bp=bp, dap=dap, hw=hw, fwd_bwd=False,
                              elt=elt, overlap=overlap)
    f_msa, f_pair = evo_branch_flops(cfg)
    main_fwd = cfg.n_evoformer * (f_msa + f_pair)
    total_fwd = af2_model_flops(cfg, 1.0)
    scale = total_fwd / main_fwd if main_fwd > 0 else 1.0
    nr = max(float(n_recycle), 1.0)
    per_protein = scale * cfg.n_evoformer * ((nr - 1.0) * t_f + t_fb)
    predicted = local_batch * per_protein
    # model FLOPs actually spent per optimizer step (backward ~ 2x forward,
    # on the differentiated last cycle only)
    flops_per_protein = af2_model_flops(cfg, nr) + 2.0 * af2_model_flops(cfg, 1.0)
    return {
        "predicted_step_s": predicted,
        "block_fwdbwd_s": t_fb,
        "block_fwd_s": t_f,
        "trunk_scale": scale,
        "local_batch": local_batch,
        "model_flops_per_step": flops_per_protein * global_batch,
        "n_devices": d_groups * max(bp, 1) * max(dap, 1),
    }


def af2_model_flops(cfg, n_recycle: float = 1.0) -> float:
    """Analytical AF2 trunk FLOPs per protein per fwd pass (x3 for train).

    Per-block terms (s=N_seq, r=N_res, m=c_m, z=c_z, per DESIGN.md §2):
    MSA row attn ~ s·r²·(4m·h_c... ) — we count the dominant matmuls exactly.
    """
    def evo_block_flops(s, r, m, z, c_att, c_opm, c_mul, heads):
        ha = heads * c_att
        row = 2 * s * r * m * ha * 4 + 2 * s * r * r * ha * 2 + \
            2 * r * r * z * heads
        col = 2 * s * r * m * ha * 4 + 2 * r * s * s * ha * 2
        mtrans = 2 * s * r * m * 4 * m * 2
        opm = 2 * s * r * m * c_opm * 2 + 2 * r * r * s * c_opm * c_opm + \
            2 * r * r * c_opm * c_opm * z
        tri_mul = 2 * (2 * r * r * z * c_mul * 3 + 2 * r * r * r * c_mul +
                       2 * r * r * c_mul * z)
        tri_att = 2 * (2 * r * r * z * 4 * 32 * 4 + 2 * r * r * r * 4 * 32 * 2 +
                       2 * r * r * z * 4)
        ptrans = 2 * r * r * z * 4 * z * 2
        return row + col + mtrans + opm + tri_mul + tri_att + ptrans

    e = cfg.evoformer
    main = cfg.n_evoformer * evo_block_flops(
        cfg.n_seq, cfg.n_res, e.c_m, e.c_z, e.c_hidden_att, e.c_hidden_opm,
        e.c_hidden_mul, e.n_head_msa)
    x = cfg.extra
    extra = cfg.n_extra_msa_blocks * evo_block_flops(
        cfg.n_extra_seq, cfg.n_res, x.c_m, x.c_z, x.c_hidden_att,
        x.c_hidden_opm, x.c_hidden_mul, x.n_head_msa)
    st = cfg.structure
    ipa = st.n_layer * (2 * cfg.n_res * st.c_s * st.n_head * st.c_hidden * 3 +
                        2 * cfg.n_res * cfg.n_res * st.n_head *
                        (st.c_hidden + st.c_z + st.n_qk_points * 3) +
                        2 * cfg.n_res * st.c_s * st.c_s * 4)
    return n_recycle * (main + extra + ipa)
