"""Model FLOPs of one AF2 training step, counted term by term from the
model's contractions (2 FLOPs per multiply-add). Elementwise work, and
the forward work that rematerialisation repeats in the backward pass, do
not count.

``sz`` is a configuration's ``model`` dict (``bench/configs/*.json``).
"""
from __future__ import annotations


def attention(rows, seq, c_in, heads, c, *, bias_c=None):
    """Gated self-attention along ``seq`` for each of ``rows`` rows:
    q, k, v and gate projections, logits, weighted sum, output projection,
    and the pair-bias projection when the bias comes from a pair rep."""
    hc = heads * c
    f = 4 * 2 * rows * seq * c_in * hc          # q, k, v, gate
    f += 2 * 2 * rows * heads * seq * seq * c   # logits, weighted sum
    f += 2 * rows * seq * hc * c_in             # output projection
    if bias_c is not None:
        f += 2 * seq * seq * bias_c * heads
    return f


def global_attention(rows, seq, c_in, heads, c):
    """Extra-MSA column attention: one mean query per row, one shared
    key and value head."""
    hc = heads * c
    f = 2 * rows * c_in * hc                    # q from the mean
    f += 2 * 2 * rows * seq * c_in * c          # k, v
    f += 2 * rows * seq * c_in * hc             # gate
    f += 2 * 2 * rows * heads * seq * c         # logits, weighted sum
    f += 2 * rows * seq * hc * c_in             # output projection
    return f


def transition(n, c, factor):
    return 2 * 2 * n * c * factor * c


def evoformer_block(e: dict, s: int, r: int) -> int:
    c_m, c_z = e["c_m"], e["c_z"]
    f = attention(s, r, c_m, e["n_head_msa"], e["c_hidden_att"], bias_c=c_z)
    if e["global_column_attn"]:
        f += global_attention(r, s, c_m, e["n_head_msa"], e["c_hidden_att"])
    else:
        f += attention(r, s, c_m, e["n_head_msa"], e["c_hidden_att"])
    f += transition(s * r, c_m, e["transition_factor"])
    co = e["c_hidden_opm"]
    f += 2 * 2 * s * r * c_m * co + 2 * s * r * r * co * co \
        + 2 * r * r * co * co * c_z                       # outer product mean
    cm = e["c_hidden_mul"]
    f += 2 * (4 * 2 * r * r * c_z * cm + 2 * r * r * r * cm
              + 2 * r * r * cm * c_z + 2 * r * r * c_z * c_z)  # tri-mult x2
    f += 2 * attention(r, r, c_z, e["n_head_pair"], e["c_hidden_pair_att"],
                       bias_c=c_z)                        # tri-att x2
    f += transition(r * r, c_z, e["transition_factor"])
    return f


def structure_module(st: dict, r: int, c_z: int) -> int:
    c_s, h, c = st["c_s"], st["n_head"], st["c_hidden"]
    p, pv = st["n_qk_points"], st["n_v_points"]
    ipa = 3 * 2 * r * c_s * h * c                         # q, k, v
    ipa += 2 * r * c_s * h * (2 * p + pv) * 3             # point projections
    ipa += 2 * 9 * r * h * (2 * p + pv)                   # points to global
    ipa += 2 * r * r * c_z * h                            # pair bias
    ipa += 2 * 2 * h * r * r * c                          # logits, scalar out
    ipa += 2 * h * r * r * c_z                            # pair out
    ipa += 2 * h * r * r * pv * 3 + 2 * 9 * r * h * pv   # point out, local
    ipa += 2 * r * h * (c + c_z + 4 * pv) * c_s           # output projection
    layer = ipa + 3 * 2 * r * c_s * c_s + 2 * r * c_s * 6 \
        + 2 * 27 * r + 2 * 9 * r                          # frame update
    return 2 * r * c_s * c_s + st["n_layer"] * layer


def cycle(sz: dict) -> int:
    """One recycling iteration: embedders, both stacks, structure module."""
    e, x, st = sz["evoformer"], sz["extra"], sz["structure"]
    r, s, se = sz["n_res"], sz["n_seq"], sz["n_extra_seq"]
    c_m, c_z, f_m, f_t = e["c_m"], e["c_z"], sz["msa_feat_dim"], \
        sz["target_feat_dim"]
    f = 2 * s * r * f_m * c_m + 2 * r * f_t * c_m + 2 * 2 * r * f_t * c_z
    f += 2 * r * r * (2 * sz["max_relative_idx"] + 1) * c_z
    f += 2 * se * r * f_m * x["c_m"] + 2 * r * r * 15 * c_z
    f += 2 * r * c_m * st["c_s"]                          # single projection
    f += sz["n_extra_msa_blocks"] * evoformer_block(x, se, r)
    f += sz["n_evoformer"] * evoformer_block(e, s, r)
    return f + structure_module(st, r, c_z)


def heads(sz: dict) -> int:
    """Distogram, masked-MSA and pLDDT heads, and FAPE's frame
    transforms, evaluated once per step."""
    r, s, c_s = sz["n_res"], sz["n_seq"], sz["structure"]["c_s"]
    c_m, c_z = sz["evoformer"]["c_m"], sz["evoformer"]["c_z"]
    return (2 * r * r * c_z * sz["n_distogram_bins"]
            + 2 * s * r * c_m * sz["n_aatype"]
            + 2 * 2 * r * c_s * c_s + 2 * r * c_s * sz["n_plddt_bins"]
            + 2 * 9 * r * r * (sz["structure"]["n_layer"] + 1))


def forward(sz: dict) -> int:
    """One forward pass with the heads, as a single-cycle loss sees it."""
    return cycle(sz) + heads(sz)


def train_step_per_protein(sz: dict, n_recycle: int) -> int:
    """(n_recycle - 1) cycles without gradient, then one cycle with the
    heads forward and backward (backward = 2 x forward)."""
    return (n_recycle - 1) * cycle(sz) + 3 * forward(sz)
