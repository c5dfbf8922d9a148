"""Structure module: rigid frames, Invariant Point Attention, backbone update.

Single-representation decoder of AlphaFold2 (suppl. Algorithms 20-23),
CA-frame-only (no side-chain torsions): enough to exercise the full training
path (IPA is part of the 'Other' 22-38% of step time in paper Table 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.config import StructureConfig
from repro.nn import layers as nn

Params = dict


# ---------------------------------------------------------------------------
# Rigid-body frames: rotation matrices (..., 3, 3) + translations (..., 3)
# ---------------------------------------------------------------------------

def identity_rigid(shape, dtype=jnp.float32):
    rots = jnp.broadcast_to(jnp.eye(3, dtype=dtype), (*shape, 3, 3))
    trans = jnp.zeros((*shape, 3), dtype)
    return rots, trans


def quat_to_rot(q: jnp.ndarray) -> jnp.ndarray:
    """Unit quaternion (..., 4) [w, x, y, z] -> rotation matrix (..., 3, 3)."""
    w, x, y, z = jnp.moveaxis(q, -1, 0)
    return jnp.stack([
        jnp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y)], -1),
        jnp.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x)], -1),
        jnp.stack([2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y)], -1),
    ], -2)


def rigid_apply(rots, trans, points):
    """Map local points (..., 3) to global: R @ p + t."""
    return jnp.einsum("...ij,...j->...i", rots, points) + trans


def rigid_invert_apply(rots, trans, points):
    """Map global points to local: R^T (p - t)."""
    return jnp.einsum("...ji,...j->...i", rots, points - trans)


def rigid_compose(rots_a, trans_a, rots_b, trans_b):
    """(R_a, t_a) ∘ (R_b, t_b): first apply b in a's frame."""
    rots = jnp.einsum("...ij,...jk->...ik", rots_a, rots_b)
    trans = rigid_apply(rots_a, trans_a, trans_b)
    return rots, trans


# ---------------------------------------------------------------------------
# Invariant Point Attention (Algorithm 22)
# ---------------------------------------------------------------------------

def ipa_init(key, cfg: StructureConfig) -> Params:
    ks = nn.split_keys(key, 8)
    h, c = cfg.n_head, cfg.c_hidden
    return {
        "q": nn.dense_init(ks[0], cfg.c_s, h * c, use_bias=False),
        "k": nn.dense_init(ks[1], cfg.c_s, h * c, use_bias=False),
        "v": nn.dense_init(ks[2], cfg.c_s, h * c, use_bias=False),
        "q_pts": nn.dense_init(ks[3], cfg.c_s, h * cfg.n_qk_points * 3),
        "k_pts": nn.dense_init(ks[4], cfg.c_s, h * cfg.n_qk_points * 3),
        "v_pts": nn.dense_init(ks[5], cfg.c_s, h * cfg.n_v_points * 3),
        "pair_bias": nn.dense_init(ks[6], cfg.c_z, h, use_bias=False),
        "head_weights": jnp.zeros((h,), jnp.float32),  # softplus -> gamma
        "out": nn.dense_init(
            ks[7], h * (c + cfg.c_z + cfg.n_v_points * 4), cfg.c_s, scale="zeros"),
    }


def invariant_point_attention(p: Params, cfg: StructureConfig, s, z, rots,
                              trans, res_mask=None):
    r = s.shape[0]
    h, c, n_qp, n_vp = cfg.n_head, cfg.c_hidden, cfg.n_qk_points, cfg.n_v_points

    q = nn.dense(p["q"], s).reshape(r, h, c)
    k = nn.dense(p["k"], s).reshape(r, h, c)
    v = nn.dense(p["v"], s).reshape(r, h, c)

    q_pts = nn.dense(p["q_pts"], s).reshape(r, h * n_qp, 3)
    k_pts = nn.dense(p["k_pts"], s).reshape(r, h * n_qp, 3)
    v_pts = nn.dense(p["v_pts"], s).reshape(r, h * n_vp, 3)
    # globalize points with each residue's frame
    q_pts = rigid_apply(rots[:, None], trans[:, None], q_pts).reshape(r, h, n_qp, 3)
    k_pts = rigid_apply(rots[:, None], trans[:, None], k_pts).reshape(r, h, n_qp, 3)
    v_pts_g = rigid_apply(rots[:, None], trans[:, None], v_pts).reshape(r, h, n_vp, 3)

    scalar = jnp.einsum("ihc,jhc->hij", q, k).astype(jnp.float32) * (c ** -0.5)
    pair = jnp.moveaxis(nn.dense(p["pair_bias"], z), -1, 0).astype(jnp.float32)
    d2 = jnp.sum(
        jnp.square(q_pts[:, None].astype(jnp.float32) -
                   k_pts[None, :].astype(jnp.float32)), axis=-1)  # (i, j, h, P)
    gamma = jax.nn.softplus(p["head_weights"])  # (h,)
    w_c = (2.0 / (9.0 * n_qp)) ** 0.5
    point = -0.5 * w_c * gamma[None, None] * jnp.sum(d2, axis=-1)   # (i, j, h)
    point = jnp.moveaxis(point, -1, 0)
    w_l = (1.0 / 3.0) ** 0.5
    logits = w_l * (scalar + pair + point)
    if res_mask is not None:
        # padded-bucket residues must not be attended to (their frames and
        # point clouds are garbage); queries at padded i stay garbage but
        # never feed back into valid rows
        from repro.core.evoformer import mask_bias
        logits = logits + mask_bias(res_mask)[None, None]
    att = jax.nn.softmax(logits, axis=-1)                            # (h, i, j)

    o_scalar = jnp.einsum("hij,jhc->ihc", att.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)
    o_pair = jnp.einsum("hij,ijc->ihc", att.astype(z.dtype), z,
                        preferred_element_type=jnp.float32)
    o_scalar = o_scalar.astype(v.dtype).reshape(r, -1)
    o_pair = o_pair.astype(z.dtype).reshape(r, -1)
    o_pts_g = jnp.einsum("hij,jhpc->ihpc", att.astype(jnp.float32),
                         v_pts_g.astype(jnp.float32))                # (i, h, P, 3)
    o_pts = rigid_invert_apply(rots[:, None, None], trans[:, None, None], o_pts_g)
    o_pts_norm = jnp.sqrt(jnp.sum(jnp.square(o_pts), -1) + 1e-8)     # (i, h, P)
    feats = jnp.concatenate([
        o_scalar, o_pair,
        o_pts.reshape(r, -1).astype(s.dtype), o_pts_norm.reshape(r, -1).astype(s.dtype),
    ], axis=-1)
    return nn.dense(p["out"], feats.astype(s.dtype))


# ---------------------------------------------------------------------------
# Structure module (Algorithm 20, shared weights across iterations)
# ---------------------------------------------------------------------------

def structure_module_init(key, cfg: StructureConfig) -> Params:
    ks = nn.split_keys(key, 6)
    return {
        "ln_s": nn.layernorm_init(cfg.c_s),
        "ln_z": nn.layernorm_init(cfg.c_z),
        "proj_s": nn.dense_init(ks[0], cfg.c_s, cfg.c_s),
        "ipa": ipa_init(ks[1], cfg),
        "ln_ipa": nn.layernorm_init(cfg.c_s),
        "trans_mlp": {
            "w1": nn.dense_init(ks[2], cfg.c_s, cfg.c_s),
            "w2": nn.dense_init(ks[3], cfg.c_s, cfg.c_s),
            "w3": nn.dense_init(ks[4], cfg.c_s, cfg.c_s, scale="zeros"),
            "ln": nn.layernorm_init(cfg.c_s),
        },
        "backbone_update": nn.dense_init(ks[5], cfg.c_s, 6, scale="zeros"),
    }


def structure_module(p: Params, cfg: StructureConfig, s_init, z,
                     res_mask=None):
    """Returns final (rots, trans), per-iteration trans trajectory, final s.

    ``res_mask`` (r,) masks IPA keys against padded-bucket residues
    (inference); ``None`` = training fast path (loss already masks).
    """
    with jax.named_scope("structure"):
        return _structure_module(p, cfg, s_init, z, res_mask)


def _structure_module(p: Params, cfg: StructureConfig, s_init, z, res_mask):
    r = s_init.shape[0]
    s = nn.dense(p["proj_s"], nn.layernorm(p["ln_s"], s_init))
    z = nn.layernorm(p["ln_z"], z)
    rots, trans = identity_rigid((r,), jnp.float32)

    def iteration(carry, _):
        s, rots, trans = carry
        s = s + invariant_point_attention(p["ipa"], cfg, s, z, rots, trans,
                                          res_mask)
        s = nn.layernorm(p["ln_ipa"], s)
        mlp = p["trans_mlp"]
        h = jax.nn.relu(nn.dense(mlp["w1"], s))
        h = jax.nn.relu(nn.dense(mlp["w2"], h))
        s = nn.layernorm(mlp["ln"], s + nn.dense(mlp["w3"], h))
        upd = nn.dense(p["backbone_update"], s).astype(jnp.float32)  # (r, 6)
        bcd, t_upd = upd[:, :3], upd[:, 3:]
        quat = jnp.concatenate([jnp.ones((r, 1), jnp.float32), bcd], -1)
        quat = quat / jnp.linalg.norm(quat, axis=-1, keepdims=True)
        rots_u = quat_to_rot(quat)
        rots, trans = rigid_compose(rots, trans, rots_u, t_upd)
        # AF2: stop rotation gradients between iterations for stability;
        # per-iteration frames (with grad) are emitted for the FAPE trajectory.
        rots_carry = jax.lax.stop_gradient(rots)
        return (s, rots_carry, trans), (rots, trans)

    (s, _, _), (rots_traj, trans_traj) = jax.lax.scan(
        iteration, (s, rots, trans), None, length=cfg.n_layer)
    rots, trans = rots_traj[-1], trans_traj[-1]
    return (rots, trans), (rots_traj, trans_traj), s
