"""The synthetic training stream, made from the seed.

A copy of the program's synthetic protein stream (the same draws from the
same keys), so that the reference trains on the rows the program is fed
without taking them from the program: sample ``i`` of step ``t`` comes from
``split(fold_in(PRNGKey(seed), t), batch)[i]``. Features have AF2's shapes;
structures are smooth random chains with 3.8 A CA spacing and orthonormal
per-residue frames.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _chain_coords(key, n_res):
    steps = jax.random.normal(key, (n_res, 3))
    kernel = jnp.ones((5,)) / 5.0
    steps = jnp.stack([jnp.convolve(steps[:, i], kernel, mode="same")
                       for i in range(3)], -1)
    steps = steps / (jnp.linalg.norm(steps, axis=-1, keepdims=True) + 1e-6)
    return jnp.cumsum(3.8 * steps, axis=0)


def _frames(x):
    nxt = jnp.concatenate([x[1:], x[-1:] + (x[-1:] - x[-2:-1])], 0)
    prv = jnp.concatenate([x[:1] - (x[1:2] - x[:1]), x[:-1]], 0)
    e1 = nxt - x
    e1 = e1 / (jnp.linalg.norm(e1, axis=-1, keepdims=True) + 1e-6)
    v2 = x - prv
    e2 = v2 - jnp.sum(v2 * e1, -1, keepdims=True) * e1
    n2 = jnp.linalg.norm(e2, axis=-1, keepdims=True)
    ref = jnp.where(jnp.abs(e1[..., :1]) < 0.9, jnp.array([1.0, 0.0, 0.0]),
                    jnp.array([0.0, 1.0, 0.0]))
    alt = ref - jnp.sum(ref * e1, -1, keepdims=True) * e1
    alt = alt / (jnp.linalg.norm(alt, axis=-1, keepdims=True) + 1e-9)
    e2 = jnp.where(n2 > 1e-3, e2 / (n2 + 1e-9), alt)
    return jnp.stack([e1, e2, jnp.cross(e1, e2)], axis=-1)


def sample(key, sz):
    ks = jax.random.split(key, 8)
    s, se, r = sz["n_seq"], sz["n_extra_seq"], sz["n_res"]
    n_aa, f_m = sz["n_aatype"], sz["msa_feat_dim"]
    true_msa = jax.random.randint(ks[0], (s, r), 0, n_aa - 1)
    mask_pos = jax.random.bernoulli(ks[1], 0.15, (s, r))
    msa_feat = jax.nn.one_hot(true_msa, f_m)
    msa_feat = jnp.where(mask_pos[..., None],
                         jax.nn.one_hot(jnp.full((s, r), n_aa - 1), f_m),
                         msa_feat)
    msa_feat = msa_feat + 0.1 * jax.random.normal(ks[2], (s, r, f_m))
    extra = jax.nn.one_hot(jax.random.randint(ks[3], (se, r), 0, n_aa - 1),
                           f_m)
    coords = _chain_coords(ks[4], r)
    return {
        "msa_feat": msa_feat.astype(jnp.float32),
        "extra_msa_feat": extra.astype(jnp.float32),
        "target_feat": jax.nn.one_hot(true_msa[0] % 21, sz["target_feat_dim"]
                                      ).astype(jnp.float32),
        "residue_index": jnp.arange(r, dtype=jnp.int32),
        "res_mask": jnp.ones((r,), jnp.float32),
        "true_msa": true_msa.astype(jnp.int32),
        "msa_mask_positions": mask_pos,
        "true_rots": _frames(coords).astype(jnp.float32),
        "true_trans": coords.astype(jnp.float32),
    }


def batch(seed: int, step: int, batch_size: int, sz: dict) -> dict:
    base = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    keys = jax.random.split(base, batch_size)
    return jax.vmap(lambda k: sample(k, sz))(keys)
