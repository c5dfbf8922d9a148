"""Plain AlphaFold2 training step: the benchmark's reference.

It states, in straightforward ``jax.numpy``, the model the program trains
(embedders, recycling, extra-MSA stack with global column attention,
Evoformer in the serial or the parallel order, the CA-frame structure
module with IPA, the four losses) and its training step (per-sample
gradient clipping, AdamW, the parameters' moving average). It imports
nothing of the program and takes no weights, tables or data the program
made: weights come from ``bench/weights.py`` and inputs from
``bench/data.py``, both from the seed.

Sizes come from the configuration file as a plain dict (``sizes``).

Numerics: ``Numerics("f32")`` is float32 with every contraction at
``Precision.HIGHEST``. ``Numerics("fp8")`` is the control: every
contraction's forward operands are rounded to float8 e4m3 (saturating
at its largest finite value) before an exact product, the one step below
the bfloat16 the configuration computes in.

Memory: each Evoformer block is recomputed in the backward pass, and
attention runs over blocks of rows, so that the step fits one chip at the
timed sizes.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
# bytes of f32 attention logits one row block may hold
ROW_BLOCK_BYTES = 1 << 28
FP8_MAX = 448.0


class Numerics:
    """How the reference rounds the operands of its contractions."""

    def __init__(self, kind: str):
        if kind not in ("f32", "fp8"):
            raise ValueError(f"unknown numerics {kind!r}")
        self.kind = kind

    def q(self, x):
        """The operand as the contraction sees it. The control rounds the
        forward operands only; gradients pass the rounding unchanged
        (straight through), as in training with a float8 forward."""
        if self.kind == "f32":
            return x
        r = jnp.clip(x, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)
        return x + jax.lax.stop_gradient(r.astype(x.dtype) - x)

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HI)

    def dense(self, p, x):
        y = self.ein("...i,io->...o", x, p["w"])
        return y + p["b"] if "b" in p else y


def layernorm(p, x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def dropout(key, x, rate, shared_axis):
    """Row- or column-shared dropout: one mask along ``shared_axis``."""
    shape = list(x.shape)
    shape[shared_axis] = 1
    keep = jax.random.bernoulli(key, 1.0 - rate, shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def over_row_blocks(fn, x, per_row_bytes):
    """Apply ``fn`` to blocks of the leading axis of ``x`` in turn, each
    block recomputed in the backward pass: the logits of one block at a
    time are live."""
    n = x.shape[0]
    rows = max(1, min(n, ROW_BLOCK_BYTES // max(per_row_bytes, 1)))
    while n % rows:
        rows -= 1
    if rows == n:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(n // rows, rows,
                                                    *x.shape[1:]))
    return out.reshape(n, *out.shape[2:])


# ---------------------------------------------------------------------------
# Evoformer pieces (AF2 suppl. Algorithms 7-15, 19)
# ---------------------------------------------------------------------------

def pair_bias(nx: Numerics, p, z):
    """(S, S, c_z) -> (h, S, S)."""
    return jnp.moveaxis(nx.dense(p["bias_proj"], layernorm(p["bias_ln"], z)),
                        -1, 0)


def gated_attention(nx: Numerics, p, x, n_head, c, bias=None):
    """Attention along axis -2 of x (L, S, c_in), independently per row."""
    s = x.shape[-2]

    def rows(xb):
        h = layernorm(p["ln"], xb)
        lead = xb.shape[:-2]
        q = nx.dense(p["q"], h).reshape(*lead, s, n_head, c)
        k = nx.dense(p["k"], h).reshape(*lead, s, n_head, c)
        v = nx.dense(p["v"], h).reshape(*lead, s, n_head, c)
        logits = nx.ein("...qhc,...khc->...hqk", q, k) * (c ** -0.5)
        if bias is not None:
            logits = logits + bias
        w = jax.nn.softmax(logits, -1)
        o = nx.ein("...hqk,...khc->...qhc", w, v).reshape(*lead, s, n_head * c)
        g = jax.nn.sigmoid(nx.dense(p["gate"], h))
        return nx.dense(p["out"], g * o)

    return over_row_blocks(rows, x, 4 * n_head * s * s)


def global_attention(nx: Numerics, p, x, n_head, c):
    """Extra-MSA column attention (Algorithm 19): one mean query per row
    of x (L, S, c_in), keys and values shared by the heads."""
    h = layernorm(p["ln"], x)
    lead = x.shape[:-2]
    q = nx.dense(p["q"], jnp.mean(h, -2)).reshape(*lead, n_head, c) * c ** -0.5
    k = nx.dense(p["k"], h)
    v = nx.dense(p["v"], h)
    w = jax.nn.softmax(nx.ein("...hc,...sc->...hs", q, k), -1)
    o = nx.ein("...hs,...sc->...hc", w, v).reshape(*lead, 1, n_head * c)
    g = jax.nn.sigmoid(nx.dense(p["gate"], h))
    return nx.dense(p["out"], g * o)


def transition(nx: Numerics, p, x):
    h = layernorm(p["ln"], x)
    return nx.dense(p["w2"], jax.nn.relu(nx.dense(p["w1"], h)))


def outer_product_mean(nx: Numerics, p, msa):
    h = layernorm(p["ln"], msa)
    a = nx.dense(p["a"], h)
    b = nx.dense(p["b"], h)
    outer = nx.ein("sic,sjd->ijcd", a, b) / msa.shape[0]
    return nx.dense(p["out"], outer.reshape(*outer.shape[:2], -1))


def triangle_mult(nx: Numerics, p, z, outgoing):
    x = layernorm(p["ln_in"], z)
    a = jax.nn.sigmoid(nx.dense(p["a_gate"], x)) * nx.dense(p["a"], x)
    b = jax.nn.sigmoid(nx.dense(p["b_gate"], x)) * nx.dense(p["b"], x)
    spec = "ikc,jkc->ijc" if outgoing else "kic,kjc->ijc"
    o = nx.dense(p["out"], layernorm(p["ln_out"], nx.ein(spec, a, b)))
    return jax.nn.sigmoid(nx.dense(p["gate"], x)) * o


def evoformer_block(nx: Numerics, sz: dict, p, msa, z, key, train: bool):
    """One block in ``sz['variant']`` order: 'af2' is the serial block of
    AF2 (MSA stack, outer product mean, pair stack); 'parallel' runs both
    stacks from the block inputs and adds the outer product mean of the
    MSA output last."""
    k_msa, k_pair = jax.random.split(key)
    h_msa, c_att = sz["n_head_msa"], sz["c_hidden_att"]
    h_pair, c_pair = sz["n_head_pair"], sz["c_hidden_pair_att"]

    def msa_stack(msa, z):
        upd = gated_attention(nx, p["row_attn"], msa, h_msa, c_att,
                              bias=pair_bias(nx, p["row_attn"], z))
        if train:
            upd = dropout(jax.random.split(k_msa)[1], upd,
                          sz["dropout_msa"], 0)
        msa = msa + upd
        cols = msa.swapaxes(0, 1)
        if sz["global_column_attn"]:
            col = global_attention(nx, p["col_attn"], cols, h_msa, c_att)
        else:
            col = gated_attention(nx, p["col_attn"], cols, h_msa, c_att)
        msa = msa + col.swapaxes(0, 1)
        return msa + transition(nx, p["msa_trans"], msa)

    def pair_stack(z):
        def drop(i, x, axis):
            if not train:
                return x
            return dropout(jax.random.fold_in(k_pair, i), x,
                           sz["dropout_pair"], axis)
        z = z + drop(0, triangle_mult(nx, p["tri_mul_out"], z, True), 0)
        z = z + drop(1, triangle_mult(nx, p["tri_mul_in"], z, False), 0)
        pa = p["tri_att_start"]
        z = z + drop(2, gated_attention(nx, pa, z, h_pair, c_pair,
                                        bias=pair_bias(nx, pa, z)), 0)
        pa, zt = p["tri_att_end"], z.swapaxes(0, 1)
        end = gated_attention(nx, pa, zt, h_pair, c_pair,
                              bias=pair_bias(nx, pa, zt))
        z = z + drop(3, end.swapaxes(0, 1), 1)
        return z + transition(nx, p["pair_trans"], z)

    if sz["variant"] == "af2":
        msa = msa_stack(msa, z)
        z = z + outer_product_mean(nx, p["opm"], msa)
        return msa, pair_stack(z)
    if sz["variant"] == "parallel":
        msa_out = msa_stack(msa, z)
        return msa_out, pair_stack(z) + outer_product_mean(nx, p["opm"],
                                                            msa_out)
    raise ValueError(f"unknown variant {sz['variant']!r}")


def stack(nx, sz, params, n_blocks, msa, z, key, train):
    keys = jax.random.split(key, n_blocks)

    @jax.checkpoint
    def one(carry, xs):
        bp, k = xs
        return evoformer_block(nx, sz, bp, *carry, k, train), None

    (msa, z), _ = jax.lax.scan(one, (msa, z), (params, keys))
    return msa, z


# ---------------------------------------------------------------------------
# Structure module (Algorithms 20-23, CA frames only)
# ---------------------------------------------------------------------------

def quat_to_rot(q):
    w, x, y, z = jnp.moveaxis(q, -1, 0)
    return jnp.stack([
        jnp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z),
                   2 * (x * z + w * y)], -1),
        jnp.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z),
                   2 * (y * z - w * x)], -1),
        jnp.stack([2 * (x * z - w * y), 2 * (y * z + w * x),
                   1 - 2 * (x * x + y * y)], -1)], -2)


def to_global(rots, trans, pts):
    return jnp.einsum("...ij,...j->...i", rots, pts, precision=HI) + trans


def to_local(rots, trans, pts):
    return jnp.einsum("...ji,...j->...i", rots, pts - trans, precision=HI)


def ipa(nx: Numerics, st: dict, p, s, z, rots, trans):
    r = s.shape[0]
    h, c, n_qp, n_vp = st["n_head"], st["c_hidden"], st["n_qk_points"], \
        st["n_v_points"]
    q = nx.dense(p["q"], s).reshape(r, h, c)
    k = nx.dense(p["k"], s).reshape(r, h, c)
    v = nx.dense(p["v"], s).reshape(r, h, c)

    def points(name, n):
        local = nx.dense(p[name], s).reshape(r, h * n, 3)
        return to_global(rots[:, None], trans[:, None], local).reshape(
            r, h, n, 3)

    q_pts, k_pts, v_pts = points("q_pts", n_qp), points("k_pts", n_qp), \
        points("v_pts", n_vp)
    scalar = nx.ein("ihc,jhc->hij", q, k) * c ** -0.5
    bias = jnp.moveaxis(nx.dense(p["pair_bias"], z), -1, 0)
    d2 = jnp.sum(jnp.square(q_pts[:, None] - k_pts[None, :]), -1)  # i j h P
    gamma = jax.nn.softplus(p["head_weights"])
    w_c = (2.0 / (9.0 * n_qp)) ** 0.5
    point = jnp.moveaxis(-0.5 * w_c * gamma * jnp.sum(d2, -1), -1, 0)
    att = jax.nn.softmax((1.0 / 3.0) ** 0.5 * (scalar + bias + point), -1)
    o_scalar = nx.ein("hij,jhc->ihc", att, v).reshape(r, -1)
    o_pair = nx.ein("hij,ijc->ihc", att, z).reshape(r, -1)
    o_pts = to_local(rots[:, None, None], trans[:, None, None],
                     jnp.einsum("hij,jhpc->ihpc", att, v_pts, precision=HI))
    o_norm = jnp.sqrt(jnp.sum(jnp.square(o_pts), -1) + 1e-8)
    feats = jnp.concatenate([o_scalar, o_pair, o_pts.reshape(r, -1),
                             o_norm.reshape(r, -1)], -1)
    return nx.dense(p["out"], feats)


def structure_module(nx: Numerics, st: dict, p, single, z):
    """Returns the frame trajectory (rots, trans) over the shared-weight
    iterations and the final single representation."""
    r = single.shape[0]
    s = nx.dense(p["proj_s"], layernorm(p["ln_s"], single))
    z = layernorm(p["ln_z"], z)
    rots0 = jnp.broadcast_to(jnp.eye(3), (r, 3, 3))
    trans0 = jnp.zeros((r, 3))

    def it(carry, _):
        s, rots, trans = carry
        s = layernorm(p["ln_ipa"], s + ipa(nx, st, p["ipa"], s, z, rots,
                                             trans))
        m = p["trans_mlp"]
        hh = jax.nn.relu(nx.dense(m["w2"], jax.nn.relu(nx.dense(m["w1"], s))))
        s = layernorm(m["ln"], s + nx.dense(m["w3"], hh))
        upd = nx.dense(p["backbone_update"], s)
        quat = jnp.concatenate([jnp.ones((r, 1)), upd[:, :3]], -1)
        quat = quat / jnp.linalg.norm(quat, axis=-1, keepdims=True)
        new_rots = jnp.einsum("...ij,...jk->...ik", rots, quat_to_rot(quat),
                              precision=HI)
        new_trans = to_global(rots, trans, upd[:, 3:])
        # rotations carry no gradient from one iteration into the next
        return (s, jax.lax.stop_gradient(new_rots), new_trans), \
            (new_rots, new_trans)

    (s, _, _), traj = jax.lax.scan(it, (s, rots0, trans0), None,
                                   length=st["n_layer"])
    return traj, s


# ---------------------------------------------------------------------------
# Whole model, losses
# ---------------------------------------------------------------------------

def distance_bins(x, edges):
    d = jnp.sqrt(jnp.sum(jnp.square(x[:, None] - x[None, :]), -1) + 1e-8)
    return jnp.sum(d[..., None] > edges, -1)


def trunk(nx, sz, params, batch, prev, key, train):
    """One recycling iteration: embed, recycle, extra stack, main stack."""
    e = params["embedder"]
    mx = sz["max_relative_idx"]
    tf = batch["target_feat"]
    msa = nx.dense(e["msa_proj"], batch["msa_feat"]) + nx.dense(
        e["target_msa"], tf)[None]
    z = nx.dense(e["target_left"], tf)[:, None] + nx.dense(
        e["target_right"], tf)[None, :]
    ri = batch["residue_index"]
    rel = jnp.clip(ri[:, None] - ri[None, :], -mx, mx) + mx
    z = z + nx.dense(e["relpos"], jax.nn.one_hot(rel, 2 * mx + 1))
    extra = nx.dense(e["extra_msa_proj"], batch["extra_msa_feat"])
    prev_msa0, prev_z, prev_x = prev
    msa = msa.at[0].add(layernorm(e["rec_msa_ln"], prev_msa0))
    z = z + layernorm(e["rec_z_ln"], prev_z)
    bins = distance_bins(prev_x, jnp.linspace(3.375, 21.375, 14))
    z = z + nx.dense(e["rec_dist"], jax.nn.one_hot(bins, 15))
    _, k_extra, k_main = jax.random.split(key, 3)
    _, z = stack(nx, sz["extra"], params["extra_stack"],
                 sz["n_extra_msa_blocks"], extra, z, k_extra, train)
    msa, z = stack(nx, sz["evoformer"], params["evoformer"],
                   sz["n_evoformer"], msa, z, k_main, train)
    return msa, z, nx.dense(e["single_proj"], msa[0])


def forward(nx, sz, params, batch, key, n_recycle, train):
    """``n_recycle`` trunk passes, the gradient through the last only;
    cycle ``i`` draws its dropout from ``fold_in(key, i)``."""
    r, c_m, c_z = sz["n_res"], sz["evoformer"]["c_m"], sz["evoformer"]["c_z"]
    st = sz["structure"]

    def cycle(p, prev, i):
        msa, z, single = trunk(nx, sz, p, batch, prev,
                               jax.random.fold_in(key, i), train)
        traj, s_final = structure_module(nx, st, p["structure"], single, z)
        return msa, z, traj, s_final

    prev = (jnp.zeros((r, c_m)), jnp.zeros((r, r, c_z)), jnp.zeros((r, 3)))
    frozen = jax.lax.stop_gradient(params)

    def body(i, prev):
        msa, z, traj, _ = cycle(frozen, prev, i)
        return jax.lax.stop_gradient((msa[0], z, traj[1][-1]))

    prev = jax.lax.fori_loop(0, n_recycle - 1, body, prev)
    return cycle(params, prev, n_recycle - 1)


def xent(logits, onehot, mask):
    ll = jnp.sum(onehot * jax.nn.log_softmax(logits, -1), -1)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lddt_ca_per_residue(pred, true, mask, cutoff=15.0):
    dp = jnp.sqrt(jnp.sum(jnp.square(pred[:, None] - pred[None, :]), -1)
                  + 1e-10)
    dt = jnp.sqrt(jnp.sum(jnp.square(true[:, None] - true[None, :]), -1)
                  + 1e-10)
    scored = ((dt < cutoff) * mask[:, None] * mask[None, :]
              * (1.0 - jnp.eye(dt.shape[0])))
    l1 = jnp.abs(dt - dp)
    frac = 0.25 * sum((l1 < t).astype(jnp.float32) for t in (0.5, 1, 2, 4))
    return 100.0 * jnp.sum(scored * frac, 1) / jnp.maximum(
        jnp.sum(scored, 1), 1e-10)


def loss(nx, sz, params, batch, key, n_recycle, train):
    msa, z, (rots_traj, trans_traj), s_final = forward(
        nx, sz, params, batch, key, n_recycle, train)
    hp = params["heads"]
    mask = batch["res_mask"]
    m2 = mask[:, None] * mask[None, :]
    tr, tt = batch["true_rots"], batch["true_trans"]

    # FAPE over CA points, clamped at 10 A, averaged over the trajectory
    def fape(rots, trans):
        x = to_local(rots[:, None], trans[:, None], trans[None, :])
        xt = to_local(tr[:, None], tt[:, None], tt[None, :])
        err = jnp.sqrt(jnp.sum(jnp.square(x - xt), -1) + 1e-8)
        return jnp.sum(jnp.clip(err, 0.0, 10.0) / 10.0 * m2) / jnp.maximum(
            jnp.sum(m2), 1.0)
    l_fape = jnp.mean(jax.vmap(fape)(rots_traj, trans_traj))

    n_dist = sz["n_distogram_bins"]
    half = nx.dense(hp["distogram"], z)
    d_true = distance_bins(tt, jnp.linspace(2.3125, 21.6875, n_dist - 1))
    l_dist = xent(half + half.swapaxes(0, 1), jax.nn.one_hot(d_true, n_dist),
                  m2)

    msa_logits = nx.dense(hp["masked_msa"], msa)
    l_msa = xent(msa_logits, jax.nn.one_hot(batch["true_msa"],
                                            msa_logits.shape[-1]),
                 batch["msa_mask_positions"].astype(jnp.float32))

    n_pl = sz["n_plddt_bins"]
    pl = hp["plddt"]
    hh = jax.nn.relu(nx.dense(pl["w1"], layernorm(pl["ln"], s_final)))
    pl_logits = nx.dense(pl["out"], jax.nn.relu(nx.dense(pl["w2"], hh)))
    lddt = jax.lax.stop_gradient(
        lddt_ca_per_residue(trans_traj[-1], tt, mask))
    pl_bins = jnp.clip((lddt / 100.0 * n_pl).astype(jnp.int32), 0, n_pl - 1)
    l_plddt = xent(pl_logits, jax.nn.one_hot(pl_bins, n_pl), mask)
    return 0.5 * l_fape + 0.3 * l_dist + 2.0 * l_msa + 0.01 * l_plddt


# ---------------------------------------------------------------------------
# Training step: per-sample clipping, AdamW
# ---------------------------------------------------------------------------

def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree_util.tree_leaves(tree)))


def sample_keys(seed: int, step: int, batch: int, dp: int):
    """Dropout key of each sample of a step's global batch: the step's key
    folds in the data-parallel shard, and splits over the shard's
    samples."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    local = batch // dp
    return jnp.stack([jax.random.split(jax.random.fold_in(base, d), local)[j]
                      for d in range(dp) for j in range(local)])


def make_step(nx: Numerics, sz: dict, tr: dict):
    """Jitted ``step(params, m, v, t, batch, keys) -> (params, m, v, loss,
    grad)``: one AdamW step on the mean of the per-sample gradients, each
    clipped to ``tr['per_sample_clip']`` global norm."""
    b1, b2, eps = tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]
    n_recycle, train = tr["n_recycle"], tr["dropout"]

    def per_sample(params, xs):
        sample, key = xs
        l, g = jax.value_and_grad(
            lambda p: loss(nx, sz, p, sample, key, n_recycle, train))(params)
        scale = jnp.minimum(1.0, tr["per_sample_clip"] / jnp.maximum(
            global_norm(g), 1e-12))
        return l, jax.tree_util.tree_map(lambda x: x * scale, g)

    def step(params, m, v, t, batch, keys):
        ls, gs = jax.lax.map(lambda xs: per_sample(params, xs), (batch, keys))
        g = jax.tree_util.tree_map(lambda x: jnp.mean(x, 0), gs)
        t = t + 1
        tf = t.astype(jnp.float32)
        lr = tr["lr"] * jnp.minimum(1.0, (tf + 1) / tr["warmup_steps"])
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                   v, g)
        c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
            params, m, v)
        return params, m, v, t, jnp.mean(ls), g

    return jax.jit(step, donate_argnums=(0, 1, 2))


@jax.jit
def leaf_norms(tree):
    """Norm of each leaf, in ``tree_leaves`` order."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


# one compiled step per numerics, sizes and traffic in a process
_STEPS: dict = {}


def readings_of_steps(nx, sz, tr, seed, n_steps, make_params, make_batch,
                      dp: int):
    """Run ``n_steps`` reference steps from the seed's weights; returns the
    losses, the per-leaf norms of the first step's gradient, of the
    parameters' change over all steps, of the weights themselves
    (``weights``) and, where the traffic keeps an EMA copy of the
    parameters, of that copy's change (``ema``)."""
    key = json.dumps([nx.kind, sz, tr], sort_keys=True)
    if key not in _STEPS:
        _STEPS[key] = make_step(nx, sz, tr)
    step = _STEPS[key]
    params = make_params()
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v, t = zeros(), zeros(), jnp.zeros((), jnp.int32)
    decay = tr.get("ema_decay")
    ema = (jax.tree_util.tree_map(jnp.copy, params) if decay else None)
    losses, g_norms = [], None
    for i in range(n_steps):
        batch = make_batch(i)
        keys = sample_keys(seed, i, tr["global_batch"], dp)
        params, m, v, t, l, g = step(params, m, v, t, batch, keys)
        if decay:
            ema = ema_update(ema, params, decay)
        losses.append(float(l))
        if i == 0:
            g_norms = [float(x) for x in leaf_norms(g)]
        del g
    p0 = make_params()
    change = lambda tree: [float(x) for x in leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, tree, p0))]
    out = {"loss": losses, "grad": g_norms, "update": change(params),
           "weights": [float(x) for x in leaf_norms(p0)]}
    if decay:
        out["ema"] = change(ema)
    return out


@functools.partial(jax.jit, static_argnums=2)
def ema_update(ema, params, decay):
    """One step of the parameters' exponential moving average, in float32:
    ``decay * ema + (1 - decay) * params``."""
    return jax.tree_util.tree_map(
        lambda e, p: decay * e + (1.0 - decay) * p, ema, params)
