"""Plain float32 reference of the dense transformer family.

Written from the published description (pre-norm decoder, RMSNorm or
non-parametric LayerNorm, optional RMS qk-norm, rotary embeddings with the
rotate-half layout, grouped-query attention, SwiGLU, tied embeddings) and
importing nothing of the program. Every matrix product runs at
``Precision.HIGHEST`` in float32, layer by layer, with attention in blocks
of queries and logits in blocks of rows, so that it fits next to nothing
else on the chip.

``fp8=True`` is the control: the same arithmetic with the two operands of
every product rounded to float8 e4m3 (one scale per weight matrix, one per
activation row), the step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                # largest finite float8 e4m3fn
Q_BLOCK = 512
ROW_BLOCK = 512
# The type the embedding rows are looked up in. float32 in every run; a
# witness in ``tests/control.py`` sets bfloat16, which makes the gradient
# of the lookup a bfloat16 scatter-add.
LOOKUP_DTYPE = jnp.float32


def _q8(x, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = F8_MAX / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def mm(eq: str, a, b, fp8: bool = False, a_axes=-1, b_axes=None):
    """einsum in float32 at full precision; with ``fp8`` the activation
    ``a`` is rounded per slice over ``a_axes`` and ``b`` over ``b_axes``
    (None: one scale for the whole of ``b``)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8:
        a = _q8(a, a_axes)
        b = _q8(b, tuple(range(b.ndim)) if b_axes is None else b_axes)
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def norm(x, scale, kind: str, eps: float):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * scale.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)      # nonparametric


def rope(x, pos, theta: float):
    """Rotate-half rotary embedding. x [n, L, heads, hd], pos [L]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]        # [L, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, fp8: bool):
    """Causal softmax attention over query blocks. q [n,L,H,hd], k/v
    [n,L,Kv,hd] -> [n,L,H,hd]."""
    n, L, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = min(Q_BLOCK, L)
    if L % qb:
        raise ValueError(f"{L} positions are not a whole number of "
                         f"{qb}-query blocks")
    qs = q.reshape(n, L // qb, qb, H, hd).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        qi, i = args
        s = mm("nqhd,nkhd->nhqk", qi, k, fp8, -1, -1) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        mask = jnp.arange(L)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("nhqk,nkhd->nqhd", p, v, fp8, -1, 1)

    out = jax.lax.map(block, (qs, jnp.arange(L // qb)))
    return out.swapaxes(0, 1).reshape(n, L, H, hd)


def layer(conf: dict, fp8: bool, x, lp):
    kind, eps = conf["norm"], _eps(conf)
    pos = jnp.arange(x.shape[1])
    h = norm(x, lp.get("norm1", {}).get("scale"), kind, eps)
    a = lp["attn"]
    q = mm("nld,dhk->nlhk", h, a["wq"], fp8)
    k = mm("nld,dhk->nlhk", h, a["wk"], fp8)
    v = mm("nld,dhk->nlhk", h, a["wv"], fp8)
    if conf["qk_norm"]:
        q = norm(q, a["q_norm"], "rmsnorm", _eps(conf))
        k = norm(k, a["k_norm"], "rmsnorm", _eps(conf))
    q = rope(q, pos, float(conf["rope_theta"]))
    k = rope(k, pos, float(conf["rope_theta"]))
    o = attention(q, k, v, fp8)
    x = x + mm("nlhk,hkd->nld", o, a["wo"], fp8, (-2, -1))
    h = norm(x, lp.get("norm2", {}).get("scale"), kind, eps)
    m = lp["mlp"]
    g = mm("nld,df->nlf", h, m["w_gate"], fp8)
    u = mm("nld,df->nlf", h, m["w_up"], fp8)
    return x + mm("nlf,fd->nld", jax.nn.silu(g) * u, m["w_down"], fp8)


def _eps(conf: dict) -> float:
    return float(conf.get("rms_norm_eps",
                          conf.get("assumed", {}).get("layer_norm_eps", 1e-5)))


def hidden(conf: dict, w, tokens, fp8: bool = False):
    """Final-normed hidden states [n, L, d] for tokens [n, L]."""
    x = jnp.take(w["embed"]["table"].astype(LOOKUP_DTYPE), tokens,
                 axis=0).astype(jnp.float32)
    body = jax.checkpoint(partial(layer, conf, fp8))
    x, _ = jax.lax.scan(lambda x, lp: (body(x, lp), None), x, w["layers"])
    return norm(x, w["final_norm"].get("scale"), conf["norm"], _eps(conf))


def head_matrix(conf: dict, w):
    """[V, d] rows whose dot with a hidden state is the logit."""
    if conf["tie_word_embeddings"]:
        return w["embed"]["table"]
    return w["head"]["w"].T


def _row_blocks(x, block):
    r = x.shape[0]
    pad = (-r) % block
    x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x.reshape((-1, block) + x.shape[1:])


@partial(jax.jit, static_argnames=("fp8_control",))
def _gaps(h, h_ctl, targets, mask, table, fp8_control):
    """Per row: reference max logit minus the reference logit of
    ``targets`` (the served token), and of the control's first choice."""
    d = h.shape[-1]
    rows = (_row_blocks(h.reshape(-1, d), ROW_BLOCK),
            _row_blocks(h_ctl.reshape(-1, d), ROW_BLOCK),
            _row_blocks(targets.reshape(-1), ROW_BLOCK),
            _row_blocks(mask.reshape(-1), ROW_BLOCK))

    def one(args):
        hb, cb, tb, mb = args
        lg = mm("rd,vd->rv", hb, table)
        best = jnp.max(lg, -1)
        served = jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]
        gap = jnp.where(mb, best - served, 0.0)
        if not fp8_control:
            return gap, jnp.zeros_like(gap)
        lc = mm("rd,vd->rv", cb, table, True)
        pick = jnp.argmax(lc, -1)
        cgap = best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return gap, jnp.where(mb, cgap, 0.0)

    gap, cgap = jax.lax.map(one, rows)
    return gap.reshape(-1), cgap.reshape(-1)


def served_gaps(conf: dict, w, seqs: list, served_from: list,
                control: bool = False, bucket: int = Q_BLOCK) -> dict:
    """For each sequence (prompt + served tokens), the gaps of the served
    tokens ``seq[served_from:]`` below the reference's best logit at the
    positions that produced them. With ``control`` also the gaps of the
    tokens the fp8 control would put first there."""
    import numpy as np
    L = max(len(s) for s in seqs)
    L = -(-L // bucket) * bucket
    n = -(-len(seqs) // 4) * 4           # fewer shapes, fewer compiles
    toks = np.zeros((n, L), np.int32)
    targ = np.zeros((n, L), np.int32)
    mask = np.zeros((n, L), bool)
    for i, (s, p) in enumerate(zip(seqs, served_from)):
        toks[i, :len(s)] = s
        targ[i, :len(s) - 1] = s[1:]
        mask[i, p - 1:len(s) - 1] = True       # position t predicts s[t+1]
    fh = jax.jit(partial(hidden, conf), static_argnames=("fp8",))
    h = fh(w, jnp.asarray(toks))
    hc = fh(w, jnp.asarray(toks), fp8=True) if control else h
    gap, cgap = _gaps(h, hc, jnp.asarray(targ), jnp.asarray(mask),
                      head_matrix(conf, w), control)
    m = mask.reshape(-1)
    gap = np.asarray(gap)[:m.size][m]
    out = {"tokens": int(m.sum()), "max_gap": float(gap.max()),
           "mean_gap": float(gap.mean()),
           "off_argmax": int((gap > 0).sum())}
    if control:
        cgap = np.asarray(cgap)[:m.size][m]
        out.update(control_max_gap=float(cgap.max()),
                   control_off_argmax=int((cgap > 0).sum()))
    return out


# ------------------------------------------------------------ training
def loss(conf: dict, w, batch, fp8: bool = False):
    """Mean next-token cross entropy over every position."""
    h = hidden(conf, w, batch["tokens"], fp8)
    d = h.shape[-1]
    table = head_matrix(conf, w)
    hb = _row_blocks(h.reshape(-1, d), ROW_BLOCK)
    tb = _row_blocks(batch["targets"].reshape(-1), ROW_BLOCK)
    n = h.shape[0] * h.shape[1]
    valid = _row_blocks(jnp.ones((n,), jnp.float32), ROW_BLOCK)

    @jax.checkpoint
    def one(carry, args):
        hr, tr, vr = args
        lg = mm("rd,vd->rv", hr, table, fp8)
        ce = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tr[:, None], -1)[:, 0]
        return carry + jnp.sum(ce * vr), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (hb, tb, valid))
    return total / n


def lr_at(t: dict, step):
    """Learning rate of update number ``step`` (1-based): linear warm-up,
    then cosine (or linear, or constant) decay."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(t["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - t["warmup_steps"])
                    / max(t["total_steps"] - t["warmup_steps"], 1), 0.0, 1.0)
    decay = {"cosine": 0.5 * (1 + jnp.cos(jnp.pi * frac)),
             "linear": 1.0 - frac, "constant": 1.0}[t["schedule"]]
    return t["learning_rate"] * warm * decay


def adamw_step(conf: dict, w, m, v, step: int, batch, fp8: bool = False):
    """One AdamW update with global-norm clipping. Returns the new
    (w, m, v), the loss and the clipped gradient.

    ``w`` is the float32 master copy. Where the configuration keeps its
    weights in a narrower type (``train.weights_dtype``), the forward and
    backward run on the master rounded to that type, and the gradient
    with respect to it, rounded to it as well, updates the master: the
    storage the configuration states, with float32 arithmetic."""
    t = conf["train"]
    held = jnp.dtype(t.get("weights_dtype", "float32"))
    wf = jax.tree_util.tree_map(
        lambda p: p.astype(held).astype(jnp.float32), w)
    val, g = jax.value_and_grad(partial(loss, conf, fp8=fp8))(wf, batch)
    g = jax.tree_util.tree_map(
        lambda x: x.astype(held).astype(jnp.float32), g)
    leaves = jax.tree_util.tree_leaves(g)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(gn, 1e-9)),
        g)
    b1, b2 = t["beta1"], t["beta2"]
    m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    lr = lr_at(t, step)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    w = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + t["eps"])
                                  + t["weight_decay"] * p), w, m, v)
    return w, m, v, val, g
