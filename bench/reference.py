"""Plain float32 reference, and what it is compared by.

Each family's forward (``hidden``, ``head_matrix`` in
``families/<family>.py``) is written from the published description and
imports nothing of the program; what is here is the same for every family:
the products, the served tokens' gaps, the loss and the AdamW step. Every
matrix product runs at ``Precision.HIGHEST`` in float32, layer by layer,
with attention in blocks of queries and logits in blocks of rows, so that
it fits next to nothing else on the chip.

``fp8=True`` is the control: the same arithmetic with the two operands of
every product rounded to float8 e4m3 (one scale per weight matrix, one per
activation row), the step below the bfloat16 the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import plugins

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


def hidden(conf: dict, w, tokens, fp8: bool = False):
    """Final-normed hidden states [n, L, d] for tokens [n, L], by the
    configuration's family (``families/<family>.py``)."""
    return plugins.family(conf).hidden(conf, w, tokens, fp8)


def head_matrix(conf: dict, w):
    """[V, d] rows whose dot with a hidden state is the logit."""
    return plugins.family(conf).head_matrix(conf, w)


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
