"""jit'd wrappers for flash attention over [B,S,H,D] layouts.

``flash_attention`` is the standalone kernel (zero state, normalized
output). ``flash_hop`` is the hop-fused form used by
``core/ring_attention``: it folds one K/V block into carried online-
softmax state ``(m, l, acc)`` — the [B,H,Sq]-shaped state of
``ring_attention._block_update`` — in a single Pallas launch. GQA is
handled natively by both: query heads are grouped per KV head on a grid
dimension instead of materializing ``jnp.repeat``-expanded K/V.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_carry, sublane_block

_WARNED_SHAPES: set = set()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _warn_shrunk_block(dim: int, preferred: int, what: str) -> int:
    """The kernel's block for this dim, warning once per (dim, preferred)
    pair when it differs from the preferred one."""
    b = sublane_block(dim, preferred)
    if b != min(preferred, dim) and (what, dim, preferred) not in _WARNED_SHAPES:
        _WARNED_SHAPES.add((what, dim, preferred))
        warnings.warn(
            f"flash_attention: {what}={dim} does not tile by {preferred}; "
            f"shrinking block to {b}", stacklevel=3)
    return b


def _fold_gqa(q, k, v):
    """[B,Sq,H,D] x [B,T,Kv,D] -> kernel layout without expanding KV.

    Query head i shares KV head i // (H/Kv) (the ``jnp.repeat`` pairing),
    so q reshapes to [B*Kv, G, Sq, D] with G = H/Kv and K/V to [B*Kv, T, D].
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q4 = q.transpose(0, 2, 1, 3).reshape(b, kvh, g, sq, d) \
        .reshape(b * kvh, g, sq, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * kvh, -1, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * kvh, -1, d)
    return q4, k3, v3


def _state_to_kernel(state, b, kvh, g):
    """(m, l, acc) of [B,H,Sq]/[B,H,Sq,hd] -> [B*Kv, G, Sq, {1,hd}]."""
    m, l, acc = state
    sq = m.shape[-1]
    m4 = m.reshape(b, kvh, g, sq)[..., None].reshape(b * kvh, g, sq, 1)
    l4 = l.reshape(b, kvh, g, sq)[..., None].reshape(b * kvh, g, sq, 1)
    acc4 = acc.reshape(b, kvh, g, sq, -1).reshape(b * kvh, g, sq, -1)
    return m4, l4, acc4


def _state_from_kernel(m4, l4, acc4, b, kvh, g):
    sq = m4.shape[2]
    m = m4.reshape(b, kvh * g, sq)
    l = l4.reshape(b, kvh * g, sq)
    acc = acc4.reshape(b, kvh * g, sq, -1)
    return m, l, acc


def _klen_vector(k_len, b, kvh, t_hi):
    """Normalize k_len (None | scalar | [B] per-row) to [B*Kv] int32."""
    if k_len is None:
        kl = jnp.full((b,), t_hi, jnp.int32)
    else:
        kl = jnp.broadcast_to(jnp.asarray(k_len, jnp.int32), (b,))
    return jnp.repeat(kl, kvh)


def _carry_reference(q4, k3, v3, m4, l4, acc4, q_offset, k_offset, klen, *,
                     causal: bool, window: int):
    """jnp twin of ``flash_carry(normalize=False)`` over the whole KV block
    at once (one-shot softmax merge == the kernel's per-block online merge).
    Differentiable — it is the backward rule for the fused launch."""
    d = q4.shape[-1]
    scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bgsd,btd->bgst", q4.astype(jnp.float32),
                   k3.astype(jnp.float32)) * scale
    qp = q_offset + jnp.arange(q4.shape[2], dtype=jnp.int32)
    kp = k_offset + jnp.arange(k3.shape[1], dtype=jnp.int32)
    mask = kp[None, None, None, :] < klen[:, None, None, None]
    if causal:
        mask = jnp.logical_and(mask, (kp[None, :] <= qp[:, None])[None, None])
    if window:
        mask = jnp.logical_and(
            mask, (qp[:, None] - kp[None, :] < window)[None, None])
    s = jnp.where(mask, s, -1e30)
    m_new = jnp.maximum(m4, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m4 - m_new)
    l_new = l4 * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc4 * corr + jnp.einsum("bgst,btd->bgsd", p,
                                       v3.astype(jnp.float32))
    return m_new, l_new, acc_new


@functools.lru_cache(maxsize=None)
def _carry_fused(causal: bool, window: int, bq: int, bkv: int,
                 interpret: bool):
    """The fused launch with a custom VJP: forward is the Pallas kernel,
    backward is the jnp oracle's gradient (Pallas has no JVP rule here, and
    the ring schedules are differentiated by the training loop)."""
    def prim(q4, k3, v3, m4, l4, acc4, q_offset, k_offset, klen):
        return flash_carry(q4, k3, v3, m4, l4, acc4, q_offset, k_offset,
                           klen, causal=causal, window=window, bq=bq,
                           bkv=bkv, normalize=False, interpret=interpret)

    ref = functools.partial(_carry_reference, causal=causal, window=window)
    f = jax.custom_vjp(prim)

    def fwd(*args):
        return prim(*args), args

    def bwd(res, ct):
        _, vjp = jax.vjp(ref, *res)
        return vjp(ct)

    f.defvjp(fwd, bwd)
    return f


def flash_hop(q, k, v, state, *, q_offset=0, k_offset=0, k_len=None,
              causal: bool = True, window: int = 0, bq: int = 128,
              bkv: int = 128, interpret: bool | None = None):
    """One ring hop as one fused kernel launch.

    q:      [B, Sq, H, hd] resident queries (any float dtype).
    k, v:   [B, T, Kv, hd] the arriving K/V block (unexpanded GQA).
    state:  (m, l, acc) = ([B,H,Sq], [B,H,Sq], [B,H,Sq,hd]) fp32 — the
            carried online-softmax state of ``_block_update``.
    q_offset / k_offset: global position of row/key 0 (traced values OK —
            ring hops pass shard origins from ``_source_table``).
    k_len:  None, scalar, or per-row [B] int32: key at global position p
            participates iff p < k_len (padded tails; decode ``pos+1``).

    Returns the updated (m, l, acc). The caller normalizes (acc / l) after
    the last hop, exactly like the jnp path.
    """
    b, sq, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    if interpret is None:
        interpret = not _on_tpu()
    _warn_shrunk_block(sq, bq, "Sq")
    _warn_shrunk_block(t, bkv, "T")
    q4, k3, v3 = _fold_gqa(q, k, v)
    m4, l4, acc4 = _state_to_kernel(state, b, kvh, g)
    klen = _klen_vector(k_len, b, kvh, 2 ** 30)
    m4, l4, acc4 = _carry_fused(causal, window, bq, bkv, interpret)(
        q4, k3, v3, m4, l4, acc4, jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(k_offset, jnp.int32), klen)
    return _state_from_kernel(m4, l4, acc4, b, kvh, g)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bkv"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bkv: int = 128):
    """q: [B,S,H,D], k/v: [B,T,Kv,D]. GQA is native — KV heads stay
    unexpanded and query head groups ride their own grid dimension."""
    b, sq, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    _warn_shrunk_block(sq, bq, "Sq")
    _warn_shrunk_block(t, bkv, "T")
    q4, k3, v3 = _fold_gqa(q, k, v)
    m0 = jnp.full((b * kvh, g, sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b * kvh, g, sq, 1), jnp.float32)
    acc0 = jnp.zeros((b * kvh, g, sq, d), jnp.float32)
    klen = jnp.full((b * kvh,), t, jnp.int32)
    _, _, o4 = flash_carry(
        q4, k3, v3, m0, l0, acc0, 0, 0, klen, causal=causal,
        window=window, bq=bq, bkv=bkv, normalize=True,
        interpret=not _on_tpu(), out_dtype=q.dtype)
    return o4.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
