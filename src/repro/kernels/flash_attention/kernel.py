"""Streaming-softmax (flash) attention Pallas kernel with carried state.

The KV stream is the systolic reading of attention: the stationary state
per q block is (m, l, acc) in VMEM scratch; KV blocks flow through the
grid's sequential dimension exactly like queue pops, with Pallas's implicit
double-buffering prefetching block k+1 during block k's MXU work (the QLR
analogue). Oracle: models/attention.blocked_attention (same online-softmax
math in pure jnp).

Two entry points share one kernel body:

  * ``flash_carry`` — the hop-fused form: (m, l, acc) enters as *inputs*
    and leaves as *outputs*, so one ring hop of
    ``core/ring_attention.ring_attention`` is a single kernel launch that
    folds the arriving K/V block into the resident online-softmax state
    (the paper's queue-pop-feeds-the-MAC at PE level). Masking is
    position-based (global q/k offsets for out-of-order ring arrival,
    sliding ``window``, per-row valid length ``klen`` for padded tails and
    per-row decode positions), and GQA is native: the query head groups
    ride a separate grid dimension over one unexpanded KV head — no
    ``jnp.repeat`` materialization.
  * ``normalize=True`` — the self-contained form (zero state in, the
    normalized output written on the last KV block).

The offsets and the per-row bounds are scalar-prefetched into SMEM; the
kernel rebuilds the positions of its block from them with ``iota``, so no
operand needs a block narrower than the TPU's (8, 128) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def sublane_block(dim: int, preferred: int) -> int:
    """Largest divisor of dim <= preferred that is a multiple of 8, or dim
    itself when there is none: the second-minor block dimension on TPU must
    be one or the other. Non-tiling shapes (e.g. S=192 under the default
    128) shrink instead of crashing; the wrappers warn once per shape."""
    for b in range(min(preferred, dim), 7, -1):
        if dim % b == 0 and b % 8 == 0:
            return b
    return dim


def _flash_kernel(off_ref, klen_ref, q_ref, k_ref, v_ref, m_ref, l_ref,
                  acc_ref, mo_ref, lo_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, bq: int, bkv: int, n_kv: int, causal: bool,
                  window: int, normalize: bool):
    """Grid point (b', g, iq, ik): fold KV block ik into q block (b',g,iq).

    b' indexes batch x KV-head (the unexpanded GQA layout), g the query
    head group sharing that KV head. The q/k offsets and the row bounds
    arrive as data (they are traced device/shard offsets inside
    shard_map), so the same compiled kernel serves every ring hop.
    """
    row, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _load_state():
        m_scr[...] = m_ref[0, 0]
        l_scr[...] = l_ref[0, 0]
        acc_scr[...] = acc_ref[0, 0]

    q = q_ref[0, 0].astype(jnp.float32)                      # [bq, d]
    k = k_ref[0].astype(jnp.float32)                         # [bkv, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = (off_ref[0] + iq * bq
             + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0))
    k_pos = (off_ref[1] + ik * bkv
             + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1))
    mask = k_pos < klen_ref[row]
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _store():
        mo_ref[0, 0] = m_scr[...]
        lo_ref[0, 0] = l_scr[...]
        if normalize:
            o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                           ).astype(o_ref.dtype)
        else:
            o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)


def flash_carry(q, k, v, m, l, acc, q_offset, k_offset, klen, *,
                causal: bool = True, window: int = 0, bq: int = 128,
                bkv: int = 128, normalize: bool = False,
                interpret: bool = False, out_dtype=None):
    """One fused online-softmax pass with carried state.

    q:          [B', G, Sq, D] — B' = batch x KV-heads, G = heads per KV
                head (native GQA; G=1 for MHA).
    k, v:       [B', T, D] — one unexpanded KV block.
    m, l:       [B', G, Sq, 1] fp32 running max / normalizer.
    acc:        [B', G, Sq, D] fp32 accumulator.
    q_offset:   int32 scalar, global position of query 0 (may be traced);
                query i sits at q_offset + i.
    k_offset:   int32 scalar, global position of key 0.
    klen:       [B'] int32 per-row valid-key bound: key j participates
                iff k_offset + j < klen[b'] (padded tails, decode positions).

    Returns (m, l, acc) updated; with ``normalize=True`` the third output
    is instead the normalized attention output acc/l cast to ``out_dtype``
    (default q.dtype) — the self-contained single-launch form.
    """
    bh, g, sq, d = q.shape
    t = k.shape[1]
    bq = sublane_block(sq, bq)
    bkv = sublane_block(t, bkv)
    scale = 1.0 / (d ** 0.5)
    n_kv = t // bkv
    out_dtype = (out_dtype or q.dtype) if normalize else jnp.float32
    body = functools.partial(
        _flash_kernel, scale=scale, bq=bq, bkv=bkv, n_kv=n_kv,
        causal=causal, window=window, normalize=normalize)
    state = lambda b, h, i, j, *_: (b, h, i, 0)              # noqa: E731
    kv = lambda b, h, i, j, *_: (b, j, 0)                    # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, g, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), state),
            pl.BlockSpec((1, bkv, d), kv),
            pl.BlockSpec((1, bkv, d), kv),
            pl.BlockSpec((1, 1, bq, 1), state),
            pl.BlockSpec((1, 1, bq, 1), state),
            pl.BlockSpec((1, 1, bq, d), state),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, 1), state),
            pl.BlockSpec((1, 1, bq, 1), state),
            pl.BlockSpec((1, 1, bq, d), state),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, g, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, g, sq, d), out_dtype),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])
    return tuple(call(offsets, klen.astype(jnp.int32), q, k, v, m, l, acc))
