"""Mamba2 SSD chunk Pallas kernel.

The SSD decomposition (models/ssm.py) has three parts: MXU-heavy
intra-chunk matmuls, per-chunk boundary states, and a linear inter-chunk
recurrence. This kernel computes the first two for one (batch*head, chunk)
grid cell; the recurrence — the systolic chain — runs outside (ops.py),
matching the paper's split between PE-local compute and queue traffic.

The B/C projections are shared across the heads of a group (ngroups);
their BlockSpec index_map maps head -> group, so the same VMEM block is
served to every head of the group — the QLR "data reuse degree" expressed
as an index map (no materialized expansion).

``dt`` arrives twice, as a column and as a row, so that the decay matrix
``cum[t] - cum[s]`` is a broadcast of the two; the cumulative sums are
triangular matmuls, which the TPU's compiler lowers (``jnp.cumsum`` it
does not).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref,
                      y_ref, state_ref, expcum_ref, *, chunk: int):
    l = chunk
    x = x_ref[0, 0].astype(jnp.float32)                      # [L, P]
    dt_col = dtc_ref[0, 0].astype(jnp.float32)               # [L, 1]
    dt_row = dtr_ref[0, 0].astype(jnp.float32)               # [1, L]
    a = a_ref[0, 0]                                          # [1, 1]
    bmat = b_ref[0, 0].astype(jnp.float32)                   # [L, N]
    cmat = c_ref[0, 0].astype(jnp.float32)                   # [L, N]

    # tril[t, s] = s <= t; cum = tril @ dA in both layouts
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    mask = s_idx <= t_idx
    tril = mask.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    dA_row = dt_row * a                                      # [1, L]
    cum_col = jax.lax.dot_general(                           # [L, 1]
        tril, dA_row, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    cum_row = jax.lax.dot_general(                           # [1, L]
        dA_row, tril, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    # decay[t, s] = exp(cum[t] - cum[s]) for s <= t
    decay = jnp.where(mask, jnp.exp(cum_col - cum_row), 0.0)
    # intra-chunk: M = (C B^T) * decay * dt[s];  y = M @ x   (MXU)
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    m = cb * decay * dt_row
    y = jnp.dot(m, x, preferred_element_type=jnp.float32)    # [L, P]
    # chunk boundary state: S = (x * (exp(cum[-1]-cum) * dt))^T @ B  [P, N]
    w = jnp.exp(cum_col[l - 1:, :] - cum_col) * dt_col       # [L, 1]
    state = jax.lax.dot_general(x * w, bmat, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0] = state.astype(state_ref.dtype)
    expcum_ref[0, 0] = jnp.exp(cum_col).astype(expcum_ref.dtype)


def ssd_chunks(x, dt, a, b, c, *, nheads: int, ngroups: int,
               interpret: bool = False):
    """Intra-chunk SSD pass.

    x:  [BH, NC, L, P]   (batch*heads, chunks, chunk_len, headdim)
    dt: [BH, NC, L, 1]   (post-softplus)
    a:  [BH, 1, 1, 1]    (negative per-head decay rate)
    b/c:[BG, NC, L, N]   (batch*groups; shared across heads of a group)

    Returns y_intra [BH,NC,L,P], states [BH,NC,P,N], expcum [BH,NC,L,1].
    """
    bh, nc, l, p = x.shape
    n = b.shape[-1]
    heads_per_group = nheads // ngroups
    body = functools.partial(_ssd_chunk_kernel, chunk=l)

    def bc_index(i, j):
        # head i of batch (i // nheads) -> group row in the [BG, ...] array
        batch = i // nheads
        head = i % nheads
        return (batch * ngroups + head // heads_per_group, j, 0, 0)

    call = pl.pallas_call(
        body,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, l), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, l, n), bc_index),
            pl.BlockSpec((1, 1, l, n), bc_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, 1), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, p, n), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, l, 1), jnp.float32),
        ],
        interpret=interpret,
    )
    return call(x, dt, dt.reshape(bh, nc, 1, l), a, b, c)
