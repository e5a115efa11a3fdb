"""Weight-stationary 3x3 conv2d Pallas kernel — the paper's conv2d PE
program (§V-B) on the TPU memory hierarchy.

MemPool PE view: the 3x3 kernel is stationary in registers; image rows
stream in — two rows popped from the upstream PE's queue, the rest loaded
from memory. TPU view: the kernel weights are a stationary VMEM block; row
blocks stream HBM->VMEM through the grid pipeline. The halo rows are
expressed by passing the image three times with shifted index maps
(prev/current/next row block) — the "pop from neighbor" of the chain
topology; boundary blocks mask their missing neighbor. Inside a block the
row and column neighbours are lane/sublane rotations (``pltpu.roll``)
with the wrapped edge masked: the zero padding, without a padded copy.
The nine weights sit in SMEM as scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv_kernel(xp_ref, xc_ref, xn_ref, k_ref, o_ref, *, n_blocks: int):
    i = pl.program_id(0)
    xc = xc_ref[...].astype(jnp.float32)
    bm, w = xc.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (bm, w), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, w), 1)
    zero = jnp.zeros((bm, w), jnp.float32)
    top = jnp.where(i == 0, 0.0, xp_ref[bm - 1:, :].astype(jnp.float32))
    bot = jnp.where(i == n_blocks - 1, 0.0, xn_ref[:1, :].astype(jnp.float32))
    # rows[dr][r] = x[r + dr - 1]: the block's rows shifted, the edge row
    # taken from the neighbouring block (zero outside the image)
    up = jnp.where(row == 0, top, pltpu.roll(xc, 1, 0))
    down = jnp.where(row == bm - 1, bot, pltpu.roll(xc, bm - 1, 0))
    acc = zero
    for dr, r in enumerate((up, xc, down)):
        left = jnp.where(col == 0, zero, pltpu.roll(r, 1, 1))
        right = jnp.where(col == w - 1, zero, pltpu.roll(r, w - 1, 1))
        for dc, x in enumerate((left, r, right)):
            acc = acc + k_ref[dr, dc] * x
    o_ref[...] = acc.astype(o_ref.dtype)


def conv2d_3x3(x: jax.Array, kernel: jax.Array, *, bm: int = 128,
               interpret: bool = False) -> jax.Array:
    """Zero-padded 3x3 convolution. x: [H, W]; kernel: [3, 3]."""
    h, w = x.shape
    bm = min(bm, h)
    assert h % bm == 0, (h, bm)
    n_blocks = h // bm
    body = functools.partial(_conv_kernel, n_blocks=n_blocks)
    call = pl.pallas_call(
        body,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((bm, w), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((bm, w), lambda i: (i, 0)),
            pl.BlockSpec((bm, w), lambda i: (jnp.minimum(i + 1, n_blocks - 1), 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, w), x.dtype),
        interpret=interpret,
    )
    return call(x, x, x, kernel.astype(jnp.float32))
