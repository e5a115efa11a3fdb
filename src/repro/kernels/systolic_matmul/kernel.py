"""Output-stationary tiled GEMM Pallas kernel — the paper's matmul PE
program adapted to the TPU memory hierarchy.

MemPool PE view: C tile stationary in the register file; A/B operands
arrive through queues; QLRs autonomously stream the next operands while the
IPU MACs. TPU view: the C tile is a VMEM fp32 scratch accumulator; the
(bm,bk)/(bk,bn) operand tiles stream HBM->VMEM through Pallas's implicit
grid pipeline (the QLR analogue: block k+1 is DMA'd while block k is in the
MXU); the K grid dimension is the systolic stream, M/N are parallel.

Block shapes default to MXU-aligned 128 multiples; the "data reuse degree"
of the paper (2x2 -> 4x4 PE tiles, Table II) maps to (bm, bn) scaling and
is swept by the matmul-variants benchmark.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def largest_dividing_block(dim: int, preferred: int) -> int:
    """Largest block size <= preferred that divides dim exactly (>= 1)."""
    b = max(1, min(preferred, dim))
    while dim % b:
        b -= 1
    return b


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the stationary C tile accumulates the streamed operand product (MXU)
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _matmul_acc_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, n_k: int):
    """Carry-in variant: the stationary tile starts from C, not zero.

    This is the hop-fused form for ring/Cannon schedules — each hop's
    partial product folds into the traveling accumulator inside the
    kernel instead of a separate `partial + x @ w` HLO."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, acc: jax.Array | None = None, *,
           bm: int = 128, bn: int = 128, bk: int = 128,
           interpret: bool = False, out_dtype=None) -> jax.Array:
    """C[M,N] = (acc +) A[M,K] @ B[K,N], output-stationary tiling.

    Non-tiling shapes shrink each block to the largest divisor instead of
    crashing (e.g. M=192 under the default 128)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm = largest_dividing_block(m, bm)
    bn = largest_dividing_block(n, bn)
    bk = largest_dividing_block(k, bk)
    out_dtype = out_dtype or a.dtype
    n_k = k // bk
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    if acc is None:
        kernel = functools.partial(_matmul_kernel, n_k=n_k)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ]
        operands = (a, b)
    else:
        assert acc.shape == (m, n), (acc.shape, (m, n))
        kernel = functools.partial(_matmul_acc_kernel, n_k=n_k)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ]
        operands = (a, b, acc)
    call = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )
    return call(*operands)
