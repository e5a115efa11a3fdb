"""Radix-4 DIT FFT stage Pallas kernel — the paper's cfft PE program.

MemPool PE view (§V-C): each PE of stage group s holds its stage-constant
twiddles preloaded in registers (weight-stationary) and processes radix-4
butterflies for a stream of FFTs. TPU view: the twiddle vectors are a
stationary VMEM block; batches of FFTs stream through the grid. Complex
values travel as separate real/imag planes (VPU-friendly; TPUs have no
complex MXU type). One kernel call = one stage; the 4-stage pipeline is
driven by ops.py (or distributed across devices by core.fft.pipelined_fft).

The radix-4 butterflies of a stage mix lanes that lie ``n / 4**(stage+1)``
apart, which no lane-splitting reshape the TPU's compiler accepts can
express. So the butterflies are one stationary [n, n] matrix per stage
(entries 0, +-1, +-1j, as real and imaginary planes) applied on the MXU at
full fp32 precision; the products by 0 and +-1 are exact.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def butterfly_matrix(n: int, stage: int) -> tuple[np.ndarray, np.ndarray]:
    """(real, imag) planes of the [n, n] matrix B with ``y @ B`` = the
    radix-4 butterflies of ``stage``: within each group of L = 4**(stage+1)
    lanes, out[k*q + j] = sum_k' F4[k, k'] * y[k'*q + j], q = L / 4."""
    L = 4 ** (stage + 1)
    q = L // 4
    f4 = np.exp(-2j * np.pi * np.outer(np.arange(4), np.arange(4)) / 4)
    f4 = np.round(f4.real) + 1j * np.round(f4.imag)
    b = np.kron(np.eye(n // L), np.kron(f4.T, np.eye(q)))
    return b.real.astype(np.float32), b.imag.astype(np.float32)


def _stage_kernel(xr_ref, xi_ref, twr_ref, twi_ref, br_ref, bi_ref,
                  or_ref, oi_ref):
    xr = xr_ref[...].astype(jnp.float32)                     # [bb, n]
    xi = xi_ref[...].astype(jnp.float32)
    twr = twr_ref[...].astype(jnp.float32)                   # [1, n]
    twi = twi_ref[...].astype(jnp.float32)
    # twiddle multiply (complex): y = x * tw
    yr = xr * twr - xi * twi
    yi = xr * twi + xi * twr
    br, bi = br_ref[...], bi_ref[...]                        # [n, n]

    def mm(u, v):
        return jnp.dot(u, v, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    # radix-4 butterflies: (yr + i yi) @ (br + i bi)
    or_ref[...] = (mm(yr, br) - mm(yi, bi)).astype(or_ref.dtype)
    oi_ref[...] = (mm(yr, bi) + mm(yi, br)).astype(oi_ref.dtype)


def fft_stage(xr: jax.Array, xi: jax.Array, twr: jax.Array, twi: jax.Array,
              *, stage: int, bb: int = 64, interpret: bool = False):
    """One radix-4 stage over a batch. xr/xi: [B, n]; twr/twi: [n]."""
    b, n = xr.shape
    bb = min(bb, b)
    assert b % bb == 0
    br, bi = butterfly_matrix(n, stage)
    rows = pl.BlockSpec((bb, n), lambda i: (i, 0))
    call = pl.pallas_call(
        _stage_kernel,
        grid=(b // bb,),
        in_specs=[
            rows,
            rows,
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((n, n), lambda i: (0, 0)),
        ],
        out_specs=[rows, rows],
        out_shape=[jax.ShapeDtypeStruct((b, n), xr.dtype),
                   jax.ShapeDtypeStruct((b, n), xi.dtype)],
        interpret=interpret,
    )
    return call(xr, xi, twr[None], twi[None], jnp.asarray(br),
                jnp.asarray(bi))
