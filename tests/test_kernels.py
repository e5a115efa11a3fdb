"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.systolic_matmul.ops import systolic_matmul
from repro.kernels.systolic_matmul.ref import matmul_ref
from repro.kernels.conv2d.ops import conv2d
from repro.kernels.conv2d.ref import conv2d_ref
from repro.kernels.fft.ops import fft256
from repro.kernels.fft.ref import fft_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_sequential_ref
from repro.models.ssm import ssd_chunked
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


# ---------------------------------------------------------------- matmul
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_systolic_matmul(m, k, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(kb, (k, n), jnp.float32).astype(dtype)
    y = systolic_matmul(a, b)
    r = matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol * k)


@pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (64, 128, 256)])
def test_systolic_matmul_blocks(bm, bn, bk):
    ka, kb = jax.random.split(jax.random.PRNGKey(1))
    a = jax.random.normal(ka, (256, 512), jnp.float32)
    b = jax.random.normal(kb, (512, 256), jnp.float32)
    y = systolic_matmul(a, b, bm=bm, bn=bn, bk=bk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-2)


# ---------------------------------------------------------------- conv2d
@pytest.mark.parametrize("h,w,bm", [(256, 256, 128), (128, 64, 32),
                                    (64, 256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d(h, w, bm, dtype):
    kx, kk = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (h, w), jnp.float32).astype(dtype)
    kern = jax.random.normal(kk, (3, 3), jnp.float32).astype(dtype)
    y = conv2d(x, kern, bm=bm)
    r = conv2d_ref(x.astype(jnp.float32), kern.astype(jnp.float32))
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(r),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------- fft
@pytest.mark.parametrize("batch", [16, 64])
def test_fft256(batch):
    key = jax.random.PRNGKey(3)
    kr, ki = jax.random.split(key)
    x = (jax.random.normal(kr, (batch, 256))
         + 1j * jax.random.normal(ki, (batch, 256))).astype(jnp.complex64)
    y = fft256(x)
    r = fft_ref(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-3,
                               atol=1e-3)


def test_fft256_impulse():
    x = jnp.zeros((4, 256), jnp.complex64).at[:, 1].set(1.0)
    y = fft256(x)
    r = fft_ref(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), atol=1e-4)


# ---------------------------------------------------------------- ssd
@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32)])
@pytest.mark.parametrize("g", [1, 2])
def test_ssd_kernel_vs_sequential(s, chunk, g):
    b, h, p, n = 2, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, g, n), jnp.float32) * 0.3
    cc = jax.random.normal(ks[4], (b, s, g, n), jnp.float32) * 0.3
    d = jnp.ones((h,), jnp.float32)
    y = ssd(x, dt, a, bb, cc, d, chunk=chunk)
    r = ssd_sequential_ref(x, dt, a, bb, cc, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-3,
                               atol=1e-3)


def test_ssd_kernel_vs_model_chunked():
    """Kernel twin == the model-layer SSD implementation."""
    from repro.configs.base import ModelConfig
    b, s, h, p, n, g = 2, 64, 4, 8, 16, 1
    cfg = ModelConfig(ssm_chunk=16)
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, g, n), jnp.float32) * 0.3
    cc = jax.random.normal(ks[4], (b, s, g, n), jnp.float32) * 0.3
    d = jnp.ones((h,), jnp.float32)
    y_kernel = ssd(x, dt, a, bb, cc, d, chunk=16)
    y_model = ssd_chunked(x, dt, a, bb, cc, d, cfg)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize("s,bq,bkv", [(256, 128, 128), (256, 64, 128),
                                      (512, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(s, bq, bkv, dtype):
    b, h, d = 2, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32).astype(dtype)
    y = flash_attention(q, k, v, bq=bq, bkv=bkv)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    r = attention_ref(qf, kf, vf).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


def test_flash_attention_gqa():
    b, s, h, kvh, d = 2, 256, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    y = flash_attention(q, k, v)
    ke = jnp.repeat(k, h // kvh, axis=2)
    ve = jnp.repeat(v, h // kvh, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = ke.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = ve.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    r = attention_ref(qf, kf, vf).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=2e-4,
                               atol=2e-4)


# ------------------------------------ hop-fused flash kernel (carried state)
def _zero_state(b, h, sq, hd):
    return (jnp.full((b, h, sq), -1e30, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, h, sq, hd), jnp.float32))


@pytest.mark.parametrize("window", [0, 37])
@pytest.mark.parametrize("kvh", [4, 2])
@pytest.mark.parametrize("s", [192, 256])   # 192 = non-tiling under bq=128
def test_flash_hop_vs_block_update(window, kvh, s):
    """Multi-hop carried state == ring_attention._block_update, over
    causal x window x GQA x non-tiling S."""
    from repro.core.ring_attention import _block_update
    from repro.kernels.flash_attention.ops import flash_hop
    b, h, hd = 2, 4, 16
    sq = t = s // 2                               # two hops of half the keys
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    q = jax.random.normal(ks[0], (b, sq, h, hd), jnp.float32)
    blocks = [
        (jax.random.normal(ks[1 + 2 * i], (b, t, kvh, hd), jnp.float32),
         jax.random.normal(ks[2 + 2 * i], (b, t, kvh, hd), jnp.float32))
        for i in range(2)
    ]
    scale = 1.0 / np.sqrt(hd)
    q_off = sq                                    # pretend we are shard 1
    q_pos = q_off + jnp.arange(sq)

    st_j = _zero_state(b, h, sq, hd)
    st_k = _zero_state(b, h, sq, hd)
    for i, (kb, vb) in enumerate(blocks):
        k_off = i * t
        st_j = _block_update(st_j, q.astype(jnp.float32), kb, vb, q_pos,
                             k_off + jnp.arange(t), causal=True,
                             window=window, scale=scale, num_heads=h)
        st_k = flash_hop(q, kb, vb, st_k, q_offset=q_off, k_offset=k_off,
                         causal=True, window=window)
    for a, r in zip(st_k, st_j):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5,
                                   atol=1e-5)


def test_flash_hop_padded_tail():
    """Scalar k_len masks padded key positions exactly like the oracle."""
    from repro.core.ring_attention import _block_update
    from repro.kernels.flash_attention.ops import flash_hop
    b, sq, t, h, hd = 2, 32, 48, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, hd), jnp.float32)
    scale = 1.0 / np.sqrt(hd)
    st_j = _block_update(_zero_state(b, h, sq, hd), q, k, v,
                         jnp.arange(sq) + t, jnp.arange(t), causal=True,
                         window=0, scale=scale, num_heads=h, k_len=t - 11)
    st_k = flash_hop(q, k, v, _zero_state(b, h, sq, hd), q_offset=t,
                     k_offset=0, k_len=t - 11, causal=True)
    for a, r in zip(st_k, st_j):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5,
                                   atol=1e-5)


def test_flash_hop_per_row_klen_decode():
    """Per-row k_len (decode positions) == dense masked attention."""
    from repro.kernels.flash_attention.ops import flash_hop
    b, t, h, kvh, hd = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kvh, hd), jnp.float32)
    pos = jnp.asarray([13, 57], jnp.int32)
    m, l, acc = flash_hop(q, k, v, _zero_state(b, h, 1, hd), q_offset=0,
                          k_offset=0, k_len=pos + 1, causal=False)
    out = acc / l[..., None]
    ke = jnp.repeat(k, h // kvh, axis=2)
    ve = jnp.repeat(v, h // kvh, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, ke) / np.sqrt(hd)
    valid = (jnp.arange(t)[None, :] <= pos[:, None])[:, None, None, :]
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    ref = jnp.einsum("bhst,bthd->bhsd", p, ve)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_flash_attention_vs_blocked_attention_nontiling():
    """Self-contained form vs models/attention.blocked_attention on a
    non-tiling sequence (S=192 under the 128 default), GQA + window."""
    from repro.models.attention import blocked_attention
    b, s, h, kvh, hd = 2, 192, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, hd), jnp.float32)
    for window in (0, 50):
        y = flash_attention(q, k, v, causal=True, window=window)
        r = blocked_attention(q, jnp.repeat(k, h // kvh, axis=2),
                              jnp.repeat(v, h // kvh, axis=2), causal=True,
                              window=window)
        np.testing.assert_allclose(np.asarray(y), np.asarray(r), rtol=1e-5,
                                   atol=1e-5)


def test_kernel_wrappers_nontiling_no_crash():
    """S=192 with the default 128 block used to hard-crash on the
    clamp-then-assert; now it shrinks (flash) or falls back (matmul)."""
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (1, 192, 2, 16), jnp.float32)
    y = flash_attention(q, q, q, causal=True)          # shrinks to bq=96
    assert y.shape == (1, 192, 2, 16)
    a = jax.random.normal(ks[1], (192, 160), jnp.float32)
    b = jax.random.normal(ks[2], (160, 96), jnp.float32)
    np.testing.assert_allclose(np.asarray(systolic_matmul(a, b)),
                               np.asarray(a @ b), rtol=1e-4, atol=1e-2)
    c = jax.random.normal(ks[0], (97, 64), jnp.float32)  # prime M: jnp path
    d = jax.random.normal(ks[1], (64, 64), jnp.float32)
    np.testing.assert_allclose(np.asarray(systolic_matmul(c, d)),
                               np.asarray(c @ d), rtol=1e-4, atol=1e-2)


def test_tile_matmul_acc_carry():
    """The carry-in kernel: (acc + x @ w) with leading batch dims, exactly
    matching the jnp promotion path."""
    from repro.kernels.systolic_matmul.ops import tile_matmul
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    x = jax.random.normal(ks[0], (2, 3, 64, 160), jnp.float32)
    w = jax.random.normal(ks[1], (160, 96), jnp.float32)
    acc = jax.random.normal(ks[2], (2, 3, 64, 96), jnp.float32)
    y = tile_matmul(x, w, acc)
    ref = acc + jnp.einsum("...k,kn->...n", x, w)
    assert y.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-2)


@pytest.mark.parametrize("dim,pref,want", [
    (256, 128, 128), (192, 128, 96), (1, 128, 1), (600, 128, 120),
    (200, 128, 40), (12, 128, 12), (7, 128, 7),
])
def test_flash_sublane_block(dim, pref, want):
    """Row blocks of the flash kernel divide the dim and are a multiple of
    8, or the whole dim (the TPU's second-minor tiling rule)."""
    from repro.kernels.flash_attention.kernel import sublane_block
    b = sublane_block(dim, pref)
    assert b == want
    assert dim % b == 0 and (b % 8 == 0 or b == dim)
