"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels at real
widths and the full-width qwen3-0.6b and olmo-1b decode steps at the default
``ServeConfig``. Nothing runs; the TPU compiler either accepts the program
or raises what the chip would raise. Every kernel case compiles with
``interpret=False`` and checks for ``tpu_custom_call``, so an interpret-mode
lowering cannot pass in silence.

The topology is described inside a module-scoped fixture (only one process
may load the TPU library, and only once a test of this file runs); where it
cannot be described, every test here skips.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ServeConfig, get_config
from repro.kernels.conv2d.kernel import conv2d_3x3
from repro.kernels.fft.kernel import fft_stage
from repro.kernels.flash_attention.kernel import flash_carry
from repro.kernels.ssd.kernel import ssd_chunks
from repro.kernels.systolic_matmul.ops import tile_matmul
from repro.models import build_model, split_tree
from repro.serve.sharded_cache import jit_donating_cache

GIB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler/library in this env
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    """AOT-compile ``fn`` for ``sharding``'s device on (shape, dtype)
    arguments."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# qwen3-0.6b widths (configs/qwen3_0p6b.py)
D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 1024, 3072, 16, 8, 128


@pytest.mark.parametrize("with_acc", [False, True])
def test_tile_matmul_compiles(one_chip, with_acc):
    """FFN up-projection [B*S, d_model] @ [d_model, d_ff] (bf16) and the
    carried-accumulator down-projection into an fp32 tile."""
    m, bf = 2048, jnp.bfloat16
    shapes = ([((m, D_FF), bf), ((D_FF, D_MODEL), bf),
               ((m, D_MODEL), jnp.float32)] if with_acc
              else [((m, D_MODEL), bf), ((D_MODEL, D_FF), bf)])
    fn = functools.partial(tile_matmul, interpret=False)
    _assert_kernel(_compile(fn, one_chip, *shapes))


@pytest.mark.parametrize("rows,sq,t", [
    (32 * KV_HEADS, 1, 512),        # ring decode hop: 32 slots, 2048/4 keys
    (KV_HEADS, 256, 256),           # block prefill hop of a 256-token chunk
])
def test_flash_carry_compiles(one_chip, rows, sq, t):
    g = HEADS // KV_HEADS

    def fn(q, k, v, m, l, acc, klen):
        return flash_carry(q, k, v, m, l, acc, 7, 3, klen, causal=sq > 1,
                           interpret=False)
    c = _compile(fn, one_chip,
                 ((rows, g, sq, HEAD_DIM), jnp.float32),
                 ((rows, t, HEAD_DIM), jnp.bfloat16),
                 ((rows, t, HEAD_DIM), jnp.bfloat16),
                 ((rows, g, sq, 1), jnp.float32),
                 ((rows, g, sq, 1), jnp.float32),
                 ((rows, g, sq, HEAD_DIM), jnp.float32),
                 ((rows,), jnp.int32))
    _assert_kernel(c)


def test_ssd_chunks_compiles(one_chip):
    """mamba2-1.3b widths: 64 heads of 64, state 128, chunk 256, 1 group."""
    bh, nc, l, p, n = 64, 4, 256, 64, 128
    fn = functools.partial(ssd_chunks, nheads=64, ngroups=1, interpret=False)
    c = _compile(fn, one_chip, ((bh, nc, l, p), jnp.float32),
                 ((bh, nc, l, 1), jnp.float32), ((bh, 1, 1, 1), jnp.float32),
                 ((1, nc, l, n), jnp.float32), ((1, nc, l, n), jnp.float32))
    _assert_kernel(c)


def test_conv2d_3x3_compiles(one_chip):
    fn = functools.partial(conv2d_3x3, bm=128, interpret=False)
    c = _compile(fn, one_chip, ((512, 512), jnp.float32),
                 ((3, 3), jnp.float32))
    _assert_kernel(c)


@pytest.mark.parametrize("stage", [0, 3])
def test_fft_stage_compiles(one_chip, stage):
    fn = functools.partial(fft_stage, stage=stage, interpret=False)
    c = _compile(fn, one_chip, ((256, 256), jnp.float32),
                 ((256, 256), jnp.float32), ((256,), jnp.float32),
                 ((256,), jnp.float32))
    _assert_kernel(c)


def _compile_decode_step(arch, sharding):
    """The full-width decode step at the default ServeConfig, compiled the
    way the serving backends jit it (cache donated)."""
    cfg = get_config(arch)
    scfg = ServeConfig()
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: split_tree(model.init(jax.random.PRNGKey(0)))[0])
    cache = jax.eval_shape(
        functools.partial(model.init_cache, scfg.max_batch, scfg.max_seq_len))
    args = [jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        t) for t in (params, cache)]
    tokens = jax.ShapeDtypeStruct((scfg.max_batch, 1), jnp.int32,
                                  sharding=sharding)
    active = jax.ShapeDtypeStruct((scfg.max_batch,), jnp.bool_,
                                  sharding=sharding)
    step = jit_donating_cache(model.decode_step, "decode_step")
    return step.lower(*args, tokens, active).compile(), cache


def test_qwen3_decode_step_fits_one_chip(one_chip):
    """The full-width decode step at the default ServeConfig, compiled the
    way the serving backends jit it (cache donated), fits in one v5e's
    16 GiB with margin: without the donation the step holds two caches."""
    compiled, _ = _compile_decode_step("qwen3-0.6b", one_chip)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 6 * GIB, mem      # the cache is donated
    assert total < 15 * GIB, mem


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)")


def _layer_copies(hlo: str, dtype: str, slab: tuple) -> list[str]:
    """Instructions of optimized HLO that copy one layer's cache slab
    (``dtype[*slab]``, leading 1s aside) out of the stacked cache or back
    into it: a slab-shaped value written to memory (produced outside any
    fusion, or the result of a fusion) or a dynamic-update-slice whose
    update is a slab. A slice fused into the op that reads it is no copy."""
    comps: dict[str, list] = {}
    body: list = []
    for line in hlo.splitlines():
        if (m := _COMPUTATION.match(line)):
            body = comps.setdefault(m.group(1), [])
        elif (m := _INSTRUCTION.match(line)):
            dims = tuple(int(d) for d in m.group(4).split(",") if d)
            body.append((bool(m.group(1)), m.group(2), m.group(3), dims,
                         m.group(5), re.findall(r"%([\w.\-]+)", m.group(6)),
                         line.strip()))
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    shape = {i[1]: (i[2], i[3]) for b in comps.values() for i in b}

    def is_slab(dt, dims):
        while dims[:1] == (1,):
            dims = dims[1:]
        return dt == dtype and dims == tuple(slab)

    copies = []
    for name, instrs in comps.items():
        for root, _, dt, dims, op, operands, line in instrs:
            if op == "dynamic-update-slice":
                if is_slab(*shape.get(operands[1], ("", ()))):
                    copies.append(line)
            elif is_slab(dt, dims) and (
                    root if name in fused
                    else op not in ("parameter", "get-tuple-element")):
                copies.append(line)
    return copies


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "olmo-1b"])
def test_decode_step_writes_cache_in_place(one_chip, arch):
    """Each layer's new K/V rows go straight into the stacked cache and
    attention reads the layer where it lies: no layer's [B,S,Kv,hd] slab
    is copied out of the stack or written back. olmo-1b (MHA, nothing to
    widen) then needs almost no temporaries."""
    compiled, cache = _compile_decode_step(arch, one_chip)
    k = cache["layers"]["k"]
    dtype = {"bfloat16": "bf16", "float32": "f32"}[k.dtype.name]
    copies = _layer_copies(compiled.as_text(), dtype, k.shape[1:])
    assert not copies, "\n".join(c[:200] for c in copies)
    if arch == "olmo-1b":
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 64 * 2 ** 20, temp
