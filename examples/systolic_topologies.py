"""Systolic-topology playground: the paper's reconfigurable queue networks
on fake CPU devices.

Demonstrates (on an 8-device 'pe' axis):
  * ring / chains / snake topologies as queue graphs,
  * the three link modes (sw / xqueue / qlr) on a ring all-gather matmul,
    with HLO op counts showing the software-queue bookkeeping overhead the
    paper's Xqueue/QLR extensions eliminate,
  * the hybrid conv2d (halo pops + local loads),
  * a 4-stage pipelined FFT stream.

  PYTHONPATH=src python examples/systolic_topologies.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core.collective_matmul import ring_ag_matmul
from repro.core.fft import pipelined_fft
from repro.core.halo import conv2d_ref, conv2d_systolic
from repro.core.topology import chains, ring, snake_ring
from repro.launch.mesh import make_mesh


def op_count(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return sum(1 for l in text.splitlines() if " = " in l and l.startswith("  "))


def main():
    mesh = make_mesh((8,), ("pe",))
    print("topologies over 8 PEs:")
    for topo in (ring("pe", 8), chains("pe", 8, 2), snake_ring("pe", 2, 4)):
        print(f"  {topo.name:12s} links={len(topo.perm)} "
              f"perm={list(topo.perm)[:6]}{'...' if len(topo.perm) > 6 else ''}")

    # ring AG-matmul under the three link modes
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (16, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8), jnp.float32)
    ref = x @ w
    print("\nring all-gather matmul (A streamed, W resident):")
    topo = ring("pe", 8)
    for mode in ("baseline", "sw", "xqueue", "qlr"):
        def body(xl, wl, mode=mode):
            (out,) = ring_ag_matmul(xl, [wl], topo, mode)
            return out
        fn = shard_map(body, mesh=mesh, in_specs=(P("pe", None), P(None, None)),
                           out_specs=P(None, None), check_vma=False)
        y = jax.jit(fn)(jax.device_put(x, NamedSharding(mesh, P("pe", None))), w)
        err = float(jnp.abs(y - ref).max())
        ops = op_count(fn, jax.device_put(x, NamedSharding(mesh, P("pe", None))), w)
        print(f"  {mode:9s} err={err:.1e} hlo_ops={ops:4d}"
              f"{'  <- software-queue bookkeeping overhead' if mode == 'sw' else ''}")

    # ring attention: q shards resident, K/V blocks stream the ring
    print("\nring attention (q resident / K/V streamed, online softmax):")
    from repro.core.ring_attention import systolic_ring_attention
    B, S, H, HD = 1, 32, 2, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, HD), jnp.float32)
    kk = jax.random.normal(ks[1], (B, S, H, HD), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, HD), jnp.float32)
    # the wrapper rings over a 'model' axis, so demo it on its own mesh
    mesh_m = make_mesh((8,), ("model",))
    args = [jax.device_put(a, NamedSharding(mesh_m, P(None, "model", None,
                                                      None)))
            for a in (q, kk, v)]
    ref = None
    for mode in ("baseline", "sw", "xqueue", "qlr"):
        fn = jax.jit(lambda q, k, v, m=mode: systolic_ring_attention(
            q, k, v, mesh_m, m, causal=True))
        y = fn(*args)
        if ref is None:
            ref = y
        err = float(jnp.abs(y - ref).max())
        ops = op_count(fn, *args)
        print(f"  {mode:9s} err={err:.1e} hlo_ops={ops:4d}"
              f"{'  <- software-queue bookkeeping overhead' if mode == 'sw' else ''}")

    # expert-ring MoE on a Mixtral-shaped config: expert shards resident,
    # routed token blocks stream the ring (the dual of ring attention)
    print("\nexpert-ring MoE (Mixtral 8-expert top-2; experts resident, "
          "tokens streamed):")
    from dataclasses import replace
    from repro.configs.mixtral_8x22b import SMOKE
    from repro.models import moe as moe_lib
    from repro.models.common import split_tree, use_sharding
    mcfg = replace(SMOKE, num_experts=8,           # full Mixtral expert count
                   dtype="float32", param_dtype="float32")
    mparams, _ = split_tree(moe_lib.init_moe(jax.random.PRNGKey(4), mcfg))
    xt = jax.random.normal(jax.random.PRNGKey(5), (2, 32, mcfg.d_model))
    y_ref, _ = jax.jit(lambda p, x: moe_lib.apply_moe(p, x, mcfg))(mparams, xt)
    with use_sharding(mesh_m):
        for mode in ("baseline", "sw", "xqueue", "qlr"):
            cfg_m = replace(mcfg, systolic_mode=mode)
            fn = jax.jit(lambda p, x, c=cfg_m: moe_lib.apply_moe(p, x, c)[0])
            err = float(jnp.abs(fn(mparams, xt) - y_ref).max())
            ops = op_count(lambda p, x, c=cfg_m: moe_lib.apply_moe(p, x, c)[0],
                           mparams, xt)
            print(f"  {mode:9s} err={err:.1e} hlo_ops={ops:4d}"
                  f"{'  <- software-queue bookkeeping overhead' if mode == 'sw' else ''}")

    # hybrid conv2d: halo rows popped from neighbors, interior rows local
    img = jax.random.normal(key, (64, 32), jnp.float32)
    kern = jax.random.normal(jax.random.PRNGKey(2), (3, 3), jnp.float32)
    img_s = jax.device_put(img, NamedSharding(mesh, P("pe", None)))
    y = jax.jit(lambda a, k: conv2d_systolic(a, k, mesh, "pe", "qlr"))(img_s, kern)
    err = float(jnp.abs(jax.device_get(y) - conv2d_ref(img, kern)).max())
    print(f"\nhybrid conv2d (halo queues + local loads): err={err:.1e}")

    # pipelined FFT over a 4-stage group
    mesh4 = make_mesh((4,), ("pe",))
    xs = (jax.random.normal(key, (8, 4, 256))
          + 1j * jax.random.normal(jax.random.PRNGKey(3), (8, 4, 256))
          ).astype(jnp.complex64)
    y = jax.jit(lambda v: pipelined_fft(v, mesh4, "pe", "qlr"))(xs)
    ref = np.fft.fft(np.asarray(xs), axis=-1)
    err = float(np.abs(np.asarray(y) - ref).max() / np.abs(ref).max())
    print(f"4-stage pipelined radix-4 FFT: rel err={err:.1e}")


if __name__ == "__main__":
    main()
