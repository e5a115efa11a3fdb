"""Multi-device correctness checks for core systolic modules.

Run as a subprocess with 8 fake CPU devices (the test wrapper sets
XLA_FLAGS before jax import). Prints one JSON line with results.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core import queues
from repro.core.collective_matmul import (
    cannon_matmul,
    ffn_applicable,
    ring_ag_matmul,
    ring_matmul_rs,
    systolic_ffn,
)
from repro.core.topology import chains, ring, torus_shift
from repro.launch.mesh import make_mesh

results = {}


def record(name, ok, detail=""):
    results[name] = {"ok": bool(ok), "detail": str(detail)}


mesh = make_mesh((2, 4), ("data", "model"))
n = 4

# --- ring_ag_matmul vs reference -------------------------------------------
key = jax.random.PRNGKey(0)
k1, k2, k3, k4 = jax.random.split(key, 4)
B, S, D, F = 2, 16, 8, 12
x = jax.random.normal(k1, (B, S, D), jnp.float32)
w1 = jax.random.normal(k2, (D, F), jnp.float32)
w2 = jax.random.normal(k3, (D, F), jnp.float32)
ref1 = x @ w1
ref2 = x @ w2

topo = ring("model", n)
for mode in ("baseline", "sw", "xqueue", "qlr"):
    def body(xl, w1_, w2_):
        o1, o2 = ring_ag_matmul(xl, [w1_, w2_], topo, mode)
        return o1, o2
    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "model", None), P(None, None), P(None, None)),
        out_specs=(P(None, None, None), P(None, None, None)),
        check_vma=False))
    o1, o2 = fn(x, w1, w2)
    err = max(float(jnp.abs(o1 - ref1).max()), float(jnp.abs(o2 - ref2).max()))
    record(f"ag_matmul_{mode}", err < 1e-4, err)

# fused tile-kernel local MAC, per-hop partials through the Pallas path
def body_k(xl, w1_, w2_):
    o1, o2 = ring_ag_matmul(xl, [w1_, w2_], topo, "qlr", use_kernel=True)
    return o1, o2
fn = jax.jit(shard_map(
    body_k, mesh=mesh,
    in_specs=(P(None, "model", None), P(None, None), P(None, None)),
    out_specs=(P(None, None, None), P(None, None, None)),
    check_vma=False))
o1, o2 = fn(x, w1, w2)
err = max(float(jnp.abs(o1 - ref1).max()), float(jnp.abs(o2 - ref2).max()))
record("ag_matmul_qlr_kernel", err < 1e-4, err)

# --- ring_matmul_rs vs reference -------------------------------------------
xh = jax.random.normal(k4, (B, S, F), jnp.float32)
wd = jax.random.normal(k2, (F, D), jnp.float32)
ref = xh @ wd
for mode in ("baseline", "sw", "xqueue", "qlr"):
    def body(xl, w):
        return ring_matmul_rs(xl, w, topo, mode)
    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, "model"), P("model", None)),
        out_specs=P(None, "model", None),
        check_vma=False))
    # x sharded over F on model; w sharded over F; output seq-sharded
    y = fn(xh, wd)
    err = float(jnp.abs(y - ref).max())
    record(f"matmul_rs_{mode}", err < 1e-4, err)

fn = jax.jit(shard_map(
    lambda xl, w: ring_matmul_rs(xl, w, topo, "qlr", use_kernel=True),
    mesh=mesh,
    in_specs=(P(None, None, "model"), P("model", None)),
    out_specs=P(None, "model", None),
    check_vma=False))
err = float(jnp.abs(fn(xh, wd) - ref).max())
record("matmul_rs_qlr_kernel", err < 1e-4, err)

# --- cannon 2x2 (use 4-device 'model' axis as 2x2 grid) ---------------------
rows = cols = 2
rt = torus_shift("model", rows, cols, direction="right")
ct = torus_shift("model", rows, cols, direction="down")
# inverse direction for cannon (shift left/up = step -1 rings on the fold)
rt_inv = ring("model", 4, step=0)  # placeholder (not used)
M = K = N = 8
a = jax.random.normal(k1, (M, K), jnp.float32)
b = jax.random.normal(k2, (K, N), jnp.float32)
ref_c = a @ b

# build left/up topologies: invert right/down perms
from repro.core.topology import Topology
left = Topology("left", "model", 4, tuple((d, s) for s, d in rt.perm))
up = Topology("up", "model", 4, tuple((d, s) for s, d in ct.perm))

def make_cbody(mode, use_kernel=False):
    def cbody(al, bl):
        # al: A tile [M/rows, K/cols] (grid (r,c) holds A[r, c])
        # bl: B tile [K/rows, N/cols]
        return cannon_matmul(al[0], bl[0], left, up, rows, cols, mode,
                             use_kernel=use_kernel)[None]
    return cbody

def gather_c(c_t):
    c = np.zeros((M, N), np.float32)
    for r in range(rows):
        for cc in range(cols):
            c[r * M // rows:(r + 1) * M // rows,
              cc * N // cols:(cc + 1) * N // cols] = \
                np.asarray(c_t[r * cols + cc])
    return c

# lay out tiles: reshape A to [rows, cols, m, k] then index by device id
a_t = a.reshape(rows, M // rows, cols, K // cols).swapaxes(1, 2).reshape(4, M // rows, K // cols)
b_t = b.reshape(rows, K // rows, cols, N // cols).swapaxes(1, 2).reshape(4, K // rows, N // cols)

# mode matrix: the skew hops must honor every requested link mode (the bug
# was a hardcoded qlr hop inside _masked_rot), with and without the fused
# Pallas tile kernel as the local MAC
for mode in ("sw", "xqueue", "qlr"):
    for use_kernel in (False, True):
        fn = jax.jit(shard_map(
            make_cbody(mode, use_kernel), mesh=mesh,
            in_specs=(P("model"), P("model")),
            out_specs=P("model"), check_vma=False))
        c = gather_c(fn(a_t, b_t))
        err = float(np.abs(c - np.asarray(ref_c)).max())
        tag = f"cannon_2x2_{mode}" + ("_kernel" if use_kernel else "")
        record(tag, err < 1e-4, err)

# skew hops are FaultSpec-reachable: a corrupt fault on the skew hop index
# (t0 = n-1 = 1 for the 2x2 fold) must poison the result. NaN does not
# survive XLA's max-reduce, so detect via isfinite, not a max-diff.
from repro.core import faults

spec = faults.FaultSpec(kind="corrupt", hop=rows - 1, device=3, seed=7)
with faults.inject(spec):
    fn_f = jax.jit(shard_map(
        make_cbody("qlr"), mesh=mesh,
        in_specs=(P("model"), P("model")),
        out_specs=P("model"), check_vma=False))
    c_f = fn_f(a_t, b_t)
record("cannon_skew_fault_reachable",
       not bool(jnp.isfinite(c_f).all()),
       f"finite={bool(jnp.isfinite(c_f).all())}")

# --- systolic_ffn vs baseline swiglu ----------------------------------------
D2, F2 = 8, 16
xb = jax.random.normal(k1, (4, 16, D2), jnp.float32)
wg = jax.random.normal(k2, (D2, F2), jnp.float32) * 0.3
wu = jax.random.normal(k3, (D2, F2), jnp.float32) * 0.3
wdn = jax.random.normal(k4, (F2, D2), jnp.float32) * 0.3
ref_ffn = (jax.nn.silu(xb @ wg) * (xb @ wu)) @ wdn
assert ffn_applicable(xb, F2, mesh)
for mode in ("baseline", "xqueue", "qlr"):
    y = jax.jit(lambda *a: systolic_ffn(*a, mesh=mesh, mode=mode))(xb, wg, wu, wdn)
    err = float(jnp.abs(y - ref_ffn).max())
    record(f"systolic_ffn_{mode}", err < 1e-3, err)

# --- queue semantics: ring stream visits every shard once -------------------
vals = jnp.arange(n, dtype=jnp.float32)[:, None]  # device i holds value i
def visit(xl):
    def consume(seen, buf, t):
        return seen + buf[0, 0] * (10.0 ** t)
    state, _ = queues.stream(ring("model", n), xl, n, consume,
                             jnp.zeros(()), "qlr")
    return state[None]
fn = jax.jit(shard_map(visit, mesh=mesh, in_specs=P("model"),
                           out_specs=P("model"), check_vma=False))
seen = fn(vals)
# device 0 sees 0,3,2,1 -> 0 + 3*10 + 2*100 + 1*1000 = 1230
record("stream_order", float(seen[0]) == 1230.0, seen.tolist())

# chains: no wraparound (head receives zeros)
def chain_visit(xl):
    moved = queues.hop(chains("model", n, 2), xl, "qlr")
    return moved
fn = jax.jit(shard_map(chain_visit, mesh=mesh, in_specs=P("model"),
                           out_specs=P("model"), check_vma=False))
moved = fn(vals)
record("chains_no_wrap",
       moved[:, 0].tolist() == [0.0, 0.0, 0.0, 2.0] or
       moved[:, 0].tolist() == [0.0, 0.0, 2.0, 0.0],
       moved[:, 0].tolist())

print(json.dumps(results))
failed = {k: v for k, v in results.items() if not v["ok"]}
raise SystemExit(1 if failed else 0)
