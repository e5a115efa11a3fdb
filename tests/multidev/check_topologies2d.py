"""Multi-device check: 2-D systolic schedules (snake_fold / torus2d /
cannon_grid) match the dense oracles in every link mode — values and
grads — on 8 fake CPU devices, plus the cycle-only decode guard and the
one-hop Cannon grid skew. Prints one JSON line with results."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import queues
from repro.core.collective_matmul import (
    cannon_matmul,
    ring_ag_matmul,
    ring_matmul_rs,
)
from repro.core.ring_attention import systolic_ring_attention, \
    systolic_ring_decode
from repro.core.ring_moe import systolic_ring_moe
from repro.core.topology import (
    GridSchedule,
    Topology,
    resolve,
    ring,
    torus_shift,
)
from repro.launch.mesh import make_mesh

results = {}


def record(name, ok, detail=""):
    results[name] = {"ok": bool(ok), "detail": str(detail)}


TOPOS = ("snake_fold", "torus2d", "cannon_grid")
LINK_MODES = ("sw", "xqueue", "qlr")

mesh = make_mesh((8,), ("model",))     # grids fold 2x4
n = 8

# --- ring attention: any full-coverage visit order preserves the online
# --- softmax (values AND grads vs the dense oracle) -------------------------
key = jax.random.PRNGKey(0)
k1, k2, k3 = jax.random.split(key, 3)
B, S, H, HD = 2, 32, 4, 8
q = jax.random.normal(k1, (B, S, H, HD), jnp.float32)
k = jax.random.normal(k2, (B, S, H, HD), jnp.float32)
v = jax.random.normal(k3, (B, S, H, HD), jnp.float32)


def ref_attention(q, k, v):
    s = q.shape[1]
    scores = jnp.einsum("bshk,bthk->bhst", q, k) / math.sqrt(HD)
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), -1)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


ref = ref_attention(q, k, v)
for name in TOPOS:
    sched = resolve(name, "model", n)
    for mode in LINK_MODES:
        y = jax.jit(lambda q, k, v, m=mode, t=sched: systolic_ring_attention(
            q, k, v, mesh, m, topo=t))(q, k, v)
        err = float(jnp.abs(y - ref).max())
        record(f"attn_{name}_{mode}", err < 1e-4, err)

    def loss(q, k, v, t=sched):
        return jnp.sum(systolic_ring_attention(q, k, v, mesh, "qlr",
                                               topo=t) ** 2)
    g = jax.jit(jax.grad(loss))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(ref_attention(q, k, v) ** 2))(
        q, k, v)
    err = float(jnp.abs(g - gr).max())
    record(f"attn_grad_{name}", err < 1e-3, err)

# --- AG / RS collective matmuls on grid schedules ---------------------------
D, F = 8, 16
x = jax.random.normal(k1, (B, S, D), jnp.float32)
w = jax.random.normal(k2, (D, F), jnp.float32)
ref_mm = x @ w
for name in TOPOS:
    sched = resolve(name, "model", n)
    for mode in LINK_MODES:
        def body(xl, wl, m=mode, t=sched):
            (y,) = ring_ag_matmul(xl, [wl], t, m)
            return y
        y = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "model", None), P(None, None)),
            out_specs=P(None, None, None), check_vma=False))(x, w)
        err = float(jnp.abs(y - ref_mm).max())
        record(f"agmm_{name}_{mode}", err < 1e-4, err)

xh = jax.random.normal(k3, (B, S, F), jnp.float32)
wd = jax.random.normal(k2, (F, D), jnp.float32)
ref_rs = xh @ wd
for name in TOPOS:
    sched = resolve(name, "model", n)
    for mode in LINK_MODES:
        def body(xl, wl, m=mode, t=sched):
            return ring_matmul_rs(xl, wl, t, m)
        y = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, "model"), P("model", None)),
            out_specs=P(None, "model", None), check_vma=False))(xh, wd)
        err = float(jnp.abs(y - ref_rs).max())
        record(f"rsmm_{name}_{mode}", err < 1e-4, err)

# grads flow through a grid schedule's AG ring
sched = resolve("cannon_grid", "model", n)


def mm_loss(x, w):
    def body(xl, wl):
        (y,) = ring_ag_matmul(xl, [wl], sched, "qlr")
        return y
    y = shard_map(body, mesh=mesh,
                  in_specs=(P(None, "model", None), P(None, None)),
                  out_specs=P(None, None, None), check_vma=False)(x, w)
    return jnp.sum(y ** 2)


g = jax.jit(jax.grad(mm_loss, argnums=(0, 1)))(x, w)
gr = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2), argnums=(0, 1))(x, w)
err = max(float(jnp.abs(a - b).max()) for a, b in zip(g, gr))
record("agmm_grad_cannon_grid", err < 1e-3, err)

# --- expert-ring MoE rides the snake_fold placement -------------------------
from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib
from repro.models.common import split_tree

E, Sm, Dm, Fm = 8, 32, 16, 32
cfg = ModelConfig(name="t2d-moe", family="moe", d_model=Dm, d_ff=Fm,
                  d_ff_expert=Fm, num_experts=E, experts_per_token=2,
                  capacity_factor=2.0, dtype="float32",
                  param_dtype="float32")
params, _ = split_tree(moe_lib.init_moe(jax.random.PRNGKey(0), cfg))
xm = jax.random.normal(k1, (B, Sm, Dm), jnp.float32)
cap = moe_lib.expert_capacity(cfg, Sm)


def moe_fn(p, x, mode, topo):
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    weights, idx, _ = moe_lib._topk_routing(logits, cfg)
    pos = moe_lib._positions_in_expert(idx, E)
    return systolic_ring_moe(x, idx, pos, weights, p["w_gate"], p["w_up"],
                             p["w_down"], cap, mesh, mode, topo=topo)


ref_moe = jax.jit(lambda p, x: moe_fn(p, x, "qlr", None))(params, xm)
snake = resolve("snake_fold", "model", n)
for mode in LINK_MODES:
    y = jax.jit(lambda p, x, m=mode: moe_fn(p, x, m, snake))(params, xm)
    err = float(jnp.abs(y - ref_moe).max())
    record(f"moe_snake_fold_{mode}", err < 1e-4, err)

# --- decode rides any cycle; grid schedules are rejected up front -----------
Bd, Sc, Kv = 16, 32, 2
kd = jax.random.split(key, 4)
qd = jax.random.normal(kd[0], (Bd, 1, H, HD), jnp.float32)
kc = jax.random.normal(kd[1], (Bd, Sc, Kv, HD), jnp.float32)
vc = jax.random.normal(kd[2], (Bd, Sc, Kv, HD), jnp.float32)
pos = jax.random.randint(kd[3], (Bd,), 0, Sc)
ref_dec = jax.jit(lambda *a: systolic_ring_decode(*a, mesh, "qlr"))(
    qd, kc, vc, pos)
for mode in LINK_MODES:
    y = jax.jit(lambda *a, m=mode: systolic_ring_decode(
        *a, mesh, m, topo=snake))(qd, kc, vc, pos)
    err = float(jnp.abs(y - ref_dec).max())
    record(f"decode_snake_fold_{mode}", err < 1e-4, err)

try:
    jax.jit(lambda *a: systolic_ring_decode(
        *a, mesh, "qlr", topo=resolve("torus2d", "model", n)))(
        qd, kc, vc, pos)
    record("grid_decode_raises", False, "no error raised")
except (TypeError, AssertionError) as e:
    record("grid_decode_raises", True, type(e).__name__)

# --- Cannon: one-hop grid skew == masked-rotation skew (2x2 on model=4) -----
mesh24 = make_mesh((2, 4), ("data", "model"))
rows = cols = 2
rt = torus_shift("model", rows, cols, direction="right")
ct = torus_shift("model", rows, cols, direction="down")
left = Topology("left", "model", 4, tuple((d, s) for s, d in rt.perm))
up = Topology("up", "model", 4, tuple((d, s) for s, d in ct.perm))
M = K = N = 8
a = jax.random.normal(k1, (M, K), jnp.float32)
b = jax.random.normal(k2, (K, N), jnp.float32)
a_t = a.reshape(rows, M // rows, cols, K // cols).swapaxes(1, 2).reshape(
    4, M // rows, K // cols)
b_t = b.reshape(rows, K // rows, cols, N // cols).swapaxes(1, 2).reshape(
    4, K // rows, N // cols)


def gather_c(c_t):
    c = np.zeros((M, N), np.float32)
    for r in range(rows):
        for cc in range(cols):
            c[r * M // rows:(r + 1) * M // rows,
              cc * N // cols:(cc + 1) * N // cols] = \
                np.asarray(c_t[r * cols + cc])
    return c


for mode in LINK_MODES:
    def cbody(al, bl, m=mode, sk="grid"):
        return cannon_matmul(al[0], bl[0], left, up, rows, cols, m,
                             skew=sk)[None]
    fn = jax.jit(shard_map(cbody, mesh=mesh24,
                           in_specs=(P("model"), P("model")),
                           out_specs=P("model"), check_vma=False))
    err = float(np.abs(gather_c(fn(a_t, b_t)) - np.asarray(a @ b)).max())
    record(f"cannon_grid_skew_{mode}", err < 1e-4, err)

print(json.dumps(results))
failed = {k: v for k, v in results.items() if not v["ok"]}
raise SystemExit(1 if failed else 0)
