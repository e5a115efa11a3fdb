"""Multi-device chaos checks: checked links under shard_map, and the
self-healing serve engine (serve/health.py), printed as one JSON line.

1. detection matrix — on a real 8-device ring under shard_map, every
   fault class (corrupt / drop / stale / slow) x every link mode
   (sw / xqueue / qlr) trips the checked-link sidecar at exactly the
   targeted (hop, PE) in the right health column, the fault vector rides
   as a jit argument (one compile per mode), and the clean checked
   stream matches the unchecked one bitwise.
2. ladder recovery — a checked+monitored ring engine hit by each fault
   class mid-run detects it via the link probe, rolls the tick back, and
   cascades down the mode ladder (qlr -> xqueue -> sw -> baseline)
   within one guarded step; every request still completes with status
   ``done``, and the greedy tokens are **bitwise identical** to a
   fault-free run that was force-degraded along the same ladder at the
   same tick — recovery leaves zero trace. A post-recovery submission
   on the degraded engine must also serve normally.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs import ServeConfig, get_smoke_config
from repro.core import faults, queues
from repro.core.topology import ring
from repro.launch.mesh import make_mesh
from repro.models import build_model, split_tree
from repro.serve.engine import ServeEngine
from repro.serve.health import HealthConfig
from repro.serve.sharded_cache import RingShardedBackend

results = {}


def record(name, ok, detail=""):
    results[name] = {"ok": bool(ok), "detail": str(detail)}


# --- 1. checked-link detection matrix under shard_map -----------------------
N = 8
FAULT_HOP, FAULT_DEV = 2, 5
pe_mesh = make_mesh((N,), ("pe",))
topo = ring("pe", N)
payload = (jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 4) + 1.0) / 3.0


def make_stream(mode, checked):
    def local(x, vec):
        with faults.scope(vec):
            out = queues.stream(topo, x, N, lambda s, b, t: s + jnp.sum(b),
                                jnp.zeros(()), mode, checked=checked)
        if checked:
            state, buf, health = out
            return state[None], buf, health[None]
        state, buf = out
        return state[None], buf

    out_specs = (P("pe"), P("pe", None), P("pe", None, None)) if checked \
        else (P("pe"), P("pe", None))
    return jax.jit(shard_map(local, mesh=pe_mesh,
                             in_specs=(P("pe", None), P()),
                             out_specs=out_specs, check_vma=False))


for mode in queues.MODES:
    checked = make_stream(mode, True)
    unchecked = make_stream(mode, False)

    # clean parity: the sidecar is a pure observer
    s_c, b_c, h_c = checked(payload, faults.no_fault_vec())
    s_u, b_u = unchecked(payload, faults.no_fault_vec())
    record(f"clean_parity_{mode}",
           np.array_equal(np.asarray(s_c), np.asarray(s_u))
           and np.array_equal(np.asarray(b_c), np.asarray(b_u))
           and np.asarray(h_c).sum() == 0)

    for kind in [k for k in faults.KINDS if k != "none"]:
        vec = faults.FaultSpec(kind, hop=FAULT_HOP, device=FAULT_DEV,
                               seed=11).encode()
        _, _, health = checked(payload, vec)     # same compile, new vec
        health = np.asarray(health)              # [N, N, 2]
        offsite = np.delete(health, FAULT_DEV, axis=0).sum() == 0
        tag = health[FAULT_DEV, :, 0]
        csum = health[FAULT_DEV, :, 1]
        if kind in ("corrupt", "drop"):
            want = tag.sum() == 0 and csum.tolist() == [
                1 if t == FAULT_HOP else 0 for t in range(N)]
        elif kind == "slow":
            want = csum.sum() == 0 and tag.tolist() == [
                1 if t == FAULT_HOP else 0 for t in range(N)]
        else:                                    # stale: persistent
            want = csum.sum() == 0 and tag.tolist() == [
                1 if t >= FAULT_HOP else 0 for t in range(N)]
        record(f"detect_{mode}_{kind}", offsite and want,
               health[FAULT_DEV].tolist())

# --- 2. engine ladder recovery ---------------------------------------------
cfg = get_smoke_config("qwen3-0.6b")
scfg = ServeConfig(max_batch=2, max_seq_len=32, temperature=0.0)
model = build_model(cfg)
params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
serve_mesh = make_mesh((1, 4), ("data", "model"),
                       devices=jax.devices()[:4])
FAULT_TICK = 3


def make_engine():
    be = RingShardedBackend(cfg, scfg, params, serve_mesh, mode="qlr",
                            checked=True)
    return ServeEngine(cfg, scfg, params, backend=be, health=HealthConfig())


def drive(eng, fault_kind):
    """Run a fixed submission schedule; at FAULT_TICK either arm
    fault_kind for one engine step or (clean reference) force-degrade
    down the same three rungs."""
    rng = np.random.default_rng(0)
    for _ in range(3):
        p = rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(2, 8))).astype(np.int32)
        eng.submit(p, max_new_tokens=4)
    reqs = list(eng.pending)
    ticks = 0
    while eng.sched.busy and ticks < 60:
        eng._admit()
        if ticks == FAULT_TICK and fault_kind is None:
            for _ in range(3):
                eng.monitor.force_degrade()
            eng.step()
        elif ticks == FAULT_TICK:
            with faults.inject(faults.FaultSpec(fault_kind, hop=1,
                                                device=2, seed=7)):
                eng.step()
        else:
            eng.step()
        ticks += 1
    return reqs, [tuple(r.out_tokens) for r in reqs]


ref_eng = make_engine()
ref_reqs, ref_toks = drive(ref_eng, None)
record("ref_ladder",
       ref_eng.backend.name == "ring-baseline+checked"
       and all(r.status == "done" for r in ref_reqs),
       ref_eng.backend.name)

for kind in [k for k in faults.KINDS if k != "none"]:
    eng = make_engine()
    reqs, toks = drive(eng, kind)
    degrades = [e for e in eng.monitor.events if e.kind == "degrade"]
    detected = [e for e in eng.monitor.events if e.kind == "link_fault"]
    record(f"recover_{kind}_ladder",
           eng.backend.name == "ring-baseline+checked"
           and len(degrades) == 3 and len(detected) == 3
           and all(e.tick == FAULT_TICK + 1 for e in degrades),
           "; ".join(e.detail for e in eng.monitor.events))
    record(f"recover_{kind}_status",
           all(r.status == "done" and r.done for r in reqs))
    record(f"recover_{kind}_bitwise", toks == ref_toks,
           f"{toks} vs {ref_toks}")

# post-recovery: the degraded engine keeps serving new work normally
post_req = eng.sched.submit(np.asarray([5, 7, 11], np.int32),
                            max_new_tokens=3)
n_events = len(eng.monitor.events)
eng.run(max_ticks=60)
record("post_recovery_serves",
       post_req.status == "done" and len(post_req.out_tokens) == 3
       and len(eng.monitor.events) == n_events,   # no new faults fired
       eng.backend.name)

print(json.dumps(results))
