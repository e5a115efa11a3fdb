"""Multi-device checks for the link-telemetry layer (DESIGN.md §8).

Printed as one JSON line (see tests/test_multidev.py):

1. parity — the ring backend's decode logits with telemetry armed are
   bitwise identical to the untelemetered backend (the enable is a jit
   argument; the off path never compiles the counters in);
2. counts — a qlr serve step accumulates nonzero queue push/pop and
   payload-byte totals (real traffic, per-PE, device-summed); at the
   schedule level the baseline mode of ``systolic_ring_decode`` books the
   same traffic as multicast bytes with zero queue words, while the
   baseline *serve rung* (``systolic_mode="baseline"`` — no systolic
   machinery at all, XLA inserts the gathers) records nothing;
3. toggle — ``set_telemetry(False)`` freezes the totals without a
   rebuild, and re-enabling resumes accumulation (zero retrace);
4. engine — ``launch/serve.py`` serving a monitored ring engine with
   telemetry folds the totals into the metrics registry as
   ``repro_link_*`` counters, and ``--trace-out`` records a profiler
   trace that holds the engine's ``serve.*`` spans.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import glob
import json
import shutil
import tempfile

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import ServeConfig, get_smoke_config
from repro.core.ring_attention import systolic_ring_decode
from repro.launch.mesh import make_mesh
from repro.obs import linkstats
from repro.models import build_model, split_tree
from repro.serve.sharded_cache import RingShardedBackend

results = {}


def record(name, ok, detail=""):
    results[name] = {"ok": bool(ok), "detail": str(detail)}


mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_smoke_config("qwen3-0.6b")
scfg = ServeConfig(max_batch=8, max_seq_len=64, temperature=0.0)
model = build_model(cfg)
params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
tokens = np.arange(scfg.max_batch, dtype=np.int32).reshape(-1, 1) + 1
active = np.ones(scfg.max_batch, bool)


def fresh(mode, telemetry):
    return RingShardedBackend(cfg, scfg, params, mesh, mode=mode,
                              telemetry=telemetry)


# --- 1. bitwise parity: telemetry on vs off --------------------------------
plain = fresh("qlr", telemetry=False)
tele = fresh("qlr", telemetry=True)
lp = np.asarray(plain.step(tokens, active))
lt = np.asarray(tele.step(tokens, active))
record("telemetry_parity", np.array_equal(lp, lt),
       f"max|diff|={np.abs(lp - lt).max()}")

# --- 2. real counts per rung ------------------------------------------------
d = tele.link_stats()
record("qlr_counts",
       d["pushes"] > 0 and d["pops"] == d["pushes"]
       and d["payload_bytes"] > 0 and d["mcast_bytes"] == 0,
       str(d))

# the baseline serve rung has no systolic machinery at all (XLA inserts
# the gathers), so its telemetry is legitimately all-zero
base = fresh("baseline", telemetry=True)
base.step(tokens, active)
db = base.link_stats()
record("baseline_rung_silent",
       all(v == 0 for v in db.values()), str(db))

# at the schedule level, baseline mode books the gathered cache as
# shared-memory multicast bytes with zero queue words
B, S, H, KV, HD = 8, 16, 4, 2, 8
key = jax.random.PRNGKey(1)
qd = jax.random.normal(key, (B, 1, H, HD), jnp.float32)
kd = jax.random.normal(key, (B, S, KV, HD), jnp.float32)
vd = jax.random.normal(key, (B, S, KV, HD), jnp.float32)
posd = jnp.full((B,), S - 1, jnp.int32)


def decode_stats(mode):
    @jax.jit
    def run(q, k, v, pos):
        with linkstats.collect(1) as sc:
            out = systolic_ring_decode(q, k, v, pos, mesh, mode)
        return out, sc.stats

    _, stats = run(qd, kd, vd, posd)
    return stats.as_dict()


dbs = decode_stats("baseline")
record("baseline_schedule_mcast",
       dbs["mcast_bytes"] > 0 and dbs["payload_bytes"] == 0
       and dbs["pushes"] == 0,
       str(dbs))
dqs = decode_stats("qlr")
record("qlr_schedule_counts",
       dqs["payload_bytes"] > 0 and dqs["mcast_bytes"] == 0
       and dqs["pops"] == dqs["pushes"] > 0,
       str(dqs))

# --- 3. run-time toggle, zero retrace --------------------------------------
after_one = dict(tele.link_stats())
tele.set_telemetry(False)
tele.step(tokens, active)
frozen = dict(tele.link_stats())
tele.set_telemetry(True)
tele.step(tokens, active)
resumed = dict(tele.link_stats())
record("toggle_freezes_totals", frozen == after_one,
       f"{after_one} -> {frozen}")
record("toggle_resumes",
       resumed["pushes"] == 2 * after_one["pushes"],
       f"{after_one['pushes']} -> {resumed['pushes']}")

# --- 4. engine integration + exports ---------------------------------------
from repro.launch import serve as launch_serve  # noqa: E402

out = tempfile.mkdtemp(prefix="check_obs_")
mpath, tdir = f"{out}/metrics.json", f"{out}/trace"
launch_serve.main(["--backend", "ring", "--mesh", "2x4", "--mode", "qlr",
                   "--monitor", "--telemetry", "--requests", "8",
                   "--max-new", "3", "--max-batch", "8", "--max-seq", "64",
                   "--metrics-out", mpath, "--trace-out", tdir])
snap = json.load(open(mpath))
record("engine_link_counters",
       snap["counters"].get("repro_link_pushes_total", 0) > 0
       and snap["counters"].get("repro_ticks_total", 0) > 0,
       str({k: v for k, v in snap["counters"].items()
            if k.startswith("repro_link")}))
xplane = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
names = set()
if xplane:
    from jax.profiler import ProfileData
    names = {e.name for plane in ProfileData.from_file(xplane[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")}
record("engine_trace_spans",
       {"serve.admit", "serve.admit_request", "serve.tick", "serve.plan",
        "serve.step.dispatch", "serve.sample.dispatch", "serve.device_wait",
        "serve.commit"} <= names,
       str(sorted(names)))
shutil.rmtree(out, ignore_errors=True)

print(json.dumps(results))
