"""Observability layer tests (DESIGN.md §8): metrics registry semantics
and export, link telemetry accounting, the no-retrace enable toggle,
fault visibility in the error totals, and the utilization model's mode
ordering (the engine's spans: tests/test_engine_spans.py).

Single-device tier-1: the topology axis is realized as a vmap axis (the
test_faults.py pattern) and the shard_map republish is emulated with the
same inner-scope/extra-output mechanics the systolic wrappers use."""
from __future__ import annotations

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import faults, queues
from repro.core.topology import ring
from repro.obs import linkstats, metrics, utilization

N = 4
N_STEPS = 4


# --- metrics: counters / gauges / histograms --------------------------------
def test_counter_semantics():
    reg = metrics.Registry()
    c = reg.counter("requests_total", "help text")
    c.inc()
    c.inc(3)
    assert c.value == 4
    assert reg.counter("requests_total") is c        # get-or-create
    with pytest.raises(ValueError):
        c.inc(-1)                                    # counters only go up
    with pytest.raises(ValueError):
        reg.gauge("requests_total")                  # cross-kind collision


def test_gauge_semantics():
    reg = metrics.Registry()
    g = reg.gauge("depth")
    g.set(7.0)
    g.inc(2.0)
    g.dec(4.0)
    assert g.value == 5.0


def test_histogram_quantiles():
    reg = metrics.Registry()
    h = reg.histogram("latency")
    for v in range(1, 101):                          # 1..100
        h.observe(float(v))
    assert h.count == 100
    assert h.sum == pytest.approx(5050.0)
    assert h.quantile(0.0) == pytest.approx(1.0)
    assert h.quantile(1.0) == pytest.approx(100.0)
    assert h.quantile(0.5) == pytest.approx(50.5)    # linear interpolation
    assert h.quantile(0.9) == pytest.approx(90.1, abs=0.2)
    assert math.isnan(reg.histogram("empty").quantile(0.5))


def test_histogram_timer():
    reg = metrics.Registry()
    h = reg.histogram("span_seconds")
    with h.time():
        pass
    assert h.count == 1 and h.sum >= 0.0


def test_json_and_prometheus_export(tmp_path):
    reg = metrics.Registry()
    reg.counter("repro_ticks_total", "engine ticks").inc(5)
    reg.gauge("repro_active_slots").set(2)
    h = reg.histogram("repro_tick_latency_seconds", "tick wall time")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)

    jpath, ppath = tmp_path / "m.json", tmp_path / "m.prom"
    reg.dump_json(jpath)
    reg.dump_prometheus(ppath)

    snap = json.loads(jpath.read_text())
    assert snap["counters"]["repro_ticks_total"] == 5
    assert snap["gauges"]["repro_active_slots"] == 2
    hist = snap["histograms"]["repro_tick_latency_seconds"]
    assert hist["count"] == 3 and hist["sum"] == pytest.approx(0.6)

    prom = ppath.read_text()
    assert "# HELP repro_ticks_total engine ticks" in prom
    assert "# TYPE repro_ticks_total counter" in prom
    assert "repro_ticks_total 5" in prom
    assert "# TYPE repro_tick_latency_seconds summary" in prom
    assert 'repro_tick_latency_seconds{quantile="0.5"}' in prom
    assert "repro_tick_latency_seconds_count 3" in prom


# --- linkstats: counting, gating, scan/shard republish ----------------------
def _payload(n=N, k=3):
    return (jnp.arange(n * k, dtype=jnp.float32).reshape(n, k) + 1.0) / 7.0


def _republished_stream(mode="qlr", checked=False, spec=None):
    """The shard_map republish pattern on a vmap axis: each 'device' opens
    an inner scope, ships its per-PE stats out as an extra output."""
    topo = ring("pe", N)
    xs = _payload()
    state0 = jnp.zeros((N, xs.shape[1]))

    def device_fn(x, s0):
        with linkstats.collect(1) as sc:
            out = queues.stream(topo, x, N_STEPS,
                                lambda s, b, t: s + b, s0, mode,
                                checked=checked)
        return out, linkstats.expand(sc.stats)

    fn = jax.vmap(device_fn, axis_name=topo.axis)
    if spec is None:
        out, stats = fn(xs, state0)
    else:
        with faults.inject(spec):
            out, stats = fn(xs, state0)
    flat = jax.tree_util.tree_map(lambda l: l.reshape(-1), stats)
    return out, linkstats.device_sum(flat)


def test_linkstats_stream_counts():
    _, totals = _republished_stream("qlr")
    d = totals.as_dict()
    # N devices x N_STEPS hops x 1 queue (one payload leaf)
    assert d["pushes"] == N * N_STEPS
    assert d["pops"] == N * N_STEPS
    # payload per hop per device: [3] f32 = 12 bytes
    assert d["payload_bytes"] == N * N_STEPS * 3 * 4
    assert d["tag_errors"] == 0 and d["csum_errors"] == 0
    assert d["mcast_bytes"] == 0.0


def test_linkstats_counts_mode_invariant():
    base = _republished_stream("sw")[1].as_dict()
    for mode in ("xqueue", "qlr"):
        assert _republished_stream(mode)[1].as_dict() == base


def test_corrupt_fault_shows_in_error_totals():
    """Satellite regression: a mid-stream corrupt fault must surface in the
    per-hop checked-link error totals carried by LinkStats."""
    clean = _republished_stream("qlr", checked=True)[1].as_dict()
    assert clean["csum_errors"] == 0 and clean["faulty_hops"] == 0

    spec = faults.FaultSpec("corrupt", hop=1, device=2)
    _, totals = _republished_stream("qlr", checked=True, spec=spec)
    d = totals.as_dict()
    assert d["csum_errors"] >= 1          # payload digest tripped
    assert d["faulty_hops"] >= 1
    assert d["tag_errors"] == 0           # corruption is not a stuck link
    # traffic counters are unaffected by the fault
    assert d["pushes"] == clean["pushes"]
    assert d["payload_bytes"] == clean["payload_bytes"]


def test_stale_fault_trips_tag_errors():
    spec = faults.FaultSpec("stale", hop=1, device=2)
    _, totals = _republished_stream("qlr", checked=True, spec=spec)
    d = totals.as_dict()
    assert d["tag_errors"] >= 1


def test_enable_gating_and_no_retrace():
    """The jit-argument enable: 0 zeroes every counter, and toggling it
    never retraces the compiled step (the core/faults.py trick)."""
    topo = ring("pe", N)
    xs = _payload()
    state0 = jnp.zeros((N, xs.shape[1]))
    traces = []

    @jax.jit
    def run(xs, state0, enable):
        traces.append(1)
        with linkstats.collect(enable) as sc:
            state, _buf = jax.vmap(
                lambda x, s0: queues.stream(
                    topo, x, N_STEPS, lambda s, b, t: s + b, s0, "qlr"),
                axis_name=topo.axis)(xs, state0)
        return state, sc.stats

    on = run(xs, state0, jnp.int32(1))[1].as_dict()
    off = run(xs, state0, jnp.int32(0))[1].as_dict()
    on2 = run(xs, state0, jnp.int32(1))[1].as_dict()

    # a scope over the vmapped circuit sees ONE trace -> per-PE counts
    # (mesh-wide totals come from the republish path's device_sum)
    assert on["pushes"] == N_STEPS and on["payload_bytes"] > 0
    assert all(v == 0 for v in off.values())
    assert on2 == on
    assert len(traces) == 1, "enable toggle must not retrace"


def test_unarmed_paths_record_nothing():
    topo = ring("pe", N)
    xs = _payload()
    out = jax.vmap(
        lambda x: queues.hop(topo, x),
        axis_name=topo.axis)(xs)
    assert out.shape == xs.shape          # no scope, no error, no output change
    assert not linkstats.armed()


def test_linkstats_scan_republish():
    """linkstats.scan ships per-iteration stats out as ys and folds the
    layer totals into the outer scope (the transformer layer-loop path)."""
    xs = jnp.ones((5, 3), jnp.float32)

    def body(c, x):
        linkstats.record_hops(x)          # one hop of a [3] f32 payload
        return c + jnp.sum(x), jnp.sum(x)

    # unarmed: plain lax.scan, nothing recorded
    c_plain, ys_plain = linkstats.scan(body, jnp.zeros(()), xs)
    assert float(c_plain) == 15.0

    with linkstats.collect(1) as sc:
        c_armed, ys_armed = linkstats.scan(body, jnp.zeros(()), xs)
    assert float(c_armed) == float(c_plain)
    np.testing.assert_array_equal(np.asarray(ys_armed), np.asarray(ys_plain))
    d = sc.stats.as_dict()
    assert d["pushes"] == 5 and d["pops"] == 5
    assert d["payload_bytes"] == 5 * 3 * 4


def test_mute_hides_outer_scope():
    with linkstats.collect(1) as sc:
        with linkstats.mute():
            assert not linkstats.armed()
            linkstats.record_hops(jnp.ones((3,)))   # dropped
        linkstats.record_hops(jnp.ones((3,)))       # counted
    assert sc.stats.as_dict()["pushes"] == 1


def test_multicast_recording():
    with linkstats.collect(1) as sc:
        linkstats.record_multicast(jnp.ones((8,), jnp.float32), fan_in=4)
    d = sc.stats.as_dict()
    assert d["mcast_bytes"] == 4 * 8 * 4
    assert d["pushes"] == 0               # multicast is not queue traffic


# --- utilization: the paper's issue-slot model on measured counts -----------
def _stats(qbytes=0.0, mbytes=0.0, errs=0):
    return {"pushes": 0, "pops": 0, "payload_bytes": qbytes,
            "mcast_bytes": mbytes, "tag_errors": errs, "csum_errors": 0,
            "faulty_hops": 0}


def test_utilization_mode_ladder():
    """Same measured traffic, same FLOPs: the mode ladder must order
    sw <= xqueue <= qlr (the paper's Fig. 10 structure)."""
    flops, qbytes = 2e6, 4e5
    sw = utilization.report(_stats(qbytes=qbytes), flops=flops, mode="sw")
    xq = utilization.report(_stats(qbytes=qbytes), flops=flops, mode="xqueue")
    qlr = utilization.report(_stats(qbytes=qbytes), flops=flops, mode="qlr")
    assert sw.utilization <= xq.utilization <= qlr.utilization
    assert sw.utilization < 0.5 < qlr.utilization    # sw pays 2x9 slots/word
    assert sw.gops_per_w <= xq.gops_per_w <= qlr.gops_per_w
    for r in (sw, xq, qlr):
        assert 0.0 < r.utilization <= 1.0
        assert r.queue_words == pytest.approx(qbytes / 4)


def test_utilization_baseline_counts_loads():
    flops = 2e6
    rep = utilization.report(_stats(mbytes=4e5), flops=flops, mode="baseline")
    assert rep.load_words == pytest.approx(1e5)
    assert rep.queue_ops == 0.0
    assert rep.utilization == pytest.approx(
        (flops / 2) / (flops / 2 + 1e5))
    free = utilization.report(_stats(), flops=flops, mode="baseline")
    assert free.utilization == pytest.approx(1.0)


def test_utilization_surfaces_errors_and_table():
    rep = utilization.report(_stats(qbytes=400, errs=3), flops=1e4,
                             mode="qlr")
    assert rep.errors == 3
    text = utilization.table([rep])
    assert "qlr" in text and "util%" in text
    assert "modeled" in text              # GOPS/W is flagged as modeled
    assert "3" in text.splitlines()[2]    # error count lands in the row
