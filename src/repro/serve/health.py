"""Self-healing layer for the serving engine (DESIGN.md §7).

The :class:`HealthMonitor` wraps each engine tick in a guard:

1. snapshot the scheduler's mutable tick state and the cache — the
   scheduler snapshot copies a few small host arrays; the cache snapshot
   is a device copy, since the step donates the live cache;
2. plan + run the backend step, then judge it on three signals:
   the checked-link probe (``backend.link_health()``), the wall-clock
   deadline, and row-wise logit finiteness (``core/guard.py``);
3. a **link or deadline** fault indicts the *transport*, not any one
   request: roll the scheduler back, rebuild the backend one rung down
   the mode ladder on the snapshotted cache, and retry the tick (bounded
   by ``max_retries``; a persistent fault cascades through the ladder
   within a single guarded step until it reaches a hop-free rung);
4. **non-finite logits without a link fault** indict the poisoned rows
   themselves: roll back scheduler *and* cache, evict those requests
   terminally (status ``error``), zero their cache rows, and yield the
   tick — the survivors re-plan next tick on a clean cache;
5. only a tick that passes every check commits sampled tokens, so a
   rolled-back tick leaves zero trace: recovery is bitwise-identical to
   a run that was born on the degraded rung (asserted by
   tests/multidev/check_fault_recovery.py).

The ladder orders rungs by how much systolic machinery they trust:
``qlr`` (overlapped queue links) -> ``xqueue`` (serialized links) ->
``sw`` (software FIFO emulation) -> ``baseline`` (all-gather: no
per-hop links left to fault) -> ``dense`` (single-host, no mesh
collectives at all). ``adopt_cache`` migrates the serving state across
rungs without losing a committed token.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

import jax

from repro.core import guard
from repro.obs import trace
from repro.serve.sharded_cache import DecodeBackend, RingShardedBackend

MODE_LADDER = ("qlr", "xqueue", "sw", "baseline", "dense")


class FatalFaultError(RuntimeError):
    """The monitor ran out of ladder rungs or retries; every in-flight
    request has been marked ``failed``."""

    def __init__(self, msg: str, failed: list):
        super().__init__(msg)
        self.failed = failed


@dataclass(frozen=True)
class HealthConfig:
    deadline_s: float = 0.0     # per-step wall-clock budget (0 = off);
                                # note the first step on a rung compiles
    max_retries: int = 5        # degrade attempts within one guarded step
    backoff_s: float = 0.0      # host sleep between degrade attempts


@dataclass(frozen=True)
class HealthEvent:
    tick: int
    kind: str                   # link_fault | deadline | nonfinite | degrade
    detail: str
    mode: str                   # backend name when the event fired


class HealthMonitor:
    """Per-tick guard owned by a :class:`~repro.serve.engine.ServeEngine`
    (built automatically when the engine gets a ``HealthConfig``)."""

    def __init__(self, engine, hcfg: HealthConfig | None = None):
        self.eng = engine
        self.hcfg = hcfg or HealthConfig()
        self.events: list[HealthEvent] = []
        self.tick = 0
        self._sync_rung_gauge()

    # ------------------------------------------------------------- ladder
    def _rung(self) -> str:
        b = self.eng.backend
        return b.mode if isinstance(b, RingShardedBackend) else "dense"

    def _sync_rung_gauge(self) -> None:
        self.eng.metrics.gauge(
            "repro_mode_rung",
            "ladder position, 0=qlr .. 4=dense").set(
            MODE_LADDER.index(self._rung()))

    def _note(self, kind: str, detail: str) -> None:
        self.events.append(
            HealthEvent(self.tick, kind, detail, self.eng.backend.name))
        trace.instant(f"serve.{kind}", tick=self.tick, detail=detail)
        self.eng.metrics.counter(f"repro_health_{kind}_total",
                                 f"health events of kind {kind}").inc()

    def _degrade(self, snap_cache) -> bool:
        """Rebuild the backend one rung down the ladder on the snapshotted
        cache. Returns False when already on the last rung."""
        eng, old = self.eng, self.eng.backend
        idx = MODE_LADDER.index(self._rung())
        if idx + 1 >= len(MODE_LADDER):
            return False
        nxt = MODE_LADDER[idx + 1]
        if nxt == "dense":
            new = DecodeBackend(eng.cfg, eng.scfg, eng._params)
        else:
            new = RingShardedBackend(
                eng.cfg, eng.scfg, eng._params, old.mesh, mode=nxt,
                param_axes=old.param_axes, checked=True,
                telemetry=getattr(old, "telemetry", False))
        new.adopt_cache(snap_cache)
        if hasattr(old, "_stats_total") and hasattr(new, "_stats_total"):
            new._stats_total = dict(old._stats_total)   # telemetry survives
        self._note("degrade", f"{old.name} -> {new.name}")
        eng.metrics.counter("repro_degradations_total",
                            "mode-ladder rungs stepped down").inc()
        eng.backend = new
        self._sync_rung_gauge()
        return True

    def force_degrade(self) -> str:
        """Step down one rung unconditionally (ops control, and how the
        chaos test builds its matched-ladder clean reference run).
        Returns the new backend name."""
        if not self._degrade(self.eng.backend.cache):
            raise FatalFaultError(
                "force_degrade: already on the last ladder rung",
                [])
        return self.eng.backend.name

    def _fatal(self, why: str):
        failed = self.eng.sched.fail_all(why)
        raise FatalFaultError(why, failed)

    # -------------------------------------------------------------- guard
    def guarded_step(self):
        """One guarded tick; returns the (active, sampling) rows of its
        last plan."""
        eng, hcfg = self.eng, self.hcfg
        self.tick += 1
        snap_sched = eng.sched.snapshot()
        snap_cache = eng.backend.snapshot_cache()

        for _ in range(hcfg.max_retries + 1):
            with trace.span("serve.plan"):
                tokens, active, sampling = eng.sched.plan()
            t0 = time.perf_counter()
            with trace.span("serve.step.dispatch"):
                logits = eng.backend.step(tokens, active)
            with trace.span("serve.device_wait"):
                jax.block_until_ready(logits)
            elapsed = time.perf_counter() - t0

            health = eng.backend.link_health()
            link_bad = sum(health.values()) > 0
            deadline_bad = 0.0 < hcfg.deadline_s < elapsed

            if link_bad or deadline_bad:
                # transport fault: no request is at fault — rewind the
                # tick and retry it one rung down
                why = (f"link probe {health}" if link_bad
                       else f"step took {elapsed:.3f}s > "
                            f"deadline {hcfg.deadline_s:.3f}s")
                self._note("link_fault" if link_bad else "deadline", why)
                trace.instant("serve.rollback", tick=self.tick, detail=why)
                eng.metrics.counter("repro_rollbacks_total",
                                    "ticks rolled back and retried").inc()
                eng.sched.restore(snap_sched)
                if not self._degrade(snap_cache):
                    self._fatal(f"mode ladder exhausted after {why}")
                if hcfg.backoff_s > 0:
                    time.sleep(hcfg.backoff_s)
                continue

            bad_rows = np.asarray(active) & ~guard.row_finite(
                np.asarray(logits))
            if bad_rows.any():
                # numeric poisoning with healthy links: indict the rows,
                # not the transport — evict them and keep the rung
                eng.metrics.counter("repro_rollbacks_total",
                                    "ticks rolled back and retried").inc()
                eng.sched.restore(snap_sched)
                eng.backend.adopt_cache(snap_cache)
                for slot in np.nonzero(bad_rows)[0]:
                    req = eng.sched.evict(int(slot),
                                          reason="non-finite logits")
                    self._note("nonfinite",
                               f"evicted rid={req.rid} slot={int(slot)}")
                    eng.backend.free_slot(int(slot))
                return active, sampling

            eng._sample_and_commit(logits, sampling)
            return active, sampling

        self._fatal(f"fault persisted through {hcfg.max_retries} retries")
