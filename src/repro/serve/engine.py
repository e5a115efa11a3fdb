"""Batched serving engine with continuous batching.

The engine is now a thin composition of three halves:

* :class:`repro.serve.scheduler.Scheduler` — host-side continuous batching:
  slot admission/eviction, prompt streaming (chunk-less prefill through the
  shared decode step), per-slot generation budgets and the sequence budget.
* a decode backend (:mod:`repro.serve.sharded_cache`) — parameter/cache
  placement plus the jitted step. The default is the dense single-host
  backend; pass ``RingShardedBackend(cfg, scfg, params, mesh, mode)`` to
  serve from a KV cache ring-sharded along the 'model' mesh axis with the
  paper's systolic link modes moving each row's query around the ring.
* optionally a :class:`repro.serve.health.HealthMonitor` (pass a
  ``HealthConfig``) — per-tick link-probe/finite/deadline checks with
  snapshot-rollback, poisoned-request eviction, and mode-ladder
  degradation (serve/health.py, DESIGN.md §7).

Each engine tick plans a fixed ``max_batch``-row token batch (each row is a
slot with its own cache position; the ``active`` mask keeps idle slots'
caches frozen), runs one backend step, samples, and commits. The decode
dry-run cells lower exactly this step function at production size.

Observability (DESIGN.md §8): the engine owns a metrics
:class:`~repro.obs.metrics.Registry` (tokens, ticks, tick-latency
histogram, plus the scheduler's request-lifecycle counters and the health
monitor's rollback/degrade counters) and marks each layer boundary with a
:mod:`repro.obs.trace` span on the profiler's clock: ``serve.admit`` and
one ``serve.admit_request`` per admission, ``serve.tick`` and its phases
(plan, step dispatch, sample dispatch, device wait, commit).
"""
from __future__ import annotations

import time

import numpy as np

import jax

from repro.configs.base import ModelConfig, ServeConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace
from repro.serve.sample import sample
from repro.serve.scheduler import Request, Scheduler  # noqa: F401 (re-export)
from repro.serve.sharded_cache import DecodeBackend


class TicksExhaustedError(RuntimeError):
    """run() hit max_ticks with requests still in flight; they have been
    marked ``failed`` (terminal), not silently dropped."""

    def __init__(self, msg: str, failed: list):
        super().__init__(msg)
        self.failed = failed


class ServeEngine:
    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params,
                 backend: DecodeBackend | None = None, health=None,
                 metrics: obs_metrics.Registry | None = None):
        self.cfg = cfg
        self.scfg = scfg
        self._params = params                  # kept for backend rebuilds
        self.metrics = metrics if metrics is not None \
            else obs_metrics.Registry()
        self.backend = backend if backend is not None \
            else DecodeBackend(cfg, scfg, params)
        self.sched = Scheduler(scfg.max_batch, scfg.max_seq_len,
                               bos_token=scfg.bos_token,
                               eos_token=scfg.eos_token,
                               metrics=self.metrics)
        self.key = jax.random.PRNGKey(scfg.seed)
        self._tick = 0
        self.monitor = None
        if health is not None:
            from repro.serve.health import HealthMonitor
            self.monitor = HealthMonitor(self, health)

    # ------------------------------------------------- compat conveniences
    @property
    def max_batch(self) -> int:
        return self.scfg.max_batch

    @property
    def max_seq(self) -> int:
        return self.scfg.max_seq_len

    @property
    def pending(self) -> list:
        return self.sched.pending

    @property
    def params(self):
        return self.backend.params

    @property
    def cache(self):
        return self.backend.cache

    @property
    def model(self):
        return self.backend.model

    # ------------------------------------------------------------- client
    def submit(self, prompt, max_new_tokens: int = 16) -> int:
        """Queue a request; returns its rid. Empty prompts are seeded with
        ``scfg.bos_token``; ``max_new_tokens`` is clipped to the sequence
        budget and over-long prompts raise ValueError (scheduler.submit)."""
        return self.sched.submit(prompt, max_new_tokens).rid

    # ---------------------------------------------------------- scheduler
    def _admit(self):
        with trace.span("serve.admit"):
            for slot, req in self.sched.admit():
                self._admit_request(slot, req)

    def _admit_request(self, slot: int, req: Request) -> None:
        """Reset ``slot``'s cache rows and block-prefill what of ``req``'s
        prompt the backend takes in one program."""
        with trace.span("serve.admit_request") as sp:
            on = trace.enabled()
            if on:
                queued_s = time.perf_counter() - req.t_submit
                c0 = trace.compiles()
            with trace.span("serve.slot_reset"):
                self.backend.free_slot(slot)
            n_block = self.backend.prefill_len(len(req.prompt))
            if n_block > 0:
                with trace.span("serve.prefill.dispatch"):
                    self.backend.prefill(slot, req.prompt[:n_block])
                self.sched.note_prefilled(slot, n_block)
                self.metrics.counter(
                    "repro_prefill_tokens_total",
                    "prompt tokens absorbed by block prefill").inc(n_block)
            if on:
                sp.set_metadata(rid=req.rid, slot=slot, queued_s=queued_s,
                                prefill_tokens=n_block,
                                compiles=trace.compiles() - c0)

    def _sample_and_commit(self, logits, sampling):
        with trace.span("serve.sample.dispatch"):
            self.key, sub = jax.random.split(self.key)
            next_tok = sample(logits, sub, self.scfg.temperature,
                              self.scfg.top_k)
        with trace.span("serve.device_wait"):
            next_tok = np.asarray(next_tok)
        with trace.span("serve.commit"):
            self.sched.commit(sampling, next_tok)
        self.metrics.counter("repro_tokens_total",
                             "tokens sampled and committed").inc(
            int(np.sum(sampling)))

    def _plain_step(self):
        """An unguarded tick; returns its (active, sampling) rows."""
        with trace.span("serve.plan"):
            tokens, active, sampling = self.sched.plan()
        with trace.span("serve.step.dispatch"):
            logits = self.backend.step(tokens, active)
        self._sample_and_commit(logits, sampling)
        return active, sampling

    def step(self):
        """One engine tick = one backend decode step for all slots (under
        the health monitor's guard when one is configured)."""
        self._tick += 1
        self.metrics.counter("repro_ticks_total", "engine ticks run").inc()
        with trace.span("serve.tick") as sp, \
                self.metrics.histogram("repro_tick_latency_seconds",
                                       "whole-tick wall time").time():
            on = trace.enabled()
            c0 = trace.compiles() if on else 0
            active, sampling = (self.monitor.guarded_step()
                                if self.monitor is not None
                                else self._plain_step())
            if on:
                sp.set_metadata(tick=self._tick, active=int(active.sum()),
                                sampling=int(sampling.sum()),
                                prompt_rows=self.sched.prompt_rows,
                                compiles=trace.compiles() - c0)

    def export_observability(self, metrics_json=None,
                             metrics_prom=None) -> None:
        """Write metrics as JSON and/or Prometheus text. Folds the
        backend's link telemetry into the registry as ``repro_link_*``
        counters first, so snapshots are self-contained."""
        for k, v in self.backend.link_stats().items():
            c = self.metrics.counter(f"repro_link_{k}_total",
                                     "queue telemetry (LinkStats)")
            c.value = float(v)                 # totals, not deltas
        if metrics_json:
            self.metrics.dump_json(metrics_json)
        if metrics_prom:
            self.metrics.dump_prometheus(metrics_prom)

    def run(self, max_ticks: int = 10_000) -> int:
        """Drive until all submitted requests complete. Returns #ticks.

        If ``max_ticks`` is exhausted with work still in flight, the
        leftover requests are marked terminally ``failed`` and
        :class:`TicksExhaustedError` is raised — a stuck engine must never
        silently drop requests as if they had been served."""
        ticks = 0
        while self.sched.busy and ticks < max_ticks:
            self._admit()
            self.step()
            ticks += 1
        if self.sched.busy:
            failed = self.sched.fail_all(f"max_ticks={max_ticks} exhausted")
            raise TicksExhaustedError(
                f"{len(failed)} request(s) still in flight after "
                f"{max_ticks} ticks; marked failed", failed)
        return ticks
