"""Decode backends: device-side halves of the serving engine.

A backend owns parameter placement, the decode cache, and the jitted step
functions; the host-side scheduler (serve/scheduler.py) is backend-agnostic
and drives whichever backend the engine was built with:

* :class:`DecodeBackend` — dense single-host: the cache lives wherever jit
  puts it, every step is one jitted ``model.decode_step``.
* :class:`RingShardedBackend` — the hybrid systolic layout: the KV cache's
  slot dimension is sharded along the 'model' ring
  (``sharding/partitioning.RING_SERVE_RULES``), the decode batch over
  (data x model), and the step runs under that sharding context with
  ``cfg.systolic_mode`` set to a link mode, so ``models/attention.
  gqa_decode`` streams each row's query around the resident cache shards
  (``core/ring_attention.systolic_ring_decode``) and block prefill streams
  K/V blocks through the existing ``ring_attention`` schedule.

Both backends expose the same surface — ``step``, ``free_slot``,
``prefill_len``/``prefill`` — so the scheduler cannot tell them apart; the
multidev parity check holds them to token-identical greedy outputs.

Robustness surface (serve/health.py rides on it):

* ``RingShardedBackend(..., checked=True)`` threads an encoded
  :class:`~repro.core.faults.FaultSpec` *as an argument* of the jitted
  step (so arming/disarming a fault never retraces) and runs a checked
  link **probe** after every step: a one-element canary message streamed
  around the same ring in the same mode with the tag/checksum sidecar of
  ``queues.stream(..., checked=True)``. The probe shares the model
  stream's (hop index, PE) coordinates, so a fault that poisons the
  decode math also trips the probe. ``last_health`` holds the probe's
  per-class error counts for the tick.
* ``adopt_cache`` moves a cache snapshot onto this backend's placement —
  how the health monitor migrates serving state one rung down the mode
  ladder without losing a token.

Every jitted program that takes the cache donates it
(:func:`jit_donating_cache`): the step, prefill and zero-row update write
the new cache into the old one's buffers, so a tick holds one cache, not
two. An array the caller kept of the old cache is deleted by the call —
the health monitor snapshots with :meth:`DecodeBackend.snapshot_cache`.

Telemetry surface (DESIGN.md §8): ``RingShardedBackend(...,
telemetry=True)`` compiles the step/prefill with a
:mod:`repro.obs.linkstats` scope armed and a 0/1 enable scalar as a jit
*argument* — ``set_telemetry`` flips collection at run time with zero
retrace; ``link_stats()`` returns the accumulated queue-traffic totals.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import replace

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ModelConfig, ServeConfig
from repro.core import faults, queues, topology
from repro.obs import linkstats, trace
from repro.core.topology import ring
from repro.models import build_model
from repro.models.common import use_sharding
from repro.sharding.partitioning import (
    RING_SERVE_RULES,
    serve_cache_shardings,
    shardings_from_axes,
)


def jit_donating_cache(fn, name: str, cache_argnum: int = 1):
    """``jax.jit(fn)`` with the cache argument donated, so the program
    updates the cache in place, and named ``jit_<name>`` whatever ``fn``
    is (a bound method, a closure): the device trace finds the program by
    that name."""
    def program(*args):
        return fn(*args)
    functools.update_wrapper(program, fn)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, donate_argnums=cache_argnum)


class DecodeBackend:
    """Dense single-host backend: one jitted decode step over the slot
    batch, per-slot cache rows zeroed on reuse."""

    name = "dense"

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params):
        self.cfg = cfg
        self.scfg = scfg
        self.model = build_model(cfg)
        self.max_batch = scfg.max_batch
        self.max_seq = scfg.max_seq_len
        self.params = self._place_params(params)
        self.cache = self._init_cache()
        # the program names the device trace keys on
        self._step = jit_donating_cache(self._make_step(), "decode_step")
        self._zero = jit_donating_cache(self._make_zero_row(), "zero_row", 0)
        self._prefill = jit_donating_cache(
            self._make_prefill(), "prefill_into_cache") \
            if self.supports_prefill else None

    # ---------------------------------------------------------- placement
    def _place_params(self, params):
        return params

    def _init_cache(self):
        # one program writes the cache straight into its buffers (built
        # eagerly, its per-layer pieces and their stack would coexist)
        return jax.jit(self.model.init_cache, static_argnums=(0, 1))(
            self.max_batch, self.max_seq)

    # -------------------------------------------------------------- steps
    def _make_step(self):
        return self.model.decode_step

    def _make_prefill(self):
        return self.model.prefill_into_cache

    def _make_zero_row(self):
        # locate the batch dim from the model's logical cache axes rather
        # than guessing by size: a [layers, batch, ...] leaf with
        # n_layers == max_batch would otherwise zero a layer slice of every
        # row (and leak the old occupant's KV into the new request).
        axes = self.model.cache_axes()

        def zero_row(cache, row):
            def z(leaf, ax):
                if not ax or "cache_batch" not in ax:
                    return leaf
                idx = (slice(None),) * ax.index("cache_batch") + (row,)
                return leaf.at[idx].set(jnp.zeros_like(leaf[idx]))
            return jax.tree_util.tree_map(z, cache, axes)
        return zero_row

    # ---------------------------------------------------------- interface
    def step(self, tokens: np.ndarray, active: np.ndarray):
        """One decode tick for the whole slot batch -> logits [B, V]."""
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(active))
        return logits

    def free_slot(self, slot: int) -> None:
        """Zero a freed slot's cache rows so the next occupant decodes
        bit-identically to a fresh engine."""
        self.cache = self._zero(self.cache, slot)

    def snapshot_cache(self):
        """A copy of the cache that outlives the next (donating) step."""
        return jax.tree_util.tree_map(
            lambda l: jax.device_put(l, l.sharding, may_alias=False),
            self.cache)

    def adopt_cache(self, cache) -> None:
        """Take over a cache snapshot from another backend (mode-ladder
        degradation): place a copy wherever this backend keeps its cache,
        so the snapshot survives the steps that donate it."""
        self.cache = jax.tree_util.tree_map(
            lambda l: jax.device_put(l, may_alias=False), cache)

    def link_health(self) -> dict:
        """Per-class link error counts of the last step's probe (empty for
        backends without systolic links)."""
        return {}

    def link_stats(self) -> dict:
        """Accumulated queue-traffic totals (empty for backends without
        telemetry — the dense path has no links to count)."""
        return {}

    def set_telemetry(self, on: bool) -> None:
        """Toggle link telemetry collection (no-op without links)."""

    @property
    def supports_prefill(self) -> bool:
        return (self.scfg.prefill_chunk > 0
                and hasattr(self.model, "prefill_into_cache")
                and self.cfg.attention_type == "gqa"
                and not self.cfg.sliding_window)

    def prefill_len(self, prompt_len: int) -> int:
        """How many leading prompt tokens to block-prefill for a prompt of
        this length (the rest stream through the decode step; at least the
        final prompt token always streams, so sampling stays uniform)."""
        if not self.supports_prefill:
            return 0
        chunk = min(self.scfg.prefill_chunk, self.max_seq)
        return max(min(prompt_len - 1, chunk), 0)

    def prefill(self, slot: int, prompt: np.ndarray) -> None:
        """Block-prefill ``prompt`` (already clipped to ``prefill_len``)
        into ``slot``: one full-sequence forward writes its K/V into the
        slot's cache rows and advances the row position."""
        chunk = min(self.scfg.prefill_chunk, self.max_seq)
        buf = np.zeros(chunk, np.int32)
        buf[:len(prompt)] = prompt
        _, self.cache = self._prefill(
            self.params, self.cache, jnp.asarray(buf),
            jnp.int32(slot), jnp.int32(len(prompt)))


class RingShardedBackend(DecodeBackend):
    """Ring-sharded backend: resident cache shards on the 'model' ring,
    decode queries streamed over the links in ``mode``.

    checked=True arms the robustness layer: the jitted step takes the
    host-armed fault vector as an argument (``repro.core.faults``) and a
    checked canary probe runs after each step, surfacing link health."""

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params,
                 mesh: Mesh, mode: str = "qlr", param_axes=None,
                 checked: bool = False, telemetry: bool = False,
                 plan=None):
        """``plan`` (an ``autotune.Plan``) threads a measured tuning plan
        into the backend: it overrides ``mode`` and rewrites the config's
        systolic fields (topology / kernel / block) before compilation —
        the serving end of the Config.autotune path."""
        if plan is not None:
            mode = plan.mode
        self.mesh = mesh
        self.mode = mode
        self.plan = plan
        self.param_axes = param_axes
        self.checked = checked
        self.telemetry = telemetry
        self.telemetry_on = telemetry
        self._stats_total: dict = {}
        self.name = f"ring-{mode}" + ("+checked" if checked else "") \
            + ("+tuned" if plan is not None else "")
        self.last_health: dict = {}
        cfg = replace(cfg, systolic_mode=mode)
        if plan is not None:
            from repro.autotune.api import apply_plan
            cfg = apply_plan(cfg, plan)
        super().__init__(cfg, scfg, params)
        self._probe = jax.jit(self._make_probe()) \
            if checked and mode in queues.MODES else None

    def _place_params(self, params):
        if self.param_axes is not None:
            sh = shardings_from_axes(params, self.param_axes, self.mesh,
                                     RING_SERVE_RULES)
        else:
            sh = jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), params)
        return jax.device_put(params, sh)

    def _init_cache(self):
        sh = serve_cache_shardings(self.model, self.max_batch, self.max_seq,
                                   self.mesh, ring=True)
        return jax.jit(self.model.init_cache, static_argnums=(0, 1),
                       out_shardings=sh)(self.max_batch, self.max_seq)

    def _make_step(self):
        model, mesh = self.model, self.mesh
        checked, telemetry = self.checked, self.telemetry
        if not checked and not telemetry:
            def step(params, cache, tokens, active):
                with use_sharding(mesh, rules=RING_SERVE_RULES):
                    return model.decode_step(params, cache, tokens, active)
            return step

        def step(params, cache, tokens, active, *extra):
            # fault spec and telemetry enable are *function inputs*:
            # arming a fault for a chaos window, disarming it after
            # recovery, or toggling telemetry reuses the same compiled
            # step
            i = 0
            with contextlib.ExitStack() as st:
                if checked:
                    st.enter_context(faults.scope(extra[i])); i += 1
                sc = st.enter_context(linkstats.collect(extra[i])) \
                    if telemetry else None
                st.enter_context(use_sharding(mesh, rules=RING_SERVE_RULES))
                out = model.decode_step(params, cache, tokens, active)
            return (out, sc.stats) if telemetry else out
        return step

    def _step_extra(self, vec):
        extra = []
        if self.checked:
            extra.append(vec)
        if self.telemetry:
            extra.append(jnp.int32(1 if self.telemetry_on else 0))
        return extra

    def step(self, tokens: np.ndarray, active: np.ndarray):
        if not self.checked and not self.telemetry:
            return super().step(tokens, active)
        vec = faults.injected_vec() if self.checked else None
        out = self._step(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(active), *self._step_extra(vec))
        if self.telemetry:
            (logits, self.cache), stats = out
            self._accumulate(stats)
        else:
            logits, self.cache = out
        if self.checked:
            with trace.span("serve.probe"):
                self.last_health = self._probe_links(vec)
        return logits

    def _make_prefill(self):
        model, mesh = self.model, self.mesh
        telemetry = self.telemetry

        def prefill(params, cache, tokens, row, length, *extra):
            with contextlib.ExitStack() as st:
                sc = st.enter_context(linkstats.collect(extra[0])) \
                    if telemetry else None
                st.enter_context(use_sharding(mesh, rules=RING_SERVE_RULES))
                out = model.prefill_into_cache(params, cache, tokens, row,
                                               length)
            return (out, sc.stats) if telemetry else out
        return prefill

    def prefill(self, slot: int, prompt: np.ndarray) -> None:
        if not self.telemetry:
            return super().prefill(slot, prompt)
        chunk = min(self.scfg.prefill_chunk, self.max_seq)
        buf = np.zeros(chunk, np.int32)
        buf[:len(prompt)] = prompt
        (_, self.cache), stats = self._prefill(
            self.params, self.cache, jnp.asarray(buf),
            jnp.int32(slot), jnp.int32(len(prompt)),
            jnp.int32(1 if self.telemetry_on else 0))
        self._accumulate(stats)

    # --------------------------------------------------------- robustness
    def _make_probe(self):
        """Checked canary stream over the serving ring: one small nonzero
        payload per PE makes a full circuit with the tag/checksum sidecar;
        any armed fault at (hop t, PE d) — the same coordinates the decode
        stream hops through — trips a sidecar check here."""
        mesh, mode = self.mesh, self.mode
        n = mesh.shape["model"]
        # the canary rides the same schedule the decode stream hops (tuned
        # topologies re-point it too); grids fall back to the ring the
        # decode dual actually uses
        topo = topology.resolve_safe(self.cfg.systolic_topology, "model", n,
                                     cycle_only=True)
        payload = (jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4) + 1.0)

        def local(x_l):
            _, _, health = queues.stream(
                topo, x_l, n, lambda s, b, t: s + jnp.sum(b),
                jnp.zeros(()), mode, checked=True)
            return jnp.sum(health, axis=0)[None]        # [1, 2]

        fn = shard_map(local, mesh=mesh, in_specs=(P("model", None),),
                       out_specs=P("model", None), check_vma=False)

        def probe(fault_vec):
            with faults.scope(fault_vec):
                return fn(payload)                      # [n, 2]
        return probe

    def _probe_links(self, vec) -> dict:
        if self._probe is None:
            return {}
        errs = np.asarray(self._probe(vec)).sum(axis=0)
        return {"tag_errors": int(errs[0]), "csum_errors": int(errs[1])}

    def link_health(self) -> dict:
        return dict(self.last_health)

    # ---------------------------------------------------------- telemetry
    def _accumulate(self, stats) -> None:
        for k, v in stats.as_dict().items():
            self._stats_total[k] = self._stats_total.get(k, 0) + v

    def link_stats(self) -> dict:
        return dict(self._stats_total)

    def set_telemetry(self, on: bool) -> None:
        """Flip run-time collection; requires telemetry=True at build (the
        enable rides as a step argument, so this never retraces)."""
        self.telemetry_on = bool(on) and self.telemetry

    def adopt_cache(self, cache) -> None:
        sh = jax.tree_util.tree_map(lambda l: l.sharding, self.cache)
        self.cache = jax.device_put(cache, sh, may_alias=False)
