"""Spans and counters on the profiler's clock (DESIGN.md §8).

Every span is a ``jax.profiler.TraceAnnotation``. With a profiler attached
(``jax.profiler.trace``; ``launch/serve.py --trace-out DIR``) it lands in
the trace's host plane on the same timeline as the device's operations,
so one Perfetto or TensorBoard view shows what the host did while the
device ran or stood idle. With none attached a span records nothing and
costs about a microsecond. Metadata is attached only while a profiler
records (:func:`enabled`), so the off path computes none of it; it shows
up as the event's stats in ``jax.profiler.ProfileData``.

Names are dotted and stable, since tools key on them; metadata in
brackets:

  serve.admit                one per ``ServeEngine._admit`` call
    serve.admit_request      [rid, slot, queued_s, prefill_tokens, compiles]
      serve.slot_reset
      serve.prefill.dispatch ends at enqueue, before the device finishes
  serve.tick                 [tick, active, sampling, prompt_rows, compiles]
    serve.plan
    serve.step.dispatch      ends at enqueue
    serve.probe              checked ring backends
    serve.sample.dispatch    the key split and the sampler's ops
    serve.device_wait        the host blocks on the device here
    serve.commit
  serve.<event>              zero-work: rollback, link_fault, deadline,
                             nonfinite, degrade [tick, detail]
  train.data, train.step [step], train.checkpoint,
  train.straggler [step, seconds]

``queued_s`` runs from ``Scheduler.submit`` to the start of the request's
admission; ``active``, ``sampling`` and ``prompt_rows`` count the tick's
rows that decode, that sample, and that feed a prompt token; ``compiles``
counts the backend compiles in the span (:func:`compiles`), which names
the step that recompiled.

Usage::

    with trace.span("serve.tick") as sp:
        on = trace.enabled()
        c0 = trace.compiles() if on else 0
        ...
        if on:
            sp.set_metadata(tick=n, compiles=trace.compiles() - c0)
"""
from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compiles = 0
_listening = False


def span(name: str) -> TraceAnnotation:
    """A host span named ``name``: use as a context manager; attach
    metadata with its ``set_metadata`` under :func:`enabled`."""
    return TraceAnnotation(name)


def enabled() -> bool:
    """Whether a profiler is recording, so metadata is worth computing."""
    return TraceAnnotation.is_enabled()


def instant(name: str, **meta) -> None:
    """A zero-work span carrying ``meta`` (rollbacks, degradations,
    evictions, stragglers)."""
    with span(name) as sp:
        if meta and enabled():
            sp.set_metadata(**meta)


def _on_duration(event: str, duration_s: float, **kwargs) -> None:
    global _compiles
    if event == BACKEND_COMPILE_EVENT:
        _compiles += 1


def compiles() -> int:
    """Backend compiles in this process (persistent-cache loads included)
    since the first call, which registers the ``jax.monitoring`` listener
    that counts them; take differences around a span."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return _compiles
