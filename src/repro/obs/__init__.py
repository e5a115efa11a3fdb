"""Observability subsystem (DESIGN.md §8).

Four layers, importable independently (this package init stays empty so
``core.queues`` can import :mod:`repro.obs.linkstats` without dragging in
the rest):

  linkstats    — per-PE queue-traffic counters riding inside jit
  utilization  — LinkStats + roofline FLOPs + energy models → per-mode
                 compute-unit utilization % and modeled GOPS/W
  trace        — host spans and a compile counter on the profiler's
                 clock (jax.profiler.TraceAnnotation)
  metrics      — counters / gauges / histograms registry → JSON +
                 Prometheus text exposition
"""
