"""Link telemetry: per-PE queue-traffic counters carried through the
systolic primitives (DESIGN.md §8).

The paper's headline numbers — per-PE compute-unit utilization, queue
stall behavior per link mode, GOPS/W — are *measurements* of queue
traffic. :class:`LinkStats` is the software analogue of MemPool's per-PE
performance counters: a small pytree of scalars each PE accumulates while
its hops execute, cheap enough to ride inside jit.

Counted per PE (inside ``shard_map``, every device owns its own copy):

  pushes / pops     queue operations — one per pytree *leaf* per hop (the
                    paper's several-queues-per-PE layout: each operand
                    class is its own FIFO).
  payload_bytes     bytes pushed onto the links (payload only; the
                    checked-link sidecar is control traffic and excluded).
  mcast_bytes       bytes this PE read via the shared-memory multicast
                    (the all-gather baseline's concurrent loads — not
                    queue traffic, counted separately so the baseline
                    mode's utilization is also measured, not estimated).
  tag_errors        checked-link sender-id/sequence failures (stuck/late
                    links) summed over hops.
  csum_errors       checked-link payload-checksum failures (corruption /
                    drops) summed over hops.
  faulty_hops       number of hops at which *any* sidecar check tripped.

Mechanics mirror :mod:`repro.core.faults` — the telemetry must never
change what it observes:

* **Trace scope** — ``with linkstats.collect(enabled):`` publishes a
  :class:`StatsScope`; ``queues.hop``/``stream``/``stream_carry`` record
  into the innermost active scope. No scope armed at trace time = no
  telemetry compiled in at all, so telemetry-off paths are bitwise
  identical to a build without this module.
* **jit-argument enable** — ``enabled`` may be a traced 0/1 scalar (a jit
  *argument*): every recorded delta is multiplied by it, so toggling
  telemetry at run time reuses the same compiled step — zero retrace,
  exactly the ``FaultSpec`` trick.
* **Mute** — ``with linkstats.mute():`` hides any outer scope; the stream
  drivers mute around their ``lax.scan`` so per-hop recording can't leak
  scan-body tracers, then record the whole circuit afterwards (push/pop
  and byte counts are trace-time constants; only the checked-link error
  counts are dynamic, and those come out of the scan as the health
  output).

Crossing ``shard_map``: a scope armed at jit level cannot absorb values
traced inside a ``shard_map`` body. The systolic wrappers
(``systolic_ring_attention`` & co.) therefore open an *inner* scope
inside their body, ship its per-PE stats out of the shard_map as an extra
output (``stats_specs``), and fold the device-summed totals back into the
outer scope (``merge``) — so a serve backend can arm one scope around a
whole ``model.decode_step`` and get mesh-wide totals without any model
signature changing.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

FIELDS = ("pushes", "pops", "payload_bytes", "mcast_bytes", "tag_errors",
          "csum_errors", "faulty_hops")
# byte counters are float32 (int32 would wrap at 2 GiB of traffic);
# everything else is an int32 count.
_FLOAT_FIELDS = ("payload_bytes", "mcast_bytes")


@jax.tree_util.register_pytree_node_class
@dataclass
class LinkStats:
    """One PE's accumulated queue-traffic counters (all scalars, or
    ``[n]`` per-device vectors once shipped out of a shard_map)."""
    pushes: Any
    pops: Any
    payload_bytes: Any
    mcast_bytes: Any
    tag_errors: Any
    csum_errors: Any
    faulty_hops: Any

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in FIELDS), None

    @classmethod
    def tree_unflatten(cls, _aux, leaves):
        return cls(*leaves)

    # ------------------------------------------------------------ algebra
    def add(self, other: "LinkStats") -> "LinkStats":
        return jax.tree_util.tree_map(lambda a, b: a + b, self, other)

    def scale(self, e) -> "LinkStats":
        """Multiply every counter by ``e`` (the 0/1 enable scalar)."""
        return jax.tree_util.tree_map(
            lambda l: l * jnp.asarray(e).astype(l.dtype), self)

    @property
    def total_errors(self):
        return self.tag_errors + self.csum_errors

    def as_dict(self) -> dict:
        """Host-side plain-number view (device sums if leaves are [n])."""
        import numpy as np
        out = {}
        for f in FIELDS:
            v = np.asarray(getattr(self, f)).sum()
            out[f] = float(v) if f in _FLOAT_FIELDS else int(v)
        return out


def _dtype(field: str):
    return jnp.float32 if field in _FLOAT_FIELDS else jnp.int32


def zeros() -> LinkStats:
    return LinkStats(*(jnp.zeros((), _dtype(f)) for f in FIELDS))


def make(**kw) -> LinkStats:
    """Build a delta from python/traced numbers; unset fields are 0."""
    return LinkStats(*(jnp.asarray(kw.get(f, 0), _dtype(f)) for f in FIELDS))


def stats_specs(axes):
    """out_specs pytree for shipping per-PE stats out of a shard_map whose
    body returned ``expand(scope.stats)`` (each leaf [1] -> [n_devices]).
    ``axes`` is an axis name or tuple of names — pass *all* the mesh's
    axes so per-device values concatenate instead of aliasing."""
    from jax.sharding import PartitionSpec as P
    spec = P(tuple(axes) if not isinstance(axes, str) else axes)
    return LinkStats(*(spec for _ in FIELDS))


def expand(stats: LinkStats) -> LinkStats:
    """Scalar leaves -> [1] leaves (a shard_map body's per-PE output)."""
    return jax.tree_util.tree_map(lambda l: jnp.asarray(l)[None], stats)


def device_sum(stats: LinkStats) -> LinkStats:
    """[n] per-device leaves -> scalar mesh totals."""
    return jax.tree_util.tree_map(lambda l: jnp.sum(l, axis=0), stats)


# ---------------------------------------------------------------------------
# trace scopes
# ---------------------------------------------------------------------------

_SCOPE: list = []          # StatsScope entries, or None for a mute frame


class StatsScope:
    """Accumulates LinkStats during tracing. ``enabled`` may be a python
    int or a traced 0/1 scalar; every recorded delta is scaled by it."""

    def __init__(self, enabled=1):
        self.enabled = enabled
        self.stats = zeros()

    def record(self, delta: LinkStats) -> None:
        """Add a delta, gated by the enable scalar."""
        self.stats = self.stats.add(delta.scale(self.enabled))

    def merge(self, totals: LinkStats) -> None:
        """Add already-gated totals (republished from an inner scope that
        scaled by the same enable — 0/1 gating is idempotent)."""
        self.stats = self.stats.add(totals)


@contextmanager
def collect(enabled=1):
    """Arm telemetry for the extent of the block (innermost scope wins)."""
    sc = StatsScope(enabled)
    _SCOPE.append(sc)
    try:
        yield sc
    finally:
        _SCOPE.pop()


@contextmanager
def mute():
    """Hide any outer scope (used around scan bodies and foreign traces)."""
    _SCOPE.append(None)
    try:
        yield
    finally:
        _SCOPE.pop()


def active() -> StatsScope | None:
    return _SCOPE[-1] if _SCOPE else None


def armed() -> bool:
    """True when a scope is collecting — the systolic wrappers trace their
    instrumented variant iff this holds (off = today's HLO, bit for bit)."""
    return active() is not None


# ---------------------------------------------------------------------------
# shard_map republish: inner scope -> extra output -> outer scope
# ---------------------------------------------------------------------------


def instrumented(body):
    """Wrap a shard_map body so it also returns its per-PE stats
    (expanded to [1] leaves). Records with enable=1 — the *outer* scope
    applies the real enable when it absorbs, so a traced jit-level enable
    never has to cross the shard_map boundary as a closure."""
    def wrapped(*args):
        with collect(1) as sc:
            out = body(*args)
        return out, expand(sc.stats)
    return wrapped


def absorb(stats: LinkStats) -> None:
    """Fold an instrumented body's [n]-leaf per-device stats into the
    active scope (device-summed, gated by the scope's enable)."""
    sc = active()
    if sc is not None:
        sc.record(device_sum(stats))


def shard_call(body, mesh, in_specs, out_specs, *args):
    """shard_map-and-call with transparent telemetry republish.

    Unarmed: exactly ``shard_map(body, ...)`` — the systolic wrappers all
    route through here, so telemetry-off traces stay bitwise identical.
    Armed: traces the instrumented body, ships per-PE stats out as an
    extra output sharded over *all* mesh axes, and absorbs the device
    totals into the active scope."""
    from jax import shard_map
    if armed():
        fn = shard_map(instrumented(body), mesh=mesh, in_specs=in_specs,
                       out_specs=(out_specs, stats_specs(mesh.axis_names)),
                       check_vma=False)
        out, stats = fn(*args)
        absorb(stats)
        return out
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return fn(*args)


# ---------------------------------------------------------------------------
# scan republish: inner scope -> extra ys output -> outer scope
# ---------------------------------------------------------------------------


def scan(body, init, xs, **kw):
    """``jax.lax.scan`` whose body may record telemetry.

    The same boundary problem as ``shard_map``, one level up: a scope
    armed at jit level cannot absorb values traced inside a scan body
    (they would leak the scan trace). Armed, the body runs under an inner
    scope and its per-iteration stats ride out as an extra ys output,
    summed over the scan axis and folded into the outer scope (gated by
    the outer enable). Unarmed: exactly ``jax.lax.scan(body, init, xs)``,
    so telemetry-off traces are bitwise identical. The model's layer
    loops route through here so a serve backend can arm one scope around
    a whole ``decode_step``/``prefill_into_cache`` call."""
    outer = active()
    if outer is None:
        return jax.lax.scan(body, init, xs, **kw)

    def wrapped(carry, x):
        with collect(1) as sc:
            carry2, y = body(carry, x)
        return carry2, (y, sc.stats)

    carry2, (ys, stats) = jax.lax.scan(wrapped, init, xs, **kw)
    outer.record(device_sum(stats))     # [n_steps] leaves -> totals
    return carry2, ys


# ---------------------------------------------------------------------------
# recording helpers (called by the queue primitives)
# ---------------------------------------------------------------------------


def payload_static(tree) -> tuple[int, int]:
    """(n_queues, bytes) of one hop's payload — trace-time constants."""
    leaves = jax.tree_util.tree_leaves(tree)
    return len(leaves), sum(l.size * l.dtype.itemsize for l in leaves)


def record_hops(tree, n_hops: int = 1, health=None) -> None:
    """Record ``n_hops`` hops of ``tree``'s queue set into the active
    scope, if any. ``health`` is an int32[..., 2] stack of per-hop
    (tag_err, csum_err) flags from checked links; without it the error
    counters stay untouched."""
    sc = active()
    if sc is None:
        return
    n_q, nbytes = payload_static(tree)
    if health is None:
        tag = csum = faulty = 0
    else:
        h = jnp.asarray(health).reshape(-1, 2)
        tag = jnp.sum(h[:, 0])
        csum = jnp.sum(h[:, 1])
        faulty = jnp.sum((jnp.sum(h, axis=1) > 0).astype(jnp.int32))
    sc.record(make(pushes=n_hops * n_q, pops=n_hops * n_q,
                   payload_bytes=float(n_hops * nbytes),
                   tag_errors=tag, csum_errors=csum, faulty_hops=faulty))


def record_multicast(tree, fan_in: int = 1) -> None:
    """Record a shared-memory multicast read: this PE loaded ``tree``
    from ``fan_in`` peers (all-gather output bytes = fan_in x local)."""
    sc = active()
    if sc is None:
        return
    _, nbytes = payload_static(tree)
    sc.record(make(mcast_bytes=fan_in * nbytes))   # fan_in may be traced
