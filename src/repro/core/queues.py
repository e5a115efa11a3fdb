"""Systolic links: queue push/pop over mesh axes, with the paper's three
link implementations as selectable modes.

Inside a ``shard_map`` body, a systolic *hop* (push to the neighbor + pop
from the other neighbor) is one ``ppermute`` — the single-instruction queue
access of **Xqueue** (`q.push`/`q.pop`). The three modes:

  sw      — software-emulated queues: the hop additionally performs the
            explicit circular-buffer bookkeeping the paper's Fig. 3 shows
            (head/tail updates, boundary checks, buffer writes), serialized
            with optimization barriers. Models the instruction-count
            overhead of software FIFOs (the paper's ~10x-slower variant).
  xqueue  — one ppermute per hop, but *serialized* against compute with an
            optimization barrier: fast queue access, yet communication
            occupies the critical path (explicit q.push/q.pop semantics).
  qlr     — one ppermute per hop with no false dependencies: XLA's async
            collective-permute + latency-hiding scheduler overlap the hop
            with compute, like QLRs autonomously popping into registers.

``stream()`` is the generic driver every systolic kernel builds on: it
carries an operand buffer around the topology, invoking ``consume`` once
per hop — compute and communication relate exactly as the mode dictates.

Robustness layer (DESIGN.md §7): queues are also the failure surface — a
stale, misrouted, or corrupted pop silently poisons every downstream PE.
Two opt-in facilities address that:

* **fault injection** — when a :mod:`repro.core.faults` scope is active,
  every ``hop`` that knows its hop index ``t`` applies the armed
  :class:`~repro.core.faults.FaultSpec` (corrupt / drop / stale / slow) at
  the targeted (hop, PE), so any ring schedule can be chaos-tested.
* **checked links** (``checked=True`` on ``hop``/``stream``/
  ``stream_carry``) — each message rides a sidecar of (sender id, hop
  sequence number, payload checksum): the narrow control FIFO next to the
  wide data FIFOs of the paper's several-queues-per-PE layout. The
  receiver verifies all three and surfaces per-hop health flags
  ``[tag_error, checksum_error]``. Stuck/late links (stale, slow) freeze
  the whole message and trip the *tag* check; data-word faults (corrupt,
  drop) touch only the payload FIFOs and trip the *checksum* check.

Telemetry (DESIGN.md §8): when a :mod:`repro.obs.linkstats` scope is
armed, every hop additionally accumulates per-PE queue-traffic counters
(push/pop counts, payload bytes, checked-link error totals) into it. No
scope armed = nothing compiled in; the stream drivers mute the scope
around their ``lax.scan`` and record the whole circuit afterwards, so
telemetry never perturbs the scanned computation.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.lax import optimization_barrier

from repro.core import faults
from repro.core.topology import GridSchedule, Topology
from repro.obs import linkstats

MODES = ("sw", "xqueue", "qlr")


def hop(topo: Topology, x, mode: str = "qlr", *, t=None, prev=None,
        checked: bool = False):
    """One systolic hop: push x to the linked neighbor, pop its operand.

    ``x`` may be a pytree: each leaf rides its own queue (the paper's
    several-queues-per-PE layout — one FIFO per operand class), all hopping
    the same topology in lockstep.

    ``t`` is the hop's sequence number within its schedule; passing it
    enables fault injection at this hop (and is required for ``checked``).
    ``prev`` is what a stuck pop would return instead — defaults to ``x``,
    the receiving PE's own pre-hop element. With ``checked=True`` returns
    ``(popped, health)`` where health is int32[2] = (tag_err, csum_err).
    """
    if checked:
        payload, health = _checked_hop(topo, x, mode, t=t, prev=prev)
        linkstats.record_hops(x, 1, health=health)
        return payload, health
    moved = _raw_hop(topo, x, mode)
    vec = faults.active_vec()
    if vec is not None and t is not None:
        my = jax.lax.axis_index(topo.axis)
        moved = faults.apply(vec, moved, x if prev is None else prev, t, my)
    linkstats.record_hops(x, 1)
    return moved


def _raw_hop(topo: Topology, x, mode: str):
    if mode == "sw":
        return jax.tree_util.tree_map(partial(_sw_hop, topo), x)
    return jax.lax.ppermute(x, topo.axis, topo.perm)


def _sw_hop(topo: Topology, x):
    """Software-queue emulation: 4-deep circular buffer with explicit
    head/tail bookkeeping around the transfer (cf. paper Fig. 3 left)."""
    depth = 4
    buf = jnp.zeros((depth,) + x.shape, x.dtype)
    head = jnp.zeros((), jnp.int32)
    tail = jnp.zeros((), jnp.int32)
    # push: boundary check, write at tail, bump tail
    nxt_tail = jnp.mod(tail + 1, depth)
    full = nxt_tail == head                      # boundary check (always false here)
    buf = jax.lax.dynamic_update_index_in_dim(buf, x, tail, 0)
    tail = jnp.where(full, tail, nxt_tail)
    buf, tail = optimization_barrier((buf, tail))
    # the transfer itself
    moved = jax.lax.ppermute(buf, topo.axis, topo.perm)
    moved, head = optimization_barrier((moved, head))
    # pop: boundary check, read at head, bump head
    empty = head == tail
    out = jax.lax.dynamic_index_in_dim(moved, head, 0, keepdims=False)
    head = jnp.where(empty, head, jnp.mod(head + 1, depth))
    out = optimization_barrier((out, head))[0]
    return out


# ---------------------------------------------------------------------------
# checked links: sequence tag + payload checksum sidecar
# ---------------------------------------------------------------------------


def checksum(tree) -> jnp.ndarray:
    """Order-independent int32 digest of a pytree's payload bits.

    Floats are bitcast (via an exact float32 widening) and summed with
    int32 wraparound — integer addition is associative, so the receiver's
    recomputation matches the sender's bit-for-bit regardless of how XLA
    schedules either reduction. NaN corruption, dropped (zeroed) payloads
    and bit flips all change the digest; an all-zero payload is the one
    blind spot (its digest is 0 like the dropped message's — the sequence
    tag still covers stuck links there)."""
    tot = jnp.zeros((), jnp.int32)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            bits = jax.lax.bitcast_convert_type(
                leaf.astype(jnp.float32), jnp.int32)
        else:
            bits = leaf.astype(jnp.int32)
        tot = tot + jnp.sum(bits, dtype=jnp.int32)
    return tot


def _pred_table(topo: Topology) -> jnp.ndarray:
    """pred_table[d] = the PE whose pushes device d pops (its topology
    predecessor). Heads of open chains keep 0 — checked links assume every
    PE has exactly one incoming link (rings, tori, snakes)."""
    import numpy as np
    preds = np.zeros(topo.size, np.int32)
    for s, d in topo.perm:
        preds[d] = s
    return jnp.asarray(preds)


def _checked_hop(topo: Topology, x, mode: str, *, t, prev=None):
    """One hop with the (src, seq, checksum) sidecar riding alongside.

    Returns (popped_payload, health) with health int32[2]:
      health[0] — tag error: the message was stamped by the wrong sender
                  (stale: the PE's own id) or with the wrong sequence
                  number (slow: the previous hop's) — a stuck/late link.
      health[1] — checksum error: the payload bits do not match the
                  digest stamped at push time — corruption or a drop in
                  the data FIFOs while the control FIFO survived.
    """
    assert t is not None, "checked hops need their hop index t"
    my = jax.lax.axis_index(topo.axis)
    seq = jnp.asarray(t, jnp.int32)
    msg = (x, my.astype(jnp.int32), seq, checksum(x))
    moved = _raw_hop(topo, msg, mode)
    vec = faults.active_vec()
    if vec is not None:
        payload, src_tag, seq_tag, csum = moved
        # data-word faults clobber only the payload FIFOs ...
        payload = faults.apply(vec, payload, x if prev is None else prev,
                               t, my, data_only=True)
        # ... while a stuck link freezes payload and sidecar together
        moved = faults.apply(vec, (payload, src_tag, seq_tag, csum), msg,
                             t, my, stall_only=True)
    payload, src_tag, seq_tag, csum = moved
    pred = _pred_table(topo)[my]
    tag_err = jnp.logical_or(src_tag != pred, seq_tag != seq)
    csum_err = checksum(payload) != csum
    health = jnp.stack([tag_err, csum_err]).astype(jnp.int32)
    return payload, health


def stream(topo, x0, n_steps: int,
           consume: Callable[[Any, Any, Any], Any], state0,
           mode: str = "qlr", unroll: bool = True, checked: bool = False):
    """Drive a systolic stream: per step, consume the current operand and
    forward it along the topology.

    ``topo`` is a Topology or a :class:`~repro.core.topology.GridSchedule`
    (2-D torus / Cannon orders): grid schedules change their permutation
    per hop — free queue re-pointing — so they run as an unrolled Python
    loop instead of a scan (lax.scan cannot vary a ppermute per step).

    consume(state, operand, step_index) -> state.
    qlr: hop(t) is independent of consume(t) -> overlappable.
    xqueue/sw: a barrier ties consume's output to the hop -> serialized.

    checked=True: every hop rides the tag/checksum sidecar; returns
    (state, buf, health) with health int32[n_steps, 2] — this PE's
    per-hop (tag_err, csum_err) flags. Unchecked returns (state, buf).
    """
    assert mode in MODES, mode
    if isinstance(topo, GridSchedule):
        return _stream_grid(topo, x0, n_steps, consume, state0, mode,
                            checked)

    def body(carry, t):
        buf, state = carry
        if mode == "qlr":
            nxt = hop(topo, buf, mode, t=t, checked=checked)
            state = consume(state, buf, t)      # … compute overlaps
        else:
            state = consume(state, buf, t)
            state, buf = optimization_barrier((state, buf))
            nxt = hop(topo, buf, mode, t=t, checked=checked)
        if checked:
            nxt, health = nxt
            return (nxt, state), health
        return (nxt, state), None

    with linkstats.mute():                     # no tracer leaks from the scan
        (buf, state), health = jax.lax.scan(
            body, (x0, state0), jnp.arange(n_steps),
            unroll=n_steps if unroll else 1)
    linkstats.record_hops(x0, n_steps, health=health if checked else None)
    if checked:
        return state, buf, health
    return state, buf


def _stream_grid(sched: GridSchedule, x0, n_steps: int, consume, state0,
                 mode: str, checked: bool):
    """`stream` over a per-hop permutation sequence (torus2d / Cannon).

    Runs as a Python loop — each hop may ride a different Topology, which
    a lax.scan body cannot express. The skew permutation (Cannon start
    offsets), when present, hops once *before* consume 0 with sequence
    number ``n_steps`` so fault injection / checked links can target it
    separately from the main circuit; its health folds into hop 0's row
    (keeping the documented [n_steps, 2] health shape).
    """
    assert n_steps == len(sched.hops) == sched.size, (n_steps, sched)
    buf, state = x0, state0
    skew_health = None
    if sched.skew is not None:
        moved = hop(sched.skew, buf, mode, t=n_steps, checked=checked)
        if checked:
            buf, skew_health = moved
        else:
            buf = moved
    healths = []
    for t, topo_t in enumerate(sched.hops):
        if mode == "qlr":
            nxt = hop(topo_t, buf, mode, t=t, checked=checked)
            state = consume(state, buf, t)       # … compute overlaps
        else:
            state = consume(state, buf, t)
            state, buf = optimization_barrier((state, buf))
            nxt = hop(topo_t, buf, mode, t=t, checked=checked)
        if checked:
            nxt, health = nxt
            healths.append(health)
        buf = nxt
    if checked:
        if skew_health is not None:
            healths[0] = healths[0] + skew_health
        health = jnp.stack(healths)
        return state, buf, health
    return state, buf


def stream_carry(topo: Topology, static0, carry0, n_steps: int,
                 update: Callable[[Any, Any, Any], Any], mode: str = "qlr",
                 unroll: bool = True, checked: bool = False):
    """Drive a systolic stream whose element *itself* carries state.

    ``stream`` keeps per-PE state resident and forwards the operand
    unchanged; here the traveling element is (static, carry) and each
    holder folds its **resident** operand into the carried part —
    ``update(static, carry, step_index) -> carry`` — before the element
    hops on. This is the decode-attention schedule: the per-token query
    (static) rides the ring with its online-softmax state (carry), visiting
    every resident KV shard, and arrives home complete after ``n_steps``
    hops of an n-cycle topology.

    qlr: the static leaves' hop is issued *before* the update, so the next
    element's immutable part streams in while the PE is still folding the
    current one (QLRs pre-popping the next operand); the carried leaves
    necessarily hop after the update — a true data dependency, not a false
    one, so only the static half overlaps.
    xqueue/sw: the whole element is serialized — update, barrier, hop.

    Returns (static, carry) after ``n_steps`` hops. checked=True rides the
    tag/checksum sidecar on *both* queues (the static and the carried
    halves are separate FIFOs through the same link) and returns
    (static, carry, health) with health int32[n_steps, 2] — per-hop error
    counts summed over the two queues.
    """
    assert mode in MODES, mode
    if isinstance(topo, GridSchedule):
        raise TypeError(
            "stream_carry needs a single-cycle Topology (elements must "
            "return home after n hops); grid schedules do not qualify — "
            "decode rides ring/snake_fold only")

    def body(cur, t):
        static, carry = cur
        if mode == "qlr":
            nxt_static = hop(topo, static, mode, t=t, checked=checked)
            carry = update(static, carry, t)
            nxt_carry = hop(topo, carry, mode, t=t, checked=checked)
        else:
            carry = update(static, carry, t)
            static, carry = optimization_barrier((static, carry))
            nxt_static = hop(topo, static, mode, t=t, checked=checked)
            nxt_carry = hop(topo, carry, mode, t=t, checked=checked)
        if checked:
            nxt_static, h_static = nxt_static
            nxt_carry, h_carry = nxt_carry
            return (nxt_static, nxt_carry), h_static + h_carry
        return (nxt_static, nxt_carry), None

    with linkstats.mute():                     # no tracer leaks from the scan
        (static, carry), health = jax.lax.scan(
            body, (static0, carry0), jnp.arange(n_steps),
            unroll=n_steps if unroll else 1)
    # two queue sets ride each hop; the summed health attaches to one
    # record so the error totals aren't double-counted
    linkstats.record_hops(static0, n_steps,
                          health=health if checked else None)
    linkstats.record_hops(carry0, n_steps)
    if checked:
        return static, carry, health
    return static, carry


def multicast(x, axis: str):
    """Shared-memory multicast: every device reads the same operand
    (all-gather). The paper's concurrent-load collective."""
    out = jax.lax.all_gather(x, axis, axis=0, tiled=False)
    linkstats.record_multicast(x, fan_in=jax.lax.psum(1, axis))
    return out


def gather_store(x, axis: str):
    """Shared-memory gather: concurrent independent stores land as a
    sharded output (identity inside shard_map — each PE keeps its tile)."""
    return x
