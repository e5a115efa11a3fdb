"""Systolic (ring) collective matmuls — the paper's hybrid execution model
applied to TPU tensor parallelism.

The mapping (DESIGN.md §2): MemPool's PEs stream operands through memory-
mapped queues while fetching other operands from shared memory. On a TPU
mesh, the *streamed* operand rides a ppermute ring (systolic links over
ICI), while the *resident* operand is all-gathered (the shared-memory
multicast). Output-stationary accumulation lives in each chip's output
shard, and the final sharded write-back is the gather collective.

Three link modes (cf. core/queues.py): sw / xqueue / qlr, plus ``baseline``
(plain all-gather + matmul: the pure shared-memory MemPool baseline).

Entry points:
  ring_ag_matmul    — all-gather-and-matmul as a ring stream; supports
                      multiple weights sharing one operand stream (the
                      paper's data-reuse: one queue feeds several MACs).
  ring_matmul_rs    — matmul + reduce-scatter as a ring of traveling
                      accumulators (output flows to its owner).
  cannon_matmul     — 2-D output-stationary systolic matmul (Cannon's
                      algorithm) on an RxC folding of one mesh axis: the
                      paper's pure-systolic matmul_QLR,1-4.
  systolic_ffn      — SwiGLU FFN with AG-ring in, RS-ring out; wired into
                      transformer blocks when cfg.systolic_mode != baseline.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax.lax import optimization_barrier

from repro.core import queues
from repro.core import topology as topo_lib
from repro.core.topology import Topology, ring
from repro.kernels.systolic_matmul.ops import tile_matmul
from repro.obs import linkstats

# ---------------------------------------------------------------------------
# shard_map-local primitives
# ---------------------------------------------------------------------------


def _local_mm(x, w, acc=None, use_kernel: bool = False, block: int = 0):
    """The PE-local MAC of every schedule here: (acc +) x @ w, either the
    jnp oracle or the systolic_matmul tile kernel (``use_kernel``, with
    ``block`` as the square tile edge — 0 keeps the kernel default)."""
    if use_kernel:
        if block:
            return tile_matmul(x, w, acc, bm=block, bn=block, bk=block)
        return tile_matmul(x, w, acc)
    y = jnp.einsum("...k,kn->...n", x, w)
    return y if acc is None else acc + y


def ring_ag_matmul(x_local, ws: Sequence[jax.Array], topo,
                   mode: str = "qlr", *, use_kernel: bool = False,
                   block: int = 0):
    """All-gather(x) @ w_i for each w_i, streamed around a ring.

    x_local: [..., s_local, d] (this device's shard of the streamed operand)
    ws:      list of [d, f_local] resident weights (the multicast operand)
    Returns: list of [..., n*s_local, f_local] full outputs.

    baseline: one all-gather + matmuls (shared-memory model).
    ring modes: n hops; at hop t the buffer holds the shard of origin
    ``source_table(topo)[my, t]``, and its partial products are written
    into the output at that offset — output-stationary accumulation with
    the operand flowing through. ``topo`` may be a 2-D GridSchedule
    (torus2d / cannon_grid): the source table and ``queues.stream`` handle
    per-hop permutation changes. With ``use_kernel`` the per-hop partial
    runs as one Pallas tile-kernel launch instead of the jnp einsum.
    """
    n = topo.size
    s_local = x_local.shape[-2]
    if mode == "baseline":
        xs = jax.lax.all_gather(x_local, topo.axis, axis=x_local.ndim - 2,
                                tiled=True)
        linkstats.record_multicast(x_local, fan_in=n)
        return [_local_mm(xs, w, use_kernel=use_kernel, block=block)
                for w in ws]

    my = jax.lax.axis_index(topo.axis)
    # src_table[d, t] = which shard device d holds at consume t — supports
    # non-contiguous rings (snake folds) and 2-D grid schedules with skew
    src_table = jnp.asarray(_source_table(topo))
    outs = [
        jnp.zeros(x_local.shape[:-2] + (n * s_local, w.shape[-1]),
                  jnp.promote_types(x_local.dtype, w.dtype))
        for w in ws
    ]

    def consume(state, buf, t):
        src = src_table[my, t]
        offset = src * s_local
        new_state = []
        for o, w in zip(state, ws):
            part = _local_mm(buf, w, use_kernel=use_kernel, block=block)
            new_state.append(jax.lax.dynamic_update_slice_in_dim(
                o, part.astype(o.dtype), offset, axis=o.ndim - 2))
        return new_state

    state, _ = queues.stream(topo, x_local, n, consume, outs, mode)
    return state


def _source_table(topo):
    """[n, n] table: entry (d, t) = origin shard of the buffer device d
    holds at consume t. Single-cycle topologies and 2-D grid schedules
    alike (see ``topology.source_table``)."""
    if isinstance(topo, Topology):
        assert topo_lib.is_cycle(topo), \
            "topology must be a single full cycle"
    return topo_lib.source_table(topo)


def ring_matmul_rs(x, w, topo, mode: str = "qlr", *,
                   use_kernel: bool = False, block: int = 0):
    """(x @ w) reduce-scattered over the sequence dim, as a ring of
    traveling accumulators.

    x: [..., S, f_local], w: [f_local, d]. Returns [..., S/n, d] (chunk
    ``my`` fully reduced over the ring).

    Chunk schedule: device d computes, at step t, the chunk owned by the
    device its traveling accumulator will finally land on —
    ``dest_table(topo)[d, t]``, the composition of the remaining hop
    permutations. For the +1 ring that is the classic (d + n - 1 - t)
    mod n systolic pulse; 2-D grid schedules ride their per-hop
    permutation sequence (minus the skew — reduce-scatter needs no start
    offsets). Each accumulator arrives at its owner exactly when the last
    partial joins. With ``use_kernel`` each hop's partial is folded into
    the traveling accumulator inside one Pallas launch (the kernel's
    carry-in tile), not a separate matmul + add.
    """
    n = topo.size
    s = x.shape[-2]
    assert s % n == 0, (s, n)
    s_local = s // n
    if mode == "baseline":
        y = _local_mm(x, w, use_kernel=use_kernel, block=block)
        y_s = jax.lax.psum_scatter(y, topo.axis,
                                   scatter_dimension=y.ndim - 2, tiled=True)
        linkstats.record_multicast(y_s, fan_in=n)   # n partials per chunk
        return y_s

    my = jax.lax.axis_index(topo.axis)
    dst_table = jnp.asarray(topo_lib.dest_table(topo))
    hops = topo_lib.hop_topos(topo)

    def part(t, x_src, acc=None):
        c = dst_table[my, t]
        xc = jax.lax.dynamic_slice_in_dim(x_src, c * s_local, s_local,
                                          axis=x_src.ndim - 2)
        return _local_mm(xc, w, acc, use_kernel=use_kernel, block=block)

    acc = part(0, x)
    for t in range(1, n):
        moved = queues.hop(hops[t - 1], acc, mode, t=t - 1)
        if mode in ("sw", "xqueue"):
            # serialize: the next partial waits for the queue transfer
            x_tied, moved = optimization_barrier((x, moved))
            acc = part(t, x_tied, moved)
        else:
            acc = part(t, x, moved)  # qlr: hop overlaps the partial matmul
    return acc


def cannon_matmul(a_local, b_local, row_topo: Topology, col_topo: Topology,
                  rows: int, cols: int, mode: str = "qlr",
                  preskewed: bool = False, use_kernel: bool = False,
                  skew: str = "masked", block: int = 0):
    """2-D output-stationary systolic matmul (Cannon) on an RxC grid folded
    from one mesh axis. Device (r,c) ends with C tile = sum_k A[r,k]B[k,c].

    a_local: [m_loc, k_loc] — A tile; b_local: [k_loc, n_loc] — B tile.
    Requires rows == cols (square torus) for the classic skew schedule.
    Main-loop hops carry indices t = 0..n-2; the skew phase's hops carry
    t = n-1.. so fault injection / checked links can target them
    separately.

    skew="masked" rotates each row/col its own distance via n-1 masked
    ring hops (per-PE distances over SPMD links); skew="grid" re-points
    the queues to the ``topology.cannon_skew`` grid permutations and does
    the whole skew in ONE hop per operand — the paper's free
    reconfiguration, and an autotuner-visible trade (2 hops vs 2(n-1)).
    """
    assert rows == cols, "Cannon requires a square grid"
    n = rows
    my = jax.lax.axis_index(row_topo.axis)
    r, c = my // cols, my % cols

    if not preskewed:
        if skew == "grid":
            # one skewed grid permutation per operand: row r of A shifts
            # left r and col c of B shifts up c, in a single re-pointed hop
            a_local = queues.hop(
                topo_lib.cannon_skew(row_topo.axis, rows, cols,
                                     which="rows"),
                a_local, mode, t=n - 1)
            b_local = queues.hop(
                topo_lib.cannon_skew(row_topo.axis, rows, cols,
                                     which="cols"),
                b_local, mode, t=n)
        else:
            # masked rotation: A row r shifts left r times; B col c shifts
            # up c times — over the *requested* link mode, not hardwired qlr
            a_local = _masked_rot(a_local, row_topo, r, n, mode=mode,
                                  t0=n - 1)
            b_local = _masked_rot(b_local, col_topo, c, n, mode=mode,
                                  t0=n - 1)

    acc = jnp.zeros((a_local.shape[0], b_local.shape[1]),
                    jnp.promote_types(a_local.dtype, b_local.dtype))
    for t in range(n):
        acc = _local_mm(a_local, b_local, acc, use_kernel=use_kernel,
                        block=block)
        if t < n - 1:
            if mode in ("sw", "xqueue"):
                acc, a_local, b_local = optimization_barrier(
                    (acc, a_local, b_local))
            a_local = queues.hop(row_topo, a_local, mode, t=t)
            b_local = queues.hop(col_topo, b_local, mode, t=t)
    return acc


def _masked_rot(x, topo: Topology, times, n: int, mode: str = "qlr",
                t0: int = 0):
    """Rotate ``x`` ``times`` hops (traced count) via n-step masked loop.

    The loop's i-th hop carries sequence number ``t0 + i`` so FaultSpec /
    checked links can reach skew traffic, and runs over the requested link
    ``mode`` so sw/xqueue schedules book their true skew cost.
    """
    def body(i, v):
        moved = queues.hop(topo, v, mode, t=t0 + i)
        return jnp.where(i < times, moved, v)
    with linkstats.mute():                # loop body must not leak tracers
        out = jax.lax.fori_loop(0, n - 1, body, x)
    linkstats.record_hops(x, n - 1)       # the skew always runs n-1 hops
    return out


# ---------------------------------------------------------------------------
# jit-level wrapper: systolic SwiGLU FFN
# ---------------------------------------------------------------------------


def _batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def ffn_applicable(x, d_ff: int, mesh: Mesh) -> bool:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes.get("model", 0)
    if not n:
        return False
    b, s, d = x.shape
    bsz = 1
    for a in _batch_axes(mesh):
        bsz *= sizes[a]
    return s % n == 0 and d_ff % n == 0 and b % bsz == 0 and d % max(
        sizes.get("data", 1), 1) == 0


def attn_applicable(x, num_heads: int, num_kv_heads: int, head_dim: int,
                    mesh: Mesh) -> bool:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes.get("model", 0)
    if not n:
        return False
    b, s, d = x.shape
    bsz = 1
    for a in _batch_axes(mesh):
        bsz *= sizes[a]
    return (s % n == 0 and num_heads % n == 0 and num_kv_heads % n == 0
            and b % bsz == 0 and d % max(sizes.get("data", 1), 1) == 0)


def systolic_qkv(x, wq, wk, wv, mesh: Mesh, mode: str = "qlr", *,
                 use_kernel: bool = False, topo=None, block: int = 0):
    """QKV projections as ONE systolic ring: the x stream feeds three weight
    sinks (the paper's data-reuse degree — one queue, several MACs).

    x: [B,S,D] seq-sharded over 'model'; w*: [D, H*, hd] head-sharded.
    Returns q, k, v: [B, S, H*_local... ] with heads sharded over 'model'
    (full sequence, the layout attention math wants).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes["model"]
    batch = _batch_axes(mesh)
    if topo is None:
        topo = ring("model", n)
    x_spec = P(batch if batch else None, "model", None)
    w_specs = [P("data" if "data" in sizes else None, "model", None)] * 3
    out_specs = tuple(P(batch if batch else None, None, "model", None)
                      for _ in range(3))

    def body(x_l, wq_l, wk_l, wv_l):
        ws = []
        for w_l in (wq_l, wk_l, wv_l):
            if "data" in sizes:
                w_l = jax.lax.all_gather(w_l, "data", axis=0, tiled=True)
            ws.append(w_l.reshape(w_l.shape[0], -1))
        q2, k2, v2 = ring_ag_matmul(x_l, ws, topo, mode,
                                     use_kernel=use_kernel, block=block)
        def unflat(y2, w_l):
            b_, s_ = y2.shape[0], y2.shape[1]
            return y2.reshape(b_, s_, w_l.shape[1], w_l.shape[2])
        return unflat(q2, wq_l), unflat(k2, wk_l), unflat(v2, wv_l)

    return linkstats.shard_call(body, mesh, (x_spec, *w_specs), out_specs,
                                x, wq, wk, wv)


def systolic_out_proj(attn_out, wo, mesh: Mesh, mode: str = "qlr", *,
                      use_kernel: bool = False, topo=None, block: int = 0):
    """Attention output projection with a reduce-scatter ring: partial sums
    over the head shards travel to their sequence-shard owners.

    attn_out: [B,S,H,hd] heads-sharded; wo: [H, hd, D]. Returns [B,S,D]
    seq-sharded over 'model'.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes["model"]
    batch = _batch_axes(mesh)
    if topo is None:
        topo = ring("model", n)
    x_spec = P(batch if batch else None, None, "model", None)
    w_spec = P("model", None, "data" if "data" in sizes else None)
    out_spec = P(batch if batch else None, "model", None)

    def body(o_l, wo_l):
        if "data" in sizes:
            wo_l = jax.lax.all_gather(wo_l, "data", axis=2, tiled=True)
        b_, s_, hl, hd = o_l.shape
        o2 = o_l.reshape(b_, s_, hl * hd)
        w2 = wo_l.reshape(hl * hd, wo_l.shape[2])
        return ring_matmul_rs(o2, w2, topo, mode, use_kernel=use_kernel,
                              block=block)

    return linkstats.shard_call(body, mesh, (x_spec, w_spec), out_spec,
                                attn_out, wo)


def systolic_ffn(x, w_gate, w_up, w_down, mesh: Mesh, mode: str = "qlr",
                 *, use_kernel: bool = False, topo=None, block: int = 0):
    """SwiGLU FFN with systolic sequence-parallel rings over 'model':

      x (seq-sharded) --AG-ring--> [gate|up] (one stream, two weight sinks:
      the paper's data-reuse) --silu*-- h --RS-ring--> y (seq-sharded)

    Weights are FSDP-sharded over 'data' and fetched by all-gather — the
    shared-memory multicast of the hybrid model. Falls back to the caller's
    baseline path when shapes don't divide (checked via ffn_applicable).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes["model"]
    batch = _batch_axes(mesh)
    if topo is None:
        topo = ring("model", n)

    x_spec = P(batch if batch else None, "model", None)
    wg_spec = P("data", "model") if "data" in sizes else P(None, "model")
    wd_spec = P("model", "data") if "data" in sizes else P("model", None)
    out_spec = P(batch if batch else None, "model", None)

    def body(x_l, wg_l, wu_l, wd_l):
        if "data" in sizes:
            wg = jax.lax.all_gather(wg_l, "data", axis=0, tiled=True)
            wu = jax.lax.all_gather(wu_l, "data", axis=0, tiled=True)
            wd = jax.lax.all_gather(wd_l, "data", axis=1, tiled=True)
        else:
            wg, wu, wd = wg_l, wu_l, wd_l
        gate, up = ring_ag_matmul(x_l, [wg, wu], topo, mode,
                                  use_kernel=use_kernel, block=block)
        h = jax.nn.silu(gate) * up                    # [B_l, S, f_local]
        return ring_matmul_rs(h, wd, topo, mode,      # [B_l, s_local, d]
                              use_kernel=use_kernel, block=block)

    return linkstats.shard_call(
        body, mesh, (x_spec, wg_spec, wg_spec, wd_spec), out_spec,
        x, w_gate, w_up, w_down)
