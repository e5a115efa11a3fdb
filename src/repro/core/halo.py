"""Halo exchange for the hybrid conv2d execution model.

Paper (§V-B): each chain PE computes output rows i..i+r; rows i-1..i come in
through systolic links (pops from the upstream PE), rows i+1..i+2 are loaded
from shared memory, and the rows needed downstream are pushed onward. With
multiple chains, each chain head is a mover PE that *loads* its boundary
rows from shared memory instead of popping them.

TPU mapping: shard the image rows over a mesh axis. Halo rows at shard
boundaries arrive via one ppermute from the neighbor. With k chains, the
chain-internal halos are systolic-link traffic while the k chain-boundary
halos ride the shared-memory path — the dataflow (and result) is identical;
what changes is the traffic class, which ``halo_traffic`` accounts for the
energy model, and the stall/transient behaviour, which the chain benchmark
measures.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import queues
from repro.core.topology import Topology, ring


def exchange_halo(x_local, axis: str, n: int, halo: int, mode: str = "qlr"):
    """x_local: [rows_local, cols] -> [halo + rows_local + halo, cols].
    Halo rows come from ring neighbors; true image edges get zeros."""
    fwd_topo = ring(axis, n, step=1)        # my bottom rows -> next PE's top
    bwd_topo = ring(axis, n, step=-1)       # my top rows -> prev PE's bottom
    top_in = queues.hop(fwd_topo, x_local[-halo:], mode, t=0)
    bot_in = queues.hop(bwd_topo, x_local[:halo], mode, t=0)
    idx = jax.lax.axis_index(axis)
    top_in = jnp.where(idx == 0, jnp.zeros_like(top_in), top_in)
    bot_in = jnp.where(idx == n - 1, jnp.zeros_like(bot_in), bot_in)
    return jnp.concatenate([top_in, x_local, bot_in], axis=0)


def conv2d_3x3_local(x_halo, kernel):
    """Valid 3x3 conv over halo-extended rows. x_halo: [r+2, c],
    kernel: [3,3]. Columns are zero-padded internally."""
    rows = x_halo.shape[0] - 2
    cols = x_halo.shape[1]
    xp = jnp.pad(x_halo, ((0, 0), (1, 1)))
    out = jnp.zeros((rows, cols), x_halo.dtype)
    for dr in range(3):
        for dc in range(3):
            out = out + kernel[dr, dc] * jax.lax.dynamic_slice(
                xp, (dr, dc), (rows, cols))
    return out


def conv2d_systolic(x, kernel, mesh: Mesh, axis: str, mode: str = "qlr"):
    """Hybrid systolic conv2d: image rows sharded over ``axis``; halo rows
    travel the neighbor links; interior rows are local loads; results are
    stored shard-wise (the gather collective). Zero-padded 3x3."""
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def body(x_local, k_local):
        h = exchange_halo(x_local, axis, n, 1, mode)
        return conv2d_3x3_local(h, k_local)

    fn = shard_map(
        body, mesh=mesh, in_specs=(P(axis, None), P(None, None)),
        out_specs=P(axis, None), check_vma=False)
    return fn(x, kernel)


def halo_traffic(rows: int, cols: int, n_pes: int, n_chains: int,
                 halo: int = 1, itemsize: int = 4) -> dict:
    """Traffic classes for the hybrid conv2d (per full image):

    systolic_bytes — halo rows over chain-internal links,
    shared_bytes   — chain-boundary halos + interior row loads + output
                     stores through the shared-memory path.
    """
    halo_rows_total = 2 * halo * (n_pes - 1)          # boundary exchanges
    chain_boundary = 2 * halo * (n_chains - 1) if n_chains > 1 else 0
    systolic_rows = halo_rows_total - chain_boundary
    row_bytes = cols * itemsize
    return {
        "systolic_bytes": systolic_rows * row_bytes,
        "shared_bytes": (chain_boundary + rows + rows) * row_bytes,
        "n_links": systolic_rows,
    }


def conv2d_ref(x, kernel):
    """Oracle: zero-padded 3x3 convolution (pure jnp)."""
    xp = jnp.pad(x, ((1, 1), (1, 1)))
    out = jnp.zeros_like(x)
    for dr in range(3):
        for dc in range(3):
            out = out + kernel[dr, dc] * jax.lax.dynamic_slice(
                xp, (dr, dc), x.shape)
    return out
