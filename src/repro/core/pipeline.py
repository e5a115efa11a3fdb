"""Queue-based pipeline parallelism — the paper's chain topology at pod
scale.

The conv2d evaluation (Table III) splits 256 PEs into k independent chains,
trading peak throughput (chain heads become mover PEs) against transient
fill/drain time and stall propagation. The exact analogue on a TPU mesh is
pipeline parallelism: stages = chain PEs, microbatches = the systolic pulse,
the fill/drain bubble = the chain transient, and more/shorter pipelines =
more chains working on disjoint microbatch slices. ``pipelined`` implements
GPipe-style fill-drain scheduling with ppermute stage links (the queues)
inside shard_map, supporting ``n_chains`` independent pipelines over one
mesh axis.

The bubble fraction is (S-1)/(M+S-1) for S stages and M microbatches per
chain — reported by ``bubble_fraction`` and measured by the chain benchmark,
which reproduces the paper's chain-count trade-off curve.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from jax.lax import optimization_barrier

from repro.core.topology import chains


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe fill/drain bubble = the paper's chain transient time."""
    return (n_stages - 1) / (n_stages - 1 + max(n_microbatches, 1))


def pipelined(stage_fn: Callable, mesh: Mesh, axis: str,
              n_microbatches: int, mode: str = "qlr", n_chains: int = 1):
    """Build a pipelined apply over ``axis``: device i runs stage
    (i mod n_stages) of chain (i div n_stages), with n_stages =
    axis_size / n_chains. Chains process disjoint microbatch slices.

    stage_fn(stage_params, x_microbatch, stage_index) -> y_microbatch with
    microbatch-invariant shapes (the queue element type).

    Returns fn(stage_params [n_stages, ...], xs [M, ...]) -> ys [M, ...].
    Stage links are one ppermute per tick over open chains (the queues);
    zeros flow in the bubble slots; stage 0 pops from the input stream
    (shared-memory load, the mover-PE role) and the last stage stores to the
    output (gather collective).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_dev = sizes[axis]
    assert n_dev % n_chains == 0, (n_dev, n_chains)
    n_stages = n_dev // n_chains
    assert n_microbatches % n_chains == 0, (n_microbatches, n_chains)
    m_per_chain = n_microbatches // n_chains
    topo = chains(axis, n_dev, n_chains)
    n_ticks = m_per_chain + n_stages - 1

    def run(stage_params, xs):
        # stage_params: [n_stages, ...] (replicated); xs: [M, ...] (replicated)
        idx = jax.lax.axis_index(axis)
        stage_idx = jnp.mod(idx, n_stages)
        chain_idx = idx // n_stages
        sp = jax.tree_util.tree_map(
            lambda p: jnp.take(p, stage_idx, axis=0), stage_params)
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros((m_per_chain,) + xs.shape[1:], xs.dtype)

        def tick(carry, t):
            buf, outs = carry
            mb = t - stage_idx                    # chain-local microbatch id
            active = jnp.logical_and(mb >= 0, mb < m_per_chain)
            mb_c = jnp.clip(mb, 0, m_per_chain - 1)
            # stage 0 pops from the input queue (its chain's slice)
            x_in = jnp.where(stage_idx == 0,
                             xs[chain_idx * m_per_chain + mb_c], buf)
            y = stage_fn(sp, x_in, stage_idx)
            y = jnp.where(active, y, jnp.zeros_like(y))
            outs = jnp.where(
                jnp.logical_and(stage_idx == n_stages - 1, active),
                outs.at[mb_c].set(y), outs)
            if mode in ("sw", "xqueue"):
                y, outs = optimization_barrier((y, outs))
            from repro.core import queues
            nxt = queues.hop(topo, y, mode)
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # assemble the global output: each chain's last stage contributes its
        # slice (the shared-memory gather)
        full = jnp.zeros_like(xs)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, outs, chain_idx * m_per_chain, axis=0)
        full = jnp.where(stage_idx == n_stages - 1, full,
                         jnp.zeros_like(full))
        return jax.lax.psum(full, axis)

    fn = shard_map(
        run, mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(),
        check_vma=False)
    return fn
