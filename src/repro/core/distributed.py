"""Distributed exact butterfly counting (shard_map ring) + window pipeline.

The window snapshot's biadjacency rows (i-vertices) are sharded across a mesh
axis; each device computes its diagonal block directly and streams the other
row-blocks through a collective_permute ring — the blocked-Gram schedule.
Every (u, v) row-block pair is counted exactly once; compute overlaps the
permute through the scan carry (double buffering).

This is the scale-out of the paper's Algorithm 1 (DESIGN.md SS2): on a
16x16-chip pod the 'model' axis shards one window's Gram triangle while the
'data' axis counts 16 windows concurrently, and pods pipeline window batches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["distributed_count_dense", "make_distributed_window_counter"]


def _pair_partial(mine: jax.Array, theirs: jax.Array, my_idx, their_idx,
                  symmetric: bool, block_rows: int) -> jax.Array:
    """Butterfly partial for row-blocks (mine=u rows, theirs=v rows).

    Full ring (symmetric=False): keep global_u < global_v only — each
    unordered pair is visited twice, contributing once.
    Half ring (symmetric=True): each block pair is visited once — keep all
    cross pairs; the diagonal block keeps its strict upper triangle.
    """
    w = jax.lax.dot_general(
        mine.astype(jnp.float32), theirs.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    pairs = w * (w - 1.0) * 0.5
    rows = my_idx * block_rows + jnp.arange(mine.shape[0])
    cols = their_idx * block_rows + jnp.arange(theirs.shape[0])
    if symmetric:
        keep = jnp.where(my_idx == their_idx,
                         rows[:, None] < cols[None, :],
                         jnp.ones((mine.shape[0], theirs.shape[0]), bool))
    else:
        keep = rows[:, None] < cols[None, :]
    return jnp.sum(jnp.where(keep, pairs, 0.0))


def distributed_count_dense(adj: jax.Array, mesh: Mesh, axis: str = "model",
                            *, half_ring: bool = True,
                            wire_dtype=jnp.int8) -> jax.Array:
    """Exact butterfly count of a dense biadjacency, rows sharded over
    ``axis``.  Requires n_i divisible by the axis size (pad upstream).

    half_ring + int8 wire are the beyond-paper optimizations (SSPerf):
    pass half_ring=False, wire_dtype=None for the paper-faithful schedule.
    """
    n_dev = mesh.shape[axis]
    n_i = adj.shape[0]
    if n_i % n_dev:
        raise ValueError(f"n_i={n_i} not divisible by {axis} size {n_dev}")
    block_rows = n_i // n_dev

    from ..distributed.collectives import ring_pair_count

    def local(a_block):
        return ring_pair_count(
            a_block, axis,
            functools.partial(_pair_partial, block_rows=block_rows),
            half_ring=half_ring, wire_dtype=wire_dtype)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(),
    )
    return fn(adj)


def make_distributed_window_counter(
    n_i: int,
    n_j: int,
    mesh: Mesh,
    *,
    window_axis: str = "data",
    gram_axis: str = "model",
    half_ring: bool = True,
    wire_dtype=jnp.int8,
):
    """Factory: per-window exact counts with windows sharded over
    ``window_axis`` and each window's Gram triangle sharded over
    ``gram_axis`` — one shard_map over both axes.

    Returned fn: (edge_i, edge_j, valid) [n_windows, capacity] -> [n_windows]
    float32 counts.  n_windows must divide by the window-axis size.

    half_ring + int8 wire: beyond-paper ICI optimizations (Gram symmetry
    halves the permute steps; the 0/1 adjacency rides the wire in int8).
    Pass half_ring=False, wire_dtype=None for the paper-faithful schedule.
    """
    from .butterfly import build_biadjacency
    from ..distributed.collectives import ring_pair_count

    n_dev = mesh.shape[gram_axis]
    n_i_pad = -(-n_i // n_dev) * n_dev
    block_rows = n_i_pad // n_dev

    def local_block(ei, ej, v):
        me = jax.lax.axis_index(gram_axis)
        row0 = me * block_rows

        def one(args):
            ei1, ej1, v1 = args
            # build only this device's row-block of the biadjacency
            local_rows = ei1 - row0
            in_range = (local_rows >= 0) & (local_rows < block_rows) & v1
            blk = build_biadjacency(local_rows, ej1, in_range,
                                    block_rows, n_j, dtype=jnp.float32)
            return ring_pair_count(
                blk, gram_axis,
                functools.partial(_pair_partial, block_rows=block_rows),
                half_ring=half_ring, wire_dtype=wire_dtype)

        return jax.lax.map(one, (ei, ej, v))

    fn = jax.shard_map(
        local_block, mesh=mesh,
        in_specs=(P(window_axis, None),) * 3,
        out_specs=P(window_axis),
    )
    return jax.jit(fn)
