"""Collective helpers: ring schedules and gradient compression wrappers.

``ring_reduce_tiles`` is the shard_map building block the distributed
butterfly counter uses: row-blocks of the biadjacency live on different
devices; column-blocks circulate via collective_permute so every (u, v)
block pair is evaluated exactly once while compute overlaps the permute
(double-buffered carry).

``compress_grads``/``decompress_grads`` implement the optional gradient
compression hook (bf16 or int8 with per-tensor scale) applied around the
data-parallel mean — the classic bandwidth/fidelity trade for 1000+ node
DP domains.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["compress_grads", "decompress_grads", "psum_mean_compressed",
           "ring_pair_count"]


def compress_grads(tree, method: str | None):
    if method is None:
        return tree, None
    if method == "bf16":
        return jax.tree.map(lambda g: g.astype(jnp.bfloat16), tree), None
    if method == "int8":
        def q(g):
            scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-9) / 127.0
            return (g / scale).astype(jnp.int8), scale
        pairs = jax.tree.map(q, tree)
        qs = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
        scales = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        return qs, scales
    raise ValueError(f"unknown compression {method!r}")


def decompress_grads(tree, scales, method: str | None, dtype=jnp.float32):
    if method is None:
        return tree
    if method == "bf16":
        return jax.tree.map(lambda g: g.astype(dtype), tree)
    if method == "int8":
        return jax.tree.map(lambda g, s: g.astype(dtype) * s, tree, scales)
    raise ValueError(f"unknown compression {method!r}")


def psum_mean_compressed(tree, axis_name: str, method: str | None = None):
    """DP gradient mean with optional on-the-wire compression (shard_map)."""
    q, scales = compress_grads(tree, method)
    summed = jax.lax.psum(jax.tree.map(lambda g: g.astype(jnp.float32), q), axis_name)
    n = jax.lax.psum(1, axis_name)
    mean = jax.tree.map(lambda g: g / n, summed)
    if method == "int8":
        smax = jax.lax.pmax(jax.tree.map(lambda s: s, scales), axis_name)
        mean = jax.tree.map(lambda g, s: g * s, mean, smax)
    return mean


def ring_pair_count(a_block: jax.Array, axis_name: str, pair_fn,
                    *, half_ring: bool = False, wire_dtype=None):
    """Blocked-Gram ring: every device holds a row-block; column-blocks
    circulate via collective_permute.  ``pair_fn(mine, theirs, my_idx,
    their_idx, symmetric)`` returns a partial scalar; partials are psum'd.

    half_ring=True exploits Gram symmetry: unordered block pair {a, b} is
    visited exactly once, so only floor(n/2)+1 permute steps run — ~2x less
    ICI traffic AND ~2x less dead (masked) compute than the full ring.
    Pairs at distance n/2 (even n) are visited from both ends; the lower
    index wins.  wire_dtype (e.g. int8 for 0/1 adjacencies) compresses the
    permuted payload — count math still runs in fp32.
    """
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    payload = a_block if wire_dtype is None else a_block.astype(wire_dtype)
    steps = (n // 2 + 1) if half_ring else n

    def body(carry, k):
        blk, total = carry
        their_idx = (me - k) % n
        if half_ring:
            # skip the duplicated antipodal visit (even n, k == n/2, me high)
            live = jnp.logical_or(k < (n + 1) // 2, me < their_idx)
            contrib = jnp.where(
                live,
                pair_fn(a_block, blk.astype(a_block.dtype), me, their_idx, True),
                0.0)
        else:
            contrib = pair_fn(a_block, blk.astype(a_block.dtype), me, their_idx, False)
        total = total + contrib
        blk = jax.lax.ppermute(blk, axis_name, perm)
        return (blk, total), None

    # zero carry inheriting a_block's varying-manual-axes type (shard_map VMA)
    zero = jnp.sum(a_block[:0].astype(jnp.float32))
    (_, total), _ = jax.lax.scan(body, (payload, zero), jnp.arange(steps))
    return jax.lax.psum(total, axis_name)
