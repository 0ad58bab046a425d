"""Pallas TPU kernel: fused tiled butterfly counting.

Computes  B = sum_{u<v} C(W_uv, 2),  W = A @ A.T  without ever materializing
the [n_i, n_i] wedge matrix W.  The schedule is the blocked-Gram triangle:

  grid = (T, nk) where T enumerates row-tile pairs (u <= v) via scalar-
  prefetched index maps (the TPU-idiomatic way to walk a triangular grid),
  and nk walks the contraction (j) dimension.

Per (u, v) tile pair a VMEM fp32 scratch accumulates A_u @ A_v^T across the
nk steps (MXU matmuls, 128-aligned BlockSpecs); on the last step the fused
epilogue applies w(w-1)/2, masks the u==v diagonal tile to its strict upper
triangle, reduces the tile to one partial sum and stores it.  Padding rows /
columns are all-zero and therefore contribute C(0,2) = 0 — no masking needed
beyond the triangle.

VMEM footprint per step: 2 * bi*bk (operand tiles) + bi*bi (scratch), fp32.
Default (bi=256, bk=512): 1 MiB + 256 KiB — comfortably inside a v5e core's
~16 MiB VMEM with double buffering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["butterfly_pairs_kernel_call", "butterfly_pairs_windows_kernel_call",
           "butterfly_pairs_windows_kernel_multiset_call"]

# Mosaic takes no output block smaller than one (8, 128) f32 tile and cannot
# store a scalar to VMEM: each tile pair's partial is broadcast over one such
# tile, and the callers read element [0, 0].
_OUT_TILE = (8, 128)


def _triangle_pairs(nu: int):
    """Triangular tile-pair enumeration (u <= v) as scalar-prefetch arrays."""
    upair, vpair = [], []
    for u in range(nu):
        for v in range(u, nu):
            upair.append(u)
            vpair.append(v)
    return (jnp.asarray(upair, dtype=jnp.int32),
            jnp.asarray(vpair, dtype=jnp.int32))


def _kernel(upair_ref, vpair_ref, au_ref, av_ref, out_ref, acc_ref, *, nk: int, bi: int):
    t = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    au = au_ref[...].astype(jnp.float32)
    av = av_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        au, av, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        u = upair_ref[t]
        v = vpair_ref[t]
        w = acc_ref[...]
        pairs = w * (w - 1.0) * 0.5
        row = jax.lax.broadcasted_iota(jnp.int32, (bi, bi), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bi, bi), 1)
        # strict upper triangle in *global* i-indices:
        #   u < v  -> whole tile;  u == v -> row < col
        keep = (u * bi + row) < (v * bi + col)
        out_ref[...] = jnp.broadcast_to(
            jnp.sum(jnp.where(keep, pairs, 0.0)), out_ref.shape)


def butterfly_pairs_kernel_call(
    adj: jax.Array,
    *,
    block_i: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Run the kernel over a padded biadjacency.  Returns per-tile-pair
    partial sums [T] (host reduces, optionally in float64).

    ``adj`` must already be padded to multiples of (block_i, block_k).
    """
    n_i, n_j = adj.shape
    if n_i % block_i or n_j % block_k:
        raise ValueError(f"adj {adj.shape} not padded to ({block_i},{block_k})")
    nu = n_i // block_i
    nk = n_j // block_k
    upair, vpair = _triangle_pairs(nu)
    T = int(upair.shape[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, nk),
        in_specs=[
            pl.BlockSpec((block_i, block_k), lambda t, k, up, vp: (up[t], k)),
            pl.BlockSpec((block_i, block_k), lambda t, k, up, vp: (vp[t], k)),
        ],
        out_specs=pl.BlockSpec((1,) + _OUT_TILE,
                               lambda t, k, up, vp: (t, 0, 0)),
        scratch_shapes=[pltpu.VMEM((block_i, block_i), jnp.float32)],
    )
    import functools

    fn = pl.pallas_call(
        functools.partial(_kernel, nk=nk, bi=block_i),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T,) + _OUT_TILE, jnp.float32),
        interpret=interpret,
    )
    return fn(upair, vpair, adj, adj)[:, 0, 0]


def _windows_kernel(upair_ref, vpair_ref, au_ref, av_ref, out_ref, acc_ref,
                    *, nk: int, bi: int):
    """Window-batched twin of :func:`_kernel`: grid (B, T, nk) — the window
    axis is the *outermost* grid dimension, so one launch covers a whole
    bucket of same-capacity windows.  The accumulator scratch is still per
    (window, tile-pair): nk is the innermost dimension, so the k==0 zeroing
    and k==nk-1 epilogue bracket exactly one (b, t) accumulation run."""
    t = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    au = au_ref[0].astype(jnp.float32)
    av = av_ref[0].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        au, av, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        u = upair_ref[t]
        v = vpair_ref[t]
        w = acc_ref[...]
        pairs = w * (w - 1.0) * 0.5
        row = jax.lax.broadcasted_iota(jnp.int32, (bi, bi), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bi, bi), 1)
        keep = (u * bi + row) < (v * bi + col)
        out_ref[...] = jnp.broadcast_to(
            jnp.sum(jnp.where(keep, pairs, 0.0)), out_ref.shape)


def butterfly_pairs_windows_kernel_call(
    adjs: jax.Array,
    *,
    block_i: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Run the window-batched kernel over a [B, n_i, n_j] stack of padded
    biadjacencies.  Returns per-window per-tile-pair partials [B, T] — one
    kernel launch for the whole stack (window dimension in the grid), not
    one launch per window.

    Each ``adjs[b]`` must already be padded to multiples of
    ``(block_i, block_k)``; all-zero (padding) windows contribute 0.
    """
    B, n_i, n_j = adjs.shape
    if n_i % block_i or n_j % block_k:
        raise ValueError(
            f"adjs {adjs.shape} not padded to ({block_i},{block_k})")
    nu = n_i // block_i
    nk = n_j // block_k
    upair, vpair = _triangle_pairs(nu)
    T = int(upair.shape[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, T, nk),
        in_specs=[
            pl.BlockSpec((1, block_i, block_k),
                         lambda b, t, k, up, vp: (b, up[t], k)),
            pl.BlockSpec((1, block_i, block_k),
                         lambda b, t, k, up, vp: (b, vp[t], k)),
        ],
        out_specs=pl.BlockSpec((1, 1) + _OUT_TILE,
                               lambda b, t, k, up, vp: (b, t, 0, 0)),
        scratch_shapes=[pltpu.VMEM((block_i, block_i), jnp.float32)],
    )
    import functools

    fn = pl.pallas_call(
        functools.partial(_windows_kernel, nk=nk, bi=block_i),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T) + _OUT_TILE, jnp.float32),
        interpret=interpret,
    )
    return fn(upair, vpair, adjs, adjs)[:, :, 0, 0]


def _windows_kernel_multiset(upair_ref, vpair_ref, au_ref, av_ref, out_ref,
                             accw_ref, accs_ref, *, nk: int, bi: int):
    """Multiset twin of :func:`_windows_kernel`.

    The biadjacency carries net multiplicities (A[u, j] = mult of edge
    (u, j); 0 if absent) and the per-tile epilogue applies the multiset Gram
    identity  B = sum_{u<v} (W_uv^2 - S_uv) / 2  with  W = A A^T  and
    S = (A*A)(A*A)^T — so two VMEM accumulators ride the nk contraction:
    acc_w for the weighted wedge Gram, acc_s for its squared-entry twin.
    With all multiplicities in {0, 1} this reduces exactly to the distinct
    kernel's w(w-1)/2 (then S == W), and padding stays all-zero => 0.
    """
    t = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        accw_ref[...] = jnp.zeros_like(accw_ref)
        accs_ref[...] = jnp.zeros_like(accs_ref)

    au = au_ref[0].astype(jnp.float32)
    av = av_ref[0].astype(jnp.float32)
    accw_ref[...] += jax.lax.dot_general(
        au, av, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    accs_ref[...] += jax.lax.dot_general(
        au * au, av * av, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        u = upair_ref[t]
        v = vpair_ref[t]
        w = accw_ref[...]
        s = accs_ref[...]
        pairs = (w * w - s) * 0.5
        row = jax.lax.broadcasted_iota(jnp.int32, (bi, bi), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bi, bi), 1)
        keep = (u * bi + row) < (v * bi + col)
        out_ref[...] = jnp.broadcast_to(
            jnp.sum(jnp.where(keep, pairs, 0.0)), out_ref.shape)


def butterfly_pairs_windows_kernel_multiset_call(
    adjs: jax.Array,
    *,
    block_i: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Window-batched multiset kernel over a [B, n_i, n_j] stack of padded
    *weighted* biadjacencies (entries = net edge multiplicities).  Same grid
    schedule as :func:`butterfly_pairs_windows_kernel_call`; the only extra
    cost is the second Gram accumulator (one more bi*bi fp32 VMEM scratch
    and one more MXU matmul per step)."""
    B, n_i, n_j = adjs.shape
    if n_i % block_i or n_j % block_k:
        raise ValueError(
            f"adjs {adjs.shape} not padded to ({block_i},{block_k})")
    nu = n_i // block_i
    nk = n_j // block_k
    upair, vpair = _triangle_pairs(nu)
    T = int(upair.shape[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, T, nk),
        in_specs=[
            pl.BlockSpec((1, block_i, block_k),
                         lambda b, t, k, up, vp: (b, up[t], k)),
            pl.BlockSpec((1, block_i, block_k),
                         lambda b, t, k, up, vp: (b, vp[t], k)),
        ],
        out_specs=pl.BlockSpec((1, 1) + _OUT_TILE,
                               lambda b, t, k, up, vp: (b, t, 0, 0)),
        scratch_shapes=[pltpu.VMEM((block_i, block_i), jnp.float32),
                        pltpu.VMEM((block_i, block_i), jnp.float32)],
    )
    import functools

    fn = pl.pallas_call(
        functools.partial(_windows_kernel_multiset, nk=nk, bi=block_i),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T) + _OUT_TILE, jnp.float32),
        interpret=interpret,
    )
    return fn(upair, vpair, adjs, adjs)[:, :, 0, 0]
