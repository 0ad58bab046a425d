"""Exact butterfly counting for bipartite graph snapshots.

A butterfly is a (2,2)-biclique: vertices {i1, i2} x {j1, j2} with all four
edges present.  The paper's Algorithm 1 intersects neighbor hash-sets; on TPU
we reformulate exactly (DESIGN.md SS2):

    B(G) = sum_{u<v in V_i} C(W_uv, 2),      W = A @ A.T

where ``A`` is the |V_i| x |V_j| 0/1 biadjacency matrix and ``W_uv`` is the
number of common j-neighbors (wedge multiplicity).  ``A @ A.T`` maps straight
onto the MXU; the epilogue ``w(w-1)/2`` fuses into the matmul tiles.

Counting tiers — the validation ladder (each tier validated against every
other on adversarial snapshots in ``tests/test_tier_differential.py``, and
pairwise against the one above it in the unit tests):

1. :func:`count_butterflies_np` -- numpy wedge-hash oracle, int64, always exact.
2. :func:`count_butterflies_dense` -- pure-jnp Gram formulation.
3. :func:`count_butterflies_tiled` -- lax.scan over tile grid; O(tile^2) memory.
4. ``repro.kernels.butterfly`` -- Pallas TPU kernel (fused epilogue in VMEM).
5. :func:`count_butterflies_sparse` -- wedge sort + rank aggregation;
   O(cap_e + wedge_cap) memory, never builds the biadjacency (the
   sparse-window tier the executor's ``auto`` router picks when
   edges << cap_i * cap_j).

Production window counting selects a tier at runtime through
``repro.core.executor.WindowExecutor`` (see ``docs/executor.md``): the
estimators call the executor, the executor calls these primitives at
bucketed static capacities.  All tiers produce identical integer-valued
counts, so tier choice never changes an estimate — only its speed.

All device paths accumulate in float32 by default (exact below 2**24 per
partial sum; in-window counts live far below that for realistic window
parameters) and in float64/int64 when ``jax.config.x64`` is enabled.

**Multiset counting.**  Every tier above counts *distinct* butterflies: a
duplicated edge contributes once, matching the paper's duplicate-ignoring
semantics.  The ``*_multiset`` twins count multiplicity-weighted
butterflies instead ("Counting Butterflies over Streaming Bipartite Graphs
with Duplicate Edges" semantics): an edge of multiplicity ``m`` behaves
like ``m`` parallel copies, so a butterfly on edges of multiplicities
``(a, b, c, d)`` counts ``a * b * c * d`` times.  The Gram identity
generalizes exactly — with ``W = A A^T`` and ``S = (A∘A)(A∘A)^T`` over the
*weighted* biadjacency ``A[u, j] = mult(u, j)``,

    B_multi = sum_{u<v} (W_uv^2 - S_uv) / 2

which reduces to ``sum C(W, 2)`` when every multiplicity is 1 (then
``S = W``).  All multiset tiers take the same padded window tensors plus a
multiplicity lane of *unique* (i, j) edges (the streaming engines resolve
duplicates/deletions to net multiplicities at window close).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "count_butterflies_np",
    "count_butterflies_multiset_np",
    "butterfly_delta_np",
    "enumerate_butterflies_np",
    "butterfly_support_np",
    "count_butterflies_dense",
    "count_butterflies_dense_multiset",
    "count_butterflies_from_edges",
    "count_butterflies_from_edges_multiset",
    "count_butterflies_sampled_from_edges",
    "count_butterflies_tiled",
    "count_butterflies_tiled_multiset",
    "count_butterflies_sparse",
    "count_butterflies_sparse_multiset",
    "window_wedge_counts_np",
    "butterfly_support_dense",
    "count_caterpillars_np",
    "build_biadjacency",
    "build_biadjacency_multiset",
    "Snapshot",
]


# ---------------------------------------------------------------------------
# numpy oracle tier (host, always exact, independent algorithm)
# ---------------------------------------------------------------------------

_MAX_ID = np.int64(1) << 32  # ids pack two-per-int64 key: each must fit 32 bits


def _check_id_range_np(e: np.ndarray) -> None:
    """Host paths pack (a, b) id pairs into one int64 sort key (``a << 32 |
    b``).  The key is injective for ids in ``[0, 2**32)`` (numpy's int64
    shift wraps deterministically, mapping a/b onto disjoint halves of the
    64-bit pattern), but an id >= 2**32 wraps onto another id's key and a
    negative id smears its sign bits over the other half — either silently
    *collides* distinct pairs and corrupts counts.  Fail loudly instead."""
    if e.size and (int(e.min()) < 0 or int(e.max()) >= _MAX_ID):
        raise ValueError(
            "vertex ids must be in [0, 2**32): got range "
            f"[{int(e.min())}, {int(e.max())}] — ids outside it silently "
            "collide in the packed int64 wedge/edge keys; relabel to a "
            "compact id space first (e.g. np.unique(..., "
            "return_inverse=True))")


def _dedupe_edges_np(edges: np.ndarray) -> np.ndarray:
    """Drop duplicate (i, j) pairs, preserving nothing about order."""
    if edges.size == 0:
        return edges.reshape(0, 2).astype(np.int64)
    e = np.asarray(edges, dtype=np.int64)
    _check_id_range_np(e)
    key = e[:, 0] << 32 | e[:, 1]
    _, idx = np.unique(key, return_index=True)
    return e[np.sort(idx)]


def _group_pairs_np(starts: np.ndarray, counts: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray]:
    """All within-group index pairs (p, t) with p < t, fully vectorized.

    ``starts``/``counts`` describe contiguous groups of a sorted array; every
    element pairs with each *earlier* element of its group (rank r emits r
    pairs), so a group of size c emits C(c, 2) pairs total.  This replaces
    the per-hub ``np.triu_indices`` Python loop — the pair-emission cost is
    one ``repeat`` + arithmetic over the output size.
    """
    m = int(counts.sum())
    start_pos = np.repeat(starts, counts)                    # group start per row
    r = np.arange(m, dtype=np.int64) - start_pos             # rank within group
    total = int(r.sum())
    if total == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    t = np.repeat(np.arange(m, dtype=np.int64), r)           # later element
    off = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(r) - r, r)
    p = start_pos[t] + off                                   # earlier element
    return p, t


def count_butterflies_np(edges: np.ndarray) -> int:
    """Exact butterfly count via wedge aggregation (sort-based, int64).

    ``edges`` is an (m, 2) int array of (i, j) endpoints.  Duplicate edges are
    ignored, mirroring the paper's duplicate-insertion semantics.  Algorithm:
    every j-vertex of degree d contributes C(d, 2) wedges (i1, i2); butterflies
    are pairs of wedges with identical endpoints:  B = sum_p C(mult_p, 2).
    This is the same arithmetic as Alg. 1 but organised for vectorised numpy —
    wedge emission is one vectorized ``repeat`` (:func:`_group_pairs_np`),
    never a Python loop over hubs.  Ids must lie in ``[0, 2**32)`` (raises
    otherwise: larger ids would collide in the packed int64 wedge keys).
    """
    e = _dedupe_edges_np(np.asarray(edges))
    if e.shape[0] < 4:
        return 0
    # Group i-neighbors by j: sort by j then i.
    order = np.lexsort((e[:, 0], e[:, 1]))
    i_sorted = e[order, 0]
    j_sorted = e[order, 1]
    _, starts = np.unique(j_sorted, return_index=True)
    counts = np.diff(np.append(starts, j_sorted.shape[0]))
    # Wedge endpoints for each j-group: all pairs within the group.  In-group
    # i is sorted ascending and deduped, so i_sorted[p] < i_sorted[t].
    p, t = _group_pairs_np(starts, counts)
    if p.size == 0:
        return 0
    keys = i_sorted[p] << 32 | i_sorted[t]
    _, mult = np.unique(keys, return_counts=True)
    mult = mult.astype(np.int64)
    return int((mult * (mult - 1) // 2).sum())


def count_butterflies_multiset_np(edges: np.ndarray,
                                  mult: np.ndarray) -> int:
    """Multiplicity-weighted butterfly count, numpy oracle (int64 exact).

    ``edges`` is an (m, 2) int array of *unique* (i, j) pairs and ``mult``
    their positive multiplicities (duplicate rows are aggregated by summing
    their multiplicities, so pre-resolution edge lists are also accepted).
    A wedge (i1, i2) through hub j weighs ``mult(i1, j) * mult(i2, j)``;
    butterflies on a wedge endpoint pair are all unordered hub pairs, so

        B = sum_pairs (S^2 - S2) / 2,   S = sum_j w_j,  S2 = sum_j w_j^2

    which reduces to ``sum C(mult, 2)`` of :func:`count_butterflies_np`
    when every multiplicity is 1.  Ids must lie in ``[0, 2**32)``.
    """
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    m = np.asarray(mult, dtype=np.int64).reshape(-1)
    if e.shape[0] != m.shape[0]:
        raise ValueError(
            f"edges/mult length mismatch: {e.shape[0]} != {m.shape[0]}")
    if m.size and int(m.min()) < 1:
        raise ValueError("multiplicities must be >= 1")
    if e.shape[0] == 0:
        return 0
    _check_id_range_np(e)
    # aggregate duplicate (i, j) rows (net multiplicity per unique edge)
    key = e[:, 0] << 32 | e[:, 1]
    uk, inv = np.unique(key, return_inverse=True)
    um = np.zeros(uk.shape[0], dtype=np.int64)
    np.add.at(um, inv, m)
    if uk.shape[0] < 4:
        return 0
    ei = uk >> 32
    ej = uk & np.int64(0xFFFFFFFF)
    # group i-neighbors by j (sorted by (j, i)); emit weighted wedges
    order = np.lexsort((ei, ej))
    i_sorted, j_sorted, m_sorted = ei[order], ej[order], um[order]
    _, starts = np.unique(j_sorted, return_index=True)
    counts = np.diff(np.append(starts, j_sorted.shape[0]))
    p, t = _group_pairs_np(starts, counts)
    if p.size == 0:
        return 0
    w = m_sorted[p] * m_sorted[t]
    keys = i_sorted[p] << 32 | i_sorted[t]
    _, winv = np.unique(keys, return_inverse=True)
    s1 = np.zeros(int(winv.max()) + 1, dtype=np.int64)
    s2 = np.zeros_like(s1)
    np.add.at(s1, winv, w)
    np.add.at(s2, winv, w * w)
    return int(((s1 * s1 - s2) // 2).sum())


def butterfly_delta_np(edges: np.ndarray, deleted: np.ndarray) -> int:
    """Butterflies destroyed by deleting ``deleted`` edges from the distinct
    graph ``edges`` — the decremental half of Abacus's insert/delete
    symmetry.  Deletions process sequentially; each deleted edge (u, x)
    destroys exactly the butterflies containing it in the *current* graph:

        sum over v in N(x), v != u  of  (|N(u) ∩ N(v)| - 1)

    (the common neighborhood always contains x itself; every other shared
    hub completes a butterfly through (u, x)).  Returns
    ``B(edges) - B(edges \\ deleted)`` as an exact int.  Each deleted edge
    must be present (and not already deleted) — raises ``ValueError``
    otherwise, mirroring the engines' default ``on_missing_delete``.
    """
    e = _dedupe_edges_np(np.asarray(edges))
    d = np.asarray(deleted, dtype=np.int64).reshape(-1, 2)
    adj_i: dict[int, set[int]] = {}
    adj_j: dict[int, set[int]] = {}
    for u, x in e:
        adj_i.setdefault(int(u), set()).add(int(x))
        adj_j.setdefault(int(x), set()).add(int(u))
    total = 0
    for u, x in d:
        u, x = int(u), int(x)
        if x not in adj_i.get(u, ()):  # never inserted or already deleted
            raise ValueError(
                f"cannot delete absent edge ({u}, {x}); deletions must name "
                "a present edge")
        nu = adj_i[u]
        for v in adj_j[x]:
            if v != u:
                total += len(nu & adj_i[v]) - 1
        nu.remove(x)
        adj_j[x].remove(u)
    return total


def enumerate_butterflies_np(edges: np.ndarray) -> np.ndarray:
    """Enumerate distinct butterflies as (i1, i2, j1, j2) rows (i1<i2, j1<j2).

    Used by the SS3 analysis reproductions (hub membership, inter-arrival).
    Only intended for small snapshots (the paper itself caps at 5000 sgrs).
    """
    e = _dedupe_edges_np(np.asarray(edges))
    if e.shape[0] < 4:
        return np.zeros((0, 4), dtype=np.int64)
    order = np.lexsort((e[:, 0], e[:, 1]))
    i_sorted, j_sorted = e[order, 0], e[order, 1]
    _, starts = np.unique(j_sorted, return_index=True)
    counts = np.diff(np.append(starts, j_sorted.shape[0]))
    # wedges (i1 < i2, hub j), emitted with the vectorized pair kernel
    p, t = _group_pairs_np(starts, counts)
    if p.size == 0:
        return np.zeros((0, 4), dtype=np.int64)
    w1, w2, wj = i_sorted[p], i_sorted[t], j_sorted[t]
    # butterflies: pairs of wedges sharing (i1, i2); sorting by (key, j)
    # keeps each key-group's hubs ascending, so the emitted (j1, j2) pairs
    # satisfy j1 < j2 (hubs within a key group are distinct after dedupe)
    key = w1 << 32 | w2
    order2 = np.lexsort((wj, key))
    key_s, wj_s = key[order2], wj[order2]
    w1_s, w2_s = w1[order2], w2[order2]
    _, kstarts = np.unique(key_s, return_index=True)
    kcounts = np.diff(np.append(kstarts, key_s.shape[0]))
    p2, t2 = _group_pairs_np(kstarts, kcounts)
    if p2.size == 0:
        return np.zeros((0, 4), dtype=np.int64)
    return np.stack([w1_s[t2], w2_s[t2], wj_s[p2], wj_s[t2]], axis=1)


def butterfly_support_np(edges: np.ndarray, n_i: int, n_j: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-vertex butterfly support (Algorithm 2 semantics), numpy oracle."""
    quads = enumerate_butterflies_np(edges)
    sup_i = np.zeros(n_i, dtype=np.int64)
    sup_j = np.zeros(n_j, dtype=np.int64)
    if quads.shape[0]:
        np.add.at(sup_i, quads[:, 0], 1)
        np.add.at(sup_i, quads[:, 1], 1)
        np.add.at(sup_j, quads[:, 2], 1)
        np.add.at(sup_j, quads[:, 3], 1)
    return sup_i, sup_j


def count_caterpillars_np(edges: np.ndarray) -> int:
    """Three-paths (caterpillars): sum over edges of (deg_i - 1)(deg_j - 1).

    Used for the bipartite clustering coefficient 4B / caterpillars (SS1).
    """
    e = _dedupe_edges_np(np.asarray(edges))
    if e.shape[0] == 0:
        return 0
    di = np.bincount(e[:, 0])
    dj = np.bincount(e[:, 1])
    return int(((di[e[:, 0]] - 1) * (dj[e[:, 1]] - 1)).sum())


# ---------------------------------------------------------------------------
# jnp dense tier
# ---------------------------------------------------------------------------

def _acc_dtype() -> jnp.dtype:
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def build_biadjacency(
    edge_i: jax.Array,
    edge_j: jax.Array,
    valid: jax.Array,
    n_i: int,
    n_j: int,
    dtype=jnp.float32,
) -> jax.Array:
    """Scatter a padded edge list into a dense 0/1 biadjacency [n_i, n_j].

    Duplicate edges collapse naturally (max-scatter), reproducing the paper's
    duplicate-ignoring semantics.  Invalid (padding) lanes are routed to a
    sacrificial out-of-range row that ``mode="drop"`` discards.
    """
    ii = jnp.where(valid, edge_i, n_i)  # out-of-bounds => dropped
    jj = jnp.where(valid, edge_j, n_j)
    adj = jnp.zeros((n_i, n_j), dtype=dtype)
    return adj.at[ii, jj].max(jnp.ones_like(ii, dtype=dtype), mode="drop")


def count_butterflies_dense(adj: jax.Array) -> jax.Array:
    """B = sum_{u<v} C((A A^T)_uv, 2) on a dense biadjacency.

    Loops over whichever side is smaller (the paper iterates the lower-degree
    side; the Gram trick makes that a transpose decision).  One full Gram
    GEMM beats triangle-blocked variants in practice: backends schedule a
    single large matmul far better than several small ones, so the 25%
    flop saving of a 2-block triangle loses to GEMM efficiency.
    """
    a = adj.astype(_acc_dtype())
    if a.shape[0] > a.shape[1]:
        a = a.T
    w = a @ a.T
    pairs = w * (w - 1.0) * 0.5
    off = pairs.sum() - jnp.sum(jnp.diagonal(pairs))
    return off * 0.5


def butterfly_support_dense(adj: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-vertex butterfly support (Algorithm 2), dense Gram formulation.

    support_i[u] = sum_{v != u} C(W_uv, 2)   with W = A A^T
    support_j[x] = sum_{y != x} C(W'_xy, 2)  with W' = A^T A
    """
    a = adj.astype(_acc_dtype())

    def _side(m):
        w = m @ m.T
        pairs = w * (w - 1.0) * 0.5
        return pairs.sum(axis=1) - jnp.diagonal(pairs)

    return _side(a), _side(a.T)


def count_butterflies_from_edges(
    edge_i: jax.Array,
    edge_j: jax.Array,
    valid: jax.Array,
    n_i: int,
    n_j: int,
) -> jax.Array:
    """Count butterflies directly from a padded edge list (window snapshot)."""
    adj = build_biadjacency(edge_i, edge_j, valid, n_i, n_j, dtype=_acc_dtype())
    return count_butterflies_dense(adj)


def count_butterflies_sampled_from_edges(
    edge_i: jax.Array,
    edge_j: jax.Array,
    valid: jax.Array,
    uid_hi: jax.Array,
    uid_lo: jax.Array,
    n_i: int,
    n_j: int,
    *,
    capacity: int,
    gamma: float,
    seed: int,
) -> jax.Array:
    """FLEET subsample-and-scale count of one padded window: keep each valid
    edge with the gamma-ladder probability p chosen so at most ``capacity``
    edges survive, count the survivors exactly with the dense counter, and
    rescale by ``p**-4`` (each of a butterfly's four edges survives
    independently with probability p).  When the window statically fits the
    reservoir (``cap_e <= capacity``) the sampling provably degenerates to
    ``p = 1`` — the count is returned bit-identical to the exact dense tier,
    with no threefry work at all.  ``uid_hi``/``uid_lo`` are the uint32
    halves of the window's sampling uid (see ``fleet.sample_keep_mask``)."""
    if edge_i.shape[0] <= capacity:
        return count_butterflies_from_edges(edge_i, edge_j, valid, n_i, n_j)
    from .fleet import sample_keep_mask

    keep, p = sample_keep_mask(edge_i, edge_j, valid, uid_hi, uid_lo,
                               capacity=capacity, gamma=gamma, seed=seed)
    count = count_butterflies_from_edges(edge_i, edge_j, keep, n_i, n_j)
    inv = jnp.where(p > 0, 1.0 / p, 0.0).astype(count.dtype)
    return count * inv**4


def build_biadjacency_multiset(
    edge_i: jax.Array,
    edge_j: jax.Array,
    mult: jax.Array,
    valid: jax.Array,
    n_i: int,
    n_j: int,
    dtype=jnp.float32,
) -> jax.Array:
    """Scatter a padded (edge, multiplicity) list into a *weighted*
    biadjacency ``A[u, j] = mult(u, j)`` [n_i, n_j].

    Edges are expected unique per window (the engines resolve duplicates to
    net multiplicities at window close); a repeated (i, j) lane scatter-adds,
    which keeps the sum-of-multiplicities semantics either way.  Invalid
    (padding) lanes route to a sacrificial out-of-range row that
    ``mode="drop"`` discards.
    """
    ii = jnp.where(valid, edge_i, n_i)
    jj = jnp.where(valid, edge_j, n_j)
    w = jnp.where(valid, mult, 0).astype(dtype)
    adj = jnp.zeros((n_i, n_j), dtype=dtype)
    return adj.at[ii, jj].add(w, mode="drop")


def _pairs_multiset(w: jax.Array, s: jax.Array) -> jax.Array:
    """Per wedge-endpoint pair: unordered hub pairs weighted by multiplicity
    — ``(W^2 - S) / 2`` with ``W`` the weighted wedge count and ``S`` its
    square-weighted twin.  Equals ``C(W, 2)`` when all multiplicities are 1
    (then ``S == W``)."""
    return (w * w - s) * 0.5


def count_butterflies_dense_multiset(adj: jax.Array) -> jax.Array:
    """Multiplicity-weighted count on a weighted biadjacency:
    ``B = sum_{u<v} (W_uv^2 - S_uv) / 2`` with ``W = A A^T`` and
    ``S = (A∘A)(A∘A)^T`` — the multiset Gram identity (module doc).  The
    formula is symmetric in sides, so the smaller-side orientation transpose
    of :func:`count_butterflies_dense` stays valid."""
    a = adj.astype(_acc_dtype())
    if a.shape[0] > a.shape[1]:
        a = a.T
    a2 = a * a
    w = a @ a.T
    s = a2 @ a2.T
    pairs = _pairs_multiset(w, s)
    off = pairs.sum() - jnp.sum(jnp.diagonal(pairs))
    return off * 0.5


def count_butterflies_from_edges_multiset(
    edge_i: jax.Array,
    edge_j: jax.Array,
    mult: jax.Array,
    valid: jax.Array,
    n_i: int,
    n_j: int,
) -> jax.Array:
    """Multiset count directly from a padded (edge, multiplicity) list."""
    adj = build_biadjacency_multiset(edge_i, edge_j, mult, valid, n_i, n_j,
                                     dtype=_acc_dtype())
    return count_butterflies_dense_multiset(adj)


# ---------------------------------------------------------------------------
# tiled tier (never materializes the |Vi| x |Vi| wedge matrix)
# ---------------------------------------------------------------------------

def count_butterflies_tiled(adj: jax.Array, tile: int = 512) -> jax.Array:
    """Tiled Gram counting: scan over row-block pairs, fused epilogue.

    Memory: O(tile * n_j + tile^2) instead of O(n_i^2).  This is the pure-JAX
    twin of the Pallas kernel (same schedule, XLA-fused epilogue); it is also
    the shape the distributed ring counter shards.
    """
    acc = _acc_dtype()
    a = adj.astype(acc)
    if a.shape[0] > a.shape[1]:
        a = a.T
    n_i = a.shape[0]
    n_blocks = -(-n_i // tile)
    pad = n_blocks * tile - n_i
    a = jnp.pad(a, ((0, pad), (0, 0)))
    blocks = a.reshape(n_blocks, tile, a.shape[1])
    row_ids = jnp.arange(n_blocks * tile).reshape(n_blocks, tile)

    def pair_count(bu, bv, iu, iv):
        w = bu @ bv.T
        pairs = w * (w - 1.0) * 0.5
        mask = (iu[:, None] < iv[None, :]).astype(acc)  # strict upper: u < v
        return jnp.sum(pairs * mask)

    def outer(carry, u):
        bu, iu = blocks[u], row_ids[u]

        def inner(c, v):
            return c + pair_count(bu, blocks[v], iu, row_ids[v]), None

        c, _ = jax.lax.scan(inner, carry, jnp.arange(n_blocks))
        return c, None

    # the zero carry comes from the input so that, under shard_map, it has
    # the same varying-manual-axes type as the per-pair sums added to it
    zero = jnp.sum(a[:0])
    total, _ = jax.lax.scan(outer, zero, jnp.arange(n_blocks))
    return total


def count_butterflies_tiled_multiset(adj: jax.Array,
                                     tile: int = 512) -> jax.Array:
    """Tiled twin of :func:`count_butterflies_dense_multiset`: the same
    row-block-pair scan as :func:`count_butterflies_tiled`, accumulating the
    weighted Gram ``W`` and its square-weighted twin ``S`` per tile pair and
    fusing the ``(W^2 - S)/2`` epilogue.  Memory stays
    O(tile * n_j + tile^2)."""
    acc = _acc_dtype()
    a = adj.astype(acc)
    if a.shape[0] > a.shape[1]:
        a = a.T
    n_i = a.shape[0]
    n_blocks = -(-n_i // tile)
    pad = n_blocks * tile - n_i
    a = jnp.pad(a, ((0, pad), (0, 0)))
    blocks = a.reshape(n_blocks, tile, a.shape[1])
    blocks2 = blocks * blocks
    row_ids = jnp.arange(n_blocks * tile).reshape(n_blocks, tile)

    def pair_count(bu, bu2, bv, bv2, iu, iv):
        w = bu @ bv.T
        s = bu2 @ bv2.T
        pairs = _pairs_multiset(w, s)
        mask = (iu[:, None] < iv[None, :]).astype(acc)  # strict upper: u < v
        return jnp.sum(pairs * mask)

    def outer(carry, u):
        bu, bu2, iu = blocks[u], blocks2[u], row_ids[u]

        def inner(c, v):
            return c + pair_count(bu, bu2, blocks[v], blocks2[v], iu,
                                  row_ids[v]), None

        c, _ = jax.lax.scan(inner, carry, jnp.arange(n_blocks))
        return c, None

    # the zero carry comes from the input so that, under shard_map, it has
    # the same varying-manual-axes type as the per-pair sums added to it
    zero = jnp.sum(a[:0])
    total, _ = jax.lax.scan(outer, zero, jnp.arange(n_blocks))
    return total


# ---------------------------------------------------------------------------
# sparse tier (wedge sort + segment_sum; never builds the biadjacency)
# ---------------------------------------------------------------------------

def count_butterflies_sparse(
    edge_i: jax.Array,
    edge_j: jax.Array,
    valid: jax.Array,
    n_i: int,
    n_j: int,
    wedge_cap: int,
) -> jax.Array:
    """Butterfly count from a padded edge list via wedge aggregation —
    the paper's sort-side formulation (Wang et al.'s wedge iteration) in
    pure JAX, O(cap_e + wedge_cap) memory instead of O(n_i * n_j).

    Schedule (all static shapes, fully vmap/shard_map-compatible):

    1. sort edges by ``(j, i)`` — invalid lanes carry sentinel ids ``(n_j,
       n_i)`` so they group last — and invalidate exact duplicates;
    2. a second sort compacts the surviving edges back into
       contiguous j-groups (dup lanes rejoin the sentinel group);
    3. every edge of in-group rank ``r`` owes ``r`` wedges, one per earlier
       group member; an inclusive rank cumsum + ``searchsorted`` scatters
       the wedge slots ``[0, wedge_cap)`` to their ``(earlier, later)``
       edge pair — in-group ``i`` is ascending and deduped, so the wedge
       endpoints satisfy ``i1 < i2`` by construction;
    4. sort wedges by ``(i1, i2)`` and aggregate each run of equal keys:
       summing every live wedge's within-run rank is exactly
       ``sum_runs C(mult, 2)`` — the segment-sum of wedge multiplicities
       with the C(w, 2) epilogue algebraically folded in; each run's start
       is a ``searchsorted`` of the sorted keys into themselves instead of
       a segment scatter (scatters are the slowest primitive on every XLA
       backend).

    Both sort phases pack their id pair into a single int32 key — XLA's
    variadic multi-key sort lowers to a generic comparator loop that is
    several times slower than the single-key path.  The sorts are unstable:
    equal int keys are indistinguishable, so the result is the same, and the
    TPU compiler builds an unstable sort several times faster.  For the same
    reason the wedge-run starts avoid ``cummax``: over a wedge lane of 2**20
    slots it alone takes the TPU compiler about 35 s.

    ``wedge_cap`` must bound the window's wedge count (the executor computes
    it host-side per bucket and rounds it up the capacity ladder); dead
    slots carry sentinel endpoints and contribute a zero multiplicity.
    """
    if wedge_cap < 1:
        raise ValueError("wedge_cap must be >= 1")
    # both sort phases pack their two ids into ONE int32 key (XLA's variadic
    # two-key sort lowers to a slow generic comparator; a single-key sort is
    # several times faster on every backend) — the packing needs headroom
    if (n_i + 2) * (n_j + 2) >= 2**31 or (n_i + 2) * (n_i + 2) >= 2**31:
        raise ValueError(
            "sparse tier requires (n_i + 2) * (max(n_i, n_j) + 2) < 2**31 "
            "to pack sort keys into int32; use the dense/tiled tiers for "
            "id spaces this large")
    acc = _acc_dtype()
    cap_e = edge_i.shape[0]
    pos = jnp.arange(cap_e, dtype=jnp.int32)
    first = pos == 0
    ii = jnp.where(valid, edge_i, n_i).astype(jnp.int32)
    jj = jnp.where(valid, edge_j, n_j).astype(jnp.int32)
    # sort edges by packed (j, i); invalid lanes carry (n_j, n_i) => last
    span_i = jnp.int32(n_i + 2)
    ekey = jax.lax.sort(jj * span_i + ii, is_stable=False)
    dup = (~first) & (ekey == jnp.roll(ekey, 1))
    sent = jnp.int32(n_j) * span_i              # every live key sorts below
    # compact dups out
    ekey = jax.lax.sort(jnp.where(dup, sent + ii, ekey), is_stable=False)
    jj = ekey // span_i
    ii = ekey - jj * span_i
    live = jj < n_j
    # in-group rank r: distance to the group's first position (cummax of
    # group-start markers); sentinel lanes rank 0 — they owe no wedges
    is_start = first | (jj != jnp.roll(jj, 1))
    start = jax.lax.cummax(jnp.where(is_start, pos, -1))
    r = jnp.where(live, pos - start, 0)
    cum_r = jnp.cumsum(r)                       # inclusive; total wedges last
    total_w = cum_r[-1]
    w = jnp.arange(wedge_cap, dtype=jnp.int32)
    t = jnp.clip(jnp.searchsorted(cum_r, w, side="right"), 0, cap_e - 1)
    t = t.astype(jnp.int32)
    p = start[t] + (w - (cum_r[t] - r[t]))      # the earlier in-group edge
    alive = w < total_w
    i1 = jnp.where(alive, ii[jnp.clip(p, 0, cap_e - 1)], n_i)
    i2 = jnp.where(alive, ii[t], n_i)
    # aggregate wedge multiplicities: sort the packed (i1, i2) keys, then
    # sum each live wedge's rank within its run of equal keys — a run of
    # multiplicity m contributes 0 + 1 + ... + (m-1) = C(m, 2), which is
    # exactly the per-key butterfly count, summed without a segment scatter
    # dead wedges (>= n_i * span) sort last
    wkey = jax.lax.sort(i1 * span_i + i2, is_stable=False)
    wpos = jnp.arange(wedge_cap, dtype=jnp.int32)
    wstart = jnp.searchsorted(wkey, wkey, side="left").astype(jnp.int32)
    wrank = jnp.where(wkey < jnp.int32(n_i) * span_i, wpos - wstart, 0)
    return jnp.sum(wrank.astype(acc))


def count_butterflies_sparse_multiset(
    edge_i: jax.Array,
    edge_j: jax.Array,
    mult: jax.Array,
    valid: jax.Array,
    n_i: int,
    n_j: int,
    wedge_cap: int,
) -> jax.Array:
    """Multiset twin of :func:`count_butterflies_sparse`: weighted wedge
    aggregation over a padded (edge, multiplicity) list of *unique* (i, j)
    pairs (the engines resolve duplicates to net multiplicities before
    packing, so no dup-invalidation resort is needed here).

    The schedule mirrors the distinct tier — edge sort by packed ``(j, i)``
    key (multiplicities ride as the sort payload), rank-cumsum wedge-slot
    emission — but each wedge carries weight ``mult(i1, j) * mult(i2, j)``
    and the per-run epilogue becomes ``(S^2 - S2) / 2`` with ``S`` /
    ``S2`` the run's weight and squared-weight sums, evaluated at run tails
    from exclusive-cumsum run bases (cummax-propagated, scatter-free —
    both cumsums are non-decreasing since weights are >= 0).  All static
    shapes; same int32 key-packing bound as the distinct tier.
    """
    if wedge_cap < 1:
        raise ValueError("wedge_cap must be >= 1")
    if (n_i + 2) * (n_j + 2) >= 2**31 or (n_i + 2) * (n_i + 2) >= 2**31:
        raise ValueError(
            "sparse tier requires (n_i + 2) * (max(n_i, n_j) + 2) < 2**31 "
            "to pack sort keys into int32; use the dense/tiled tiers for "
            "id spaces this large")
    acc = _acc_dtype()
    cap_e = edge_i.shape[0]
    pos = jnp.arange(cap_e, dtype=jnp.int32)
    first = pos == 0
    ii = jnp.where(valid, edge_i, n_i).astype(jnp.int32)
    jj = jnp.where(valid, edge_j, n_j).astype(jnp.int32)
    mm = jnp.where(valid, mult, 0).astype(jnp.int32)
    # sort edges by packed (j, i) — invalid lanes carry (n_j, n_i) => last —
    # with the multiplicity lane as sort payload
    span_i = jnp.int32(n_i + 2)
    ekey, mm = jax.lax.sort_key_val(jj * span_i + ii, mm)
    jj = ekey // span_i
    ii = ekey - jj * span_i
    live = jj < n_j
    # in-group rank r and wedge-slot emission, exactly as the distinct tier
    is_start = first | (jj != jnp.roll(jj, 1))
    start = jax.lax.cummax(jnp.where(is_start, pos, -1))
    r = jnp.where(live, pos - start, 0)
    cum_r = jnp.cumsum(r)
    total_w = cum_r[-1]
    w = jnp.arange(wedge_cap, dtype=jnp.int32)
    t = jnp.clip(jnp.searchsorted(cum_r, w, side="right"), 0, cap_e - 1)
    t = t.astype(jnp.int32)
    p = jnp.clip(start[t] + (w - (cum_r[t] - r[t])), 0, cap_e - 1)
    alive = w < total_w
    i1 = jnp.where(alive, ii[p], n_i)
    i2 = jnp.where(alive, ii[t], n_i)
    macc = mm.astype(acc)
    ww = jnp.where(alive, macc[p] * macc[t], 0.0)       # wedge weight
    # aggregate weighted wedges: sort packed (i1, i2) keys with the weight
    # as payload (dead wedges share the sentinel key and carry weight 0, so
    # their run contributes S = S2 = 0), then the per-run (S^2 - S2)/2
    # epilogue at run tails — run bases are the exclusive cumsums at run
    # heads, propagated by cummax (both cumsums are non-decreasing)
    wkey, ww = jax.lax.sort_key_val(i1 * span_i + i2, ww)
    wpos = jnp.arange(wedge_cap, dtype=jnp.int32)
    head = (wpos == 0) | (wkey != jnp.roll(wkey, 1))
    c1 = jnp.cumsum(ww)
    c2 = jnp.cumsum(ww * ww)
    base1 = jax.lax.cummax(jnp.where(head, c1 - ww, -1.0))
    base2 = jax.lax.cummax(jnp.where(head, c2 - ww * ww, -1.0))
    tail = jnp.roll(head, -1) | (wpos == wedge_cap - 1)
    s1 = c1 - base1
    s2 = c2 - base2
    return jnp.sum(jnp.where(tail, (s1 * s1 - s2) * 0.5, 0.0))


def window_wedge_counts_np(edge_i: np.ndarray, edge_j: np.ndarray,
                           valid: np.ndarray) -> np.ndarray:
    """Deduped wedge count per window, host-side: ``sum_j C(d_j, 2)`` over
    each window's valid lanes.  This is the quantity the executor's sparse
    tier needs a static capacity for (and the sparse term of the auto
    router's cost model).  ``edge_i``/``edge_j``/``valid`` are the padded
    ``[n_windows, capacity]`` window tensors (compact non-negative ids).
    """
    ei = np.asarray(edge_i, dtype=np.int64)
    ej = np.asarray(edge_j, dtype=np.int64)
    v = np.asarray(valid, dtype=bool)
    out = np.zeros(ei.shape[0], dtype=np.int64)
    if ei.size == 0:
        return out
    span = max(int(ej.max()), 0) + 1
    for k in range(ei.shape[0]):
        i, j = ei[k][v[k]], ej[k][v[k]]
        if i.size < 2:
            continue
        keys = np.unique(i * span + j)          # dedupe (i, j) pairs
        d = np.bincount(keys % span)
        out[k] = int((d * (d - 1) // 2).sum())
    return out


# ---------------------------------------------------------------------------
# Window snapshot container
# ---------------------------------------------------------------------------

class Snapshot(NamedTuple):
    """A padded, compactly-relabelled window snapshot (device-side).

    edge_i / edge_j : int32 [capacity]  compact per-window vertex ids
    valid           : bool  [capacity]
    n_i / n_j       : static ints      compact id-space sizes (padded)
    """

    edge_i: jax.Array
    edge_j: jax.Array
    valid: jax.Array
    n_i: int
    n_j: int

    def count(self) -> jax.Array:
        return count_butterflies_from_edges(
            self.edge_i, self.edge_j, self.valid, self.n_i, self.n_j
        )


@functools.partial(jax.jit, static_argnames=("n_i", "n_j"))
def snapshot_count(edge_i, edge_j, valid, *, n_i: int, n_j: int) -> jax.Array:
    return count_butterflies_from_edges(edge_i, edge_j, valid, n_i, n_j)
