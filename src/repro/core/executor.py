"""Streaming window executor: tier-selectable, bucket-batched window counting.

The estimators (sGrapp / sGrapp-x) need one number per closed window: the
exact in-window butterfly count.  Naively every window pays the *global*
compact id-space capacity ``[n_i, n_j]`` — one giant biadjacency per window
even when the window itself touches 100 vertices.  The executor instead:

1. **Buckets** windows by their per-window compact sizes.  Each window's
   ``n_edges`` is rounded up a geometric capacity ladder (``align *
   growth**k``) and its id-space sizes ``(n_i, n_j)`` up a *linear* ladder
   (multiples of ``align`` — they size the Gram quadratically, so
   power-of-2 rungs there waste ~2x flops in padding); windows sharing all
   rungs form one bucket.  XLA compiles once per bucket shape — not per
   window, and not at global capacity.
2. **Batches** each bucket through chunked-``vmap`` dispatch: a ``lax.map``
   over *chunks* of ``vmap``'d windows (``chunk`` knob, default 32).  Within
   a chunk every window counts in parallel (batched scatters and matmuls
   instead of a sequential per-window walk); across chunks the schedule is
   still streaming order, so peak device memory is bounded by
   ``chunk * cap_i * cap_j`` (plus tile scratch) — never the global
   ``n_i * n_j`` and never the whole bucket at once.  ``chunk=1`` recovers
   the fully sequential ``lax.map`` schedule bit-for-bit.
3. **Routes** through a selectable tier — the validation ladder of
   ``repro.core.butterfly``:

   ========  ==========================================================
   tier      implementation
   ========  ==========================================================
   numpy     host wedge-hash oracle (`count_butterflies_np`), int64
   dense     jnp Gram (`count_butterflies_from_edges`), MXU matmul
   tiled     `count_butterflies_tiled` lax.scan over tile pairs
   pallas    window-batched Pallas kernel (window axis in the grid: one
             launch per bucket chunk); interpret mode on CPU hosts,
             Mosaic on TPU
   sparse    `count_butterflies_sparse` wedge sort + rank aggregation;
             O(cap_e + wedge_cap) memory, no biadjacency
   auto      per-bucket cost-model router: ``sparse`` when the wedge-sort
             work beats the dense Gram flops (see :func:`route_tier`),
             ``dense`` otherwise
   sampled   FLEET subsample-and-scale (`count_butterflies_sampled_from_
             edges`): content-keyed threefry coins pick at most
             ``capacity`` edges per window at the gamma-ladder probability
             p, the survivors run the dense counter, and the count scales
             by p**-4.  Bounded memory at any window size; estimates are
             stochastic but seed-deterministic, and provably exact
             (bit-identical to ``dense``) whenever the window fits the
             reservoir.  A ``(memory_budget, target_mape)`` pair routes
             small-enough or too-lossy buckets back to exact ``dense``
             counting (see :meth:`WindowExecutor.bucket_tier`).
   ========  ==========================================================

Every exact tier returns identical integer-valued counts (differential
suite: ``tests/test_tier_differential.py``), so the production tier is a
config knob, not a semantics decision; the ``sampled`` tier joins the same
contract in its capacity-degenerate regime and is otherwise an estimator
with a gated statistical error bound (``tests/test_sampled_acceptance.py``).

**Window modes.**  ``tumbling`` is the paper's Algorithm 3: disjoint panes
of ``nt_w`` unique timestamps.  ``sliding`` derives *overlapping* windows
from the same panes by prefix-difference: output window ``k`` spans panes
``[k - span + 1, k]`` and its count is ``P[k] - P[k - span]`` with ``P`` the
prefix sum of pane counts.  Butterflies straddling pane boundaries are — as
in tumbling mode — the estimator's inter-window term, so the sliding counts
feed ``sgrapp_estimate`` unchanged.

**Sharded dispatch.**  Closed windows are embarrassingly parallel, so each
bucket's window axis can shard across devices: pass ``devices=N`` (or a
prebuilt ``mesh=``) and every bucket batch is padded to a multiple of the
shard count and dispatched through ``shard_map`` (window axis split over the
mesh's data axes) composed with the same per-device chunked-vmap schedule.
Each window is still counted whole on exactly one device by exactly the same
per-window program, so sharded counts are bit-identical to the single-device
path — verified by the multi-device differential cases in
``tests/test_tier_differential.py``.  Host/device work overlaps through the
submit/reap split: :meth:`WindowExecutor.window_counts_submit` stages and
dispatches every bucket asynchronously and returns a :class:`PendingCounts`
handle holding un-materialized device arrays; materialization (and the
host-side scatter back into window order) happens only at
:meth:`PendingCounts.reap`.  The streaming engines ride this split so host
windowizing of flush k+1 overlaps device compute of flush k;
:meth:`WindowExecutor.window_counts` is simply ``submit(...).reap()``.

Entry points: :class:`WindowExecutor` (stateful, caches compiled buckets)
and the module-level :func:`run` convenience.  ``run_sgrapp`` /
``run_sgrapp_x`` accept ``tier=...`` / ``devices=...`` / ``mesh=...`` and
route here.
"""
from __future__ import annotations

import functools
import math
import weakref
from dataclasses import dataclass, field

import jax
import numpy as np

from .butterfly import (
    build_biadjacency,
    build_biadjacency_multiset,
    butterfly_delta_np,
    count_butterflies_from_edges,
    count_butterflies_from_edges_multiset,
    count_butterflies_multiset_np,
    count_butterflies_np,
    count_butterflies_sampled_from_edges,
    count_butterflies_sparse,
    count_butterflies_sparse_multiset,
    count_butterflies_tiled,
    count_butterflies_tiled_multiset,
    window_wedge_counts_np,
)
from .fleet import check_sampling_knobs
from .windows import WindowBatch

__all__ = ["TIERS", "MODES", "WindowExecutor", "ExecutorResult", "Bucket",
           "PendingCounts", "run", "route_tier", "route_decrement",
           "bucket_capacity", "id_capacity", "expected_mape",
           "compiled_bucket_cache_info"]

TIERS = ("numpy", "dense", "tiled", "pallas", "sparse", "auto", "sampled")
MODES = ("tumbling", "sliding")

# tiers that need a per-bucket wedge capacity (host-side wedge counting)
_WEDGE_TIERS = ("sparse", "auto")


def route_tier(cap_e: int, cap_i: int, cap_j: int, cap_w: int,
               *, sort_cost: float = 96.0) -> str:
    """The ``auto`` tier's per-bucket density cost model.

    Dense counting pays the Gram matmul: ``cap_i * cap_j * min(cap_i,
    cap_j)`` MXU flops per window (biadjacency scatter included — it is a
    lower-order term).  Sparse counting pays sorts: ``cap_e log cap_e``
    (edge sort) + ``cap_w log cap_w`` (wedge sort), each element costing
    roughly ``sort_cost`` dense flops.  The default 96 is calibrated on
    CI-class x86 hosts (XLA CPU sorts run ~6ns/element while the f32 Gram
    streams ~70ps/flop; the same order holds on TPU, where sorts are
    scalar-lane work and matmuls hit the MXU).  Route to ``sparse``
    exactly when its modelled work is cheaper — sparse windows in big id
    spaces (edges << cap_i * cap_j) go sparse, dense little windows keep
    the matmul.
    """
    hi = max(cap_i, cap_j)
    if (cap_i + 2) * (hi + 2) >= 2**31:
        # beyond count_butterflies_sparse's int32 key-packing bound the
        # sparse tier would refuse at trace time — never route into a crash
        return "dense"
    dense_flops = float(cap_i) * float(cap_j) * float(min(cap_i, cap_j))
    sort_ops = (cap_e * max(math.log2(max(cap_e, 2)), 1.0)
                + cap_w * max(math.log2(max(cap_w, 2)), 1.0))
    return "sparse" if sort_cost * sort_ops < dense_flops else "dense"


def expected_mape(cap_e: int, capacity: int, gamma: float,
                  *, k_err: float = 8.0) -> float:
    """Pinned surrogate for the sampled tier's expected relative error at a
    bucket rung: each butterfly survives the subsample with probability
    ``p**4`` (p = the gamma-ladder rung the reservoir would settle at for a
    ``cap_e``-edge window), so the estimator's variance scales like
    ``(p**-4 - 1)`` spread over roughly ``capacity`` surviving edges.  The
    constant ``k_err`` is calibrated empirically against the acceptance
    suite's sgr streams (``tests/test_sampled_acceptance.py``) — it is a
    budget-router heuristic, not a guarantee.  Returns 0.0 whenever the
    window provably fits the reservoir (sampling degenerates to exact)."""
    if cap_e <= capacity:
        return 0.0
    k = max(0, math.ceil(math.log(capacity / cap_e) / math.log(gamma)))
    p = float(gamma) ** k
    return k_err * math.sqrt(max(p ** -4 - 1.0, 0.0) / max(capacity, 1))


def route_decrement(n_edges: int, n_deleted: int,
                    *, delta_frac: float = 0.25) -> str:
    """Decremental router: patch prior counts per deletion (``"delta"``) or
    recount the surviving window wholesale (``"recount"``).

    Following Abacus's insert/delete symmetry, the butterflies destroyed by
    deleting one edge cost a local wedge-neighborhood walk — cheap while few
    edges retract, but the per-deletion walks are sequential host work, so
    once more than ``delta_frac`` of the window retracts the batched device
    recount of the survivors is the better buy.  The crossover is a host-side
    static decision (like :func:`route_tier`), so both routes stay
    deterministic and differentially testable against each other.
    """
    if n_edges < 0 or n_deleted < 0:
        raise ValueError("edge/delete counts must be non-negative")
    return "delta" if n_deleted <= delta_frac * n_edges else "recount"


def bucket_capacity(n: int, *, align: int = 128, growth: int = 2) -> int:
    """Smallest ladder rung ``align * growth**k`` >= max(n, 1)."""
    cap = align
    n = max(int(n), 1)
    while cap < n:
        cap *= growth
    return cap


def id_capacity(n: int, *, align: int = 64) -> int:
    """Smallest multiple of ``align`` >= max(n, 1): the *linear* ladder the
    id-space capacities (cap_i / cap_j) climb.

    Edge-lane capacity keeps the geometric ladder (:func:`bucket_capacity`)
    — few rungs, few compilations — but id capacities size the Gram matmul
    *quadratically*: a 130-vertex side on the power-of-2 ladder pays a
    256-wide matmul, nearly 4x the flops of the 192 the linear ladder
    picks.  The linear ladder has more rungs, but windows from one stream
    cluster tightly in id-space size, so in practice it costs a handful of
    extra compilations for a large cut in padding flops.
    """
    n = max(int(n), 1)
    return -(-n // align) * align


@dataclass(frozen=True)
class Bucket:
    """One static-shape compilation unit: same-capacity windows.

    ``cap_w`` is the wedge capacity — the ladder rung over the bucket's
    max per-window deduped wedge count.  It is only computed (non-zero) for
    the ``sparse`` / ``auto`` tiers, where it sizes the wedge-sort scratch
    and feeds the auto router's cost model.
    """

    cap_e: int                      # edge-lane capacity
    cap_i: int                      # i-side id-space capacity
    cap_j: int                      # j-side id-space capacity
    windows: np.ndarray = field(compare=False)  # window indices in the batch
    cap_w: int = 0                  # wedge capacity (sparse/auto tiers only)

    @property
    def n_windows(self) -> int:
        return len(self.windows)


@dataclass
class ExecutorResult:
    """Per-output-window counts plus the stream bookkeeping the estimators
    consume.  In tumbling mode ``counts[k]`` is the exact in-window count of
    pane k.  In sliding mode it is the prefix-difference of pane counts over
    the span — butterflies whose edges straddle pane boundaries are NOT
    included (they belong to the estimator's inter-window ``|E_k|^alpha``
    term, exactly as in tumbling mode; see the module docstring).
    ``cum_sgrs[k]`` is |E_k|, total sgrs seen when window k closed.
    ``n_shards`` is the device count the bucket batches were sharded over
    (1 = single-device dispatch).  ``stream_ids[k]`` is the tenant stream
    window k belongs to when the batch carried the multi-stream provenance
    lane (``WindowBatch.stream_ids``; None for single-stream batches) —
    counts stay window-indexed, the lane just says whose window each one
    is after cross-stream co-batching."""

    counts: np.ndarray
    cum_sgrs: np.ndarray
    tier: str
    mode: str
    span: int = 1
    n_shards: int = 1
    stream_ids: np.ndarray | None = None

    @property
    def n_windows(self) -> int:
        return len(self.counts)


class PendingCounts:
    """Handle for an in-flight bucketed window count.

    Produced by :meth:`WindowExecutor.window_counts_submit`: ``_parts`` holds
    one ``(window_indices, counts)`` pair per dispatched bucket, where
    ``counts`` is an **un-materialized** device array (or a host array for
    the ``numpy`` tier, which computes eagerly at submit).  Nothing here
    blocks until :meth:`reap`, which materializes every part exactly once,
    scatters the counts back into window order, and caches the result —
    ``reap()`` is idempotent.

    The staging buffers behind a handle's dispatches are reused (ring of
    two) on the *next* submit sharing their bucket shape, so a handle must
    be reaped before two more same-shape submits — the engines enforce the
    stronger invariant of at most one handle in flight at a time.
    """

    def __init__(self, n_windows: int, parts: list):
        self._n = int(n_windows)
        self._parts: list | None = parts
        self._out: np.ndarray | None = None

    @property
    def done(self) -> bool:
        """Whether :meth:`reap` already materialized this handle."""
        return self._out is not None

    def reap(self) -> np.ndarray:
        """Block until every bucket's counts materialize; return the
        window-ordered ``[n_windows] float64`` counts (cached)."""
        if self._out is None:
            out = np.zeros(self._n, dtype=np.float64)
            for idx, dev in self._parts:
                out[idx] = np.asarray(dev, dtype=np.float64)[: len(idx)]
            self._parts = None
            self._out = out
        return self._out


# ---------------------------------------------------------------------------
# per-bucket compiled counters (cached across executors: the cache key is the
# full static configuration, so two executors with the same tier share code)
# ---------------------------------------------------------------------------

def _chunk_counts_fn(tier: str, cap_i: int, cap_j: int, cap_w: int,
                     tile: int, block_i: int, block_k: int, interpret: bool,
                     multiset: bool = False,
                     sampled: tuple | None = None):
    """(edge_i, edge_j, valid) [c, cap_e] -> [c] counts for one CHUNK of
    windows at a static ``(cap_i, cap_j)`` id-space capacity — the batched
    per-chunk body both the single-device and the sharded dispatch map over.
    Sharding the window axis never changes what runs per window, which is
    why the two paths are bit-identical.

    ``dense`` / ``tiled`` / ``sparse`` are the vmap of their per-window
    primitive (batched scatters, matmuls and sorts).  ``pallas`` dispatches
    the window-batched kernel: the chunk's window axis rides in the Pallas
    grid, so a chunk costs one kernel launch.

    ``multiset=True`` swaps in the multiplicity-weighted twins; the chunk
    fn then takes ``(edge_i, edge_j, edge_mult, valid)`` — one extra lane,
    same window axis.

    ``tier="sampled"`` takes ``(edge_i, edge_j, uid, valid)`` where ``uid``
    is a per-window ``[2] uint32`` sampling-uid lane (hi/lo halves of the
    64-bit window uid — split host-side because x64 is off and an int64
    lane would silently truncate entering jit); ``sampled`` carries the
    static ``(capacity, gamma, seed)`` knobs."""
    if tier == "sampled":
        capacity, gamma, seed = sampled

        def one(ei, ej, uid, v):
            return count_butterflies_sampled_from_edges(
                ei, ej, v, uid[0], uid[1], cap_i, cap_j,
                capacity=capacity, gamma=gamma, seed=seed)

        return jax.vmap(one)
    if tier == "pallas":
        from ..kernels.butterfly import (
            butterfly_count_pallas_windows,
            butterfly_count_pallas_windows_multiset,
        )

        if multiset:
            def chunk(ei, ej, mm, v):
                adjs = jax.vmap(
                    lambda a, b, m, c: build_biadjacency_multiset(
                        a, b, m, c, cap_i, cap_j)
                )(ei, ej, mm, v)
                return butterfly_count_pallas_windows_multiset(
                    adjs, block_i=block_i, block_k=block_k,
                    interpret=interpret)
            return chunk

        def chunk(ei, ej, v):
            adjs = jax.vmap(
                lambda a, b, c: build_biadjacency(a, b, c, cap_i, cap_j)
            )(ei, ej, v)
            # butterfly_count_pallas_windows clamps blocks to the capacity
            return butterfly_count_pallas_windows(
                adjs, block_i=block_i, block_k=block_k, interpret=interpret)
        return chunk
    if multiset:
        if tier == "dense":
            def one(ei, ej, mm, v):
                return count_butterflies_from_edges_multiset(
                    ei, ej, mm, v, cap_i, cap_j)
        elif tier == "tiled":
            eff_tile = min(tile, min(cap_i, cap_j))

            def one(ei, ej, mm, v):
                adj = build_biadjacency_multiset(ei, ej, mm, v, cap_i, cap_j)
                return count_butterflies_tiled_multiset(adj, tile=eff_tile)
        elif tier == "sparse":
            def one(ei, ej, mm, v):
                return count_butterflies_sparse_multiset(
                    ei, ej, mm, v, cap_i, cap_j, wedge_cap=max(cap_w, 1))
        else:  # pragma: no cover - guarded by WindowExecutor.__init__
            raise ValueError(f"unknown device tier {tier!r}")
        return jax.vmap(one)
    if tier == "dense":
        def one(ei, ej, v):
            return count_butterflies_from_edges(ei, ej, v, cap_i, cap_j)
    elif tier == "tiled":
        eff_tile = min(tile, min(cap_i, cap_j))

        def one(ei, ej, v):
            adj = build_biadjacency(ei, ej, v, cap_i, cap_j)
            return count_butterflies_tiled(adj, tile=eff_tile)
    elif tier == "sparse":
        def one(ei, ej, v):
            return count_butterflies_sparse(ei, ej, v, cap_i, cap_j,
                                            wedge_cap=max(cap_w, 1))
    else:  # pragma: no cover - guarded by WindowExecutor.__init__
        raise ValueError(f"unknown device tier {tier!r}")
    return jax.vmap(one)


def _donate_argnums(multiset: bool, sampled: tuple | None) -> tuple:
    """Donate every input lane to the compiled counter — off CPU only.

    The dispatch's inputs are flush-scoped staging tensors the host never
    reads back, so on accelerators XLA may reuse their device buffers for
    the outputs instead of allocating fresh ones each flush (the mb=1 /
    flush_every=1 regime dispatches per window, where that allocation is a
    measurable cost).  The CPU backend aliases host numpy memory zero-copy
    and ignores donation (with a warning), so donation is gated off there.
    """
    if jax.default_backend() == "cpu":
        return ()
    n_lanes = 4 if (multiset or sampled is not None) else 3
    return tuple(range(n_lanes))


def _chunked_dispatch(chunk_fn, chunk: int):
    """Chunked-vmap schedule: ``lax.map`` over chunks of ``chunk`` vmap'd
    windows.  Peak memory is one chunk's worth of per-window state (e.g.
    ``chunk * cap_i * cap_j`` for the biadjacency tiers); across chunks the
    dispatch stays in streaming order.  A batch smaller than ``chunk``
    dispatches as a single partial chunk; otherwise the window axis pads to
    a chunk multiple (padding lanes are all-invalid windows that count 0
    and are sliced off) and reshapes to [n_chunks, chunk, ...].  Variadic
    over the per-window lanes — 3 for distinct, 4 with the multiplicity
    lane — because every lane chunks identically along the window axis."""
    def run(*arrays):
        n = arrays[0].shape[0]
        c = max(1, min(chunk, n))
        if n <= c:
            return chunk_fn(*arrays)
        nc = -(-n // c)
        pad = nc * c - n

        def prep(a):
            if pad:
                a = jax.numpy.pad(
                    a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            return a.reshape((nc, c) + a.shape[1:])

        out = jax.lax.map(lambda t: chunk_fn(*t),
                          tuple(prep(a) for a in arrays))
        return out.reshape(nc * c)[:n]
    return run


@functools.lru_cache(maxsize=None)
def _bucket_counter(tier: str, cap_i: int, cap_j: int, cap_w: int, tile: int,
                    block_i: int, block_k: int, interpret: bool, chunk: int,
                    multiset: bool = False, sampled: tuple | None = None):
    """Jitted (edge_i, edge_j, valid) [B, cap_e] -> [B] counts at a static
    ``(cap_i, cap_j)`` id-space capacity via the chunked-vmap schedule
    (:func:`_chunked_dispatch`): windows count ``chunk`` at a time in one
    batched dispatch, chunks run in streaming order, and peak memory stays
    bounded at one chunk of bucket-capacity state.  ``multiset=True`` keys a
    separate compiled program taking the extra multiplicity lane;
    ``sampled=(capacity, gamma, seed)`` keys the subsample-and-scale program
    taking the per-window uid lane instead."""
    chunk_fn = _chunk_counts_fn(tier, cap_i, cap_j, cap_w, tile,
                                block_i, block_k, interpret, multiset,
                                sampled)
    return jax.jit(_chunked_dispatch(chunk_fn, chunk),
                   donate_argnums=_donate_argnums(multiset, sampled))


@functools.lru_cache(maxsize=None)
def _sharded_bucket_counter(tier: str, cap_i: int, cap_j: int, cap_w: int,
                            tile: int, block_i: int, block_k: int,
                            interpret: bool, chunk: int, mesh, axes: tuple,
                            multiset: bool = False,
                            sampled: tuple | None = None):
    """Sharded twin of :func:`_bucket_counter`: the window axis is split over
    the mesh's data-parallel ``axes`` via shard_map, and each device runs the
    identical chunked-vmap schedule over its shard.  Per-device peak memory
    stays one chunk of bucket-capacity state; the batch dimension must be
    padded to a multiple of the shard count (padding lanes are all-invalid
    windows, which every tier counts as 0)."""
    from jax.sharding import PartitionSpec as P

    chunk_fn = _chunk_counts_fn(tier, cap_i, cap_j, cap_w, tile,
                                block_i, block_k, interpret, multiset,
                                sampled)
    local = _chunked_dispatch(chunk_fn, chunk)

    batch = axes if len(axes) > 1 else axes[0]
    n_lanes = 4 if (multiset or sampled is not None) else 3
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(batch, None),) * n_lanes,
                       out_specs=P(batch),
                       # pallas_call has no varying-axes rule to check
                       check_vma=(tier != "pallas"))
    return jax.jit(fn, donate_argnums=_donate_argnums(multiset, sampled))


def compiled_bucket_cache_info() -> dict:
    """Sizes of the process-wide compiled-bucket caches.

    The per-bucket counters are memoized on their full static configuration,
    so every executor — and every flush of the streaming engine — reuses the
    same compiled program for a recurring bucket shape instead of re-tracing.
    ``tests/test_streaming_engine.py`` asserts the size stays flat across
    flushes with recurring shapes.
    """
    return {
        "single_device": _bucket_counter.cache_info().currsize,
        "sharded": _sharded_bucket_counter.cache_info().currsize,
    }


def _resolve_window_mesh(devices, mesh):
    """Normalize the ``devices=`` / ``mesh=`` knobs to
    ``(mesh | None, shard_axes, n_shards)``.

    ``devices`` is an int (first N of ``jax.devices()``) or an explicit
    device sequence; ``mesh`` is a prebuilt ``jax.sharding.Mesh`` whose
    data-parallel axes (``batch_partition_axes``) carry the window dimension.
    A single-device resolution collapses to the unsharded dispatch path.
    """
    if devices is not None and mesh is not None:
        raise ValueError("pass devices= or mesh=, not both")
    if mesh is None:
        if devices is None:
            return None, (), 1
        if isinstance(devices, int) and devices == 1:
            return None, (), 1
        from ..launch.mesh import make_window_mesh

        mesh = make_window_mesh(devices)
    from ..distributed.sharding import batch_partition_axes

    axes = tuple(batch_partition_axes(mesh))
    n_shards = 1
    for a in axes:
        n_shards *= int(mesh.shape[a])
    if n_shards <= 1:
        return None, (), 1
    return mesh, axes, n_shards


def _pad_window_axis(*arrays: np.ndarray, multiple: int):
    """Pad the leading (window) axis to a multiple of the shard count with
    all-invalid windows — every tier counts an all-padding window as 0, so
    the pad lanes are sliced off host-side without touching the real ones.
    Variadic over the per-window lanes (3 distinct, 4 with multiplicity)."""
    pad = (-arrays[0].shape[0]) % multiple
    if pad == 0:
        return arrays

    def z(a):
        return np.concatenate(
            [a, np.zeros((pad,) + a.shape[1:], dtype=a.dtype)])

    return tuple(z(a) for a in arrays)


class WindowExecutor:
    """Counts closed windows through one of the six tiers (see module doc).

    Parameters
    ----------
    tier : "numpy" | "dense" | "tiled" | "pallas" | "sparse" | "auto"
    align, growth : capacity-ladder geometry.  Edge-lane and wedge
        capacities climb the geometric ladder ``align * growth**k``; the
        id-space capacities (cap_i / cap_j) climb the *linear* ladder
        (multiples of ``align``, :func:`id_capacity`) because they size the
        Gram quadratically — power-of-2 rungs there nearly double the
        matmul flops in padding.  Default ``align=64``; on TPU the kernels
        re-pad to their (8, 128) minimum tiles internally.
    chunk : chunked-vmap dispatch width — how many windows of a bucket count
        in one batched dispatch.  Peak memory scales as ``chunk * cap_i *
        cap_j`` for the biadjacency tiers (``chunk * (cap_e + cap_w)`` for
        ``sparse``); ``chunk=1`` recovers the fully sequential per-window
        schedule.  Counts are bit-identical for every chunk size.
    snap : compile each bucket at its windows' actual max id-space sizes
        rounded to a multiple of ``snap`` (and clamped to the rung), so
        Gram padding tracks the data instead of the ladder.  0 disables
        (compile at the rung itself) — the streaming engine does this
        because its flushes see the stream piecewise and must never
        re-trace at steady state, while a batch replay knows every
        window's size up front.
    tile : tile edge for the ``tiled`` tier (clamped to bucket capacity).
    block_i, block_k : Pallas kernel block shape (clamped per bucket).
    interpret : Pallas interpreter mode; default auto (True off-TPU).
    sort_cost : ``auto`` router knob — modelled cost of one sort element in
        dense-Gram flops (see :func:`route_tier`).
    capacity, gamma, seed : ``sampled`` tier knobs — FLEET reservoir
        capacity (max edges counted per window), gamma schedule factor in
        (0, 1), and the threefry seed behind the content-keyed coins.
        Windows that fit ``capacity`` count exactly (bit-identical to
        ``dense``); larger windows subsample-and-scale.
    memory_budget, target_mape : the ``sampled`` tier's budget router
        (:meth:`bucket_tier`): buckets whose edge rung fits
        ``memory_budget`` run exact ``dense`` (sampling buys nothing that
        fits the budget anyway), and buckets whose modelled error
        (:func:`expected_mape`) would exceed ``target_mape`` also fall back
        to ``dense`` (accuracy outranks the budget).  Both default to None
        (= every bucket above ``capacity`` samples).
    devices : int (first N of ``jax.devices()``) or device sequence —
        shard each bucket's window axis over a 1-D data mesh of those
        devices.  Counts stay bit-identical to the single-device path.
    mesh : prebuilt ``jax.sharding.Mesh`` (mutually exclusive with
        ``devices``); windows shard over its data-parallel axes and
        replicate over the rest.  The ``numpy`` tier is a host oracle and
        ignores both knobs.
    """

    def __init__(self, tier: str = "dense", *, align: int = 64,
                 growth: int = 2, chunk: int = 32, snap: int = 16,
                 tile: int = 512, block_i: int = 256, block_k: int = 512,
                 interpret: bool | None = None, sort_cost: float = 96.0,
                 capacity: int = 8192, gamma: float = 0.7, seed: int = 0,
                 memory_budget: int | None = None,
                 target_mape: float | None = None,
                 devices=None, mesh=None):
        if tier not in TIERS:
            raise ValueError(f"tier must be one of {TIERS}, got {tier!r}")
        if align < 1 or growth < 2:
            raise ValueError("align must be >= 1 and growth >= 2")
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if snap < 0:
            raise ValueError("snap must be >= 0 (0 disables cap snapping)")
        # sampling knobs validate unconditionally (cheap, and a bad value
        # should fail at construction, not when someone later flips the
        # tier) but only steer the "sampled" tier
        check_sampling_knobs(capacity, gamma, seed)
        if memory_budget is not None and (
                isinstance(memory_budget, bool)
                or not isinstance(memory_budget, (int, np.integer))
                or int(memory_budget) <= 0):
            raise ValueError(
                f"memory_budget must be a positive int or None, "
                f"got {memory_budget!r}")
        if target_mape is not None and not (float(target_mape) > 0.0):
            raise ValueError(
                f"target_mape must be positive or None, got {target_mape!r}")
        self.capacity = int(capacity)
        self.gamma = float(gamma)
        self.seed = int(seed)
        self.memory_budget = (None if memory_budget is None
                              else int(memory_budget))
        self.target_mape = (None if target_mape is None
                            else float(target_mape))
        # monotone per-executor uid for count_edges' online sampled windows:
        # each online window draws from its own coin stream
        self._online_seq = 0
        self.tier = tier
        self.align = align
        self.growth = growth
        self.chunk = chunk
        self.snap = snap
        self.tile = tile
        self.block_i = block_i
        self.block_k = block_k
        self.sort_cost = float(sort_cost)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = interpret
        if tier == "numpy":
            # host oracle: never dispatches to a device, so the sharding
            # knobs are ignored and n_shards honestly reports 1
            self.mesh, self.shard_axes, self.n_shards = None, (), 1
        else:
            self.mesh, self.shard_axes, self.n_shards = _resolve_window_mesh(
                devices, mesh)
        self._plan_cache: tuple[weakref.ref, list[Bucket]] | None = None
        # memoized online counter: (cap key) -> compiled fn; count_edges is
        # the per-window online entry (adaptive_window_stream consumers)
        # and must not redo the lru-cache hashing + tier routing per call
        self._online_cache: tuple[tuple, object] | None = None
        # persistent per-rung staging buffers: (bucket shape, lane layout) ->
        # [lanes_a, lanes_b, cursor].  A ring of two because the CPU backend
        # may alias host numpy memory zero-copy into the dispatch — the
        # buffer a dispatch reads must not be overwritten until the *next*
        # same-shape submit, and at most one handle is in flight at a time
        # (see PendingCounts), so alternating two buffers is sufficient.
        self._staging: dict[tuple, list] = {}

    # -- planning -----------------------------------------------------------

    def plan(self, batch: WindowBatch) -> list[Bucket]:
        """Group windows into static-capacity buckets (stable window order
        within a bucket).  The last batch's plan is memoized by identity, so
        repeated counts of the same batch skip the host-side grouping."""
        if self._plan_cache is not None and self._plan_cache[0]() is batch:
            return self._plan_cache[1]
        # sparse/auto need a static wedge capacity per bucket: count each
        # window's deduped wedges host-side and ladder the rung into the
        # bucket key, so a hub-heavy window never shares a (too small)
        # wedge scratch with a flat one
        wedges = (window_wedge_counts_np(batch.edge_i, batch.edge_j,
                                         batch.valid)
                  if self.tier in _WEDGE_TIERS else None)
        groups: dict[tuple[int, int, int, int], list[int]] = {}
        for k in range(batch.n_windows):
            # every ladder rung clamps to the batch's own padded capacity:
            # a bucket must never exceed what the global path would have paid
            key = (
                min(bucket_capacity(int(batch.n_edges[k]), align=self.align,
                                    growth=self.growth), batch.capacity),
                min(id_capacity(int(batch.n_i_per_window[k]),
                                align=self.align), max(batch.n_i, 1)),
                min(id_capacity(int(batch.n_j_per_window[k]),
                                align=self.align), max(batch.n_j, 1)),
                (bucket_capacity(int(wedges[k]), align=self.align,
                                 growth=self.growth)
                 if wedges is not None else 0),
            )
            groups.setdefault(key, []).append(k)
        if self.tier == "auto":
            # cap_w never reaches a dense-routed program (the compile cache
            # zeroes it), so dense-routed groups differing only in wedge
            # rung would fragment into needless extra dispatches — fuse
            # them, carrying the max rung so any later re-route to sparse
            # still covers every member window.  Sparse-routed groups stay
            # split: each keeps a tight wedge scratch.
            fused: dict[tuple[int, int, int], int] = {}
            wins: dict[tuple[int, int, int], list[int]] = {}
            kept: dict[tuple[int, int, int, int], list[int]] = {}
            for (cap_e, cap_i, cap_j, cap_w), idx in sorted(groups.items()):
                if route_tier(cap_e, cap_i, cap_j, cap_w,
                              sort_cost=self.sort_cost) == "dense":
                    k3 = (cap_e, cap_i, cap_j)
                    fused[k3] = max(fused.get(k3, 0), cap_w)
                    wins.setdefault(k3, []).extend(idx)
                else:
                    kept[(cap_e, cap_i, cap_j, cap_w)] = idx
            for k3, cap_w in fused.items():
                kept[k3 + (cap_w,)] = sorted(wins[k3])
            groups = kept
        buckets = []
        for (cap_e, cap_i, cap_j, cap_w), idx in sorted(groups.items()):
            win = np.asarray(idx, dtype=np.int64)
            if self.snap:
                # the rung groups the windows; the compiled program runs at
                # the group's *snapped* caps — max actual size rounded to a
                # multiple of ``snap`` — so the Gram pays for the data, not
                # the rung.  A whole batch is planned at once (maxes are
                # known up front), so snapping costs no extra re-traces; the
                # streaming engine disables it (snap=0) because its flushes
                # see the stream piecewise and must never re-trace at
                # steady state.
                cap_e = min(id_capacity(
                    int(batch.n_edges[win].max()), align=self.align), cap_e)
                cap_i = min(id_capacity(
                    int(batch.n_i_per_window[win].max()), align=self.snap),
                    cap_i)
                cap_j = min(id_capacity(
                    int(batch.n_j_per_window[win].max()), align=self.snap),
                    cap_j)
            buckets.append(Bucket(cap_e, cap_i, cap_j, win, cap_w=cap_w))
        self._plan_cache = (weakref.ref(batch), buckets)
        return buckets

    # -- counting -----------------------------------------------------------

    def _sampled_route(self, cap_e: int) -> str:
        """The ``sampled`` tier's per-rung budget router.  Exact ``dense``
        counting wins when the rung fits the memory budget (sampling a
        window that fits anyway only adds variance) or when the modelled
        error at this rung (:func:`expected_mape`) would blow the accuracy
        target; everything else samples.  Static per rung, so single-device
        and sharded dispatch route identically."""
        if self.memory_budget is not None and cap_e <= self.memory_budget:
            return "dense"
        if self.target_mape is not None and expected_mape(
                cap_e, self.capacity, self.gamma) > self.target_mape:
            return "dense"
        return "sampled"

    def bucket_tier(self, b: Bucket) -> str:
        """The device tier a bucket actually runs: the configured tier, the
        cost model's pick (:func:`route_tier`) under ``auto``, or the budget
        router's pick (:meth:`_sampled_route`) under ``sampled``.  Routing
        is host-side and depends only on the bucket's static capacities, so
        single-device and sharded dispatch route identically."""
        if self.tier == "auto":
            return route_tier(b.cap_e, b.cap_i, b.cap_j, b.cap_w,
                              sort_cost=self.sort_cost)
        if self.tier == "sampled":
            return self._sampled_route(b.cap_e)
        return self.tier

    def _counter(self, b: Bucket, *, multiset: bool = False):
        """The compiled counter for one bucket's static configuration —
        sharded over the window mesh when one is configured.  ``multiset``
        keys the multiplicity-weighted program variant."""
        tier = self.bucket_tier(b)
        # cap_w only shapes the sparse scratch: zero it out of the cache key
        # for the biadjacency tiers so auto's dense buckets share programs
        cap_w = b.cap_w if tier == "sparse" else 0
        sampled = ((self.capacity, self.gamma, self.seed)
                   if tier == "sampled" else None)
        if self.n_shards > 1:
            return _sharded_bucket_counter(
                tier, b.cap_i, b.cap_j, cap_w, self.tile, self.block_i,
                self.block_k, self.interpret, self.chunk, self.mesh,
                self.shard_axes, multiset, sampled)
        return _bucket_counter(tier, b.cap_i, b.cap_j, cap_w, self.tile,
                               self.block_i, self.block_k, self.interpret,
                               self.chunk, multiset, sampled)

    @staticmethod
    def _batch_uids(batch: WindowBatch) -> np.ndarray:
        """Per-window sampling uids as ``[n_windows, 2] uint32`` (hi, lo)
        device lanes.  Prefers the batch's own ``sample_uid`` lane (the
        streaming engines stamp ``(res_seed << 32) + cum_sgrs``); a lane-less
        batch (plain replay) derives the same shape from the provenance it
        does have — stream id in the high half (0 single-stream) and the
        cumulative sgr count in the low half — which is exactly what a
        seed-0 engine would have stamped, so streaming == replay holds for
        the sampled tier too.  Split into uint32 halves host-side: x64 is
        off, so an int64 lane would silently truncate entering jit."""
        uid = batch.sample_uid
        if uid is None:
            sid = (batch.stream_ids.astype(np.int64)
                   if batch.stream_ids is not None
                   else np.zeros(batch.n_windows, np.int64))
            uid = (sid << np.int64(32)) + (
                np.asarray(batch.cum_sgrs, np.int64) & np.int64(0xFFFFFFFF))
        uid = np.asarray(uid, np.int64)
        return np.stack([(uid >> np.int64(32)) & np.int64(0xFFFFFFFF),
                         uid & np.int64(0xFFFFFFFF)],
                        axis=1).astype(np.uint32)

    def _staged_lanes(self, batch: WindowBatch, b: Bucket, multiset: bool,
                      uids: np.ndarray | None) -> tuple:
        """Fill (and return) the persistent staging buffers for one bucket's
        device lanes instead of allocating fresh sub-batch tensors per flush
        (``batch.take`` copies; at mb=1 / flush_every=1 that allocation
        churn dominates).  Coverage is guaranteed by :meth:`plan` — every
        rung clamps to the batch's own capacities — so slicing the batch
        lanes to ``cap_e`` is exactly what ``take`` would have produced.

        Buffers are keyed on the full bucket shape plus lane layout, so two
        buckets of one plan never share a buffer, and alternate between two
        copies per key (see ``_staging`` in ``__init__``).
        """
        cap, win = b.cap_e, b.windows
        sampled_lane = uids is not None and self.bucket_tier(b) == "sampled"
        key = (b.cap_e, b.cap_i, b.cap_j, b.cap_w, len(win),
               multiset, sampled_lane)
        ring = self._staging.get(key)
        if ring is None:
            def make():
                lanes = [np.empty((len(win), cap), np.int32),
                         np.empty((len(win), cap), np.int32)]
                if multiset:
                    lanes.append(np.empty((len(win), cap), np.int32))
                if sampled_lane:
                    lanes.append(np.empty((len(win), 2), np.uint32))
                lanes.append(np.empty((len(win), cap), bool))
                return tuple(lanes)
            ring = [make(), make(), 0]
            self._staging[key] = ring
        lanes = ring[ring[2]]
        ring[2] ^= 1
        it = iter(lanes)
        np.take(batch.edge_i[:, :cap], win, axis=0, out=next(it))
        np.take(batch.edge_j[:, :cap], win, axis=0, out=next(it))
        if multiset:
            np.take(batch.edge_mult[:, :cap], win, axis=0, out=next(it))
        if sampled_lane:
            np.take(uids, win, axis=0, out=next(it))
        np.take(batch.valid[:, :cap], win, axis=0, out=next(it))
        return lanes

    def window_counts_submit(self, batch: WindowBatch) -> PendingCounts:
        """Stage + dispatch every bucket of ``batch`` asynchronously and
        return a :class:`PendingCounts` handle — the submit half of the
        flush pipeline.  Nothing blocks on device compute here: each
        bucket's compiled counter is dispatched and its un-materialized
        result collected, so the caller overlaps host work (windowizing the
        next flush) with device compute and materializes later via
        :meth:`PendingCounts.reap`.

        A batch carrying the multiplicity lane (``batch.edge_mult`` is not
        None — ``multiset`` duplicate policy) routes every tier through its
        multiplicity-weighted twin; a lane-less batch runs the distinct
        programs bit-identically to before the lane existed.  The ``numpy``
        tier is a host oracle with nothing to overlap: it counts eagerly at
        submit and returns an already-materializable handle.
        """
        if batch.n_windows == 0:
            return PendingCounts(0, [])
        multiset = batch.edge_mult is not None
        if multiset and self.tier == "sampled":
            raise NotImplementedError(
                "sampled tier does not support dup_policy='multiset': the "
                "subsample-and-scale identity assumes distinct edges (a "
                "multiplicity-weighted butterfly is not a p**4 event); use "
                "an exact tier for multiset streams")
        uids = self._batch_uids(batch) if self.tier == "sampled" else None
        parts: list = []
        if self.tier == "numpy":
            for b in self.plan(batch):
                counts = np.empty(b.n_windows, dtype=np.float64)
                for t, k in enumerate(b.windows):
                    v = batch.valid[k]
                    e = np.stack([batch.edge_i[k][v], batch.edge_j[k][v]],
                                 axis=1)
                    counts[t] = (count_butterflies_multiset_np(
                        e, batch.edge_mult[k][v]) if multiset
                        else count_butterflies_np(e))
                parts.append((b.windows, counts))
            return PendingCounts(batch.n_windows, parts)
        for b in self.plan(batch):
            lanes = self._staged_lanes(batch, b, multiset, uids)
            if self.n_shards > 1:
                lanes = _pad_window_axis(*lanes, multiple=self.n_shards)
            counts = self._counter(b, multiset=multiset)(*lanes)  # async
            parts.append((b.windows, counts))
        return PendingCounts(batch.n_windows, parts)

    def window_counts(self, batch: WindowBatch) -> np.ndarray:
        """Exact in-window count per tumbling window, [n_windows] float64.

        ``window_counts_submit(batch).reap()``: every bucket dispatches
        asynchronously before the first materialization blocks, so device
        compute across buckets overlaps the host-side staging — strictly
        more overlap than the old per-bucket double buffer.  Callers that
        have host work of their own to overlap (the streaming engines) hold
        the handle instead of calling this.
        """
        return self.window_counts_submit(batch).reap()

    def warmup(self, rungs, *, multiset: bool = False) -> int:
        """Pre-trace the bucket-counter ladder before the first push.

        ``rungs`` is an iterable of ``(cap_e, cap_i, cap_j)`` capacity
        triples (``EngineConfig.warmup``).  For each rung a single
        all-invalid window is dispatched through the same compiled counter
        a real flush of that shape would hit — including the B=1 partial
        chunk trace that mb=1 / flush_every=1 flushes use — so first-window
        latency becomes dispatch-only instead of trace+compile.  Blocks
        until every rung's program finished compiling; returns the number
        of rungs dispatched.  The ``numpy`` tier has nothing to compile
        (returns 0).  Wedge-capacity buckets (``sparse``, and ``auto``'s
        sparse-routed groups) key additionally on ``cap_w`` and are not
        covered by 3-tuple rungs.
        """
        if self.tier == "numpy":
            return 0
        if multiset and self.tier == "sampled":
            raise NotImplementedError(
                "sampled tier does not support dup_policy='multiset'")
        done = 0
        for rung in rungs:
            cap_e, cap_i, cap_j = (int(x) for x in rung)
            b = Bucket(cap_e, cap_i, cap_j, np.arange(1, dtype=np.int64))
            lanes = [np.zeros((1, cap_e), np.int32),
                     np.zeros((1, cap_e), np.int32)]
            if multiset:
                lanes.append(np.zeros((1, cap_e), np.int32))
            elif self.tier == "sampled" and self.bucket_tier(b) == "sampled":
                lanes.append(np.zeros((1, 2), np.uint32))
            lanes.append(np.zeros((1, cap_e), bool))
            if self.n_shards > 1:
                lanes = _pad_window_axis(*lanes, multiple=self.n_shards)
            np.asarray(self._counter(b, multiset=multiset)(*lanes))
            done += 1
        return done

    def staged_buckets(self) -> list[tuple[Bucket, bool, bool]]:
        """Every bucket shape this executor has dispatched, as ``(bucket,
        multiset, sampled)``: ``bucket.windows`` is ``arange(n)`` for its
        ``n`` real windows, before any padding to the shard count."""
        return [(Bucket(cap_e, cap_i, cap_j, np.arange(n), cap_w=cap_w),
                 multiset, sampled)
                for cap_e, cap_i, cap_j, cap_w, n, multiset, sampled
                in sorted(self._staging)]

    def program_text(self, b: Bucket, *, multiset: bool = False,
                     sampled: bool = False) -> str:
        """The compiled HLO text of the program that counts bucket ``b``,
        lowered at the lanes a dispatch of it passes (the window axis
        padded to the shard count, as :meth:`window_counts_submit` pads)."""
        n = -(-b.n_windows // self.n_shards) * self.n_shards
        lane = jax.ShapeDtypeStruct((n, b.cap_e), np.int32)
        lanes = [lane] * (3 if multiset else 2)
        if sampled:
            lanes.append(jax.ShapeDtypeStruct((n, 2), np.uint32))
        lanes.append(jax.ShapeDtypeStruct((n, b.cap_e), np.bool_))
        counter = self._counter(b, multiset=multiset)
        return counter.lower(*lanes).compile().as_text()

    def decrement_window_counts(self, per_window_edges, per_window_deletes,
                                prior_counts, *, delta_frac: float = 0.25
                                ) -> np.ndarray:
        """Decremental update for already-counted windows (sliding mode's
        late-deletion path): given each window's current distinct edge set,
        the edges retracted from it, and its prior exact count, return the
        updated exact counts.

        Per window :func:`route_decrement` picks the route — ``"delta"``
        subtracts :func:`butterfly_delta_np`'s destroyed-butterfly walk from
        the prior count on the host; ``"recount"`` drops the deleted edges
        and recounts every recount-routed window's survivors in ONE bucketed
        device dispatch through :meth:`window_counts`.  Both routes raise on
        a deletion that targets an edge absent from its window (including
        the same edge twice in one request) — the executor-level mirror of
        the engines' ``on_missing_delete="raise"`` default.  Distinct-mode
        semantics: windows are deduped edge sets, multiplicities retract
        through the engines' open-window resolution instead.
        """
        from .butterfly import _check_id_range_np
        from .windows import pack_windows

        if self.tier == "sampled":
            raise NotImplementedError(
                "sampled tier cannot decrement prior counts: a subsampled "
                "estimate has no per-edge ledger to patch and recounting "
                "survivors would redraw the coins; use an exact tier for "
                "streams with deletions")
        prior = np.asarray(prior_counts, dtype=np.float64)
        n = len(per_window_edges)
        if len(per_window_deletes) != n or prior.shape[0] != n:
            raise ValueError(
                "per_window_edges, per_window_deletes and prior_counts must "
                f"align: got {n}, {len(per_window_deletes)}, "
                f"{prior.shape[0]}")
        out = prior.copy()
        recount_edges: list[np.ndarray] = []
        recount_idx: list[int] = []
        for k in range(n):
            e = np.asarray(per_window_edges[k], dtype=np.int64).reshape(-1, 2)
            d = np.asarray(per_window_deletes[k],
                           dtype=np.int64).reshape(-1, 2)
            if d.shape[0] == 0:
                continue
            if route_decrement(e.shape[0], d.shape[0],
                               delta_frac=delta_frac) == "delta":
                out[k] = prior[k] - butterfly_delta_np(e, d)
                continue
            _check_id_range_np(e)
            _check_id_range_np(d)
            ke = e[:, 0] << 32 | e[:, 1]
            kd = d[:, 0] << 32 | d[:, 1]
            if (np.unique(kd).shape[0] != kd.shape[0]
                    or not np.isin(kd, ke).all()):
                raise ValueError(
                    f"window {k}: cannot delete an edge absent from the "
                    "window (never inserted, or already deleted)")
            recount_edges.append(e[~np.isin(ke, kd)])
            recount_idx.append(k)
        if recount_idx:
            m = len(recount_idx)
            nb = pack_windows(
                recount_edges, n_sgrs=np.zeros(m, np.int64),
                cum_sgrs=np.zeros(m, np.int64),
                window_end_tau=np.zeros(m, np.float64),
                align=self.align, dedupe=True)
            out[np.asarray(recount_idx)] = self.window_counts(nb)
        return out

    def count_edges(self, edge_i, edge_j) -> float:
        """Count one online window from raw (possibly duplicated) edge ids —
        the true-streaming entry (`adaptive_window_stream` consumers; the
        engine's flushes go through :func:`pack_windows` +
        :meth:`window_counts` instead).  Relabels to a compact id space,
        picks the bucket, dispatches.  The resolved counter is memoized on
        the window's capacity key, so a steady-state stream of same-rung
        windows skips tier routing and counter lookup entirely.  Always
        single-device: window sharding is data parallelism over the batch
        axis, and an online window is a batch of one."""
        ei = np.asarray(edge_i, dtype=np.int64)
        ej = np.asarray(edge_j, dtype=np.int64)
        if ei.size == 0:
            return 0.0
        # relabel BEFORE the tier branch: every tier (the host oracle
        # included) must accept the same raw-id domain, so arbitrary int64
        # ids never hit the oracle's packed-key range guard
        ui, inv_i = np.unique(ei, return_inverse=True)
        uj, inv_j = np.unique(ej, return_inverse=True)
        if self.tier == "numpy":
            return float(count_butterflies_np(np.stack([inv_i, inv_j],
                                                       axis=1)))
        cap_e = bucket_capacity(len(ei), align=self.align, growth=self.growth)
        cap_i = id_capacity(len(ui), align=self.align)
        cap_j = id_capacity(len(uj), align=self.align)
        cap_w = 0
        if self.tier in _WEDGE_TIERS:
            d = np.bincount(
                np.unique(inv_i * (len(uj) + 1) + inv_j) % (len(uj) + 1))
            cap_w = bucket_capacity(int((d * (d - 1) // 2).sum()),
                                    align=self.align, growth=self.growth)
        key = (cap_e, cap_i, cap_j, cap_w)
        if self._online_cache is not None and self._online_cache[0] == key:
            fn = self._online_cache[1]
        else:
            tier = self.tier
            if tier == "auto":
                tier = route_tier(cap_e, cap_i, cap_j, cap_w,
                                  sort_cost=self.sort_cost)
            elif tier == "sampled":
                tier = self._sampled_route(cap_e)
            sampled = ((self.capacity, self.gamma, self.seed)
                       if tier == "sampled" else None)
            counter = _bucket_counter(tier, cap_i, cap_j,
                                      cap_w if tier == "sparse" else 0,
                                      self.tile, self.block_i, self.block_k,
                                      self.interpret, self.chunk, False,
                                      sampled)
            # uniform (pi, pj, pv, uid) call shape so the memoized entry
            # stays lane-agnostic: the wrapper knows whether the compiled
            # program wants the online window's sampling-uid lane
            if sampled is not None:
                def fn(pi, pj, pv, uid, _c=counter):
                    return _c(pi, pj, uid, pv)
            else:
                def fn(pi, pj, pv, uid, _c=counter):
                    return _c(pi, pj, pv)
            self._online_cache = (key, fn)
        uid_row = None
        if self.tier == "sampled":
            # every online window is its own sampling draw: a monotone
            # per-executor sequence number plays the role the engines'
            # (res_seed << 32) + cum_sgrs uid plays for flushed windows
            uid = np.int64(self._online_seq)
            self._online_seq += 1
            uid_row = np.array(
                [[(uid >> np.int64(32)) & np.int64(0xFFFFFFFF),
                  uid & np.int64(0xFFFFFFFF)]], dtype=np.uint32)
        pi = np.zeros((1, cap_e), np.int32)
        pj = np.zeros((1, cap_e), np.int32)
        pv = np.zeros((1, cap_e), bool)
        pi[0, : len(ei)] = inv_i
        pj[0, : len(ej)] = inv_j
        pv[0, : len(ei)] = True
        return float(np.asarray(fn(pi, pj, pv, uid_row))[0])

    # -- the single entry point ---------------------------------------------

    def run(self, batch: WindowBatch, *, mode: str = "tumbling",
            span: int = 1) -> ExecutorResult:
        """Count every window of ``batch`` through the configured tier.

        ``mode="tumbling"`` returns the paper's disjoint pane counts.
        ``mode="sliding"`` returns overlapping-window counts spanning
        ``span`` panes via prefix-difference (module doc).
        """
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        if mode == "sliding":
            if span < 1:
                raise ValueError("sliding span must be >= 1")
            if batch.stream_ids is not None and len(
                    np.unique(batch.stream_ids)) > 1:
                # prefix-differencing across panes of *different* tenants
                # would mix their counts — sliding windows are a per-stream
                # concept; reject before paying the bucketed dispatch
                raise ValueError(
                    "sliding mode over a multi-stream batch is ambiguous; "
                    "slide each tenant's panes separately")
        counts = self.window_counts(batch)
        cum = np.asarray(batch.cum_sgrs, dtype=np.float64)
        if mode == "tumbling":
            return ExecutorResult(counts, cum, self.tier, mode,
                                  n_shards=self.n_shards,
                                  stream_ids=batch.stream_ids)
        prefix = np.concatenate([[0.0], np.cumsum(counts)])
        lo = np.maximum(np.arange(len(counts)) - span + 1, 0)
        sliding = prefix[1:] - prefix[lo]
        return ExecutorResult(sliding, cum, self.tier, mode, span,
                              n_shards=self.n_shards,
                              stream_ids=batch.stream_ids)


def run(batch: WindowBatch, *, tier: str = "dense", mode: str = "tumbling",
        span: int = 1, **kwargs) -> ExecutorResult:
    """One-shot convenience: ``WindowExecutor(tier, **kwargs).run(batch)``."""
    return WindowExecutor(tier, **kwargs).run(batch, mode=mode, span=span)
