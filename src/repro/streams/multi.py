"""Multi-tenant stream serving: N independent sgr streams through ONE engine.

:class:`MultiStreamSGrapp` serves N concurrent tenants — each an independent
bipartite edge stream with its own clock, window quota progress, estimator
carry and (optionally) supervised ground-truth prefix — through one shared
pipeline::

    push(stream_id, tau, i, j)          tagged micro-batches, any interleaving
          │
          v
    vectorized windowizer               one pass computes every record's
    (streams.state.windowizer_push)     unique-timestamp rank for ALL streams
          │                             at once; windows close per stream
          v
    per-stream pending closed windows   (fleet-wide flush_every batching)
          │
          v
    pack_windows(stream_ids=...)  ──>  ONE persistent WindowExecutor
    (stream-id provenance lane)         windows from different tenants
          │                             co-batch into the same compiled
          v                             bucket counters: same bucket ladder,
    counts scatter back per tenant      same tier router, same sharded
    via the provenance lane             dispatch
          │
          v
    estimator_step per (tenant, window) — the same jitted scalar body as
    the single-stream engine and the replay scans

**Why one engine beats N engines.**  The executor's cost is per *dispatch*,
not per window: bucketing, padding, and the chunked-vmap schedule amortize
over the windows of a flush.  N separate :class:`~repro.streams.engine.
StreamingSGrapp` instances each flush their own handful of windows; the
fleet engine flushes all tenants' pending windows in one bucketed dispatch,
so same-capacity windows from different streams share a chunk of the same
compiled program (``BENCH_multistream.json`` pins the aggregate-throughput
win).  Compiled bucket counters were already process-wide; co-batching makes
the *dispatches* shared too.

**Bit-identity contract.**  Per tenant, the fleet is exactly a dedicated
single-stream engine: same windowizer (one shared function), same packer,
same counting tiers (counts are capacity-independent integers, so
co-batching never changes a count), same float32 scalar estimator steps in
per-stream close order.  ``tests/test_multistream.py`` pins ``N=1 fleet ==
StreamingSGrapp`` and ``each tenant of an N>=4 fleet == its dedicated
engine`` bit-for-bit across every tier and the sharded dispatch path.

**Checkpointing.**  :meth:`state_dict` reuses the single-stream schema with
a stream axis: per-stream scalars become ``[N]`` lanes, the ragged
open-window buffers and per-window histories concatenate with ``[N+1]``
offset lanes.  :meth:`restore` is strict (missing/unknown keys or a version
mismatch raise), and a restored fleet resumes every tenant bit-identically.
"""
from __future__ import annotations

import numpy as np

from repro.core.executor import WindowExecutor
from repro.core.sgrapp import SGrappResult, estimator_step
from repro.core.windows import pack_windows
from repro.streams.config import (
    _UNSET,
    EngineConfig,
    resolve_engine_config,
    resolve_sync_dispatch,
)
from repro.streams.engine import (
    STATE_DICT_VERSION,
    advance_estimator,
    check_state_dict_keys,
    config_from_bytes,
    config_to_bytes,
    migrate_state_dict_to_latest,
    resolve_pending_window,
)
from repro.streams.state import (
    StreamState,
    estimator_carry,
    set_estimator_carry,
    stream_state_init,
    windowizer_close_tail,
    windowizer_push,
)

__all__ = ["MultiStreamSGrapp"]

# v1 = insert-only fleet schema; v2 adds the flat "buf_op" lane (aligned
# element-for-element with "buf_i" via the same "buf_offsets"), migrated
# forward from v1 on restore exactly like the single-stream engine; v3 adds
# the per-stream "res_seed" lane (sampled-tier reservoir identity); v4 adds
# the fleet identity — "config" (EngineConfig JSON as uint8 bytes) and
# "alpha0" (the constructor's per-stream initial exponents, [N] float64) —
# so from_state_dict can rebuild the fleet from the checkpoint alone.
_MULTI_STATE_DICT_KEYS_V1 = frozenset({
    "version", "n_streams", "nt_w", "buf_i", "buf_j", "buf_offsets",
    "buf_last_tau", "buf_len", "uniq", "last_tau", "total_sgrs", "finalized",
    "counts", "estimates", "cum_sgrs", "end_tau", "hist_offsets",
    "carry_cum", "carry_alpha", "carry_err", "carry_sup",
})
_MULTI_STATE_DICT_KEYS_V2 = _MULTI_STATE_DICT_KEYS_V1 | {"buf_op"}
_MULTI_STATE_DICT_KEYS_V3 = _MULTI_STATE_DICT_KEYS_V2 | {"res_seed"}
_MULTI_STATE_DICT_KEYS = _MULTI_STATE_DICT_KEYS_V3 | {"config", "alpha0"}
_MULTI_STATE_DICT_SCHEMAS = {1: _MULTI_STATE_DICT_KEYS_V1,
                             2: _MULTI_STATE_DICT_KEYS_V2,
                             3: _MULTI_STATE_DICT_KEYS_V3,
                             4: _MULTI_STATE_DICT_KEYS}


def _ragged_concat(parts: list[np.ndarray], dtype) -> tuple[np.ndarray, np.ndarray]:
    """Concatenate per-stream ragged arrays into (flat, offsets[N+1])."""
    offsets = np.zeros(len(parts) + 1, dtype=np.int64)
    offsets[1:] = np.cumsum([len(p) for p in parts])
    flat = (np.concatenate([np.asarray(p, dtype=dtype) for p in parts])
            if offsets[-1] else np.zeros(0, dtype=dtype))
    return flat, offsets


class MultiStreamSGrapp:
    """Online sGrapp / sGrapp-x over N concurrent tenant streams.

    Parameters
    ----------
    n_streams : number of tenants.  Stream ids are ``0 .. n_streams-1``.
    nt_w : window quota, shared by every tenant (Algorithm 3 semantics,
        as :class:`~repro.streams.engine.StreamingSGrapp`).
    alpha0 : initial inter-window exponent — a scalar (shared) or a
        length-``n_streams`` sequence (per-tenant).
    truths : ``None`` (plain sGrapp for every tenant) or a length-
        ``n_streams`` sequence whose entry s is that tenant's cumulative
        ground-truth prefix (or ``None`` for an unsupervised tenant) —
        exactly the single-stream engine's ``truths`` per tenant.
    config : an :class:`~repro.streams.config.EngineConfig` carrying every
        shared knob below — the preferred API, exactly as the single-stream
        engine: per-knob kwargs remain a deprecated shim (DeprecationWarning)
        and mixing them with ``config=`` raises.
    tol, step : Algorithm 5 band and adaptation step (shared).
    tier / executor / devices / mesh : the shared counting backend, as
        :class:`~repro.streams.engine.StreamingSGrapp` — ONE executor
        serves every tenant, and its compiled bucket counters co-batch
        windows across tenants.
    flush_every : fleet-wide pending-window budget: a flush triggers when
        the tenants' pending closed windows *in total* reach this many
        (flush timing never changes any estimate, only batching).
    drop_partial, align : as the single-stream engine, shared.
    dup_policy, on_missing_delete : duplicate-edge / missing-delete
        semantics, shared by every tenant — exactly the single-stream
        engine's knobs (the N=1 bit-identity contract covers them).
    seed : base reservoir seed for the ``sampled`` tier.  Tenant ``s``
        gets reservoir identity ``seed + s`` (so distinct tenants draw
        independent coin streams, and an ``N=1`` fleet at seed ``k``
        matches a single-stream engine at seed ``k`` bit-for-bit).
        Ignored by exact tiers.
    """

    def __init__(self, n_streams: int, nt_w: int, alpha0, *, truths=None,
                 config: EngineConfig | None = None,
                 executor: WindowExecutor | None = None,
                 tol=_UNSET, step=_UNSET, tier=_UNSET,
                 devices=_UNSET, mesh=_UNSET, flush_every=_UNSET,
                 drop_partial=_UNSET, align=_UNSET, dup_policy=_UNSET,
                 on_missing_delete=_UNSET, seed=_UNSET):
        if n_streams < 1:
            raise ValueError("n_streams must be >= 1")
        if nt_w <= 0:
            raise ValueError("nt_w must be positive")
        # knob validation lives on EngineConfig, shared verbatim with the
        # single-stream engine; per-knob kwargs are the deprecated shim
        cfg = resolve_engine_config(config, dict(
            tol=tol, step=step, tier=tier, devices=devices, mesh=mesh,
            flush_every=flush_every, drop_partial=drop_partial, align=align,
            dup_policy=dup_policy, on_missing_delete=on_missing_delete,
            seed=seed))
        self.config = cfg
        if truths is not None and len(truths) != n_streams:
            raise ValueError(
                f"truths must have one entry per stream ({n_streams}), "
                f"got {len(truths)}")
        self.nt_w = int(nt_w)
        # coerce like the single-stream engine (scalar -> float) — and a
        # per-stream sequence -> list of floats, length-checked; a numpy
        # float32 or a [N] array no longer leaks through unnormalized
        if np.ndim(alpha0) == 0:
            self.alpha0: float | list[float] = float(alpha0)
        else:
            alphas = [float(a) for a in np.asarray(alpha0).ravel()]
            if len(alphas) != n_streams:
                raise ValueError(
                    f"alpha0 must be a scalar or one entry per stream "
                    f"({n_streams}), got {len(alphas)}")
            self.alpha0 = alphas
        self.truths = (None if truths is None else
                       [None if t is None else np.asarray(t, dtype=np.float64)
                        for t in truths])
        self.tol = cfg.tol
        self.step = cfg.step
        self.flush_every = cfg.flush_every
        self.drop_partial = cfg.drop_partial
        self.align = cfg.align
        self.dup_policy = cfg.dup_policy
        self.on_missing_delete = cfg.on_missing_delete
        self.seed = cfg.seed
        # snap=0 inside make_executor, for the same reason as the single-
        # stream engine: flushes see the streams piecewise, bucket programs
        # must compile at ladder rungs and never re-trace at steady state
        self.executor = cfg.make_executor(executor)
        # phase timers and dispatch counters, shared with the executor; a
        # server replaces both references with its own table
        self.telemetry = self.executor.telemetry
        self._step_fn = estimator_step(cfg.tol, cfg.step)
        # async overlapped flush pipeline, exactly as the single-stream
        # engine: push() submits without blocking, the next flush point
        # reaps; sync_dispatch forces the old blocking path.  Estimators
        # only ever advance at reap, so both paths are bit-identical.
        self.sync_dispatch = resolve_sync_dispatch(cfg)
        # owner-driven dispatch (see StreamingSGrapp): push() skips the
        # flush_every self-submit so the owner schedules submit/reap itself
        self.defer_dispatch = False
        if cfg.warmup:
            self.executor.warmup(
                cfg.warmup, multiset=(cfg.dup_policy == "multiset"))

        n = int(n_streams)
        self._state: StreamState = stream_state_init(n, self.alpha0,
                                                     seed=cfg.seed)
        # per-stream closed-but-uncounted windows, in close order; the set
        # tracks which streams have any, so flush work scales with pending
        # tenants, never with fleet size
        self._pending: list[list[tuple[np.ndarray, np.ndarray,
                                       np.ndarray | None, int, float]]] \
            = [[] for _ in range(n)]
        self._pending_streams: set[int] = set()
        self._n_pending_total = 0
        # the one in-flight submitted flush (None or a (streams,
        # n_per_stream, handle, cum, end_tau) tuple); at most one dispatch
        # is ever in flight — _submit_flush asserts it
        self._inflight: tuple | None = None
        # per-stream per-window history (materialized at flush)
        self._counts: list[list[float]] = [[] for _ in range(n)]
        self._estimates: list[list[np.float32]] = [[] for _ in range(n)]
        self._cum_sgrs: list[list[int]] = [[] for _ in range(n)]
        self._end_tau: list[list[float]] = [[] for _ in range(n)]

    # -- introspection -------------------------------------------------------

    @property
    def n_streams(self) -> int:
        return self._state.n_streams

    @property
    def tier(self) -> str:
        return self.executor.tier

    @property
    def n_pending(self) -> int:
        """Closed-but-uncounted windows across the whole fleet: awaiting
        dispatch + in flight."""
        return self._n_pending_total + self.n_inflight

    @property
    def n_inflight(self) -> int:
        """Windows inside the submitted-but-unreaped async dispatch (0 when
        nothing is in flight; always 0 under ``sync_dispatch``)."""
        if self._inflight is None:
            return 0
        return sum(self._inflight[1])

    def _inflight_for(self, s: int) -> int:
        if self._inflight is None:
            return 0
        streams, n_per_stream = self._inflight[0], self._inflight[1]
        return n_per_stream[streams.index(s)] if s in streams else 0

    def n_windows(self, stream_id: int | None = None) -> int:
        """Windows closed so far (counted, in flight, or pending) — for one
        tenant, or fleet-wide with ``stream_id=None``."""
        if stream_id is not None:
            s = self._check_stream(stream_id)
            return (len(self._counts[s]) + len(self._pending[s])
                    + self._inflight_for(s))
        return sum(len(c) for c in self._counts) + self.n_pending

    def alpha(self, stream_id: int) -> float:
        """Tenant's current (possibly adapted) alpha — lags its pending
        windows until the next flush."""
        return float(self._state.carry_alpha[self._check_stream(stream_id)])

    def cum_sgrs(self, stream_id: int) -> int:
        """Tenant's |E|: total sgrs in its counted windows."""
        return int(self._state.total_sgrs[self._check_stream(stream_id)])

    def n_counted(self, stream_id: int) -> int:
        """Windows already counted (flushed) for one tenant — the length of
        its materialized history, excluding pending closed windows."""
        return len(self._counts[self._check_stream(stream_id)])

    def history(self, stream_id: int, start: int = 0) -> dict:
        """One tenant's counted-window history from window index ``start``
        (no flush — pending windows stay pending), as plain-Python parallel
        lists: ``window`` (indices), ``count``, ``estimate``, ``cum_sgrs``,
        ``end_tau``.  The serving front end streams estimate updates to
        subscribers by diffing ``n_counted`` and reading the new slice
        through this accessor, so the private history lists never leak."""
        s = self._check_stream(stream_id)
        if start < 0:
            raise ValueError(f"start must be >= 0, got {start}")
        return {
            "window": list(range(start, len(self._counts[s]))),
            "count": [float(c) for c in self._counts[s][start:]],
            "estimate": [float(e) for e in self._estimates[s][start:]],
            "cum_sgrs": [int(c) for c in self._cum_sgrs[s][start:]],
            "end_tau": [float(t) for t in self._end_tau[s][start:]],
        }

    def _check_stream(self, stream_id) -> int:
        s = int(stream_id)
        if not 0 <= s < self.n_streams:
            raise ValueError(
                f"stream_id {s} out of range [0, {self.n_streams})")
        return s

    # -- ingestion -----------------------------------------------------------

    def push(self, stream_id, tau, edge_i, edge_j, op=None) -> int:
        """Ingest a tagged micro-batch: ``stream_id`` is a scalar (the whole
        batch belongs to one tenant) or a per-record array (interleaved
        tenants in one batch — records group stably per stream, so
        interleaved and per-stream-sorted arrival are equivalent).  Returns
        the number of windows closed fleet-wide by this call.  Timestamps
        must be non-decreasing *per stream* (tenant clocks are independent);
        a violating batch raises before any state changes.

        ``op`` is the dynamic wire format's per-record op lane (0 = insert,
        1 = delete; ``None`` = all inserts) — deletes resolve against the
        record's own stream's open window, per the fleet's
        ``on_missing_delete`` knob."""
        if op is not None and self.tier == "sampled":
            from repro.streams.state import OP_DELETE
            if np.any(np.atleast_1d(np.asarray(op)) == OP_DELETE):
                raise NotImplementedError(
                    "the sampled tier does not support edge deletions: "
                    "reservoir estimates are insert-only (FLEET)")
        with self.telemetry.phase("windowize"):
            closed = windowizer_push(self._state, stream_id, tau, edge_i,
                                     edge_j, self.nt_w, op=op,
                                     on_missing_delete=self.on_missing_delete)
        for s, ei, ej, ops, m, end_tau in closed:
            self._pending[s].append((ei, ej, ops, m, end_tau))
            self._pending_streams.add(s)
        self._n_pending_total += len(closed)
        if (self._n_pending_total >= self.flush_every
                and not self.defer_dispatch):
            if self.sync_dispatch:
                self.flush()
            else:
                # overlapped pipeline: settle the previous flush (its device
                # compute ran while this micro-batch windowized on the
                # host), then dispatch this one and return WITHOUT blocking
                self._reap_flush()
                self._submit_flush()
        return len(closed)

    # -- counting + estimation ----------------------------------------------

    def _submit_flush(self) -> bool:
        """Submit half of the fleet flush: resolve + pack every tenant's
        pending closed windows into ONE batch (stream-id provenance lane
        included) and dispatch ONE bucketed count asynchronously, parking
        the handle in ``_inflight``.  Returns True iff a dispatch is now in
        flight.  Estimators are NOT advanced here — that happens at reap,
        so flush timing can never change any tenant's estimates."""
        if self._n_pending_total == 0:
            return False
        assert self._inflight is None, "reap the in-flight flush first"
        with self.telemetry.phase("pack", timer="submit"):
            streams, batch, cum, end_tau = self._pack_pending()
            # planned inside the span; the submit reuses the memoized plan
            self.executor.plan(batch)
        handle = self.executor.window_counts_submit(batch)
        # windows stay pending until dispatched: a packing error (one
        # tenant's bad edge ids) raises above with every pending list
        # intact, so the whole fleet stays consistent and the next flush
        # retries instead of silently dropping closed windows
        n_per_stream = [len(self._pending[s]) for s in streams]
        for s in streams:
            self._pending[s] = []
        self._pending_streams.clear()
        self._n_pending_total = 0
        self.telemetry.add("dispatch_count")
        self.telemetry.add("windows_dispatched", len(cum))
        self._inflight = (streams, n_per_stream, handle, cum, end_tau)
        return True

    def _pack_pending(self) -> tuple:
        """Resolve and pack every tenant's pending closed windows into ONE
        batch with the stream-id provenance lane; returns ``(streams,
        batch, cum, end_tau)``."""
        streams = sorted(self._pending_streams)
        per_edges: list[np.ndarray] = []
        per_mult: list[np.ndarray | None] = []
        n_sgrs: list[int] = []
        end_tau: list[float] = []
        cum: list[int] = []
        sids: list[int] = []
        for s in streams:
            c = int(self._state.total_sgrs[s])
            for ei, ej, ops, m, t in self._pending[s]:
                e, mu = resolve_pending_window(ei, ej, ops, self.dup_policy)
                per_edges.append(e)
                per_mult.append(mu)
                n_sgrs.append(m)
                end_tau.append(t)
                c += m
                cum.append(c)
                sids.append(s)
        # per-window reservoir identity: the owning tenant's res_seed in the
        # high 32 bits, its cumulative sgr count in the low 32 — the same
        # uint64-wraparound packing as the single-stream engine, so each
        # tenant's uid sequence matches its dedicated engine bit-for-bit
        rs = self._state.res_seed[np.asarray(sids, dtype=np.int64)]
        hi = (rs & np.int64(0xFFFFFFFF)).astype(np.uint64)
        lo = (np.asarray(cum, dtype=np.int64) & np.int64(0xFFFFFFFF)) \
            .astype(np.uint64)
        uid = ((hi << np.uint64(32)) + lo).astype(np.int64)
        if self.dup_policy == "multiset":
            batch = pack_windows(
                per_edges, n_sgrs=np.asarray(n_sgrs, dtype=np.int64),
                cum_sgrs=np.asarray(cum, dtype=np.int64),
                window_end_tau=np.asarray(end_tau, dtype=np.float64),
                align=self.align, stream_ids=np.asarray(sids, dtype=np.int32),
                dedupe=False, per_window_mult=per_mult,
                sample_uid=uid)
        else:
            batch = pack_windows(
                per_edges, n_sgrs=np.asarray(n_sgrs, dtype=np.int64),
                cum_sgrs=np.asarray(cum, dtype=np.int64),
                window_end_tau=np.asarray(end_tau, dtype=np.float64),
                align=self.align, stream_ids=np.asarray(sids, dtype=np.int32),
                sample_uid=uid)
        return streams, batch, cum, end_tau

    def _reap_flush(self) -> int:
        """Reap half of the fleet flush: block on the in-flight dispatch's
        counts, scatter them back per tenant, and advance each tenant's
        estimator over its windows in close order.  Returns the number of
        windows settled (0 when nothing is in flight).  The ONLY place any
        tenant's estimator advances."""
        if self._inflight is None:
            return 0
        streams, n_per_stream, handle, cum, end_tau = self._inflight
        with self.telemetry.phase("count_wait"):
            counts = handle.reap()   # float64 [m]
        try:
            with self.telemetry.phase("estimate", timer="estimator"):
                self._advance_estimators(streams, n_per_stream, counts, cum,
                                         end_tau)
        finally:
            # in flight until the history holds its windows: a reader on
            # another thread (the server's /metrics) that sees nothing in
            # flight then sees every counted window
            self._inflight = None
        return len(counts)

    def _advance_estimators(self, streams, n_per_stream, counts, cum,
                            end_tau) -> None:
        # scatter counts back per tenant: windows were appended stream by
        # stream in ascending id, so each tenant's windows are a contiguous
        # slice, in close order (the batch's stream_ids lane records the
        # same provenance for external consumers) — and advance each
        # tenant's estimator with the SAME jitted scalar step as the
        # single-stream engine, via the shared advance_estimator helper:
        # bit-identical per-tenant arithmetic by construction
        off = 0
        for s, n_new in zip(streams, n_per_stream):
            sl = slice(off, off + n_new)
            tr = self.truths[s] if self.truths is not None else None
            carry = advance_estimator(
                self._step_fn, estimator_carry(self._state, s), tr,
                counts[sl], cum[sl], end_tau[sl], self._counts[s],
                self._estimates[s], self._cum_sgrs[s], self._end_tau[s])
            set_estimator_carry(self._state, s, carry)
            self._state.total_sgrs[s] = int(cum[off + n_new - 1])
            off += n_new

    def flush(self) -> int:
        """Count every closed-but-uncounted window fleet-wide — the
        in-flight async dispatch AND every tenant's pending list — through
        the shared executor (ONE ``pack_windows`` + ONE bucketed dispatch
        for the whole fleet) and advance each tenant's estimator in close
        order.  Returns the number of windows settled.  Idempotent when
        nothing is outstanding.  This is the blocking entry; the async
        pipeline's halves live in :meth:`_submit_flush` /
        :meth:`_reap_flush`."""
        n = self._reap_flush()
        if self._submit_flush():
            n += self._reap_flush()
        return n

    def _close_tail(self, s: int) -> None:
        if self._state.finalized[s]:
            return
        tail = windowizer_close_tail(self._state, s, self.nt_w,
                                     drop_partial=self.drop_partial)
        if tail is not None:
            _, ei, ej, ops, m, end_tau = tail
            self._pending[s].append((ei, ej, ops, m, end_tau))
            self._pending_streams.add(s)
            self._n_pending_total += 1

    def finalize(self) -> list[SGrappResult]:
        """End every stream: close trailing windows (kept iff the quota
        filled, else per ``drop_partial``), flush the fleet, and return one
        :class:`SGrappResult` per tenant.  Further ``push`` calls raise."""
        for s in range(self.n_streams):
            self._close_tail(s)
        return self.results()

    def finalize_stream(self, stream_id: int) -> SGrappResult:
        """End ONE tenant's stream (its trailing window closes per
        ``drop_partial`` and further pushes to it raise) without touching
        the other tenants — the serving front end's per-tenant end-of-
        stream.  Bit-identical to a dedicated engine's ``finalize()``."""
        s = self._check_stream(stream_id)
        self._close_tail(s)
        return self.result(s)

    def result(self, stream_id: int) -> SGrappResult:
        """One tenant's estimate so far (flushes the fleet first).  Field-
        compatible with the replay drivers' :class:`SGrappResult`."""
        s = self._check_stream(stream_id)
        self.flush()
        return SGrappResult(
            estimates=np.array(self._estimates[s], dtype=np.float32),
            window_counts=np.array(self._counts[s], dtype=np.float64),
            cum_edges=np.array(self._cum_sgrs[s], dtype=np.float64),
            alpha_final=float(self._state.carry_alpha[s]),
            truths=self.truths[s] if self.truths is not None else None,
        )

    def results(self) -> list[SGrappResult]:
        """Every tenant's result, indexed by stream id."""
        self.flush()
        return [self.result(s) for s in range(self.n_streams)]

    # -- checkpointing -------------------------------------------------------

    def state_dict(self) -> dict:
        """Whole-fleet state as a flat dict of numpy leaves — the single-
        stream schema with a stream axis: per-stream scalars are ``[N]``
        lanes, ragged buffers/histories concatenate with ``[N+1]`` offset
        lanes.  Pending windows are flushed first (semantically invisible).
        """
        self.flush()
        st = self._state
        n = self.n_streams
        bufs_i = [st.buf_i[s, :int(st.buf_len[s])] for s in range(n)]
        bufs_j = [st.buf_j[s, :int(st.buf_len[s])] for s in range(n)]
        bufs_op = [st.buf_op[s, :int(st.buf_len[s])] for s in range(n)]
        buf_i, buf_off = _ragged_concat(bufs_i, np.int64)
        buf_j, _ = _ragged_concat(bufs_j, np.int64)
        buf_op, _ = _ragged_concat(bufs_op, np.int8)
        counts, hist_off = _ragged_concat(self._counts, np.float64)
        estimates, _ = _ragged_concat(self._estimates, np.float32)
        cum_sgrs, _ = _ragged_concat(self._cum_sgrs, np.int64)
        end_tau, _ = _ragged_concat(self._end_tau, np.float64)
        return {
            "version": np.int64(STATE_DICT_VERSION),
            "n_streams": np.int64(n),
            "nt_w": np.int64(self.nt_w),
            "buf_i": buf_i,
            "buf_j": buf_j,
            "buf_op": buf_op,
            "buf_offsets": buf_off,
            "buf_last_tau": st.buf_last_tau.copy(),
            "buf_len": st.buf_len.copy(),
            "uniq": st.uniq.copy(),
            "last_tau": st.last_tau.copy(),
            "total_sgrs": st.total_sgrs.copy(),
            "finalized": st.finalized.copy(),
            "counts": counts,
            "estimates": estimates,
            "cum_sgrs": cum_sgrs,
            "end_tau": end_tau,
            "hist_offsets": hist_off,
            "carry_cum": st.carry_cum.copy(),
            "carry_alpha": st.carry_alpha.copy(),
            "carry_err": st.carry_err.copy(),
            "carry_sup": st.carry_sup.copy(),
            "res_seed": st.res_seed.copy(),
            # v4: fleet identity (see the single-stream engine's schema doc)
            "config": config_to_bytes(self.config),
            "alpha0": np.broadcast_to(
                np.asarray(self.alpha0, dtype=np.float64), (n,)).copy(),
        }

    def restore(self, state: dict) -> "MultiStreamSGrapp":
        """Load a :meth:`state_dict` (fleet config comes from the
        constructor; the dict carries only stream state).  Strict: missing
        or unknown keys, a version mismatch, or an ``nt_w``/``n_streams``
        mismatch raise ``ValueError``.  A restored fleet resumes every
        tenant bit-identically."""
        version = check_state_dict_keys(state, _MULTI_STATE_DICT_SCHEMAS,
                                        schema="MultiStreamSGrapp")
        state = migrate_state_dict_to_latest(state, version)
        if int(state["nt_w"]) != self.nt_w:
            raise ValueError(
                f"checkpoint nt_w={int(state['nt_w'])} != engine "
                f"nt_w={self.nt_w}")
        if int(state["n_streams"]) != self.n_streams:
            raise ValueError(
                f"checkpoint n_streams={int(state['n_streams'])} != engine "
                f"n_streams={self.n_streams}")
        n = self.n_streams
        buf_off = np.asarray(state["buf_offsets"], dtype=np.int64)
        buf_i = np.asarray(state["buf_i"], dtype=np.int64)
        buf_j = np.asarray(state["buf_j"], dtype=np.int64)
        buf_op = np.asarray(state["buf_op"], dtype=np.int8)
        buf_len = np.asarray(state["buf_len"], dtype=np.int64)
        cap = max(256, int(buf_len.max()) if n else 256)
        st = stream_state_init(n, self.alpha0, buf_capacity=cap,
                               seed=self.seed)
        for s in range(n):
            a, b = int(buf_off[s]), int(buf_off[s + 1])
            st.buf_i[s, :b - a] = buf_i[a:b]
            st.buf_j[s, :b - a] = buf_j[a:b]
            st.buf_op[s, :b - a] = buf_op[a:b]
        st.buf_len[:] = buf_len
        st.buf_last_tau[:] = np.asarray(state["buf_last_tau"], np.float64)
        st.uniq[:] = np.asarray(state["uniq"], np.int64)
        st.last_tau[:] = np.asarray(state["last_tau"], np.float64)
        st.total_sgrs[:] = np.asarray(state["total_sgrs"], np.int64)
        st.finalized[:] = np.asarray(state["finalized"], bool)
        st.carry_cum[:] = np.asarray(state["carry_cum"], np.float32)
        st.carry_alpha[:] = np.asarray(state["carry_alpha"], np.float32)
        st.carry_err[:] = np.asarray(state["carry_err"], np.float32)
        st.carry_sup[:] = np.asarray(state["carry_sup"], bool)
        # the checkpoint's reservoir seeds win over the constructor's: each
        # tenant's uid sequence must continue the saving fleet's coin stream
        st.res_seed[:] = np.asarray(state["res_seed"], np.int64)
        self._state = st
        hist_off = np.asarray(state["hist_offsets"], dtype=np.int64)
        counts = np.asarray(state["counts"], np.float64)
        estimates = np.asarray(state["estimates"], np.float32)
        cum_sgrs = np.asarray(state["cum_sgrs"], np.int64)
        end_tau = np.asarray(state["end_tau"], np.float64)
        for s in range(n):
            a, b = int(hist_off[s]), int(hist_off[s + 1])
            self._counts[s] = [float(c) for c in counts[a:b]]
            self._estimates[s] = [np.float32(e) for e in estimates[a:b]]
            self._cum_sgrs[s] = [int(c) for c in cum_sgrs[a:b]]
            self._end_tau[s] = [float(t) for t in end_tau[a:b]]
        self._pending = [[] for _ in range(n)]
        self._pending_streams = set()
        self._n_pending_total = 0
        self._inflight = None
        return self

    @classmethod
    def from_state_dict(cls, state: dict, *, truths=None,
                        config: EngineConfig | None = None,
                        executor: WindowExecutor | None = None
                        ) -> "MultiStreamSGrapp":
        """Rebuild a fleet from a self-describing (v4) :meth:`state_dict`
        alone: ``n_streams``, ``nt_w``, per-stream ``alpha0`` and the
        embedded :class:`EngineConfig` all come from the dict.  ``config=``
        overrides the embedded one (devices/mesh never serialize, so
        re-sharding happens here); a pre-v4 checkpoint raises ``ValueError``
        — construct explicitly and :meth:`restore` instead."""
        version = check_state_dict_keys(state, _MULTI_STATE_DICT_SCHEMAS,
                                        schema="MultiStreamSGrapp")
        state = migrate_state_dict_to_latest(state, version)
        if config is None:
            payload = config_from_bytes(state["config"])
            if not payload:
                raise ValueError(
                    "checkpoint carries no EngineConfig (pre-v4 schema "
                    "migrated forward): construct the fleet explicitly "
                    "and call restore(), or pass config=")
            config = EngineConfig.from_json(payload)
        alpha0 = [float(a) for a in np.asarray(state["alpha0"],
                                               dtype=np.float64)]
        fleet = cls(int(state["n_streams"]), int(state["nt_w"]), alpha0,
                    truths=truths, config=config, executor=executor)
        return fleet.restore(state)
