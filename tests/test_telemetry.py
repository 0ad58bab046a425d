"""Phase timers and ``sgrapp.*`` spans (repro.core.telemetry), the engine's
dispatch and wedge-lane counters, and the server's quiet request log."""
from __future__ import annotations

import json
import logging

import numpy as np
import pytest

from repro.core import telemetry
from repro.core.butterfly import window_wedge_counts_np
from repro.core.telemetry import LATENCY_BOUNDS_MS, Telemetry, Timer
from repro.streams.config import EngineConfig
from repro.streams.generators import bipartite_pa_stream
from repro.streams.multi import MultiStreamSGrapp
from repro.streams.server import StreamServer


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span's
    name and args, and whether it was entered and left."""

    calls: list = []

    def __init__(self, name, **args):
        self.calls.append([name, args, "made"])
        self.row = self.calls[-1]

    def __enter__(self):
        self.row[2] = "open"

    def __exit__(self, *exc):
        self.row[2] = "closed"


@pytest.fixture
def spans(monkeypatch):
    Recorder.calls = []
    monkeypatch.setattr(telemetry, "TraceAnnotation", Recorder)
    return Recorder.calls


def test_timer_reads_quantiles_off_the_histogram():
    t = Timer()
    assert t.snapshot()["p50"] == 0.0 and t.snapshot()["count"] == 0
    for ms in [0.2] * 60 + [3.0] * 39 + [1500.0]:
        t.observe(ms)
    snap = t.snapshot()
    assert snap["count"] == 100
    assert snap["mean"] == pytest.approx((0.2 * 60 + 3.0 * 39 + 1500) / 100)
    assert snap["max"] == 1500.0
    assert snap["p50"] == 0.5         # the 50th of 100 lies in (0, 0.5]
    assert snap["p99"] == 5.0         # the 99th in (2, 5]
    assert t.quantile(1.0) == 1500.0  # past the last bound: the max
    assert snap["buckets"]["<=0.5ms"] == 60
    assert snap["buckets"]["<=5.0ms"] == 39
    assert snap["buckets"][f">{LATENCY_BOUNDS_MS[-1]}ms"] == 1
    assert len(snap["buckets"]) == len(LATENCY_BOUNDS_MS) + 1
    one = Timer()
    one.observe(0.2)
    assert one.quantile(0.5) == 0.2   # never above the largest seen


def test_phase_opens_a_named_span_with_its_args(spans):
    tel = Telemetry()
    with tel.phase("launch", timer="submit", tier="sparse", cap_e=8192,
                   cap_w=1 << 20):
        assert spans[-1][2] == "open"
    with tel.phase("pack", timer="submit"):
        pass
    with tel.phase("wal_sync"):
        pass
    assert spans == [
        ["sgrapp.launch", {"tier": "sparse", "cap_e": 8192,
                           "cap_w": 1 << 20}, "closed"],
        ["sgrapp.pack", {}, "closed"],
        ["sgrapp.wal_sync", {}, "closed"]]
    timers = tel.timers()
    assert sorted(timers) == ["submit", "wal_sync"]
    assert timers["submit"]["count"] == 2
    assert timers["wal_sync"]["count"] == 1


def test_phase_times_a_step_that_raises(spans):
    tel = Telemetry()
    with pytest.raises(ValueError):
        with tel.phase("decode"):
            raise ValueError("bad records")
    assert tel.timers()["decode"]["count"] == 1
    assert spans == [["sgrapp.decode", {}, "closed"]]


def test_counters():
    tel = Telemetry()
    assert tel.count("dispatch_count") == 0
    tel.add("dispatch_count")
    tel.add("windows_dispatched", 3)
    tel.add("windows_dispatched", np.int64(2))
    assert tel.count("dispatch_count") == 1
    assert tel.count("windows_dispatched") == 5


@pytest.mark.parametrize("tier", ["sparse", "dense"])
def test_engine_counts_dispatches_and_wedge_lanes(tier, spans):
    """A standalone engine keeps a private table: dispatches and windows
    from its own flushes, and for sparse-routed buckets the wedge lanes
    against the windows' true wedge counts."""
    st = bipartite_pa_stream(1600, temporal="uniform", n_unique=400, seed=3)
    eng = MultiStreamSGrapp(2, 40, 0.95,
                            config=EngineConfig(tier=tier, flush_every=1))
    assert eng.telemetry is eng.executor.telemetry
    plans = []
    plan = eng.executor.plan

    def recorded(batch):
        buckets = plan(batch)
        if not plans or plans[-1] is not buckets:
            plans.append(buckets)
        return buckets

    eng.executor.plan = recorded
    half = len(st.tau) // 2
    eng.push(0, st.tau[:half], st.edge_i[:half], st.edge_j[:half])
    eng.push(1, st.tau[half:], st.edge_i[half:], st.edge_j[half:])
    eng.flush()
    tel = eng.telemetry
    n_windows = sum(eng.n_counted(s) for s in range(2))
    assert n_windows > 0
    assert tel.count("windows_dispatched") == n_windows
    assert tel.count("dispatch_count") == 2       # one per closing push
    timers = tel.timers()
    assert timers["count_wait"]["count"] == 2
    assert timers["estimator"]["count"] == 2
    assert timers["windowize"]["count"] == 2
    launches = [a for n, a, _ in spans if n == "sgrapp.launch"]
    assert launches and all(a["tier"] == tier for a in launches)
    assert timers["submit"]["count"] == 2 + len(launches)
    if tier == "dense":
        assert tel.count("wedge_lanes") == tel.count("wedges") == 0
        return
    # the true wedge count of every window, from the windows themselves
    wedges = 0
    for lo, hi in [(0, half), (half, len(st.tau))]:
        ref = MultiStreamSGrapp(1, 40, 0.95,
                                config=EngineConfig(tier="numpy"))
        ref.push(0, st.tau[lo:hi], st.edge_i[lo:hi], st.edge_j[lo:hi])
        batch = ref._pack_pending()[1]
        wedges += int(window_wedge_counts_np(batch.edge_i, batch.edge_j,
                                             batch.valid).sum())
    assert tel.count("wedges") == wedges > 0
    buckets = [b for p in plans for b in p]
    assert len(buckets) == len(launches)
    assert tel.count("wedge_lanes") == sum(b.cap_w * b.n_windows
                                           for b in buckets) > wedges


def test_server_hands_its_table_to_engine_and_executor():
    server = StreamServer(nt_w=40, alpha0=0.95, tenants={"t0": 0},
                          config=EngineConfig(tier="numpy"))
    tel = server.metrics.telemetry
    assert server.engine.telemetry is tel
    assert server.engine.executor.telemetry is tel


def test_request_log_is_skipped_when_off_and_unchanged_when_on(caplog):
    server = StreamServer(nt_w=40, alpha0=0.95, tenants={"t0": 0},
                          config=EngineConfig(tier="numpy"))
    log = logging.getLogger("repro.streams.server")
    with caplog.at_level(logging.WARNING, logger=log.name):
        # nothing is serialized while the logger is off: an object JSON
        # cannot encode passes silently
        server._log("push", stream_id=0, detail=object())
    assert not caplog.records
    with caplog.at_level(logging.INFO, logger=log.name):
        server._log("push", stream_id=0, n_edges=5, latency_ms=1.25)
    (rec,) = caplog.records
    assert rec.getMessage() == json.dumps(
        {"event": "push", "stream_id": 0, "n_edges": 5, "latency_ms": 1.25},
        sort_keys=True)
