"""End-to-end serving tests: the NDJSON server over real sockets.

The load-bearing contract is bit-identity: N tenants pushing concurrently
through one server (one fleet engine, co-batched flushes, arbitrary
interleavings, a kill/restart mid-stream) must produce per-tenant estimates
identical to N dedicated offline engines fed the same streams.  Everything
else — admission, backpressure, metrics, drain — is the operational shell
around that invariant.

Tests run the server in-process on ephemeral ports with ``tier="numpy"``
(no jit warmup, deterministic, fast) and drive it with plain asyncio
streams — the same protocol surface a real client uses.
"""
from __future__ import annotations

import asyncio
import json
import threading
import time

import numpy as np
import pytest

from repro.streams.config import EngineConfig
from repro.streams.engine import StreamingSGrapp
from repro.streams.generators import bipartite_pa_stream
from repro.streams.server import StreamServer, TenantPolicy
from repro.streams.wire import normalize_records, records_to_json

NT_W = 40
ALPHA0 = 0.95
CFG = EngineConfig(tier="numpy")


# ---------------------------------------------------------------------------
# protocol helpers
# ---------------------------------------------------------------------------

class Client:
    """Minimal NDJSON protocol client (one tenant, one connection)."""

    def __init__(self, reader, writer):
        self.reader = reader
        self.writer = writer
        self.estimates: list[dict] = []   # subscribe feed, in arrival order

    @classmethod
    async def connect(cls, server: StreamServer, token: str) -> "Client":
        r, w = await asyncio.open_connection(server.host, server.port)
        c = cls(r, w)
        reply = await c.call({"type": "hello", "token": token})
        assert reply["type"] == "hello_ok", reply
        c.stream_id = reply["stream_id"]
        return c

    async def send(self, msg: dict) -> None:
        self.writer.write((json.dumps(msg) + "\n").encode())
        await self.writer.drain()

    async def recv(self) -> dict:
        """Next non-estimate reply; estimate feed messages are collected
        on the side (they interleave with call replies by design)."""
        while True:
            line = await self.reader.readline()
            assert line, "server closed the connection"
            msg = json.loads(line)
            if msg.get("type") == "estimate":
                self.estimates.append(msg)
                continue
            return msg

    async def call(self, msg: dict) -> dict:
        await self.send(msg)
        return await self.recv()

    async def push(self, stream, sl: slice) -> dict:
        rb = normalize_records(stream.tau[sl], stream.edge_i[sl],
                               stream.edge_j[sl])
        return await self.call({"type": "push",
                                "records": records_to_json(rb)})

    def close(self) -> None:
        self.writer.close()


async def http_get(server: StreamServer, path: str) -> tuple[int, dict]:
    r, w = await asyncio.open_connection(server.host, server.http_port)
    w.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    data = await r.read()
    w.close()
    head, body = data.split(b"\r\n\r\n", 1)
    status = int(head.split()[1])
    return status, json.loads(body)


def tenant_streams(n: int, n_edges: int = 1200):
    return [bipartite_pa_stream(n_edges, temporal="uniform",
                                n_unique=n_edges // 4, seed=100 + s)
            for s in range(n)]


def offline_result(stream):
    """The dedicated-engine reference a served tenant must match exactly."""
    eng = StreamingSGrapp(NT_W, ALPHA0, config=CFG)
    eng.push(stream.tau, stream.edge_i, stream.edge_j)
    return eng.finalize()


def assert_matches_offline(msg: dict, stream) -> None:
    ref = offline_result(stream)
    np.testing.assert_array_equal(
        np.asarray(msg["estimates"], dtype=np.float32), ref.estimates)
    np.testing.assert_array_equal(
        np.asarray(msg["counts"], dtype=np.float64), ref.window_counts)
    np.testing.assert_array_equal(
        np.asarray(msg["cum_sgrs"], dtype=np.float64), ref.cum_edges)


# ---------------------------------------------------------------------------
# the tentpole contract: N concurrent tenants == N dedicated engines
# ---------------------------------------------------------------------------

def test_three_tenants_concurrent_bit_identical(tmp_path):
    streams = tenant_streams(3)

    async def scenario():
        server = StreamServer(
            nt_w=NT_W, alpha0=ALPHA0,
            tenants={f"t{s}": s for s in range(3)}, config=CFG,
            flush_ms=1.0)
        await server.start()
        clients = [await Client.connect(server, f"t{s}") for s in range(3)]
        for c, s in zip(clients, range(3)):
            assert c.stream_id == s
            reply = await c.call({"type": "subscribe"})
            assert reply == {"type": "subscribed", "next_window": 0}

        async def drive(c, stream, batch):
            for k in range(0, len(stream.tau), batch):
                reply = await c.push(stream, slice(k, k + batch))
                assert reply["type"] == "ack", reply
                assert reply["accepted"] == len(stream.tau[k:k + batch])

        # deliberately different batch sizes: interleavings + coalesced
        # micro-batches differ per tenant, estimates must not
        await asyncio.gather(*[drive(c, st, b) for c, st, b in
                               zip(clients, streams, (37, 128, 251))])
        finals = [await c.call({"type": "finalize"}) for c in clients]
        for msg, stream in zip(finals, streams):
            assert msg["type"] == "finalized"
            assert_matches_offline(msg, stream)
        # the subscribe feed saw every counted window, in order, with the
        # same numbers the final result reports
        await asyncio.sleep(0.05)
        for c, msg in zip(clients, finals):
            windows = [e["window"] for e in c.estimates]
            assert windows == list(range(len(windows)))
            feed = np.asarray([e["estimate"] for e in c.estimates],
                              dtype=np.float32)
            np.testing.assert_array_equal(
                feed, np.asarray(msg["estimates"],
                                 dtype=np.float32)[:len(feed)])
        for c in clients:
            c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_kill_restart_mid_stream_bit_identical(tmp_path):
    """Graceful stop -> checkpoint -> fresh server recovers -> tenants keep
    pushing: final estimates identical to uninterrupted offline engines."""
    streams = tenant_streams(3)
    ckpt = str(tmp_path / "ckpt")
    kw = dict(nt_w=NT_W, alpha0=ALPHA0,
              tenants={f"t{s}": s for s in range(3)}, config=CFG,
              flush_ms=1.0, checkpoint_dir=ckpt)

    async def first_half():
        server = await StreamServer(**kw).start()
        assert server._recovered is False
        clients = [await Client.connect(server, f"t{s}") for s in range(3)]
        for c, st in zip(clients, streams):
            half = len(st.tau) // 2
            for k in range(0, half, 100):
                reply = await c.push(st, slice(k, min(k + 100, half)))
                assert reply["type"] == "ack"
        for c in clients:
            c.close()
        await server.stop()   # drain + flush + checkpoint (not finalize)

    async def second_half():
        server = await StreamServer(**kw).start()
        assert server._recovered is True
        clients = [await Client.connect(server, f"t{s}") for s in range(3)]
        # recovered mid-stream state is already partially counted
        assert any(server.engine.n_counted(s) > 0 for s in range(3))
        for c, st in zip(clients, streams):
            half = len(st.tau) // 2
            for k in range(half, len(st.tau), 100):
                reply = await c.push(st, slice(k, k + 100))
                assert reply["type"] == "ack"
        finals = [await c.call({"type": "finalize"}) for c in clients]
        for msg, st in zip(finals, streams):
            assert_matches_offline(msg, st)
        for c in clients:
            c.close()
        await server.stop(checkpoint=False)

    asyncio.run(first_half())
    asyncio.run(second_half())


def test_result_mid_stream_matches_engine_history():
    stream = tenant_streams(1)[0]

    async def scenario():
        server = await StreamServer(nt_w=NT_W, alpha0=ALPHA0,
                                    tenants={"t0": 0}, config=CFG).start()
        c = await Client.connect(server, "t0")
        await c.push(stream, slice(0, 600))
        mid = await c.call({"type": "result"})
        assert mid["type"] == "result"
        # mid-stream result == dedicated engine's counted history (no tail)
        eng = StreamingSGrapp(NT_W, ALPHA0, config=CFG)
        eng.push(stream.tau[:600], stream.edge_i[:600], stream.edge_j[:600])
        eng.flush()
        ref = eng.result()
        np.testing.assert_array_equal(
            np.asarray(mid["estimates"], dtype=np.float32), ref.estimates)
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# admission: auth, oversized, quota, backpressure, draining, bad records
# ---------------------------------------------------------------------------

def test_auth_and_hello_required():
    async def scenario():
        server = await StreamServer(nt_w=NT_W, alpha0=ALPHA0,
                                    tenants={"good": 0}, config=CFG).start()
        # push before hello
        r, w = await asyncio.open_connection(server.host, server.port)
        c = Client(r, w)
        reply = await c.call({"type": "push", "records": {}})
        assert reply == {"type": "error", "reason": "hello_required"}
        # bad token: error + connection drop
        reply = await c.call({"type": "hello", "token": "evil"})
        assert reply == {"type": "error", "reason": "auth"}
        assert await r.read() == b""   # server hung up
        assert server.metrics.auth_rejected == 1
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_oversized_quota_and_bad_records():
    stream = tenant_streams(1, n_edges=400)[0]

    async def scenario():
        server = await StreamServer(
            nt_w=NT_W, alpha0=ALPHA0,
            tenants={"t0": TenantPolicy(stream_id=0, max_batch_records=100,
                                        max_records_per_s=50.0, burst=120)},
            config=CFG).start()
        c = await Client.connect(server, "t0")
        assert (await Client.connect(server, "t0")).stream_id == 0

        reply = await c.push(stream, slice(0, 200))
        assert reply["type"] == "reject" and reply["reason"] == "oversized"

        reply = await c.call({"type": "push",
                              "records": {"tau": [1.0], "i": [2]}})
        assert reply["type"] == "reject" and reply["reason"] == "bad_records"
        reply = await c.call({"type": "push", "records": None})
        assert reply["reason"] == "bad_records"

        # burst=120 admits one 100-record push, rejects the immediate next
        reply = await c.push(stream, slice(0, 100))
        assert reply["type"] == "ack", reply
        reply = await c.push(stream, slice(100, 200))
        assert reply["type"] == "reject" and reply["reason"] == "quota"

        t = server.metrics.tenants[0]
        assert t.rejects == {"oversized": 1, "bad_records": 2, "quota": 1}
        assert t.edges_accepted == 100
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_backpressure_reject_when_queue_full():
    """A connection has at most one in-flight push (it awaits its ack), so
    queue overflow takes concurrent connections — with the engine thread
    stalled, the 2-slot queue fills and the surplus pushes get explicit
    ``backpressure`` rejects instead of buffering unbounded."""
    stream = tenant_streams(1)[0]

    async def scenario():
        server = StreamServer(nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0},
                              config=CFG, queue_limit=2, flush_ms=0.0)
        await server.start()
        # stall the engine thread so the ingress queue can't drain
        import threading
        release = threading.Event()
        server._pool.submit(release.wait)
        clients = [await Client.connect(server, "t0") for _ in range(10)]
        for k, c in enumerate(clients):
            sl = slice(k * 50, (k + 1) * 50)
            await c.send({"type": "push", "records": records_to_json(
                normalize_records(stream.tau[sl], stream.edge_i[sl],
                                  stream.edge_j[sl]))})
        await asyncio.sleep(0.1)   # handlers admit/reject; engine stalled
        release.set()
        replies = [await c.recv() for c in clients]
        acks = [r for r in replies if r["type"] == "ack"]
        rejected = [r for r in replies if r["type"] == "reject"]
        assert acks and rejected, replies
        assert all(r["reason"] == "backpressure" for r in rejected)
        assert (server.metrics.tenants[0].rejects["backpressure"]
                == len(rejected))
        for c in clients:
            c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_draining_rejects_new_pushes():
    stream = tenant_streams(1)[0]

    async def scenario():
        server = await StreamServer(nt_w=NT_W, alpha0=ALPHA0,
                                    tenants={"t0": 0}, config=CFG).start()
        c = await Client.connect(server, "t0")
        assert (await c.push(stream, slice(0, 100)))["type"] == "ack"
        server._draining = True   # what stop() sets before the drain
        reply = await c.push(stream, slice(100, 200))
        assert reply == {"type": "reject", "reason": "draining"}
        server._draining = False
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# observability + construction validation
# ---------------------------------------------------------------------------

def test_healthz_metrics_and_404():
    stream = tenant_streams(1)[0]

    async def scenario():
        server = await StreamServer(nt_w=NT_W, alpha0=ALPHA0,
                                    tenants={"t0": 0, "t1": 1},
                                    config=CFG).start()
        status, health = await http_get(server, "/healthz")
        assert status == 200
        assert health["status"] == "ok" and health["n_streams"] == 2
        c = await Client.connect(server, "t0")
        assert (await c.push(stream, slice(0, 500)))["type"] == "ack"
        status, m = await http_get(server, "/metrics")
        assert status == 200
        agg = m["aggregate"]
        assert agg["edges_accepted"] == 500
        assert agg["batches_accepted"] == 1
        assert agg["windows_closed"] > 0
        assert agg["push_latency_ms"]["count"] >= 1
        assert agg["push_latency_ms"]["p99"] >= agg["push_latency_ms"]["p50"]
        assert m["tenants"]["0"]["edges_accepted"] == 500
        assert m["tenants"]["1"]["edges_accepted"] == 0
        assert m["queue_depth"] == 0 and m["queue_limit"] == 64
        assert m["windows_counted"][0] > 0
        status, _ = await http_get(server, "/nope")
        assert status == 404
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_metrics_settles_a_reap_in_progress():
    """``/metrics`` reports every window of a dispatch whose follow-up reap
    is still advancing the estimators when the scrape arrives."""
    stream = tenant_streams(1)[0]

    async def scenario():
        server = await StreamServer(nt_w=NT_W, alpha0=ALPHA0,
                                    tenants={"t0": 0}, config=CFG).start()
        step = server.engine._step_fn

        def slow_step(carry, xs):
            time.sleep(0.05)
            return step(carry, xs)

        server.engine._step_fn = slow_step
        c = await Client.connect(server, "t0")
        reply = await c.push(stream, slice(0, 500))
        assert reply["type"] == "ack" and reply["windows_closed"] > 0
        _, m = await http_get(server, "/metrics")
        assert m["windows_counted"] == [reply["windows_closed"]]
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


PHASES = {"decode", "queue_wait", "wal_append", "windowize", "wal_sync",
          "submit", "count_wait", "estimator", "collect", "fanout"}


def test_phase_timers_in_metrics(tmp_path):
    """Every phase timer is published with counts that agree with the
    cycles, dispatches and reaps the run made.  Two tenants' pushes share
    one coalesced cycle, so the second push's ``engine.push`` reaps the
    first one's dispatch itself: ``count_wait`` sees that reap,
    ``reap_wait_ms`` (the server's own reaps) does not."""
    streams = tenant_streams(2)

    async def scenario():
        # flush_every 1, as deployed: every push that closes a window
        # dispatches it from inside engine.push
        server = await StreamServer(
            nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0, "t1": 1},
            config=EngineConfig(tier="numpy", flush_every=1), flush_ms=500.0,
            checkpoint_dir=str(tmp_path / "ckpt")).start()
        clients = [await Client.connect(server, t) for t in ("t0", "t1")]
        for k in range(2):
            sl = slice(500 * k, 500 * (k + 1))
            replies = await asyncio.gather(
                *[c.push(st, sl) for c, st in zip(clients, streams)])
            assert all(r["type"] == "ack" and r["windows_closed"] > 0
                       for r in replies), replies
        _, m = await http_get(server, "/metrics")
        agg = m["aggregate"]
        phases = agg["phases_ms"]
        assert set(phases) == PHASES
        for t in phases.values():
            assert t["count"] > 0 and t["max"] >= t["mean"] >= 0.0
            assert t["p99"] >= t["p50"]
            assert sum(t["buckets"].values()) == t["count"]
        assert agg["pushes"] == 2 and agg["coalesced_items"] == 4
        assert phases["wal_sync"]["count"] == agg["pushes"]
        assert phases["decode"]["count"] == 4
        assert phases["queue_wait"]["count"] == 4
        assert phases["wal_append"]["count"] == 4
        assert phases["windowize"]["count"] == 4
        # at budget 0 each push that closes windows dispatches them, and
        # /metrics settles the last dispatch before counting
        assert agg["dispatch_count"] == 4
        assert agg["windows_dispatched"] == sum(m["windows_counted"])
        assert agg["coalesced_windows_per_dispatch"] == \
            agg["windows_dispatched"] / agg["dispatch_count"]
        assert phases["count_wait"]["count"] == agg["dispatch_count"]
        assert phases["estimator"]["count"] == agg["dispatch_count"]
        # a pack span and one launch span per bucket, per dispatch
        assert phases["submit"]["count"] > agg["dispatch_count"]
        assert agg["reap_wait_ms"]["count"] < phases["count_wait"]["count"]
        assert agg["executor"] == {"wedge_lanes": 0, "wedges": 0}
        for c in clients:
            c.close()
        await server.stop()
        assert server.metrics.snapshot()["aggregate"]["phases_ms"][
            "checkpoint"]["count"] == 1

    asyncio.run(scenario())


def test_phase_spans_are_flat(monkeypatch):
    """No ``sgrapp.*`` span opens inside another on the same thread, over
    a served run with subscribers, a result and a finalize."""
    from repro.core import telemetry

    depth, opened = {}, []

    class Recorder:
        def __init__(self, name, **args):
            self.name = name

        def __enter__(self):
            key = threading.get_ident()
            depth[key] = depth.get(key, 0) + 1
            opened.append((self.name, depth[key]))

        def __exit__(self, *exc):
            depth[threading.get_ident()] -= 1

    monkeypatch.setattr(telemetry, "TraceAnnotation", Recorder)
    stream = tenant_streams(1)[0]

    async def scenario():
        server = await StreamServer(nt_w=NT_W, alpha0=ALPHA0,
                                    tenants={"t0": 0}, config=CFG).start()
        c = await Client.connect(server, "t0")
        await c.call({"type": "subscribe"})
        for k in range(0, 900, 300):
            assert (await c.push(stream, slice(k, k + 300)))["type"] == "ack"
        assert (await c.call({"type": "result"}))["type"] == "result"
        assert (await c.call({"type": "finalize"}))["type"] == "finalized"
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())
    names = {n for n, _ in opened}
    assert {"sgrapp.decode", "sgrapp.windowize", "sgrapp.pack",
            "sgrapp.launch", "sgrapp.count_wait", "sgrapp.estimate",
            "sgrapp.collect", "sgrapp.fanout"} <= names
    assert all(d == 1 for _, d in opened), [x for x in opened if x[1] > 1]


def test_constructor_validation():
    with pytest.raises(ValueError, match="at least one token"):
        StreamServer(nt_w=NT_W, alpha0=1.0, tenants={})
    with pytest.raises(ValueError, match="exactly 0..N-1"):
        StreamServer(nt_w=NT_W, alpha0=1.0, tenants={"a": 0, "b": 2})
    with pytest.raises(ValueError, match="exactly 0..N-1"):
        StreamServer(nt_w=NT_W, alpha0=1.0, tenants={"a": 1, "b": 1})
    with pytest.raises(TypeError, match="EngineConfig"):
        StreamServer(nt_w=NT_W, alpha0=1.0, tenants={"a": 0},
                     config={"tier": "numpy"})
    with pytest.raises(ValueError, match="queue_limit"):
        StreamServer(nt_w=NT_W, alpha0=1.0, tenants={"a": 0}, queue_limit=0)
    # the engine config is validated by EngineConfig itself
    with pytest.raises(ValueError, match="tier"):
        StreamServer(nt_w=NT_W, alpha0=1.0, tenants={"a": 0},
                     config=EngineConfig(tier="warp"))
