"""Crash-recovery suite: exactly-once durability under injected faults.

Two layers of coverage for the durable serving stack:

* **In-process legs** drive a :class:`StreamServer` with a
  :class:`FaultPlan` installed and pin the supervision contract — per-item
  engine isolation, WAL disk-full degradation, checkpoint retry, duplicate
  seq idempotence, corrupt-checkpoint fallback, orphan-free stop.
* **Subprocess SIGKILL legs** run the real launcher, kill it at each
  planned fault point (pre-ack, post-ack-pre-WAL, mid-checkpoint-rename)
  and assert the recovered per-tenant estimates are *bit-identical* to a
  crash-free offline engine fed the same stream — the tentpole invariant:
  no acked record lost, none applied twice, client retries included.
"""
from __future__ import annotations

import asyncio
import json
import os
import socket

import numpy as np
import pytest

from repro.streams.config import EngineConfig, ServingConfig
from repro.streams.engine import StreamingSGrapp
from repro.streams.faults import (DurableClient, FaultPlan, ServerProcess,
                                  clear_plan, install_plan)
from repro.streams.generators import bipartite_pa_stream
from repro.streams.server import StreamServer
from repro.streams.wire import normalize_records, records_to_json
from repro.train.fault import BackoffPolicy

NT_W = 30
ALPHA0 = 0.95
CFG = EngineConfig(tier="numpy")
FAST = ServingConfig(restart_backoff=BackoffPolicy(0.01, 0.05),
                     checkpoint_retry=BackoffPolicy(0.01, 0.05),
                     drain_timeout_s=1.0)


@pytest.fixture(autouse=True)
def _clean_plan():
    yield
    clear_plan()


def make_stream(n_edges: int = 900, seed: int = 7):
    return bipartite_pa_stream(n_edges, temporal="uniform",
                               n_unique=n_edges // 4, seed=seed)


def stream_batches(stream, batch: int) -> list[dict]:
    return [records_to_json(normalize_records(
                stream.tau[k:k + batch], stream.edge_i[k:k + batch],
                stream.edge_j[k:k + batch]))
            for k in range(0, len(stream.tau), batch)]


def offline_result(stream):
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


class Client:
    """Minimal NDJSON client (no retry — the in-process legs want to see
    raw rejects; :class:`DurableClient` is the retrying one)."""

    def __init__(self, reader, writer):
        self.reader = reader
        self.writer = writer

    @classmethod
    async def connect(cls, server: StreamServer, token: str) -> "Client":
        r, w = await asyncio.open_connection(server.host, server.port)
        c = cls(r, w)
        c.hello = await c.call({"type": "hello", "token": token})
        assert c.hello["type"] == "hello_ok", c.hello
        return c

    async def send(self, msg: dict) -> None:
        self.writer.write((json.dumps(msg) + "\n").encode())
        await self.writer.drain()

    async def recv(self) -> dict:
        line = await self.reader.readline()
        assert line, "server closed the connection"
        return json.loads(line)

    async def call(self, msg: dict) -> dict:
        await self.send(msg)
        return await self.recv()

    async def push(self, records: dict, seq=None) -> dict:
        msg = {"type": "push", "records": records}
        if seq is not None:
            msg["seq"] = seq
        return await self.call(msg)

    def close(self) -> None:
        self.writer.close()


async def http_get(server: StreamServer, path: str) -> tuple[int, dict]:
    r, w = await asyncio.open_connection(server.host, server.http_port)
    w.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    data = await r.read()
    w.close()
    head, body = data.split(b"\r\n\r\n", 1)
    return int(head.split()[1]), json.loads(body)


# ---------------------------------------------------------------------------
# supervision: per-item isolation, degraded mode, checkpoint retry
# ---------------------------------------------------------------------------


def test_engine_apply_raise_isolated_retry_converges(tmp_path):
    """An unexpected exception inside one item's apply rejects THAT item
    (``internal``), keeps the coalescer alive, and a client retry under the
    same seq converges to the crash-free state."""
    stream = make_stream()
    batches = stream_batches(stream, 300)
    assert len(batches) == 3

    async def scenario():
        install_plan(FaultPlan(
            {"engine_apply_raise": {"action": "raise", "at": 2}}))
        server = await StreamServer(
            nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0}, config=CFG,
            flush_ms=1.0, serving=FAST,
            wal_dir=str(tmp_path / "wal")).start()
        c = await Client.connect(server, "t0")
        assert (await c.push(batches[0], seq=1))["type"] == "ack"
        reply = await c.push(batches[1], seq=2)
        assert reply["type"] == "reject" and reply["reason"] == "internal"
        assert server.metrics.engine_errors == 1
        # retry with the SAME seq: not a duplicate (never applied), applies
        reply = await c.push(batches[1], seq=2)
        assert reply["type"] == "ack" and "duplicate" not in reply
        assert (await c.push(batches[2], seq=3))["type"] == "ack"
        final = await c.call({"type": "finalize"})
        assert_matches_offline(final, stream)
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_wal_disk_full_rejects_degrades_then_recovers(tmp_path):
    stream = make_stream(300)
    batches = stream_batches(stream, 150)

    async def scenario():
        install_plan(FaultPlan(
            {"disk_full": {"action": "disk_full", "at": 1, "count": 1}}))
        server = await StreamServer(
            nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0}, config=CFG,
            flush_ms=1.0, serving=FAST,
            wal_dir=str(tmp_path / "wal")).start()
        c = await Client.connect(server, "t0")
        reply = await c.push(batches[0], seq=1)
        assert reply["type"] == "reject" and reply["reason"] == "wal_error"
        assert server.metrics.wal_errors == 1
        status, health = await http_get(server, "/healthz")
        assert health["status"] == "degraded"
        assert "wal" in health["degraded"]
        # disk recovered: same-seq retry applies and clears degraded mode
        assert (await c.push(batches[0], seq=1))["type"] == "ack"
        assert (await c.push(batches[1], seq=2))["type"] == "ack"
        _, health = await http_get(server, "/healthz")
        assert health["status"] == "ok" and health["degraded"] == []
        _, m = await http_get(server, "/metrics")
        assert m["wal"]["enabled"] and m["wal"]["errors"] == 1
        assert m["aggregate"]["edges_accepted"] == 300
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_checkpoint_failure_retries_counts_and_degrades(tmp_path):
    stream = make_stream(300)
    batches = stream_batches(stream, 300)
    ckpt = str(tmp_path / "ckpt")

    async def scenario():
        from repro.train.checkpoint import latest_step

        install_plan(FaultPlan(
            {"disk_full": {"action": "disk_full", "at": 1, "count": 1}}))
        server = await StreamServer(
            nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0}, config=CFG,
            flush_ms=1.0, checkpoint_dir=ckpt, checkpoint_every_s=0.05,
            serving=ServingConfig(wal=False,
                                  checkpoint_retry=BackoffPolicy(0.01, 0.02)),
            ).start()
        c = await Client.connect(server, "t0")
        assert (await c.push(batches[0]))["type"] == "ack"
        # first periodic save hits injected ENOSPC; the retry succeeds
        for _ in range(400):
            if (latest_step(ckpt) is not None
                    and server.metrics.checkpoint_failures >= 1
                    and "checkpoint" not in server._degraded):
                break
            await asyncio.sleep(0.01)
        assert server.metrics.checkpoint_failures >= 1
        assert latest_step(ckpt) is not None
        assert "checkpoint" not in server._degraded   # cleared on success
        _, m = await http_get(server, "/metrics")
        assert m["supervision"]["checkpoint_failures"] >= 1
        assert m["supervision"]["last_checkpoint_age_s"] is not None
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_stop_resolves_queued_futures_and_is_idempotent(tmp_path):
    """A drain that can't finish (wedged engine) must still resolve every
    queued item's future with a ``draining`` reject, and a second stop()
    must be a cheap no-op."""
    import threading

    stream = make_stream(300)

    async def scenario():
        server = StreamServer(
            nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0}, config=CFG,
            queue_limit=8, flush_ms=0.0,
            serving=ServingConfig(wal=False, drain_timeout_s=0.3))
        await server.start()
        release = threading.Event()
        server._pool.submit(release.wait)   # wedge the engine thread
        clients = [await Client.connect(server, "t0") for _ in range(4)]
        for k, c in enumerate(clients):
            sl = slice(k * 50, (k + 1) * 50)
            await c.send({"type": "push", "records": records_to_json(
                normalize_records(stream.tau[sl], stream.edge_i[sl],
                                  stream.edge_j[sl]))})
        await asyncio.sleep(0.1)
        stop1 = asyncio.create_task(server.stop(checkpoint=False))
        # every in-flight push resolves (draining) instead of hanging
        replies = await asyncio.wait_for(
            asyncio.gather(*[c.recv() for c in clients]), timeout=5.0)
        assert all(r["type"] == "reject" and r["reason"] == "draining"
                   for r in replies), replies
        release.set()
        await asyncio.wait_for(stop1, timeout=10.0)
        # idempotent: second stop returns immediately
        await asyncio.wait_for(server.stop(), timeout=1.0)
        assert server._stopped
        for c in clients:
            c.close()

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# the seq lane: duplicates, gaps, hello watermark
# ---------------------------------------------------------------------------


def test_duplicate_seq_is_idempotent_and_gaps_reject(tmp_path):
    stream = make_stream(600)
    batches = stream_batches(stream, 200)

    async def scenario():
        server = await StreamServer(
            nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0}, config=CFG,
            flush_ms=1.0, serving=FAST,
            wal_dir=str(tmp_path / "wal")).start()
        c = await Client.connect(server, "t0")
        assert c.hello["next_seq"] == 1

        ack1 = await c.push(batches[0], seq=1)
        assert ack1["type"] == "ack" and ack1["seq"] == 1

        # retry of an applied seq: idempotent ack with the CACHED outcome,
        # not a second application
        dup = await c.push(batches[0], seq=1)
        assert dup["type"] == "ack" and dup["duplicate"] is True
        assert dup["accepted"] == ack1["accepted"]
        assert dup["windows_closed"] == ack1["windows_closed"]
        assert server.metrics.duplicate_acks == 1
        assert server.metrics.tenants[0].edges_accepted == 200

        # gaps and malformed seqs reject without admission
        reply = await c.push(batches[1], seq=5)
        assert reply["type"] == "reject" and reply["reason"] == "bad_seq"
        for bad in (0, -3, "x", 1.5, True):
            reply = await c.push(batches[1], seq=bad)
            assert reply["reason"] == "bad_seq", (bad, reply)

        assert (await c.push(batches[1], seq=2))["type"] == "ack"
        assert (await c.push(batches[2]))["type"] == "ack"   # server-assigned

        # a reconnecting client learns the durable watermark
        c2 = await Client.connect(server, "t0")
        assert c2.hello["next_seq"] == 4
        final = await c2.call({"type": "finalize"})
        assert_matches_offline(final, stream)
        c.close()
        c2.close()
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


def test_restart_replays_wal_without_any_checkpoint(tmp_path):
    """WAL-only durability: no checkpoint dir at all, acked records still
    survive a restart bit-identically."""
    stream = make_stream()
    batches = stream_batches(stream, 100)
    half = len(batches) // 2
    kw = dict(nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0}, config=CFG,
              flush_ms=1.0, serving=FAST, wal_dir=str(tmp_path / "wal"))

    async def first():
        server = await StreamServer(**kw).start()
        c = await Client.connect(server, "t0")
        for rec in batches[:half]:
            assert (await c.push(rec))["type"] == "ack"
        c.close()
        await server.stop(checkpoint=False)

    async def second():
        server = await StreamServer(**kw).start()
        assert server._recovered is True
        assert server.engine.n_counted(0) > 0
        c = await Client.connect(server, "t0")
        assert c.hello["next_seq"] == half + 1
        for rec in batches[half:]:
            assert (await c.push(rec))["type"] == "ack"
        final = await c.call({"type": "finalize"})
        assert_matches_offline(final, stream)
        _, m = await http_get(server, "/metrics")
        assert m["wal"]["replayed"] == half
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(first())
    asyncio.run(second())


# ---------------------------------------------------------------------------
# corrupt checkpoints: fallback + WAL overlap
# ---------------------------------------------------------------------------


def _corrupt(path: str) -> None:
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(path, "wb").write(bytes(data))


def _ckpt_scenario(tmp_path, corrupt):
    """Push in thirds with a checkpoint after each of the first two, run
    ``corrupt(ckpt_dir)`` offline, then restart + finish + finalize."""
    stream = make_stream()
    batches = stream_batches(stream, 100)
    third = len(batches) // 3
    ckpt = str(tmp_path / "ckpt")
    kw = dict(nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0}, config=CFG,
              flush_ms=1.0, serving=FAST, checkpoint_dir=ckpt)

    async def first():
        server = await StreamServer(**kw).start()
        c = await Client.connect(server, "t0")
        for rec in batches[:third]:
            assert (await c.push(rec))["type"] == "ack"
        await server._loop.run_in_executor(server._pool,
                                           server._save_checkpoint)
        for rec in batches[third:2 * third]:
            assert (await c.push(rec))["type"] == "ack"
        c.close()
        await server.stop()    # checkpoint=True -> second step

    async def second():
        server = await StreamServer(**kw).start()
        c = await Client.connect(server, "t0")
        for rec in batches[2 * third:]:
            assert (await c.push(rec))["type"] == "ack"
        final = await c.call({"type": "finalize"})
        assert_matches_offline(final, stream)
        assert server.metrics.checkpoint_fallbacks >= 1
        _, health = await http_get(server, "/healthz")
        assert health["status"] == "degraded"
        assert "checkpoint_fallback" in health["degraded"]
        c.close()
        await server.stop(checkpoint=False)

    asyncio.run(first())
    corrupt(ckpt)
    asyncio.run(second())


def test_bit_flipped_newest_checkpoint_falls_back(tmp_path):
    def corrupt(ckpt):
        from repro.train.checkpoint import valid_steps
        steps = valid_steps(ckpt)
        assert len(steps) == 2
        _corrupt(os.path.join(ckpt, f"step_{steps[-1]:08d}", "arrays.npz"))

    _ckpt_scenario(tmp_path, corrupt)


def test_truncated_newest_manifest_falls_back(tmp_path):
    def corrupt(ckpt):
        from repro.train.checkpoint import valid_steps
        step = valid_steps(ckpt)[-1]
        path = os.path.join(ckpt, f"step_{step:08d}", "manifest.json")
        data = open(path, "rb").read()
        open(path, "wb").write(data[: len(data) // 2])

    _ckpt_scenario(tmp_path, corrupt)


def test_all_checkpoints_corrupt_full_wal_replay(tmp_path):
    def corrupt(ckpt):
        from repro.train.checkpoint import valid_steps
        for step in valid_steps(ckpt):
            _corrupt(os.path.join(ckpt, f"step_{step:08d}", "arrays.npz"))

    _ckpt_scenario(tmp_path, corrupt)


def test_stale_tmp_step_dirs_gcd_at_start(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(os.path.join(ckpt, ".tmp_step_00000007"))

    async def scenario():
        server = await StreamServer(
            nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0}, config=CFG,
            serving=FAST, checkpoint_dir=ckpt).start()
        assert not any(d.startswith(".tmp_step_") for d in os.listdir(ckpt))
        await server.stop(checkpoint=False)

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# subprocess SIGKILL legs: bit-identical recovery through the real launcher
# ---------------------------------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _sigkill_leg(tmp_path, plan: FaultPlan, *, n_batches: int = 16,
                 inter_push_sleep: float = 0.0,
                 checkpoint_every_s: float | None = None,
                 check_duplicate_retry: bool = False,
                 tier: str = "numpy", server_args: tuple = ()):
    """SIGKILL the server at a planned fault point mid-stream, restart it
    on the same state dir, let the seq-retrying client push through the
    outage, and assert bit-identity with a crash-free offline engine."""
    stream = make_stream(n_batches * 50, seed=11)
    batches = stream_batches(stream, 50)
    ckpt = str(tmp_path / "ckpt")
    port, http_port = _free_port(), _free_port()
    fixed = ["--port", str(port), "--http-port", str(http_port),
             *server_args]
    srv_kw = dict(nt_w=NT_W, alpha0=ALPHA0, tenants={"t0": 0},
                  checkpoint_dir=ckpt, tier=tier, flush_ms=1.0,
                  extra_args=fixed, env={"JAX_PLATFORMS": "cpu"})

    async def scenario():
        client = DurableClient("127.0.0.1", port, "t0")

        async def push_all():
            out = []
            for rec in batches:
                out.append(await client.push(rec))
                if inter_push_sleep:
                    await asyncio.sleep(inter_push_sleep)
            return out

        with ServerProcess(plan=plan,
                           checkpoint_every_s=checkpoint_every_s,
                           **srv_kw) as srv1:
            srv1.wait_ready()
            await client.connect()
            pusher = asyncio.create_task(push_all())
            # the planned SIGKILL fires mid-stream
            code = await asyncio.to_thread(srv1.wait_dead, 120)
            assert code == -9, f"server exited {code}, expected SIGKILL"
            # restart on the same state, no faults: recovery + retries
            with ServerProcess(plan=None, **srv_kw) as srv2:
                srv2.wait_ready()
                replies = await asyncio.wait_for(pusher, timeout=120)
                assert all(r["type"] == "ack" for r in replies)
                if check_duplicate_retry:
                    # explicit retry of the last acked seq after recovery:
                    # served from the rebuilt duplicate cache, not re-applied
                    dup = await client.call(
                        {"type": "push", "records": batches[-1],
                         "seq": client.seq})
                    assert dup["type"] == "ack", dup
                    assert dup.get("duplicate") is True, dup
                final = await client.call({"type": "finalize"})
                assert final["type"] == "finalized", final
                assert_matches_offline(final, stream)
                client.close()

    asyncio.run(scenario())


def test_sigkill_pre_ack_recovers_bit_identical(tmp_path):
    """Kill after WAL fsync + apply but before the ack: the client never
    saw the ack, retries the same seq, and must get a duplicate-deduped
    ack — applied exactly once."""
    _sigkill_leg(tmp_path,
                 FaultPlan({"pre_ack": {"action": "kill", "at": 5}}),
                 check_duplicate_retry=True)


def test_sigkill_post_ack_pre_wal_recovers_bit_identical(tmp_path):
    """Kill after the cycle's outcomes are computed but before the WAL
    fsync: the unsynced tail is lost AND unacked, so the retry re-applies
    it — still exactly once."""
    _sigkill_leg(tmp_path,
                 FaultPlan({"post_ack_pre_wal": {"action": "kill",
                                                 "at": 5}}))


def test_sigkill_mid_checkpoint_rename_recovers_bit_identical(tmp_path):
    """Kill between the checkpoint tmp-write and its atomic rename: the
    stale tmp dir is GC'd at restart and recovery replays the WAL from the
    previous watermark."""
    _sigkill_leg(
        tmp_path,
        FaultPlan({"pre_checkpoint_rename": {"action": "kill", "at": 1}}),
        n_batches=24, inter_push_sleep=0.03, checkpoint_every_s=0.4)


def test_sigkill_async_dispatch_wal_fsync_before_ack(tmp_path):
    """The async flush pipeline must not reorder durability: with count
    dispatch deferred past the ack (compiled tier + latency budget, so a
    dispatch is genuinely in flight across cycles), every acked record's
    WAL fsync still lands before its ack.  Kill between fsync and ack at
    cycle 5: the retry of the last acked seq must dedupe (it WAS durable)
    and the recovered stream is bit-identical to a crash-free offline
    engine — the in-flight dispatch's un-materialized counts are simply
    recomputed from the WAL."""
    _sigkill_leg(tmp_path,
                 FaultPlan({"pre_ack": {"action": "kill", "at": 5}}),
                 tier="dense",
                 server_args=("--latency-budget-ms", "50"),
                 check_duplicate_retry=True)
