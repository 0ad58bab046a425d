"""Serving-path benchmark: N concurrent tenants through the NDJSON server.

An in-process load generator opens one real TCP connection per tenant and
pushes that tenant's stream in fixed-size batches (each push waits for its
ack — the serving protocol's synchronous client shape), all tenants
concurrently on one event loop.  The server coalesces admitted pushes on
its latency budget and drives the fleet engine off-loop, so the rows price
the full production path: socket framing + admission + coalescing + one
co-batched engine dispatch per cycle.

Rows (us_per_call = total wall time / measured latency in us):

- ``serving/aggregate_edges_per_s_n{N}`` — accepted edges / elapsed wall
  seconds across all tenants (derived field),
- ``serving/aggregate_edges_per_s_n{N}_wal`` — the same pass with the
  write-ahead log on (group-commit fsync per coalesce cycle); the run
  asserts WAL-on stays within 2x of the WAL-off wall time — the durability
  contract's performance half (docs/serving.md),
- ``serving/p50_push_ms_n{N}`` / ``serving/p99_push_ms_n{N}`` — engine
  dispatch-cycle latency percentiles from the server's own histogram
  (what ``/metrics`` exports).

The run also asserts ``/healthz`` and ``/metrics`` respond with the
documented shapes, so the CI leg that produces ``BENCH_serving.json``
doubles as the serving smoke test.  ``--chaos`` runs the SIGKILL/recover
smoke instead: kill the real launcher subprocess at the ``pre_ack`` fault
point mid-stream, restart it on the same state dir, and assert the
recovered estimates are bit-identical to a crash-free offline engine.
"""
from __future__ import annotations

import asyncio
import json
import tempfile
import time

from repro.launch.compile_cache import use_compile_cache
from repro.streams.config import EngineConfig
from repro.streams.generators import bipartite_pa_stream
from repro.streams.server import StreamServer
from repro.streams.wire import normalize_records, records_to_json

__all__ = ["run_serving"]


async def _send(w, msg: dict) -> None:
    w.write((json.dumps(msg, separators=(",", ":")) + "\n").encode())
    await w.drain()


async def _recv(r) -> dict:
    line = await r.readline()
    if not line:
        raise ConnectionError("server closed")
    return json.loads(line)


async def _http_get(host: str, port: int, path: str) -> dict:
    r, w = await asyncio.open_connection(host, port)
    w.write(f"GET {path} HTTP/1.1\r\nHost: bench\r\n\r\n".encode())
    data = await r.read()
    w.close()
    head, body = data.split(b"\r\n\r\n", 1)
    assert b"200" in head.split(b"\r\n", 1)[0], head
    return json.loads(body)


async def _drive_tenant(host: str, port: int, token: str, stream,
                        batch: int) -> int:
    """Push one tenant's whole stream, batch by batch, each awaiting its
    ack; backpressure rejects back off and retry (the documented client
    contract)."""
    reader, writer = await asyncio.open_connection(host, port)
    await _send(writer, {"type": "hello", "token": token})
    hello = await _recv(reader)
    assert hello["type"] == "hello_ok", hello
    accepted = 0
    k = 0
    while k < len(stream.tau):
        sl = slice(k, k + batch)
        rb = normalize_records(stream.tau[sl], stream.edge_i[sl],
                               stream.edge_j[sl])
        await _send(writer, {"type": "push", "records": records_to_json(rb)})
        reply = await _recv(reader)
        if reply["type"] == "ack":
            accepted += reply["accepted"]
            k += batch
        elif reply.get("reason") == "backpressure":
            await asyncio.sleep(0.002)
        else:
            raise AssertionError(f"unexpected push reply: {reply}")
    writer.close()
    return accepted


async def _one_pass(streams, *, tier: str, batch: int,
                    check_http: bool, wal_dir: str | None = None
                    ) -> tuple[float, dict]:
    n = len(streams)
    server = StreamServer(
        nt_w=100, alpha0=0.95,
        tenants={f"tenant{s}": s for s in range(n)},
        config=EngineConfig(tier=tier), flush_ms=1.0, queue_limit=256,
        wal_dir=wal_dir)
    await server.start()
    t0 = time.perf_counter()
    totals = await asyncio.gather(*[
        _drive_tenant(server.host, server.port, f"tenant{s}", streams[s],
                      batch)
        for s in range(n)])
    dt = time.perf_counter() - t0
    if check_http:
        health = await _http_get(server.host, server.http_port, "/healthz")
        assert health["status"] == "ok" and health["n_streams"] == n, health
        metrics = await _http_get(server.host, server.http_port, "/metrics")
        agg = metrics["aggregate"]
        assert agg["edges_accepted"] == sum(totals), agg
        assert agg["push_latency_ms"]["count"] > 0, agg
        assert set(metrics["tenants"]) == {str(s) for s in range(n)}, metrics
    snap = server.metrics.snapshot()
    await server.stop(finalize=True, checkpoint=False)
    assert sum(totals) == sum(len(s.tau) for s in streams)
    return dt, snap


def run_serving(*, quick: bool = False, tier: str = "dense",
                n_tenants: int = 4) -> list[tuple]:
    n_edges = 2_000 if quick else 10_000
    batch = 200
    streams = [bipartite_pa_stream(n_edges, temporal="uniform",
                                   n_unique=n_edges // 5, seed=11 + s)
               for s in range(n_tenants)]

    async def all_passes():
        # warm pass compiles every bucket shape; the timed passes reuse the
        # process-global jit cache, so they measure serving, not compilation
        await _one_pass(streams, tier=tier, batch=batch, check_http=True)
        off = await _one_pass(streams, tier=tier, batch=batch,
                              check_http=False)
        with tempfile.TemporaryDirectory(prefix="sgrapp-bench-wal-") as d:
            on = await _one_pass(streams, tier=tier, batch=batch,
                                 check_http=False, wal_dir=d)
        return off, on

    (dt, snap), (dt_wal, snap_wal) = asyncio.run(all_passes())
    agg = snap["aggregate"]
    lat = agg["push_latency_ms"]
    total_edges = agg["edges_accepted"]
    ratio = dt_wal / dt
    # the durability contract's perf half: group-commit fsync keeps the
    # WAL-on path within 2x of WAL-off
    assert ratio < 2.0, (
        f"WAL-on serving pass is {ratio:.2f}x WAL-off (limit 2x): "
        f"{dt_wal:.3f}s vs {dt:.3f}s")
    rows = [
        (f"serving/aggregate_edges_per_s_n{n_tenants}", dt * 1e6,
         f"{total_edges / dt:.0f} ({agg['pushes']} dispatch cycles, "
         f"{agg['windows_closed']} windows, tier={tier})"),
        (f"serving/aggregate_edges_per_s_n{n_tenants}_wal", dt_wal * 1e6,
         f"{snap_wal['aggregate']['edges_accepted'] / dt_wal:.0f} "
         f"(wal group-commit, {ratio:.2f}x of wal-off, tier={tier})"),
        (f"serving/p50_push_ms_n{n_tenants}", lat["p50"] * 1e3,
         f"{lat['p50']:.2f}ms over {lat['count']} cycles"),
        (f"serving/p99_push_ms_n{n_tenants}", lat["p99"] * 1e3,
         f"{lat['p99']:.2f}ms (max {lat['max']:.2f}ms)"),
    ]
    return rows


def run_chaos(*, quick: bool = False, tier: str = "numpy") -> None:
    """SIGKILL/recover smoke (no benchmark rows): plan a kill at the
    ``pre_ack`` fault point, push through the outage with the retrying
    seq client, restart on the same state dir, and assert bit-identity
    against a crash-free offline engine."""
    import numpy as np

    from repro.streams.engine import StreamingSGrapp
    from repro.streams.faults import DurableClient, FaultPlan, ServerProcess

    nt_w, alpha0 = 30, 0.95
    n_batches = 12 if quick else 24
    stream = bipartite_pa_stream(n_batches * 50, temporal="uniform",
                                 n_unique=n_batches * 12, seed=23)
    batches = [records_to_json(normalize_records(
                   stream.tau[k:k + 50], stream.edge_i[k:k + 50],
                   stream.edge_j[k:k + 50]))
               for k in range(0, len(stream.tau), 50)]
    import socket
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()

    async def scenario(ckpt: str) -> dict:
        srv_kw = dict(nt_w=nt_w, alpha0=alpha0, tenants={"t0": 0},
                      checkpoint_dir=ckpt, tier=tier, flush_ms=1.0,
                      extra_args=["--port", str(port), "--http-port", "0"])
        client = DurableClient("127.0.0.1", port, "t0")

        async def push_all():
            return [await client.push(rec) for rec in batches]

        plan = FaultPlan({"pre_ack": {"action": "kill",
                                      "at": n_batches // 2}})
        with ServerProcess(plan=plan, **srv_kw) as srv1:
            srv1.wait_ready()
            await client.connect()
            pusher = asyncio.create_task(push_all())
            code = await asyncio.to_thread(srv1.wait_dead, 120)
            assert code == -9, f"expected SIGKILL exit, got {code}"
            print(f"[chaos] server killed at pre_ack "
                  f"(cycle {n_batches // 2}); restarting...")
            with ServerProcess(plan=None, **srv_kw) as srv2:
                srv2.wait_ready()
                replies = await asyncio.wait_for(pusher, timeout=120)
                assert all(r["type"] == "ack" for r in replies)
                dups = sum(bool(r.get("duplicate")) for r in replies)
                print(f"[chaos] {len(replies)} batches acked through the "
                      f"outage ({dups} deduped retries)")
                final = await client.call({"type": "finalize"})
                client.close()
                return final

    with tempfile.TemporaryDirectory(prefix="sgrapp-chaos-") as d:
        final = asyncio.run(scenario(d))
    # the first JAX computation of this process comes only now, after both
    # servers exited: on a chip host the children must own the chip
    eng = StreamingSGrapp(nt_w, alpha0, config=EngineConfig(tier=tier))
    eng.push(stream.tau, stream.edge_i, stream.edge_j)
    ref = eng.finalize()
    np.testing.assert_array_equal(
        np.asarray(final["estimates"], dtype=np.float32), ref.estimates)
    np.testing.assert_array_equal(
        np.asarray(final["counts"], dtype=np.float64), ref.window_counts)
    print(f"[chaos] recovered estimates bit-identical to crash-free run "
          f"({len(ref.estimates)} windows)")


def main() -> None:
    import argparse

    from .artifacts import write_bench_json

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller per-tenant streams (CI smoke check)")
    ap.add_argument("--tier", default="dense")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--no-json", action="store_true")
    ap.add_argument("--chaos", action="store_true",
                    help="SIGKILL/recover smoke instead of benchmark rows")
    args = ap.parse_args()
    use_compile_cache()
    if args.chaos:
        run_chaos(quick=args.quick,
                  tier="numpy" if args.tier == "dense" else args.tier)
        return
    print("name,us_per_call,derived")
    rows = run_serving(quick=args.quick, tier=args.tier,
                       n_tenants=args.tenants)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if not args.no_json:
        write_bench_json("BENCH_serving.json", rows, quick=args.quick)


if __name__ == "__main__":
    main()
