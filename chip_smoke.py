"""Smoke test of the served path on the chip.

    python chip_smoke.py              # one TPU: tiers auto, dense and pallas
    python chip_smoke.py --chips 4    # four TPUs: the sharded path only

Each phase starts ``StreamServer`` in this process with four tenants, pushes
every tenant's stream to it over TCP (NDJSON) concurrently, finalizes each
stream over the same connection, and then checks:

- every served window count equals the numpy host oracle
  (``repro.streams.oracle``) exactly;
- the served estimates equal those of an offline ``MultiStreamSGrapp`` fed the
  same batches in this process, on the same chip;
- the largest window count is below 2**24, the bound below which the f32
  device accumulators are exact;
- ``/healthz`` says ok and not degraded, and the server counted no flush,
  engine or internal error (it isolates those from its clients, so a smoke
  run has to look for them);
- for ``pallas``: the executor does not interpret the kernel, and the
  compiled bucket program holds a Mosaic kernel (``tpu_custom_call``).

The streams are the win_8k rung of the paper's setting: per tenant,
``bipartite_pa_stream(131072, temporal="uniform", n_unique=65536)`` cut into
windows of 4096 unique timestamps, about 16 windows of ~6.4k edges each.
The one-chip phases dispatch each push cycle's closed windows at once, as
the launcher does by default.  Such a dispatch holds about one window per
tenant, each of its own shape, so every bucket holds one window; sharded, the
three other devices would count only padding.  The four-chip phases
therefore let the server gather closed windows for up to
``CHIPS4_GATHER_MS`` before it dispatches them (its ``latency_budget_ms``),
on an id ladder of 1024 (``EngineConfig.align``), so that most buckets hold
many windows and every device counts real ones.  Each tier runs twice there,
sharded and on one device, and both are checked against the oracle and each
other.

The script refuses to run anywhere but a TPU: it exits non-zero and prints no
result when JAX finds none.  The last line of standard output is the result,
``{"ok": true, "device": {...}}``; the lines before it are for the record.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

N_TENANTS = 4
N_EDGES = 131072
N_UNIQUE = 65536
NT_W = 4096
ALPHA0 = 1.0
PUSH_RECORDS = 2048
# the four-chip phases' batching: see the module docstring
CHIPS4_GATHER_MS = 2000.0
CHIPS4_ALIGN = 1024
CHIPS4_TIERS = ("dense", "sparse", "pallas")
ONE_CHIP_TIERS = ("auto", "dense", "pallas")
F32_EXACT = 2 ** 24


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- data and references (host only) ----------------------------------------

def make_tenants(n_edges: int, n_unique: int, nt_w: int) -> list[dict]:
    """Per tenant: its push batches (wire JSON) and the oracle's counts."""
    from repro.streams.generators import bipartite_pa_stream
    from repro.streams.oracle import oracle_window_counts, replay_dynamic
    from repro.streams.wire import normalize_records, records_to_json

    tenants = []
    for s in range(N_TENANTS):
        st = bipartite_pa_stream(n_edges, temporal="uniform",
                                 n_unique=n_unique, seed=s)
        tau, ei, ej = st.tau, st.edge_i, st.edge_j
        oracle = oracle_window_counts(replay_dynamic(tau, ei, ej, nt_w=nt_w))
        cuts = range(0, len(tau), PUSH_RECORDS)
        tenants.append({
            "cols": [(tau[k:k + PUSH_RECORDS], ei[k:k + PUSH_RECORDS],
                      ej[k:k + PUSH_RECORDS]) for k in cuts],
            "wire": [records_to_json(normalize_records(
                tau[k:k + PUSH_RECORDS], ei[k:k + PUSH_RECORDS],
                ej[k:k + PUSH_RECORDS])) for k in cuts],
            "oracle": oracle,
            "n_edges": len(tau),
        })
    return tenants


def offline_results(tenants: list[dict], config) -> list:
    """The reference: one offline fleet engine fed the same batches, round by
    round, so its flushes see the windows the server's flushes saw."""
    from repro.streams.multi import MultiStreamSGrapp

    eng = MultiStreamSGrapp(N_TENANTS, NT_W, ALPHA0, config=config)
    for k in range(max(len(t["cols"]) for t in tenants)):
        for s, t in enumerate(tenants):
            if k < len(t["cols"]):
                eng.push(s, *t["cols"][k])
    return eng.finalize()


# -- the served phase ---------------------------------------------------------

async def _send(w, msg: dict) -> None:
    w.write((json.dumps(msg, separators=(",", ":")) + "\n").encode())
    await w.drain()


async def _recv(r) -> dict:
    line = await r.readline()
    if not line:
        raise ConnectionError("server closed the connection")
    return json.loads(line)


async def _http_get(host: str, port: int, path: str) -> dict:
    r, w = await asyncio.open_connection(host, port)
    w.write(f"GET {path} HTTP/1.1\r\nHost: smoke\r\n\r\n".encode())
    data = await r.read()
    w.close()
    head, body = data.split(b"\r\n\r\n", 1)
    check(b" 200 " in head.split(b"\r\n", 1)[0], f"GET {path}: {head!r}")
    return json.loads(body)


async def _drive_tenant(host: str, port: int, token: str,
                        batches: list) -> tuple[int, dict]:
    """Push every batch (each waits for its ack; backpressure rejects back
    off and retry), then finalize the stream on the same connection."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        await _send(writer, {"type": "hello", "token": token})
        hello = await _recv(reader)
        check(hello["type"] == "hello_ok", f"hello: {hello}")
        accepted, k = 0, 0
        while k < len(batches):
            await _send(writer, {"type": "push", "records": batches[k]})
            reply = await _recv(reader)
            if reply["type"] == "ack":
                accepted += reply["accepted"]
                k += 1
            elif reply.get("reason") == "backpressure":
                await asyncio.sleep(0.002)
            else:
                raise SmokeFailure(f"{token}: push rejected: {reply}")
        await _send(writer, {"type": "finalize"})
        final = await _recv(reader)
        check(final["type"] == "finalized", f"{token}: {final}")
        return accepted, final
    finally:
        writer.close()


async def _serve(tenants: list[dict], config, gather_ms: float) -> dict:
    from repro.streams.server import StreamServer

    server = StreamServer(
        nt_w=NT_W, alpha0=ALPHA0,
        tenants={f"tenant{s}": s for s in range(N_TENANTS)},
        config=config, flush_ms=1.0, queue_limit=256,
        latency_budget_ms=gather_ms)
    await server.start()
    try:
        t0 = time.perf_counter()
        out = await asyncio.gather(*[
            _drive_tenant(server.host, server.port, f"tenant{s}",
                          t["wire"]) for s, t in enumerate(tenants)])
        wall = time.perf_counter() - t0
        health = await _http_get(server.host, server.http_port, "/healthz")
        metrics = await _http_get(server.host, server.http_port, "/metrics")
    finally:
        await server.stop(checkpoint=False)
    return {"server": server, "wall_s": wall, "health": health,
            "metrics": metrics, "accepted": [a for a, _ in out],
            "final": [f for _, f in out]}


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts as compiling), from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.trace_s = 0.0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
        elif event.startswith("/jax/core/compile/"):
            self.trace_s += secs

    def reset(self) -> None:
        self.trace_s = self.compile_s = 0.0


def shard_windows(buckets: list, devices: int) -> list[int]:
    """Real windows each device counted over ``buckets``: a bucket of ``n``
    windows is padded at its end to a multiple of ``devices`` and split
    into equal contiguous lanes, one per device."""
    per = [0] * devices
    for b in buckets:
        n = b.n_windows
        lane = -(-n // devices)
        for d in range(devices):
            per[d] += max(0, min(lane, n - d * lane))
    return per


def run_phase(tenants: list[dict], tier: str, clock: CompileClock, *,
              devices: int | None = None, chips4: bool = False) -> dict:
    """One served phase plus its checks; returns the counts it served.

    ``chips4`` selects the four-chip phases' batching, on ``devices``
    devices or (``devices=None``) on one."""
    import jax
    import numpy as np

    from repro.core.executor import compiled_bucket_cache_info
    from repro.streams.config import EngineConfig

    # the launcher's defaults (launch/serve_streams.py): flush_every=1, and
    # no gathering of closed windows before a dispatch; the four-chip
    # phases gather and use a coarser id ladder (see the module docstring)
    knobs = {"align": CHIPS4_ALIGN} if chips4 else {}
    config = EngineConfig(tier=tier, flush_every=1, seed=0, devices=devices,
                          **knobs)
    gather_ms = CHIPS4_GATHER_MS if chips4 else 0.0
    name = f"{tier}" + (f"/devices={devices}" if devices else "")
    clock.reset()
    before = compiled_bucket_cache_info()
    res = asyncio.run(_serve(tenants, config, gather_ms))
    served_compile_s, served_trace_s = clock.compile_s, clock.trace_s
    server = res["server"]
    executor = server.engine.executor

    # health and the errors the server isolates from its clients
    check(res["health"]["status"] == "ok" and not res["health"]["degraded"],
          f"{name}: /healthz {res['health']}")
    agg = res["metrics"]["aggregate"]
    for k in ("flush_errors", "engine_errors", "internal_errors"):
        check(agg[k] == 0, f"{name}: {k}={agg[k]}")
    check(res["accepted"] == [t["n_edges"] for t in tenants],
          f"{name}: accepted {res['accepted']}")

    # exact against the host oracle, and within the f32 exactness bound
    served_counts = []
    for s, (t, final) in enumerate(zip(tenants, res["final"])):
        got = np.asarray(final["counts"], dtype=np.float64)
        check(got.shape == t["oracle"].shape and np.array_equal(
            got, t["oracle"]), f"{name}: tenant {s} counts differ from the "
            f"oracle: {got.tolist()} vs {t['oracle'].tolist()}")
        served_counts.append(got)
    top = max(float(c.max()) for c in served_counts)
    check(top < F32_EXACT, f"{name}: count {top} reaches 2**24")

    # the same estimates as an offline engine on the same chip
    ref = offline_results(tenants, config)
    for s, (final, r) in enumerate(zip(res["final"], ref)):
        est = np.asarray(final["estimates"], dtype=np.float32)
        check(np.array_equal(est, np.asarray(r.estimates, np.float32)),
              f"{name}: tenant {s} estimates differ from the offline engine")
        check(np.array_equal(served_counts[s], r.window_counts),
              f"{name}: tenant {s} counts differ from the offline engine")

    staged = executor.staged_buckets()
    check(staged and not any(m or s for _, m, s in staged),
          f"{name}: unexpected lanes in {staged}")
    buckets = [b for b, _, _ in staged]
    # the bucket with the most windows: on four devices, every lane of it
    # holds real windows
    big = max(buckets, key=lambda b: (b.n_windows, b.cap_e))
    text = executor.program_text(big)
    if tier == "pallas":
        check(executor.interpret is False, f"{name}: kernel interpreted")
        check("tpu_custom_call" in text,
              f"{name}: no Mosaic kernel in the compiled bucket program")
    per_device = None
    if devices:
        check(executor.n_shards == devices,
              f"{name}: {executor.n_shards} shards")
        # the compiled program is partitioned over the devices, each one
        # taking an equal lane of the window axis
        lane = -(-big.n_windows // devices)
        check(f"num_partitions={devices}" in text
              and f"s32[{lane},{big.cap_e}]" in text,
              f"{name}: the window axis is not split over {devices} devices")
        # and the windows really spread: every device counted real windows,
        # each checked above against the oracle and the offline engine
        per_device = shard_windows(buckets, devices)
        check(big.n_windows >= devices and min(per_device) > 0,
              f"{name}: real windows per device {per_device}")

    after = compiled_bucket_cache_info()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:devices or 1])
    record = {
        "phase": name,
        "bucket_shapes": len(buckets),
        "bucket_programs_new": sum(after.values()) - sum(before.values()),
        "compile_s": served_compile_s,
        "trace_lower_s": served_trace_s,
        "wall_s": res["wall_s"],
        "edges_pushed": sum(res["accepted"]),
        "windows_counted": sum(len(c) for c in served_counts),
        "max_window_count": top,
        "peak_bytes_in_use": peak,
    }
    if per_device:
        record["windows_per_device"] = per_device
    print(json.dumps(record), flush=True)
    print(json.dumps({"phase": name, "shapes": [
        [b.cap_e, b.cap_i, b.cap_j, b.cap_w, b.n_windows] for b in buckets]}),
        flush=True)
    return {"counts": served_counts}


# -- entry point ---------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its references")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devs)}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    print(json.dumps({"device_kind": devs[0].device_kind,
                      "devices": len(devs), "compile_cache": cache}),
          flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    tenants = make_tenants(N_EDGES, N_UNIQUE, NT_W)
    print(json.dumps({"setup_s": time.perf_counter() - t0,
                      "windows_per_tenant": [len(t["oracle"])
                                             for t in tenants]}),
          flush=True)
    if args.chips == 1:
        for tier in ONE_CHIP_TIERS:
            run_phase(tenants, tier, clock)
    else:
        import numpy as np

        for tier in CHIPS4_TIERS:
            sharded = run_phase(tenants, tier, clock, devices=args.chips,
                                chips4=True)
            single = run_phase(tenants, tier, clock, chips4=True)
            for a, b in zip(sharded["counts"], single["counts"]):
                check(np.array_equal(a, b),
                      f"{tier}: sharded counts differ from one device")
    print(json.dumps({"total_s": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
