"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is ``bench/configs/<config>.json`` and its traffic
``bench/traffic/<traffic>.json``.  This process starts ``StreamServer`` on
the chip with the configuration's settings and its TCP NDJSON data plane.
The load generator (``bench/loadgen.py``) runs as a process of its own that
never imports JAX, one push connection and one subscription per tenant.

A run: make the traffic (in the generator) while JAX starts; warm the
bucket programs of every rung a fixed sample of the configuration's streams
reaches (``bench/ladder.py``); start the server; drive the traffic until
every tenant has closed the traffic's ``min_windows_per_tenant`` and no
program has compiled for its ``quiet_s`` (set-up ends there); measure ``--seconds``; let the generator
wait for every ack and estimate; read the device's peak memory; stop the
server; let the generator replay the acked records through the reference;
compare; print the metrics.  With ``--trace 1`` the window is traced and
the per-layer metrics are printed instead of the end-to-end ones.

The last line of standard output is the result, a JSON object; its last
key, ``checks``, holds every number compared with its limit, and the same
lines end standard error.  Without a TPU, or with fewer chips than the cell
asks for, or on a device missing from ``bench/peaks.json``, the run exits
non-zero and prints no result.  The persistent compile cache lives at
``.bench_cache/jax`` in the checkout.
"""
from __future__ import annotations

import os
import time

_T_IMPORT = time.monotonic()


def _process_start() -> float:
    """This process's start on the ``time.monotonic`` clock."""
    with open("/proc/self/stat") as f:
        start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return _T_IMPORT - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))


PROCESS_START = _process_start()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from bench import ladder as ladder_mod  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.record import (Run, ack_latencies_ms, estimate_latencies_ms,  # noqa: E402
                          lag_trend_ms, published_in_window)
from bench.reference import compare  # noqa: E402
from bench.streams import stream_params  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RunError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_reader(name: str):
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(BENCH, "metrics", f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench.metrics.{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise RunError(f"no reader for metric {name!r} in bench/metrics")


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end ones, or with a trace
    its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def check_device(devs: list, chips: int, peaks: dict) -> None:
    """RunError where the devices JAX found are no TPU, fewer than the cell
    asks for, or of a kind missing from the peak table."""
    if devs[0].platform != "tpu":
        raise RunError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    if devs[0].device_kind not in peaks["devices"]:
        raise RunError(f"device kind {devs[0].device_kind!r} is not in "
                       "bench/peaks.json")


class CompileLog:
    """Times (monotonic) of the programs JAX compiled or loaded."""

    def __init__(self):
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.times.append(time.monotonic())


class Generator:
    """The load generator's process and its line protocol."""

    def __init__(self, spec: dict, tmp: str):
        path = os.path.join(tmp, "spec.json")
        with open(path, "w") as f:
            json.dump(spec, f)
        env = dict(os.environ, PYTHONPATH=ROOT)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "loadgen.py"), path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True)

    def send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    async def expect(self, key: str, timeout: float):
        loop = asyncio.get_running_loop()
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise RunError(f"the load generator sent no {key!r}")
            line = await asyncio.wait_for(
                loop.run_in_executor(None, self.proc.stdout.readline), left)
            if not line:
                raise RunError(f"the load generator ended before {key!r} "
                               f"(exit {self.proc.poll()})")
            msg = json.loads(line)
            if key in msg:
                return msg

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def engine_config(cfg: dict):
    from repro.streams.config import EngineConfig

    eng = cfg["engine"]
    return EngineConfig(tier=eng["tier"], flush_every=int(eng["flush_every"]),
                        dup_policy=eng["dup_policy"], align=int(eng["align"]),
                        seed=0)


def make_server(cfg: dict, tmp: str):
    from repro.streams.config import ServingConfig
    from repro.streams.server import StreamServer

    srv = cfg["server"]
    return StreamServer(
        nt_w=int(cfg["nt_w"]), alpha0=float(cfg["alpha0"]),
        tenants={f"tenant{s}": s for s in range(int(cfg["tenants"]))},
        config=engine_config(cfg),
        flush_ms=float(srv["flush_ms"]),
        latency_budget_ms=float(srv["latency_budget_ms"]),
        queue_limit=int(srv["queue_limit"]),
        checkpoint_dir=os.path.join(tmp, "checkpoints"),
        serving=ServingConfig(wal=bool(srv["wal"]),
                              wal_fsync=bool(srv["wal_fsync"])))


async def drive(cfg: dict, traffic: dict, gen: Generator, seconds: float,
                trace_dir: str | None, tmp: str, compiles: CompileLog,
                devices, server_hook=None) -> dict:
    """Serve the traffic; returns what the process itself recorded."""
    import jax

    warm = traffic["warmup"]
    t_ladder = time.monotonic()
    ladder = ladder_mod.warm(engine_config(cfg).make_executor(),
                             stream_params(cfg), int(cfg["nt_w"]),
                             int(cfg["engine"]["align"]),
                             int(warm["ladder_streams"]))
    ladder["s"] = time.monotonic() - t_ladder
    log(f"ladder {json.dumps(ladder)}")
    server = make_server(cfg, tmp)
    if server_hook is not None:
        server_hook(server)
    await server.start()
    try:
        await gen.expect("ready", 300)
        gen.send({"host": server.host, "port": server.port})
        started = (await gen.expect("traffic_started", 60))["traffic_started"]
        tenants = range(int(cfg["tenants"]))
        while True:
            await asyncio.sleep(0.1)
            now = time.monotonic()
            last = max([started] + compiles.times)
            closed = server.metrics.snapshot()["tenants"]
            if now - started >= warm["max_s"] or (
                    now - started >= warm["min_s"]
                    and now - last >= warm["quiet_s"]
                    and min(closed[str(s)]["windows_closed"] for s in tenants)
                    >= warm["min_windows_per_tenant"]):
                break
        loop = asyncio.get_running_loop()
        warmup = {"ladder_s": ladder["s"], "ladder_warmed": ladder["warmed"],
                  "ladder_missed": len(ladder["missed"]),
                  "warmup_s": time.monotonic() - started,
                  "warmup_capped": time.monotonic() - started
                  >= warm["max_s"],
                  "compiles_in_warmup": sum(t >= started
                                            for t in compiles.times)}
        if trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # host spans only, no calls
            await loop.run_in_executor(None, functools.partial(
                jax.profiler.start_trace, trace_dir,
                profiler_options=options))
        t0 = time.monotonic() + 0.05
        t1 = t0 + seconds
        gen.send({"window": [t0, t1]})
        await asyncio.sleep(max(t0 - time.monotonic(), 0.0))
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            server0 = server.metrics.snapshot()
            await asyncio.sleep(max(t1 - time.monotonic(), 0.0))
            server1 = server.metrics.snapshot()
        if trace_dir is not None:
            await loop.run_in_executor(None, jax.profiler.stop_trace)
        drained = await gen.expect("drained", seconds + 3600)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        errors = {k: server1["aggregate"][k] for k in
                  ("flush_errors", "engine_errors", "internal_errors")}
    finally:
        await server.stop(checkpoint=False)
    del server
    gen.send({"reference": True})
    done = await gen.expect("done", 600)
    return {"t0": t0, "t1": t1, "server0": server0, "server1": server1,
            "peak": peak, "lateness": drained["lateness"],
            "npz": done["done"], "errors": errors, "warmup": warmup}


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True, limits=None,
             server_hook=None, configs: dict | None = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``require_chip=False``, ``limits``, ``server_hook`` and ``configs``
    are for the benchmark's own tests: they run a small configuration on
    the CPU and break the served path underneath."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json")
    configs = configs or {}
    cfg = configs.get(cell["config"]) or load_json(
        "configs", f"{cell['config']}.json")
    traffic = configs.get(cell["traffic"]) or load_json(
        "traffic", f"{cell['traffic']}.json")
    if limits is None:
        limits = load_json("limits", f"{workload}.json")
    readers = [(m, load_reader(m["name"]))
               for m in cell_metrics(bench, workload, trace)]
    peaks = load_json("peaks.json")
    tmp = tempfile.mkdtemp(prefix="bench-")
    gen = Generator({"config": cfg, "traffic": traffic, "seed": seed,
                     "out": tmp}, tmp)
    try:
        import jax

        devices = jax.devices()
        if require_chip:
            check_device(devices, int(cell["chips"]), peaks)
            dev_peaks = peaks["devices"][devices[0].device_kind]
        else:
            dev_peaks = next(iter(peaks["devices"].values()))
        devices = devices[:int(cell["chips"])]
        compiles = CompileLog()
        sys.path.insert(0, os.path.join(ROOT, "src"))
        trace_dir = os.path.join(tmp, "trace") if trace else None
        got = asyncio.run(drive(cfg, traffic, gen, seconds, trace_dir, tmp,
                                compiles, devices, server_hook))
        gen.proc.wait(timeout=60)
        tables = dict(np.load(got["npz"]))
        run = Run(workload=workload, t0=got["t0"], t1=got["t1"],
                  setup_s=got["t0"] - PROCESS_START, tables=tables,
                  server0=got["server0"], server1=got["server1"],
                  compiles=compiles.times, peaks=dev_peaks)
        reduced = None
        if trace:
            path = trace_mod.find_xplane(trace_dir)
            if path is not None:
                reduced = trace_mod.reduce(*trace_mod.load(path))
            run.trace = reduced
        checks = check_numbers(tables, limits)
        metrics = {}
        for m, read in readers:
            v = read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        due = tables["push_due"]
        in_window = (due >= run.t0) & (due < run.t1)
        attempted = int(in_window.sum())
        failed = int((in_window & ~tables["push_ok"].astype(bool)).sum())
        log(f"generator lateness {json.dumps(got['lateness'])}")
        log(f"server errors {json.dumps(got['errors'])}")
        d0 = devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(got["peak"])}
        result = {"correct": all(c["value"] <= c["limit"]
                                 for c in checks.values()),
                  "attempted": attempted, "failed": failed,
                  "metrics": metrics, "device": device}
        if trace and reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        print(json.dumps({
            **got["warmup"],
            "lateness": got["lateness"],
            "estimate_samples": int(estimate_latencies_ms(run).shape[0]),
            "ack_samples": int(ack_latencies_ms(run).shape[0]),
            "lag_trend_ms": lag_trend_ms(run),
            "windows_published_in_window": int(
                published_in_window(run).sum()),
            "programs_compiled_in_window": sum(
                run.t0 <= t < run.t1 for t in run.compiles)}), flush=True)
        for name, c in checks.items():
            log(f"check {name} {c['value']!r} limit {c['limit']!r}")
        result["checks"] = checks
        return result
    finally:
        gen.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def check_numbers(tables: dict, limits: dict) -> dict:
    """The compared numbers beside their limits."""
    published, reference = [], []
    for s in range(tables["closed_by_acks"].shape[0]):
        e = tables["est_tenant"] == s
        published.append({k: tables[f"est_{k}"][e] for k in
                          ("window", "count", "estimate", "cum_sgrs",
                           "end_tau")})
        r = tables["ref_tenant"] == s
        reference.append({k: tables[f"ref_{k}"][r] for k in
                          ("count", "cum_sgrs", "end_tau", "estimate")})
    got = compare(published, reference, list(tables["closed_by_acks"]))
    return {k: {"value": v, "limit": limits[k]} for k, v in got.items()}


def use_cache(jax) -> None:
    """Keep every program in the checkout's cache, whatever its size or
    compile time, with no eviction (eviction's access-time files failed to
    write on the chip's host, which left the cache empty)."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        import jax

        use_cache(jax)
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (RunError, OSError, ImportError, ValueError, KeyError) as e:
        log(f"failed: {type(e).__name__}: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
