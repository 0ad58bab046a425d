"""The load generator: a process of its own that never imports JAX.

    python3 bench/loadgen.py SPEC_JSON

It makes every tenant's stream from the seed, opens for each tenant one
ordered connection for pushes and one subscription for estimates, and
drives the traffic the spec names:

- ``closed``: each tenant sends its next push as soon as the last one is
  acked; a push is due when it is sent.
- ``open``: each tenant's pushes are due on a fixed even schedule, at the
  traffic's total rate spread evenly over tenants, whether or not the server
  keeps up.  Each push is stamped with the time it was due, and the
  generator reports how late it sent.

It talks to the benchmark over its standard input and output, one JSON
object a line.  It says ``ready`` once every stream holds the traffic's
``records_ready_per_tenant``; it is told the server's ``ports`` and starts
the traffic; it is told the measured ``window`` ``[t0, t1]``
(``time.monotonic`` seconds, a clock both processes share) and sends
nothing due at or after ``t1``; it then
waits up to ``grace_s`` for every ack and for the estimate of every window
the acks closed, says ``drained``, and, told ``reference``, replays the
acked records through the plain reference (``bench/reference.py``), writes
everything to ``<out>/run.npz`` and says ``done``.
"""
from __future__ import annotations

import asyncio
import gc
import json
import os
import sys
import time
from collections import deque

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.reference import replay  # noqa: E402
from bench.streams import TenantStream, stream_params  # noqa: E402


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def encode_push(seq: int, cols: tuple) -> bytes:
    tau, ei, ej = cols
    return (json.dumps({"type": "push", "seq": seq, "records": {
        "tau": tau.tolist(), "i": ei.tolist(), "j": ej.tolist()}},
        separators=(",", ":")) + "\n").encode()


async def hello(host: str, port: int, token: str):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write((json.dumps({"type": "hello", "token": token}) + "\n")
                 .encode())
    reply = json.loads(await reader.readline())
    if reply.get("type") != "hello_ok":
        raise RuntimeError(f"hello refused for {token}: {reply}")
    return reader, writer


class Tenant:
    """One tenant's pushes, acks and received estimates."""

    def __init__(self, s: int, stream: TenantStream):
        self.s = s
        self.stream = stream
        self.push_rows: list[tuple] = []     # k, n, due, sent
        self.acks: dict[int, tuple] = {}     # k -> (t, ok, windows_closed)
        self.est_rows: list[tuple] = []
        self.sending = True

    def record_ack(self, k: int, reply: dict) -> None:
        ok = reply.get("type") == "ack"
        self.acks[k] = (time.monotonic(), ok,
                        int(reply.get("windows_closed", 0)) if ok else 0)

    def closed_by_acks(self) -> int:
        return sum(w for _, ok, w in self.acks.values() if ok)


class LoadGen:
    def __init__(self, spec: dict):
        cfg, traffic = spec["config"], spec["traffic"]
        self.cfg, self.traffic = cfg, traffic
        self.seed = int(spec["seed"])
        self.out = spec["out"]
        self.push_records = int(cfg["push_records"])
        n = int(cfg["tenants"])
        self.tenants = [Tenant(s, TenantStream(stream_params(cfg),
                                               cfg["nt_w"], self.seed, s,
                                               self.push_records))
                        for s in range(n)]
        self.t1: float | None = None
        self.t0: float | None = None
        self.drain_end: float | None = None

    # -- traffic ------------------------------------------------------------

    async def closed_loop(self, t: Tenant, reader, writer) -> None:
        k = 0
        while self.t1 is None or time.monotonic() < self.t1:
            line = encode_push(k + 1, t.stream.push(k))
            due = time.monotonic()
            writer.write(line)
            t.push_rows.append((k, self.push_records, due, due))
            raw = await reader.readline()
            if not raw:
                break
            t.record_ack(k, json.loads(raw))
            k += 1
        t.sending = False

    async def open_loop(self, t: Tenant, reader, writer, start: float,
                        interval: float) -> None:
        sent: deque = deque()

        async def acks():
            while t.sending or sent:
                raw = await reader.readline()
                if not raw:
                    return
                t.record_ack(sent.popleft(), json.loads(raw))

        ack_task = asyncio.create_task(acks())
        phase = interval * t.s / len(self.tenants)
        k = 0
        while True:
            due = start + phase + k * interval
            if self.t1 is not None and due >= self.t1:
                break
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            line = encode_push(k + 1, t.stream.push(k))
            sent.append(k)
            writer.write(line)
            t.push_rows.append((k, self.push_records, due, time.monotonic()))
            k += 1
        t.sending = False
        if not sent:
            ack_task.cancel()
        try:
            await ack_task
        except asyncio.CancelledError:
            pass

    async def subscribe(self, t: Tenant, reader) -> None:
        while True:
            raw = await reader.readline()
            if not raw:
                return
            now = time.monotonic()
            msg = json.loads(raw)
            if msg.get("type") == "estimate":
                t.est_rows.append((msg["window"], now, msg["count"],
                                   msg["estimate"], msg["cum_sgrs"],
                                   msg["end_tau"]))

    def all_published(self) -> bool:
        return all(len({r[0] for r in t.est_rows}) >= t.closed_by_acks()
                   for t in self.tenants)

    async def drive(self, host: str, port: int, commands) -> dict:
        conns = [await hello(host, port, f"tenant{t.s}")
                 for t in self.tenants]
        subs = []
        for t in self.tenants:
            r, w = await hello(host, port, f"tenant{t.s}")
            w.write(b'{"type":"subscribe"}\n')
            reply = json.loads(await r.readline())
            if reply.get("type") != "subscribed":
                raise RuntimeError(f"subscribe refused: {reply}")
            subs.append((r, w))
        sub_tasks = [asyncio.create_task(self.subscribe(t, r))
                     for t, (r, _) in zip(self.tenants, subs)]
        start = time.monotonic()
        if self.traffic["loop"] == "closed":
            senders = [self.closed_loop(t, r, w)
                       for t, (r, w) in zip(self.tenants, conns)]
        else:
            per_tenant = float(self.traffic["rate_edges_per_s"]) / len(
                self.tenants)
            interval = self.push_records / per_tenant
            senders = [self.open_loop(t, r, w, start, interval)
                       for t, (r, w) in zip(self.tenants, conns)]
        emit({"traffic_started": start})
        send_task = asyncio.gather(*senders)
        # the benchmark names the window once the warm-up is over
        cmd = await commands.get()
        self.t0, self.t1 = cmd["window"]
        grace = float(self.traffic["grace_s"])
        try:
            await asyncio.wait_for(send_task,
                                   self.t1 - time.monotonic() + grace)
        except asyncio.TimeoutError:
            pass
        while (not self.all_published()
               and time.monotonic() < self.t1 + grace):
            await asyncio.sleep(0.01)
        self.drain_end = time.monotonic()
        emit({"drained": self.drain_end, "lateness": self.lateness()})
        cmd = await commands.get()            # the server has stopped
        for task in sub_tasks:
            task.cancel()
        for _, w in conns + subs:
            w.close()
        return cmd

    def lateness(self) -> dict:
        """How late the generator sent the pushes due in the window (ms)."""
        late = [(sent - due) * 1e3 for t in self.tenants
                for _, _, due, sent in t.push_rows
                if self.t0 <= due < self.t1]
        if not late:
            return {"n": 0}
        a = np.sort(np.asarray(late))
        return {"n": len(late), "p50_ms": float(np.median(a)),
                "p99_ms": float(a[min(len(a) - 1, int(0.99 * len(a)))]),
                "max_ms": float(a[-1])}

    # -- the reference and the record -----------------------------------------

    def write(self) -> str:
        cfg = self.cfg
        push_cols = {n: [] for n in ("tenant", "k", "n", "due", "sent",
                                     "ack", "ok", "windows_closed")}
        est_cols = {n: [] for n in ("tenant", "window", "arrival", "count",
                                    "estimate", "cum_sgrs", "end_tau")}
        ref_cols = {n: [] for n in ("tenant", "window", "count", "cum_sgrs",
                                    "end_tau", "estimate", "n_i", "n_j",
                                    "close_due")}
        for t in self.tenants:
            acked = []
            for k, n, due, sent in t.push_rows:
                at, ok, wc = t.acks.get(k, (np.nan, False, 0))
                for name, v in zip(push_cols, (t.s, k, n, due, sent, at, ok,
                                               wc)):
                    push_cols[name].append(v)
                if ok:
                    acked.append((k, due))
            for row in t.est_rows:
                est_cols["tenant"].append(t.s)
                for name, v in zip(list(est_cols)[1:], row):
                    est_cols[name].append(v)
            cols = [t.stream.push(k) for k, _ in acked]
            if cols:
                tau, ei, ej = (np.concatenate(c) for c in zip(*cols))
            else:
                tau, ei, ej = np.zeros(0), np.zeros(0, int), np.zeros(0, int)
            ref = replay(tau, ei, ej, int(cfg["nt_w"]), float(cfg["alpha0"]))
            # the push that carried each window's closing record
            offs = np.cumsum([self.push_records] * len(acked))
            pos = np.searchsorted(offs, ref["close_record"], side="right")
            dues = np.asarray([d for _, d in acked])
            n_closed = ref["count"].shape[0]
            ref_cols["tenant"].extend([t.s] * n_closed)
            ref_cols["window"].extend(range(n_closed))
            ref_cols["close_due"].extend(dues[pos] if n_closed else [])
            for name in ("count", "cum_sgrs", "end_tau", "estimate", "n_i",
                         "n_j"):
                ref_cols[name].extend(ref[name])
        path = os.path.join(self.out, "run.npz")
        arrays = {f"push_{k}": np.asarray(v) for k, v in push_cols.items()}
        arrays.update({f"est_{k}": np.asarray(v) for k, v in est_cols.items()})
        arrays.update({f"ref_{k}": np.asarray(v) for k, v in ref_cols.items()})
        arrays["closed_by_acks"] = np.asarray(
            [t.closed_by_acks() for t in self.tenants])
        arrays["drain_end"] = np.asarray(self.drain_end)
        np.savez(path, **arrays)
        return path


async def amain(spec: dict) -> None:
    gen = LoadGen(spec)
    # draw the segments a run sends before the traffic starts: a segment
    # drawn while it runs stalls every tenant's schedule at once
    ready = int(gen.traffic["records_ready_per_tenant"])
    for t in gen.tenants:
        t.stream.push(max(ready // gen.push_records - 1, 0))
    loop = asyncio.get_running_loop()
    stdin = asyncio.StreamReader()
    await loop.connect_read_pipe(lambda: asyncio.StreamReaderProtocol(stdin),
                                 sys.stdin)
    commands: asyncio.Queue = asyncio.Queue()

    async def read_commands():
        while True:
            raw = await stdin.readline()
            if not raw:
                await commands.put({"eof": True})
                return
            await commands.put(json.loads(raw))

    reader_task = asyncio.create_task(read_commands())
    emit({"ready": True})
    cmd = await commands.get()
    if "port" not in cmd:
        return
    cmd = await gen.drive(cmd["host"], cmd["port"], commands)
    if cmd.get("reference"):
        emit({"done": gen.write()})
    reader_task.cancel()


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        spec = json.load(f)
    # a collection of the generator's growing tables would stall its
    # schedule; a run makes few reference cycles and ends in minutes
    gc.disable()
    asyncio.run(amain(spec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
