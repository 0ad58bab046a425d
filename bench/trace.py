"""Reduce a profiler trace (``.xplane.pb``) to the device numbers.

- busy: the union of the intervals in which an operation ran on a device,
  inside the window that the benchmark marks with a host span named
  :data:`WINDOW_SPAN`, averaged over the devices that ran anything;
- ops: device time by operation name, most first;
- gaps: the device's idle gaps inside the window, each named by the host
  span that overlapped it most, longest first.

Device planes are those named ``/device:<kind>:<n>``.  On each, the line of
single operations (``XLA Ops``) is read where the plane has one, and every
line otherwise.
"""
from __future__ import annotations

import glob
import os

import numpy as np

WINDOW_SPAN = "bench_window"
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted ``[start, end)`` rows of possibly overlapping ones."""
    if iv.shape[0] == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if iv.shape[0] == 0:
        return iv
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def load(path: str) -> tuple[dict, list]:
    """(device planes {name: [(op, start_ns, end_ns)]}, host spans
    [(thread, name, start_ns, end_ns)]) of one trace file."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            use = [ln for ln in lines if ln.name == OPS_LINE] or lines
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for ln in use for e in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend((ln.name, e.name, e.start_ns,
                         e.start_ns + e.duration_ns)
                        for ln in lines for e in ln.events)
    return devices, host


def reduce(devices: dict, host: list) -> dict | None:
    """The numbers of one trace; None when no device ran anything."""
    marks = [(s, e) for _, name, s, e in host if name == WINDOW_SPAN]
    if marks:
        lo, hi = marks[0]
    else:
        ends = [x for evs in devices.values() for _, s, e in evs
                for x in (s, e)]
        ends += [x for _, _, s, e in host for x in (s, e)]
        if not ends:
            return None
        lo, hi = min(ends), max(ends)
    window_ns = hi - lo
    busy, per_op, gaps_all = [], {}, []
    for evs in devices.values():
        iv = _clip(np.asarray([(s, e) for _, s, e in evs], np.float64),
                   lo, hi)
        if iv.shape[0] == 0:
            continue
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0.0) + d
        edges = np.r_[lo, u.ravel(), hi].reshape(-1, 2)
        gaps_all.extend((s, e) for s, e in edges if e > s)
    if not busy:
        return None
    # what the host was doing: any span but the mark and those that hold
    # the whole window (an event loop's wait, the tracer's own)
    spans = [(f"{th}:{name}", s, e) for th, name, s, e in host
             if name != WINDOW_SPAN and not (s <= lo and e >= hi)]
    gaps = []
    for s, e in sorted(gaps_all, key=lambda g: g[0] - g[1])[:TOP]:
        best, label = 0.0, "no host span"
        for name, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, name
        gaps.append([label, (e - s) * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": float(np.mean(busy)) * 1e-9,
            "window_s": window_ns * 1e-9,
            "device_ops": [[n, v * 1e-9] for n, v in ops],
            "idle_gaps": gaps}
