"""Warm the bucket programs a configuration's streams reach, in set-up.

The executor compiles one program per bucket shape: each window's edge,
id-space and wedge rungs.  Streams drawn fresh from every seed bring
shapes of their own, so a warm-up on the run's own traffic alone leaves
programs to compile inside the measured window.  Set-up therefore plans,
with the program's own ``WindowExecutor.plan``, every window of a fixed
sample of the configuration's streams (the same sample in every run), takes
the rungs each dimension reached, and dispatches one made-up window for
every combination of them through ``WindowExecutor.window_counts``: the
programs a served window of those rungs runs, compiled or loaded from the
persistent cache before the window opens.  A window whose rung lies outside
the sample still compiles in the window, and ``programs_compiled_in_window``
counts it.
"""
from __future__ import annotations

import itertools

import numpy as np

from bench.streams import segment_windows

LADDER_TAG = 0x1ADD


def pack(edges: np.ndarray, align: int):
    """One window packed as the engine packs a flush of it."""
    from repro.core.windows import pack_windows

    m = edges.shape[0]
    return pack_windows([edges], n_sgrs=np.array([m]), cum_sgrs=np.array([m]),
                        window_end_tau=np.zeros(1), align=align)


def bucket_key(executor, batch) -> tuple:
    """(cap_e, cap_i, cap_j, cap_w) of a one-window batch's bucket."""
    b = executor.plan(batch)[0]
    return (b.cap_e, b.cap_i, b.cap_j, b.cap_w)


def sampled_rungs(executor, stream_cfg: dict, nt_w: int, align: int,
                  streams: int) -> list[list[int]]:
    """The rungs each key dimension reached over every window of
    ``streams`` sample segments."""
    seen: list[set] = [set(), set(), set(), set()]
    for g in range(streams):
        for edges in segment_windows(stream_cfg, nt_w, [LADDER_TAG, g]):
            for d, v in enumerate(bucket_key(executor, pack(edges, align))):
                seen[d].add(int(v))
    return [sorted(s) for s in seen]


def wedges(n_hub: int, d: int, rest: int, n_j: int) -> int:
    """Wedges of :func:`window`'s layout: ``n_hub`` items of degree ``d``,
    ``rest`` more edges spread evenly over the other items."""
    others = n_j - n_hub
    q, r = divmod(rest, others)
    return (n_hub * d * (d - 1) // 2 + r * (q + 1) * q // 2
            + (others - r) * q * (q - 1) // 2)


def window(cap_e: int, cap_i: int, cap_j: int, cap_w: int, align: int):
    """Records of a window with ``cap_e`` distinct edges over ``cap_i``
    users and ``cap_j`` items whose deduped wedge count climbs the wedge
    ladder (rungs ``align * 2**k``) to ``cap_w``, or None where this layout
    cannot: a few hub items joined to the first ``d`` users, the other edges
    spread over the other items and users in turn.  ``cap_w`` 0 (a tier
    with no wedge rung) takes any wedge count."""
    if cap_j < 2 or cap_e < max(cap_i, cap_j):
        return None
    top = cap_w if cap_w else cap_e * cap_e
    floor = cap_w // 2 if cap_w > align else -1
    for n_hub in range(1, min(8, cap_j - 1) + 1):
        hi = min(cap_i, (cap_e - (cap_j - n_hub)) // n_hub)
        if n_hub > 1:
            hi = min(hi, (cap_e - cap_i) // (n_hub - 1))
        if hi < 0:
            break
        lo = 0
        while lo < hi:                       # largest d with wedges <= top
            mid = (lo + hi + 1) // 2
            if wedges(n_hub, mid, cap_e - n_hub * mid, cap_j) <= top:
                lo = mid
            else:
                hi = mid - 1
        w = wedges(n_hub, lo, cap_e - n_hub * lo, cap_j)
        if floor < w <= top:
            d, rest = lo, cap_e - n_hub * lo
            hub = np.stack(np.meshgrid(np.arange(d), np.arange(n_hub),
                                       indexing="ij"), axis=-1).reshape(-1, 2)
            t = np.arange(rest)
            tail = np.stack([(d + t) % cap_i,
                             n_hub + t % (cap_j - n_hub)], axis=1)
            return np.concatenate([hub, tail]).astype(np.int64)
    return None


def warm(executor, stream_cfg: dict, nt_w: int, align: int,
         streams: int) -> dict:
    """Dispatch one window of every combination of the sampled rungs;
    returns what was warmed and what no made-up window reached."""
    rungs = sampled_rungs(executor, stream_cfg, nt_w, align, streams)
    warmed, missed = [], []
    for key in itertools.product(*rungs):
        edges = window(*key, align)
        batch = pack(edges, align) if edges is not None else None
        if batch is None or bucket_key(executor, batch) != key:
            missed.append(list(key))
            continue
        executor.window_counts(batch)
        warmed.append(list(key))
    return {"rungs": rungs, "warmed": len(warmed), "missed": missed}
