"""One tenant's record stream, cut into pushes, made from the seed.

A tenant's stream is a run of segments, each drawn fresh from the
configuration's family with a seed of its own, made from the run's seed,
the tenant and the segment's place in the run.  A segment is cut to its
last whole window of ``nt_w`` unique timestamps, so no window spans two
segments, and its timestamps are shifted past the segment before it.  Two
runs with one seed send the same records; two seeds send different ones,
with the sizes the family gives.
"""
from __future__ import annotations

import importlib

import numpy as np

# timestamps of one segment lie in [0, TAU_SPAN): the families draw them
# over [0, 1e6) and the wave mode moves them by at most 1e5
TAU_SPAN = 2.0e6
STREAM_TAG = 0x5EED


def whole_windows(tau: np.ndarray, nt_w: int) -> tuple[int, np.ndarray]:
    """(records in whole windows, start record of each whole window)."""
    new_ts = np.r_[True, tau[1:] != tau[:-1]]
    ordinal = np.cumsum(new_ts) - 1
    n_win = int(ordinal[-1] + 1) // nt_w
    starts = np.searchsorted(ordinal, np.arange(n_win) * nt_w)
    end = int(np.searchsorted(ordinal, n_win * nt_w))
    return end, starts


def stream_params(cfg: dict) -> dict:
    """The family's parameters for a configuration: its ``stream`` group
    with the universe sizes and the segment length from the top level."""
    return dict(cfg["stream"], n_users=int(cfg["users"]),
                n_items=int(cfg["items"]),
                segment_records=int(cfg["records"]))


def segment(stream_cfg: dict, nt_w: int, key: list[int]) -> tuple:
    """One segment of whole windows, timestamps from 0, drawn from the
    configuration's family with the seed made from ``key``."""
    params = dict(stream_cfg)
    family = importlib.import_module(f"bench.families.{params.pop('family')}")
    n = int(params.pop("segment_records"))
    ss = np.random.SeedSequence([STREAM_TAG] + [int(k) % 2**64 for k in key])
    seed = int(ss.generate_state(2, np.uint32).view(np.uint64)[0])
    tau, ei, ej = family.generate(n, seed, **params)
    end, _ = whole_windows(tau, nt_w)
    return tau[:end], ei[:end], ej[:end]


def segment_windows(stream_cfg: dict, nt_w: int, key: list[int]) -> list:
    """The ``[m, 2]`` (i, j) records of each whole window of a segment."""
    tau, ei, ej = segment(stream_cfg, nt_w, key)
    end, starts = whole_windows(tau, nt_w)
    bounds = np.r_[starts, end]
    edges = np.stack([ei, ej], axis=1)
    return [edges[a:b] for a, b in zip(bounds[:-1], bounds[1:])]


class TenantStream:
    def __init__(self, stream_cfg: dict, nt_w: int, seed: int, tenant: int,
                 push_records: int):
        self.stream_cfg = dict(stream_cfg)
        self.nt_w, self.push_records = int(nt_w), int(push_records)
        self.seed, self.tenant = int(seed), int(tenant)
        self.tau = np.zeros(0)
        self.ei = np.zeros(0, dtype=np.int64)
        self.ej = np.zeros(0, dtype=np.int64)
        self.passes = 0

    def _grow(self) -> None:
        tau, ei, ej = segment(self.stream_cfg, self.nt_w,
                              [self.seed, self.tenant, self.passes])
        self.tau = np.concatenate([self.tau, tau + self.passes * TAU_SPAN])
        self.ei = np.concatenate([self.ei, ei])
        self.ej = np.concatenate([self.ej, ej])
        self.passes += 1

    def push(self, k: int) -> tuple:
        """Columns of push ``k`` (records ``[k * P, (k + 1) * P)``)."""
        lo, hi = k * self.push_records, (k + 1) * self.push_records
        while self.tau.shape[0] < hi:
            self._grow()
        return self.tau[lo:hi], self.ei[lo:hi], self.ej[lo:hi]
