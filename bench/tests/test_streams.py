"""Streams are made from the seed, of whole windows, fresh for every seed."""
import json
import os

import numpy as np

from bench.reference import replay
from bench.streams import TAU_SPAN, TenantStream, stream_params, whole_windows

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name, **top):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(top)
    return cfg


def records(cfg, seed, tenant, pushes):
    st = TenantStream(stream_params(cfg), cfg["nt_w"], seed, tenant,
                      cfg["push_records"])
    return [np.concatenate(c) for c in zip(*[st.push(k)
                                              for k in range(pushes)])]


def test_the_same_seed_gives_the_same_records():
    cfg = config("netflix-win512", records=4096)
    a, b = records(cfg, 2**32 + 3, 5, 40), records(cfg, 2**32 + 3, 5, 40)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    c = records(cfg, 2**32 + 4, 5, 40)
    assert not np.array_equal(a[1], c[1])
    d = records(cfg, 2**32 + 3, 6, 40)        # another tenant
    assert not np.array_equal(a[1], d[1])


def test_segments_hold_whole_windows():
    cfg = config("netflix-win512", records=4096)
    tau, ei, ej = records(cfg, 9, 0, 40)      # 10240 records, 3 segments
    assert np.all(np.diff(tau) >= 0)
    end, starts = whole_windows(tau, cfg["nt_w"])
    # every window lies in one segment: its timestamps share a segment
    seg = (tau // TAU_SPAN).astype(int)
    assert seg[-1] >= 2
    for a, b in zip(starts[:-1], starts[1:]):
        assert seg[a] == seg[b - 1]


def test_every_seed_draws_windows_of_its_own():
    cfg = config("movielens-win8k", records=8192, nt_w=256,
                 push_records=128)

    def windows(seed):
        tau, ei, ej = records(cfg, seed, 0, 48)
        return tau, replay(tau, ei, ej, cfg["nt_w"], 1.0)

    (ta, a), (tb, b) = windows(11), windows(2**33 + 12)
    assert a["count"].shape[0] >= 10 and b["count"].shape[0] >= 10
    # fresh draws: other records, windows of alike sizes
    assert not np.array_equal(ta, tb)
    assert not np.array_equal(a["count"], b["count"])
    assert 0.8 < a["n_i"].mean() / b["n_i"].mean() < 1.25
