"""The reference against the program's own oracle, and the comparison."""
import json
import os

import numpy as np
import pytest

from bench.families import synthetic_rating
from bench.reference import compare, replay, window_bounds
from bench.streams import TenantStream, stream_params

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("temporal", ["uniform", "wave"])
@pytest.mark.parametrize("seed", [0, 5, 2**33 + 1])
def test_families_match_the_program_generators(seed, temporal):
    from repro.streams.generators import synthetic_rating_stream

    st = synthetic_rating_stream(n_users=4096, n_items=16384, n_edges=4096,
                                 item_exponent=1.2, user_exponent=1.1,
                                 temporal=temporal, n_unique=2048, seed=seed)
    got = synthetic_rating.generate(4096, seed, n_users=4096, n_items=16384,
                                    user_exponent=1.1, item_exponent=1.2,
                                    temporal=temporal, edges_per_timestamp=2)
    for a, b in zip(got, (st.tau, st.edge_i, st.edge_j)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("config,pushes",
                         [("movielens-win8k", 12), ("netflix-win512", 40)])
def test_replay_matches_the_program_oracle(config, pushes):
    from repro.streams.oracle import oracle_window_counts, replay_dynamic

    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    st = TenantStream(stream_params(cfg), cfg["nt_w"], 11, 0,
                      cfg["push_records"])
    tau, ei, ej = (np.concatenate(c) for c in zip(
        *[st.push(k) for k in range(pushes)]))
    ref = replay(tau, ei, ej, cfg["nt_w"], cfg["alpha0"])
    windows = replay_dynamic(tau, ei, ej, nt_w=cfg["nt_w"])
    n = ref["count"].shape[0]
    assert n >= 2
    np.testing.assert_array_equal(ref["count"],
                                  oracle_window_counts(windows)[:n])
    np.testing.assert_array_equal(ref["cum_sgrs"],
                                  np.cumsum([w.n_sgrs for w in windows])[:n])
    np.testing.assert_array_equal(ref["end_tau"],
                                  [w.end_tau for w in windows[:n]])


def test_windows_close_at_the_next_unique_timestamp():
    tau = np.array([1.0, 1.0, 2.0, 3.0, 3.0, 4.0, 5.0])
    np.testing.assert_array_equal(window_bounds(tau, 2), [0, 3, 6])


def test_replay_counts_one_butterfly_and_the_estimate():
    # window 0: the 2x2 biclique (one butterfly) plus a duplicate edge;
    # window 1: a path; window 2 stays open
    tau = np.array([1., 1., 1., 1., 1., 2., 2., 3., 3.])
    ei = np.array([0, 0, 1, 1, 1, 5, 5, 0, 0])
    ej = np.array([0, 1, 0, 1, 1, 7, 8, 0, 1])
    ref = replay(tau, ei, ej, 1, 1.0)
    np.testing.assert_array_equal(ref["count"], [1, 0])
    np.testing.assert_array_equal(ref["cum_sgrs"], [5, 7])
    np.testing.assert_array_equal(ref["n_i"], [2, 1])
    np.testing.assert_array_equal(ref["n_j"], [2, 2])
    # B_0 = 1; B_1 = 1 + 0 + 7**1
    np.testing.assert_array_equal(ref["estimate"], [1.0, 8.0])


def _pub(ref, n=None):
    n = ref["count"].shape[0] if n is None else n
    return {"window": np.arange(n),
            **{k: ref[k][:n].copy() for k in
               ("count", "estimate", "cum_sgrs", "end_tau")}}


def _ref():
    tau = np.repeat(np.arange(40.0), 3)
    rng = np.random.default_rng(0)
    ei, ej = rng.integers(0, 6, 120), rng.integers(0, 6, 120)
    return replay(tau, ei, ej, 4, 1.0)


def test_compare_finds_nothing_in_the_reference_itself():
    ref = _ref()
    got = compare([_pub(ref)], [ref], [ref["count"].shape[0]])
    assert got == {"windows_wrong": 0, "windows_missing": 0,
                   "closed_gap": 0, "estimate_rel_gap": 0.0}


@pytest.mark.parametrize("fault,key", [
    ("count", "windows_wrong"), ("cum_sgrs", "windows_wrong"),
    ("twice", "windows_wrong"), ("missing", "windows_missing"),
    ("closed", "closed_gap"), ("estimate", "estimate_rel_gap")])
def test_compare_finds_each_fault(fault, key):
    ref = _ref()
    pub, closed = _pub(ref), ref["count"].shape[0]
    if fault in ("count", "cum_sgrs"):
        pub[fault][3] += 1
    elif fault == "twice":
        pub = {k: np.r_[v, v[-1:]] for k, v in pub.items()}
    elif fault == "missing":
        pub = _pub(ref, closed - 1)
    elif fault == "closed":
        closed += 1
    else:
        pub["estimate"][5] *= 1.001
    got = compare([pub], [ref], [closed])
    assert got[key] > 0
    assert all(v == 0 for k, v in got.items() if k != key)
