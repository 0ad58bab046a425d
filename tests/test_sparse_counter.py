"""The sparse tier's wedge lane, on its own.

Both twins (``count_butterflies_sparse`` and its multiset twin) are checked
bit for bit against the numpy oracle and the dense tier on the edge cases of
the wedge lane: no wedges at all, a wedge capacity exactly at and far above
the wedge count, lanes that are all duplicates, one hub column holding every
edge, and a ``vmap`` batch of windows with different fills.  A structural
test keeps binary searches (``while`` loops) and ``cummax`` / ``cumsum``
(``reduce_window``) off the wedge lane of the program lowered for the TPU.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.butterfly import (
    count_butterflies_from_edges,
    count_butterflies_from_edges_multiset,
    count_butterflies_multiset_np,
    count_butterflies_np,
    count_butterflies_sparse,
    count_butterflies_sparse_multiset,
    window_wedge_counts_np,
)

CAP_E = 64


def _rand(n_i, n_j, m, seed):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, n_i, m), rng.integers(0, n_j, m)], 1)


_K34 = [(i, j) for i in range(3) for j in range(4)]

# each case: the windows of one vmap batch (edge lists, duplicates allowed)
# and the batch's wedge capacity as a function of its largest wedge count
CASES = {
    "zero_wedges": ([[(0, j) for j in range(20)], []], lambda w: 1),
    "cap_exact": ([_rand(12, 10, 40, seed=1)], lambda w: w),
    "cap_far_above": ([_rand(12, 10, 40, seed=1)], lambda w: 16 * w + 1000),
    "all_duplicates": ([(_K34 * 6)[:CAP_E]], lambda w: w),
    "hub_column": ([[(i, 0) for i in range(CAP_E)]], lambda w: w),
    "vmap_batch": ([_rand(9, 7, 5, seed=2), _rand(9, 7, 30, seed=3),
                    _rand(20, 6, CAP_E, seed=4), [], _K34],
                   lambda w: w + 3),
}


def _lanes(edge_lists, multiset):
    """Padded [B, CAP_E] lanes of the windows.  The multiset twin takes
    unique (i, j) lanes: each window's duplicates are resolved to a
    multiplicity (occurrences, plus a weight from the ids so that windows
    without duplicates are weighted too)."""
    rows = []
    for edges in edge_lists:
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        m = np.ones(len(e), np.int64)
        if multiset and len(e):
            e, m = np.unique(e, axis=0, return_counts=True)
            m = m + (7 * e[:, 0] + e[:, 1]) % 3
        rows.append((e, m))
    ei, ej, mm = (np.zeros((len(rows), CAP_E), np.int32) for _ in range(3))
    valid = np.zeros((len(rows), CAP_E), bool)
    for k, (e, m) in enumerate(rows):
        ei[k, :len(e)], ej[k, :len(e)], mm[k, :len(e)] = e[:, 0], e[:, 1], m
        valid[k, :len(e)] = True
    return rows, ei, ej, mm, valid


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _sparse(ei, ej, v, n_i, n_j, cap_w):
    return jax.vmap(lambda a, b, c: count_butterflies_sparse(
        a, b, c, n_i, n_j, cap_w))(ei, ej, v)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _sparse_multiset(ei, ej, mm, v, n_i, n_j, cap_w):
    return jax.vmap(lambda a, b, m, c: count_butterflies_sparse_multiset(
        a, b, m, c, n_i, n_j, cap_w))(ei, ej, mm, v)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _dense(ei, ej, v, n_i, n_j):
    return jax.vmap(lambda a, b, c: count_butterflies_from_edges(
        a, b, c, n_i, n_j))(ei, ej, v)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _dense_multiset(ei, ej, mm, v, n_i, n_j):
    return jax.vmap(lambda a, b, m, c: count_butterflies_from_edges_multiset(
        a, b, m, c, n_i, n_j))(ei, ej, mm, v)


@pytest.mark.parametrize("multiset", [False, True],
                         ids=["distinct", "multiset"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_sparse_counter_matches_oracle_and_dense(case, multiset):
    edge_lists, cap_rule = CASES[case]
    rows, ei, ej, mm, valid = _lanes(edge_lists, multiset)
    n_i, n_j = int(ei.max()) + 2, int(ej.max()) + 3
    wedges = window_wedge_counts_np(ei, ej, valid)
    cap_w = max(cap_rule(int(wedges.max())), 1)
    if case == "cap_exact":
        assert cap_w == wedges[0] and cap_w % 128   # a ragged last row
    if multiset:
        want = [count_butterflies_multiset_np(e, m) for e, m in rows]
        got = _sparse_multiset(ei, ej, mm, valid, n_i, n_j, cap_w)
        dense = _dense_multiset(ei, ej, mm, valid, n_i, n_j)
    else:
        want = [count_butterflies_np(e) for e, _ in rows]
        got = _sparse(ei, ej, valid, n_i, n_j, cap_w)
        dense = _dense(ei, ej, valid, n_i, n_j)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want, float))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))
    if case in ("all_duplicates", "vmap_batch"):
        assert max(want) > 0


def test_edge_cases_reach_what_they_name():
    """The cases hold what their names say, so a change to the data cannot
    quietly turn one into another."""
    def wedges(case):
        _, ei, ej, _, valid = _lanes(CASES[case][0], False)
        return window_wedge_counts_np(ei, ej, valid)

    assert wedges("zero_wedges").tolist() == [0, 0]
    assert wedges("hub_column").tolist() == [CAP_E * (CAP_E - 1) // 2]
    assert min(wedges("vmap_batch")) == 0 < max(wedges("vmap_batch"))
    _, _, _, _, valid = _lanes(CASES["vmap_batch"][0], False)
    assert len(set(valid.sum(axis=1).tolist())) == 5     # five fills


@pytest.mark.parametrize("multiset", [False, True],
                         ids=["distinct", "multiset"])
def test_wedge_lane_lowers_without_search_loops_or_reduce_window(multiset):
    """Lowered for the TPU, nothing on the wedge lane is a ``while`` (the
    loop ``searchsorted`` lowers to, one gather pass over the lane per
    step) or a ``reduce_window`` (what ``cummax`` and ``cumsum`` lower to
    there, which takes the TPU compiler tens of seconds at 2**20 slots).
    The edge lane's ``cummax`` and ``cumsum`` stay: the lanes differ in
    length, so each op is matched on the wedge lane's."""
    cap_e, wedge_lane = 8192, 2 ** 16
    lane = jax.ShapeDtypeStruct((cap_e,), jnp.int32)
    args = [lane, lane] + [lane] * multiset
    args.append(jax.ShapeDtypeStruct((cap_e,), jnp.bool_))
    fn = count_butterflies_sparse_multiset if multiset else \
        count_butterflies_sparse
    counter = jax.jit(lambda *a: fn(*a, 4096, cap_e, wedge_lane))
    text = counter.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    ops = [ln for ln in text.splitlines()
           if "stablehlo.while" in ln or "reduce_window" in ln]

    def on(n, ln):                # n as a whole number: tensor<65536xi32>
        return re.search(rf"(?<!\d){n}(?!\d)", ln)

    on_lane = [ln for ln in ops if on(wedge_lane, ln)]
    assert not on_lane, on_lane
    # the edge lane's running max and sum are still there, and still found
    assert any(on(cap_e, ln) for ln in ops)


@pytest.mark.parametrize("n", [1, 2, 127, 128, 129, 300, 16384, 16385])
def test_running_scan_matches_numpy(n):
    """The wedge lane's running max and sum, across the 128-lane rows it is
    blocked by and a ragged last row, for ints and floats."""
    from repro.core.butterfly import _running

    running = jax.jit(_running, static_argnums=0)
    x = np.random.default_rng(n).integers(-5, 1000, n).astype(np.int32)
    for v in (x, x.astype(np.float32)):
        np.testing.assert_array_equal(np.asarray(running(jax.lax.max, v)),
                                      np.maximum.accumulate(v))
        np.testing.assert_array_equal(np.asarray(running(jax.lax.add, v)),
                                      np.cumsum(v))
