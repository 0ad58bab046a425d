"""Hypothesis property tests on the jitted FLEET reservoir and the sampled
streaming path.

The load-bearing invariant is *chunking-independence*: every edge owns one
content-keyed uniform for its whole lifetime, so the reservoir an ingested
prefix leaves behind is a pure function of (distinct edge set, seed,
capacity, gamma) — never of how the prefix was sliced into chunks,
micro-batches, or checkpoint halves.  The suite also pins the hard
occupancy bound (never ``capacity + 1`` resident edges, not even
transiently observable), the equivalence of in-scan dedupe with host-side
pre-dedupe, and basic sanity of the estimates (finite, non-negative).

``hypothesis`` is an optional test dependency; without it this module
skips at collection.  Draws are shaped to reuse a handful of static jit
signatures (fixed lane counts, a small capacity/gamma set) so the suite
spends its budget on cases, not compiles.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from jax import random as jrandom

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.fleet import (  # noqa: E402
    _reservoir_scan,
    edge_uniforms,
    gamma_ladder,
    reservoir_ingest,
    reservoir_init,
    reservoir_run,
)
from repro.core.executor import WindowExecutor  # noqa: E402
from repro.streams import StreamingSGrapp, synthetic_rating_stream  # noqa: E402

LANES = 64          # one static ingest shape for every drawn stream
CAPS = (4, 16)      # two static reservoir shapes
GAMMA = 0.7


@st.composite
def dup_heavy_edges(draw, max_m=LANES):
    """A small-id-space edge stream with heavy duplication (ids in an 8x6
    grid, so repeats are the norm, not the exception)."""
    m = draw(st.integers(0, max_m))
    ii = draw(st.lists(st.integers(0, 7), min_size=m, max_size=m))
    jj = draw(st.lists(st.integers(0, 5), min_size=m, max_size=m))
    return np.asarray(ii, np.int64), np.asarray(jj, np.int64)


def pad_lanes(ei, ej, n=LANES):
    m = len(ei)
    li = np.zeros(n, np.int32); li[:m] = ei
    lj = np.zeros(n, np.int32); lj[:m] = ej
    lv = np.zeros(n, bool); lv[:m] = True
    return li, lj, lv


def resident_set(res):
    v = np.asarray(res.valid)
    return set(zip(np.asarray(res.edge_i)[v].tolist(),
                   np.asarray(res.edge_j)[v].tolist()))


# -- reservoir invariants ------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(dup_heavy_edges(), st.sampled_from(CAPS), st.integers(0, 3))
def test_occupancy_bound_and_estimate_sanity(edges, capacity, seed):
    ei, ej = edges
    est, res = reservoir_run(ei, ej, capacity=capacity, gamma=GAMMA,
                             seed=seed, chunk=LANES)
    assert int(np.asarray(res.valid).sum()) <= capacity
    assert int(res.k) >= 0
    assert np.isfinite(est) and est >= 0.0
    # invalid lanes carry u = +inf, valid lanes u < 1 (the lane contract)
    u = np.asarray(res.u)
    v = np.asarray(res.valid)
    assert np.all(u[~v] == np.inf)
    assert np.all(u[v] < 1.0)
    # every resident survives at the current rung: u < gamma**k
    assert np.all(u[v] < np.float32(GAMMA) ** int(res.k))


@settings(max_examples=25, deadline=None)
@given(dup_heavy_edges(), st.sampled_from(CAPS), st.integers(0, 3),
       st.sampled_from([1, 7, 16, LANES]))
def test_chunk_size_never_changes_the_estimate(edges, capacity, seed, chunk):
    ei, ej = edges
    ref_est, ref = reservoir_run(ei, ej, capacity=capacity, gamma=GAMMA,
                                 seed=seed, chunk=LANES)
    est, res = reservoir_run(ei, ej, capacity=capacity, gamma=GAMMA,
                             seed=seed, chunk=chunk)
    assert est == ref_est
    assert int(res.k) == int(ref.k)
    assert resident_set(res) == resident_set(ref)


@settings(max_examples=25, deadline=None)
@given(dup_heavy_edges(), st.sampled_from(CAPS), st.integers(0, 3))
def test_ingest_dedupe_matches_host_prededupe(edges, capacity, seed):
    """Feeding raw duplicated lanes through the in-merge lexsort dedupe
    lands on the same reservoir as reservoir_run's host-side first-occurrence
    filter — duplicates carry zero information either way.  The scan gets
    the same id compaction reservoir_run applies (uniforms are content-keyed
    on the *compacted* ids, so the coins only match in that space)."""
    ei, ej = edges
    ci = np.searchsorted(np.unique(ei), ei) if len(ei) else ei
    cj = np.searchsorted(np.unique(ej), ej) if len(ej) else ej
    li, lj, lv = pad_lanes(ci, cj)
    res = _reservoir_scan(li[None], lj[None], lv[None],
                          reservoir_init(capacity),
                          jrandom.PRNGKey(seed), gamma=GAMMA, dedupe=True)
    _, ref = reservoir_run(ei, ej, capacity=capacity, gamma=GAMMA, seed=seed)
    assert int(res.k) == int(ref.k)
    assert resident_set(res) == resident_set(ref)


@settings(max_examples=25, deadline=None)
@given(dup_heavy_edges(), st.sampled_from(CAPS), st.integers(0, 3),
       st.integers(0, LANES))
def test_incremental_ingest_equals_batch(edges, capacity, seed, cut):
    """Two ingests (prefix, then suffix through the carried state) land on
    the same reservoir as one ingest of the whole stream."""
    ei, ej = edges
    cut = min(cut, len(ei))
    key = jrandom.PRNGKey(seed)

    def ingest(res, i, j):
        li, lj, lv = pad_lanes(i, j)
        u = edge_uniforms(key, jnp.asarray(li), jnp.asarray(lj))
        return reservoir_ingest(res, jnp.asarray(li), jnp.asarray(lj),
                                jnp.asarray(lv), u, gamma=GAMMA)

    whole = ingest(reservoir_init(capacity), ei, ej)
    halves = ingest(ingest(reservoir_init(capacity), ei[:cut], ej[:cut]),
                    ei[cut:], ej[cut:])
    assert int(whole.k) == int(halves.k)
    assert resident_set(whole) == resident_set(halves)


@settings(max_examples=40, deadline=None)
@given(st.floats(0.0, 2.0), st.sampled_from([0.5, 0.7, 0.9]))
def test_gamma_ladder_is_the_minimal_rung(t, gamma):
    """k is the smallest rung with gamma**k <= t (in f32 arithmetic), and
    p is exactly that power — the keep-mask and the ladder agree."""
    k, p = gamma_ladder(jnp.float32(t), gamma)
    k, p = int(k), float(p)
    g32 = np.float32(gamma)
    t32 = np.float32(t)
    # the device flushes subnormal results to zero, numpy keeps them
    power = g32 ** np.float32(k)
    power = power if power >= np.finfo(np.float32).tiny else np.float32(0)
    assert k >= 0
    assert np.float32(p) == power
    if t32 >= 1.0:
        assert (k, p) == (0, 1.0)
    elif p > 0.0:
        assert np.float32(p) <= t32
        if k > 0:  # one rung shallower would overshoot
            assert g32 ** np.float32(k - 1) > t32


# -- streaming engine: slicing-independence ------------------------------------

NT_W = 20
STREAM = synthetic_rating_stream(n_users=40, n_items=30, n_edges=600, seed=3,
                                 temporal="uniform", n_unique=120)


def run_split(splits, *, seed=0, flush_every=4, restore_at=None):
    """Push STREAM through a sampled engine in the given slices; optionally
    checkpoint/restore into a fresh engine at slice boundary ``restore_at``.
    capacity=32 sits well below the ~100-edge windows, so the coins are
    genuinely in play — slicing-invariance is not vacuous exactness."""
    def make():
        return StreamingSGrapp(
            NT_W, 0.95, flush_every=flush_every, seed=seed,
            executor=WindowExecutor("sampled", align=64, snap=0, capacity=32))

    eng = make()
    bounds = [0] + sorted(splits) + [len(STREAM)]
    for n, (a, b) in enumerate(zip(bounds[:-1], bounds[1:])):
        if restore_at is not None and n == restore_at:
            eng = make().restore(eng.state_dict())
        if a < b:
            eng.push(STREAM.tau[a:b], STREAM.edge_i[a:b], STREAM.edge_j[a:b])
    return eng.finalize()


REF = run_split([])


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 600), min_size=1, max_size=6),
       st.sampled_from([1, 4, 32]))
def test_micro_batch_splits_never_move_estimates(splits, flush_every):
    res = run_split(splits, flush_every=flush_every)
    np.testing.assert_array_equal(res.window_counts, REF.window_counts)
    np.testing.assert_array_equal(res.estimates, REF.estimates)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, 600), min_size=1, max_size=4),
       st.integers(0, 4))
def test_checkpoint_cut_never_moves_estimates(splits, restore_at):
    restore_at = min(restore_at, len(splits))
    res = run_split(splits, restore_at=restore_at)
    np.testing.assert_array_equal(res.window_counts, REF.window_counts)
    np.testing.assert_array_equal(res.estimates, REF.estimates)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 5))
def test_seed_moves_coins_but_not_window_structure(seed):
    """Different reservoir seeds redraw the sampling coins (counts may
    move) but the windowizer is seed-independent: same window boundaries,
    same cumulative sgr counts."""
    res = run_split([], seed=seed)
    np.testing.assert_array_equal(res.cum_edges, REF.cum_edges)
    assert len(res.window_counts) == len(REF.window_counts)
