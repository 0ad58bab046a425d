"""Differential validation of the window executor across all counting tiers.

The ladder: ``numpy`` wedge-hash oracle == ``dense`` Gram == ``tiled`` scan
== ``pallas`` (interpret mode on hosts), on adversarial window snapshots —
empty windows, all-duplicate edges, hub stars, non-tile-multiple shapes and
``n_i > n_j`` orientation flips — and bit-identical ``run_sgrapp`` estimates
regardless of tier.

The sharded dispatch path (``devices=`` / ``mesh=``) gets the same
treatment: multi-device-CPU differential cases run in a subprocess (the
``--xla_force_host_platform_device_count`` flag must precede jax init) and
in-process whenever the test runner itself already has >= 2 devices (the CI
multi-device job sets ``XLA_FLAGS`` for the whole suite).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.butterfly import count_butterflies_np
from repro.core.executor import (
    TIERS,
    WindowExecutor,
    bucket_capacity,
    id_capacity,
    route_tier,
    run as executor_run,
)
from repro.core.sgrapp import run_sgrapp, window_exact_counts
from repro.core.windows import WindowBatch, windowize
from repro.streams import synthetic_rating_stream

DEVICE_TIERS = ("dense", "tiled", "pallas", "sparse", "auto", "sampled")


# -- adversarial snapshot construction ----------------------------------------

def rand_edges(n_i, n_j, m, seed):
    rng = np.random.default_rng(seed)
    return list(zip(rng.integers(0, n_i, m).tolist(),
                    rng.integers(0, n_j, m).tolist()))


ADVERSARIAL = {
    "i_hub_star": [(0, j) for j in range(37)],                  # 0 butterflies
    "j_hub_star": [(i, 0) for i in range(41)],                  # 0 butterflies
    "hub_plus_column": [(i, 0) for i in range(40)]
                       + [(i, 1) for i in range(0, 40, 2)],     # cross-tile pairs
    "all_duplicates": [(3, 5)] * 25,                            # dedupe -> 1 edge
    "complete_k9_7": [(i, j) for i in range(9) for j in range(7)],
    "orientation_flip": rand_edges(150, 40, 400, seed=1),       # n_i > n_j
    "non_tile_multiple": rand_edges(13, 300, 350, seed=2),      # skinny
    "dense_random": rand_edges(30, 30, 500, seed=3),
    "duplicate_heavy": rand_edges(12, 10, 600, seed=4),         # ~5x dup rate
}


def batch_of(edge_lists) -> WindowBatch:
    """One window per edge list (each window = one unique timestamp)."""
    tau, ei, ej = [], [], []
    for k, edges in enumerate(edge_lists):
        for i, j in edges:
            tau.append(float(k)); ei.append(i); ej.append(j)
    return windowize(np.asarray(tau), np.asarray(ei), np.asarray(ej), 1)


def empty_window_batch() -> WindowBatch:
    """Two all-padding windows — no edge is valid."""
    cap = 8
    z = np.zeros((2, cap), np.int32)
    zi = np.zeros(2, np.int64)
    return WindowBatch(
        edge_i=z, edge_j=z.copy(), valid=np.zeros((2, cap), bool),
        n_edges=zi.copy(), n_sgrs=zi.copy(), cum_sgrs=np.array([1, 2]),
        n_i=1, n_j=1, window_end_tau=np.zeros(2, np.float64),
        n_i_per_window=zi.copy(), n_j_per_window=zi.copy(),
    )


def oracle_counts(batch: WindowBatch) -> np.ndarray:
    out = np.zeros(batch.n_windows, dtype=np.float64)
    for k in range(batch.n_windows):
        v = batch.valid[k]
        out[k] = count_butterflies_np(
            np.stack([batch.edge_i[k][v], batch.edge_j[k][v]], axis=1))
    return out


# -- snapshot-level differential ----------------------------------------------

@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("align", [128, 8])
def test_all_tiers_match_oracle_on_adversarial(tier, align):
    batch = batch_of(ADVERSARIAL.values())
    want = oracle_counts(batch)
    got = WindowExecutor(tier, align=align).window_counts(batch)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tier", TIERS)
def test_all_tiers_zero_on_empty_windows(tier):
    got = WindowExecutor(tier).window_counts(empty_window_batch())
    np.testing.assert_array_equal(got, np.zeros(2))


@pytest.mark.parametrize("tier", DEVICE_TIERS)
def test_count_edges_online_entry(tier):
    ex = WindowExecutor(tier, align=8)
    for name, edges in ADVERSARIAL.items():
        e = np.asarray(edges, dtype=np.int64)
        want = count_butterflies_np(e)
        got = ex.count_edges(e[:, 0], e[:, 1])
        assert got == want, name
    assert ex.count_edges([], []) == 0.0


# -- bucketing ----------------------------------------------------------------

def test_bucket_capacity_ladder():
    assert bucket_capacity(0) == 128
    assert bucket_capacity(1) == 128
    assert bucket_capacity(128) == 128
    assert bucket_capacity(129) == 256
    assert bucket_capacity(300) == 512
    assert bucket_capacity(5, align=8, growth=2) == 8
    assert bucket_capacity(9, align=8, growth=2) == 16


def test_plan_partitions_all_windows():
    batch = batch_of(ADVERSARIAL.values())
    ex = WindowExecutor("dense", align=8)
    buckets = ex.plan(batch)
    seen = np.concatenate([b.windows for b in buckets])
    assert sorted(seen.tolist()) == list(range(batch.n_windows))
    for b in buckets:
        # every window fits its bucket capacities
        assert (batch.n_edges[b.windows] <= b.cap_e).all()
        assert (batch.n_i_per_window[b.windows] <= b.cap_i).all()
        assert (batch.n_j_per_window[b.windows] <= b.cap_j).all()
    # heterogeneous window sizes must not collapse into one bucket
    assert len(buckets) > 1


def test_staged_buckets_report_the_dispatched_shapes():
    """``staged_buckets`` lists each dispatched bucket shape once, with its
    real window count; ``program_text`` compiles the program that ran it."""
    batch = batch_of(ADVERSARIAL.values())
    ex = WindowExecutor("dense", align=8)
    assert ex.staged_buckets() == []
    ex.window_counts(batch)
    ex.window_counts(batch)
    want = sorted((b.cap_e, b.cap_i, b.cap_j, b.n_windows)
                  for b in ex.plan(batch))
    staged = ex.staged_buckets()
    assert sorted((b.cap_e, b.cap_i, b.cap_j, b.n_windows)
                  for b, _, _ in staged) == want
    assert not any(multiset or sampled for _, multiset, sampled in staged)
    b = staged[0][0]
    assert f"s32[{b.n_windows},{b.cap_e}]" in ex.program_text(b)


def test_bucket_caps_never_exceed_global_capacity():
    """A window whose ladder rung overshoots the batch's padded capacity
    (e.g. ~300 i-vertices: rung 512 > global 384) must clamp to it — the
    bucket path never pays more than the global path would have."""
    batch = batch_of([rand_edges(300, 20, 900, seed=9)])
    assert batch.n_i < 512  # the scenario is live: rung would exceed global
    ex = WindowExecutor("dense")
    for b in ex.plan(batch):
        assert b.cap_e <= batch.capacity
        assert b.cap_i <= batch.n_i
        assert b.cap_j <= batch.n_j
    np.testing.assert_array_equal(ex.window_counts(batch),
                                  oracle_counts(batch))


def test_id_capacity_linear_ladder():
    assert id_capacity(0) == 64
    assert id_capacity(1) == 64
    assert id_capacity(64) == 64
    assert id_capacity(65) == 128
    assert id_capacity(130) == 192
    assert id_capacity(5, align=8) == 8
    assert id_capacity(9, align=8) == 16


# -- chunked-vmap dispatch ----------------------------------------------------

@pytest.mark.parametrize("tier", ("dense", "sparse", "pallas"))
def test_chunk_sweep_bit_identical_to_sequential(tier):
    """chunk=1 is the seed's fully sequential per-window ``lax.map``
    schedule; every other chunk size (dividing, non-dividing, and larger
    than any bucket) must reproduce its counts bit-for-bit — chunking is a
    dispatch decision, never a semantics decision."""
    batch = batch_of(ADVERSARIAL.values())
    want = oracle_counts(batch)
    seq = WindowExecutor(tier, align=8, chunk=1).window_counts(batch)
    np.testing.assert_array_equal(seq, want)
    for chunk in (2, 3, 5, 64):
        got = WindowExecutor(tier, align=8, chunk=chunk).window_counts(batch)
        np.testing.assert_array_equal(got, seq, err_msg=f"chunk={chunk}")


def test_chunk_validates():
    with pytest.raises(ValueError):
        WindowExecutor("dense", chunk=0)


# -- sparse tier + auto routing -----------------------------------------------

def test_sparse_buckets_carry_wedge_capacity():
    batch = batch_of(ADVERSARIAL.values())
    ex = WindowExecutor("sparse", align=8)
    for b in ex.plan(batch):
        assert b.cap_w >= 1  # every sparse bucket sized for its wedges
    np.testing.assert_array_equal(ex.window_counts(batch),
                                  oracle_counts(batch))


def test_route_tier_cost_model():
    # few edges lost in a huge id space: wedge-sort work << Gram flops
    assert route_tier(128, 2048, 2048, 256) == "sparse"
    # dense little window: the matmul is cheaper than sorting the wedges
    assert route_tier(512, 192, 192, 16384) == "dense"
    # sort_cost knob moves the boundary
    assert route_tier(512, 192, 192, 16384, sort_cost=1e-6) == "sparse"
    # beyond the sparse tier's int32 key-packing bound the router must fall
    # back to dense even though the cost model screams sparse — routing
    # into a tier that refuses to trace would crash the auto path
    assert route_tier(128, 50_000, 50_000, 256) == "dense"
    assert route_tier(128, 50_000, 64, 256) == "dense"


def test_auto_fuses_dense_routed_wedge_rungs():
    """Dense-routed windows whose capacities differ only in wedge rung must
    share one bucket — cap_w never reaches a dense program, so splitting on
    it would only fragment dispatches."""
    # same capacity rungs (align=8: cap_e 128, cap_i/j 32), wildly
    # different wedge counts: two 29-hubs (~812 wedges) vs a flat random
    # window (~90 wedges) — distinct wedge rungs by construction
    hub = ([(i, 0) for i in range(29)] + [(i, 1) for i in range(29)]
           + [(0, j) for j in range(2, 30)])
    flat = rand_edges(29, 30, 90, seed=21)
    batch = batch_of([hub, flat])
    ex = WindowExecutor("auto", align=8, sort_cost=1e9)  # force all-dense
    assert {ex.bucket_tier(b) for b in ex.plan(batch)} == {"dense"}
    assert len(ex.plan(batch)) == 1, "dense-routed buckets fragmented"
    np.testing.assert_array_equal(ex.window_counts(batch),
                                  oracle_counts(batch))


def test_auto_routes_per_bucket_and_matches_oracle():
    """One batch holding both regimes: auto must route at least one bucket
    to each side of the cost model and still match the oracle exactly."""
    edge_lists = [
        rand_edges(2000, 2000, 60, seed=11),   # sparse regime
        rand_edges(2000, 1900, 80, seed=12),   # sparse regime
        rand_edges(25, 25, 500, seed=13),      # dense regime
    ]
    batch = batch_of(edge_lists)
    ex = WindowExecutor("auto")
    routed = {ex.bucket_tier(b) for b in ex.plan(batch)}
    assert routed == {"sparse", "dense"}
    np.testing.assert_array_equal(ex.window_counts(batch),
                                  oracle_counts(batch))


def test_count_edges_memoizes_online_counter():
    """Repeated online windows with the same capacity rung must reuse the
    memoized counter (the streaming engine's flush path)."""
    ex = WindowExecutor("dense", align=8)
    e = np.asarray(ADVERSARIAL["dense_random"], dtype=np.int64)
    want = count_butterflies_np(e)
    assert ex.count_edges(e[:, 0], e[:, 1]) == want
    key, fn = ex._online_cache
    assert ex.count_edges(e[:, 0], e[:, 1]) == want
    assert ex._online_cache[0] == key and ex._online_cache[1] is fn


def test_take_subbatch_validates_capacity():
    batch = batch_of(ADVERSARIAL.values())
    sub = batch.take([0, 2], capacity=64)
    assert sub.n_windows == 2 and sub.capacity == 64
    with pytest.raises(ValueError):
        batch.take([5], capacity=8)  # orientation_flip has ~400 edges


# -- sharded dispatch (multi-device) ------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str, n_virtual_devices: int):
    # XLA honours the LAST occurrence of a repeated flag, so appending
    # overrides any ambient device-count setting (e.g. the CI job's =2)
    env = {**os.environ,
           "PYTHONPATH": os.path.join(REPO, "src"),
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                         + f" --xla_force_host_platform_device_count="
                           f"{n_virtual_devices}").strip()}
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=540, env=env, cwd=REPO)


def test_sharded_differential_all_tiers_subprocess():
    """Bit-identical counts single-device vs sharded, every tier, on >= 2
    virtual CPU devices — including a shard count that does NOT divide the
    window count (the padding path) and the estimator-level plumbing."""
    code = r"""
import numpy as np
from repro.core.executor import TIERS, WindowExecutor
from repro.core.sgrapp import run_sgrapp
from repro.launch.mesh import make_window_mesh
from repro.streams import bipartite_pa_stream

s = bipartite_pa_stream(2500, temporal="uniform", n_unique=600, seed=5)
wb = s.windowize(40)
assert wb.n_windows > 3
ref = WindowExecutor("dense").window_counts(wb)
for tier in TIERS:
    for dev in (2, 3):  # 3 never divides evenly here -> padding lanes live
        got = WindowExecutor(tier, devices=dev).window_counts(wb)
        np.testing.assert_array_equal(got, ref, err_msg=f"{tier} dev={dev}")
# prebuilt-mesh knob
got = WindowExecutor("dense", mesh=make_window_mesh(2)).window_counts(wb)
np.testing.assert_array_equal(got, ref)
# estimator-level: estimates bit-identical across device counts
a = run_sgrapp(wb, 0.95, tier="dense")
b = run_sgrapp(wb, 0.95, tier="dense", devices=4)
np.testing.assert_array_equal(a.estimates, b.estimates)
assert WindowExecutor("dense", devices=4).run(wb).n_shards == 4
print("SHARDED_EXACT")
"""
    r = _run_subprocess(code, 4)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SHARDED_EXACT" in r.stdout


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (CI multi-device job)")
@pytest.mark.parametrize("tier", DEVICE_TIERS)
def test_sharded_matches_single_device_in_process(tier):
    batch = batch_of(ADVERSARIAL.values())
    want = WindowExecutor(tier, align=8).window_counts(batch)
    got = WindowExecutor(tier, align=8,
                         devices=jax.device_count()).window_counts(batch)
    np.testing.assert_array_equal(got, want)


def test_sharding_knobs_validate():
    with pytest.raises(ValueError):
        WindowExecutor("dense", devices=2, mesh=object())  # mutually exclusive
    with pytest.raises(ValueError):
        WindowExecutor("dense", devices=0)
    with pytest.raises(ValueError):
        WindowExecutor("dense", devices=jax.device_count() + 1)
    # executor= already owns its mesh: devices=/mesh= alongside it is an error
    batch = batch_of([ADVERSARIAL["dense_random"]])
    ex = WindowExecutor("dense")
    with pytest.raises(ValueError):
        window_exact_counts(batch, executor=ex, devices=2)


def test_numpy_tier_ignores_sharding_knobs():
    """The numpy tier never dispatches to a device: sharding knobs are
    ignored outright (even impossible device counts) and n_shards honestly
    reports 1 — the executor must not claim parallelism that never ran."""
    ex = WindowExecutor("numpy", devices=jax.device_count() + 7)
    assert ex.mesh is None and ex.n_shards == 1
    batch = batch_of(ADVERSARIAL.values())
    res = ex.run(batch)
    assert res.n_shards == 1
    np.testing.assert_array_equal(res.counts, oracle_counts(batch))


def test_devices_one_collapses_to_unsharded():
    ex = WindowExecutor("dense", devices=1)
    assert ex.mesh is None and ex.n_shards == 1 and ex.shard_axes == ()
    batch = batch_of(ADVERSARIAL.values())
    res = ex.run(batch)
    assert res.n_shards == 1
    np.testing.assert_array_equal(res.counts, oracle_counts(batch))


# -- executor modes -----------------------------------------------------------

def test_sliding_mode_prefix_difference():
    batch = batch_of(ADVERSARIAL.values())
    ex = WindowExecutor("dense", align=8)
    pane = ex.run(batch, mode="tumbling").counts
    for span in (1, 2, 3):
        res = ex.run(batch, mode="sliding", span=span)
        want = np.array([
            pane[max(0, k - span + 1): k + 1].sum() for k in range(len(pane))
        ])
        np.testing.assert_array_equal(res.counts, want)
    # span=1 sliding degenerates to tumbling
    np.testing.assert_array_equal(
        ex.run(batch, mode="sliding", span=1).counts, pane)


def test_sliding_span_exceeding_pane_count():
    """span > n_panes: the lower bound clamps at pane 0, so window k holds
    the cumulative count of every closed pane — no index underflow, and the
    final window equals the all-pane total regardless of how far the span
    overshoots."""
    batch = batch_of(ADVERSARIAL.values())
    ex = WindowExecutor("dense", align=8)
    pane = ex.run(batch, mode="tumbling").counts
    cum = np.cumsum(pane)
    for span in (batch.n_windows, batch.n_windows + 1, batch.n_windows * 10):
        res = ex.run(batch, mode="sliding", span=span)
        np.testing.assert_array_equal(res.counts, cum)
        assert res.span == span and res.mode == "sliding"


def test_sliding_span_one_equals_tumbling_result():
    """span=1 degenerates to tumbling for the full ExecutorResult contract,
    not just the counts array."""
    batch = batch_of(ADVERSARIAL.values())
    ex = WindowExecutor("dense", align=8)
    tum = ex.run(batch, mode="tumbling")
    sli = ex.run(batch, mode="sliding", span=1)
    np.testing.assert_array_equal(sli.counts, tum.counts)
    np.testing.assert_array_equal(sli.cum_sgrs, tum.cum_sgrs)
    assert sli.n_windows == tum.n_windows


def test_sliding_prefix_difference_non_negative():
    """Prefix-differencing must never produce a negative count: pane counts
    are non-negative integers far below 2**53, so the float64 cumsum is
    exact and differences stay >= 0 — and sliding counts grow monotonically
    with span."""
    rng_batches = [
        batch_of(ADVERSARIAL.values()),
        batch_of([rand_edges(60, 45, 700, seed=s) for s in range(12)]),
    ]
    for batch in rng_batches:
        ex = WindowExecutor("dense", align=8)
        prev = np.zeros(batch.n_windows)
        for span in range(1, batch.n_windows + 2):
            c = ex.run(batch, mode="sliding", span=span).counts
            assert (c >= 0).all()
            assert (c >= prev).all()  # widening the span never loses panes
            prev = c


def test_run_rejects_bad_config():
    batch = batch_of([ADVERSARIAL["dense_random"]])
    with pytest.raises(ValueError):
        WindowExecutor("nope")
    with pytest.raises(ValueError):
        WindowExecutor("dense").run(batch, mode="hopping")
    with pytest.raises(ValueError):
        WindowExecutor("dense").run(batch, mode="sliding", span=0)


# -- estimator-level differential --------------------------------------------

def test_run_sgrapp_bit_identical_across_tiers():
    s = synthetic_rating_stream(n_users=80, n_items=60, n_edges=1500, seed=6,
                                temporal="uniform", n_unique=300)
    wb = s.windowize(50)
    ref = run_sgrapp(wb, 0.95, tier="dense")
    for tier in TIERS:
        res = run_sgrapp(wb, 0.95, tier=tier)
        np.testing.assert_array_equal(res.window_counts, ref.window_counts)
        np.testing.assert_array_equal(res.estimates, ref.estimates)


def test_window_exact_counts_rejects_tier_executor_conflict():
    batch = batch_of([ADVERSARIAL["dense_random"]])
    ex = WindowExecutor("tiled")
    with pytest.raises(ValueError):
        window_exact_counts(batch, tier="pallas", executor=ex)
    # matching tier (or omitting it) is fine
    a = np.asarray(window_exact_counts(batch, tier="tiled", executor=ex))
    b = np.asarray(window_exact_counts(batch, executor=ex))
    np.testing.assert_array_equal(a, b)


def test_window_exact_counts_executor_reuse():
    s = synthetic_rating_stream(n_users=80, n_items=60, n_edges=1200, seed=7,
                                temporal="uniform", n_unique=240)
    wb = s.windowize(40)
    ex = WindowExecutor("tiled")
    a = np.asarray(window_exact_counts(wb, executor=ex))
    b = np.asarray(window_exact_counts(wb, tier="dense"))
    np.testing.assert_array_equal(a, b)


def test_module_level_run_entry():
    batch = batch_of(ADVERSARIAL.values())
    res = executor_run(batch, tier="dense", align=8)
    np.testing.assert_array_equal(res.counts, oracle_counts(batch))
    assert res.tier == "dense" and res.mode == "tumbling"
    assert res.n_windows == batch.n_windows
