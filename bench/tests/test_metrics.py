"""The latency, throughput, counter and roofline arithmetic of the
readers, on a hand-made record."""
import numpy as np
import pytest

from bench import run as bench_run
from bench.record import Run, percentile
from bench.roofline import gram_work, least_time_s

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def read(name, run):
    return bench_run.load_reader(name)(run)


def make_run(trace=None):
    """Two tenants, window [10, 20).  Tenant 0 closes windows 0 and 1,
    tenant 1 closes window 0; tenant 1's estimate never arrives."""
    t = {
        "push_tenant": np.array([0, 0, 0, 1, 1]),
        "push_k": np.array([0, 1, 2, 0, 1]),
        "push_n": np.full(5, 4),
        "push_due": np.array([9.0, 11.0, 12.0, 11.5, 19.0]),
        "push_sent": np.array([9.0, 11.0, 12.0, 11.5, 19.0]),
        "push_ack": np.array([9.01, 11.02, 12.03, np.nan, 19.05]),
        "push_ok": np.array([True, True, True, False, True]),
        "push_windows_closed": np.array([0, 1, 1, 0, 1]),
        "est_tenant": np.array([0, 0]),
        "est_window": np.array([0, 1]),
        "est_arrival": np.array([11.1, 21.0]),
        "est_count": np.array([3.0, 4.0]),
        "est_estimate": np.array([3.0, 12.0]),
        "est_cum_sgrs": np.array([5, 8]),
        "est_end_tau": np.array([1.0, 2.0]),
        "ref_tenant": np.array([0, 0, 1]),
        "ref_window": np.array([0, 1, 0]),
        "ref_count": np.array([3, 4, 0]),
        "ref_cum_sgrs": np.array([5, 8, 6]),
        "ref_end_tau": np.array([1.0, 2.0, 1.0]),
        "ref_estimate": np.array([3.0, 12.0, 0.0]),
        "ref_n_i": np.array([100, 200, 10]),
        "ref_n_j": np.array([50, 300, 10]),
        "ref_close_due": np.array([11.0, 12.0, 19.0]),
        "closed_by_acks": np.array([2, 1]),
        "drain_end": np.array(25.0),
    }
    snap = lambda lat, reap: {  # noqa: E731
        "aggregate": {"push_latency_ms": {"mean": lat[0], "count": lat[1]},
                      "reap_wait_ms": {"mean": reap[0], "count": reap[1]}}}
    return Run(workload="w", t0=10.0, t1=20.0, setup_s=42.0, tables=t,
               server0=snap((2.0, 10), (1.0, 4)),
               server1=snap((3.0, 30), (2.0, 8)),
               compiles=[5.0, 10.5, 19.9, 20.0], trace=trace, peaks=PEAKS)


def test_percentile_is_nearest_rank():
    x = list(range(1, 101))
    assert percentile(x, 0.5) == 50
    assert percentile(x, 0.95) == 95
    assert percentile(x, 0.99) == 99
    assert percentile([7.0], 0.99) == 7.0
    assert percentile([], 0.5) is None


def test_edges_per_s_counts_windows_published_in_the_window():
    # only tenant 0's window 0 (5 records) arrived inside [10, 20)
    assert read("edges_per_s", make_run()) == pytest.approx(5 / 10.0)


def test_estimate_latency_counts_a_missing_estimate_to_the_drain():
    # windows closed in the window: t0 w0 (11 -> 11.1), t0 w1 (12 -> 21),
    # t1 w0 (19 -> none: 25)
    run = make_run()
    assert read("estimate_p50_ms", run) == pytest.approx(6000.0)
    assert read("estimate_p95_ms", run) == pytest.approx(9000.0)


def test_ack_latency_counts_a_rejected_push_to_the_drain():
    # pushes due in [10, 20): 11 -> 11.02, 12 -> 12.03, 11.5 -> rejected
    # (25), 19 -> 19.05
    assert read("ack_p99_ms.live", make_run()) == pytest.approx(13500.0)


def test_server_counters_are_differenced_over_the_window():
    run = make_run()
    # (3*30 - 2*10) / 20 and (2*8 - 1*4) / 4
    assert read("engine_cycle_ms.catchup", run) == pytest.approx(3.5)
    assert read("reap_wait_ms.live", run) == pytest.approx(3.0)
    assert read("programs_compiled_in_window.live", run) == 2.0
    assert read("setup_s", run) == 42.0


def test_trace_metrics_read_nothing_without_a_trace():
    run = make_run()
    for name in ("device_idle_share.live", "count_roofline.catchup",
                 "count_device_ms_per_window.catchup"):
        assert read(name, run) is None


def test_trace_metrics():
    run = make_run(trace={"busy_s": 2.5, "window_s": 10.0})
    assert read("device_idle_share.catchup", run) == pytest.approx(75.0)
    # one window published in the window
    assert read("count_device_ms_per_window.catchup",
                run) == pytest.approx(2500.0)
    # a 100 x 50 window is memory-bound at these peaks
    need = max(2 * 100 * 50 * 50 / 197e12, 100 * 50 / 819e9)
    assert need == 100 * 50 / 819e9
    assert read("count_roofline.catchup", run) == pytest.approx(
        100 * need / 2.5)


def test_gram_work_and_its_bound():
    assert gram_work(3, 5) == (90.0, 15.0)
    t, bound = least_time_s(2000, 1500, PEAKS)
    assert bound == "compute" and t == pytest.approx(2 * 2000 * 1500 * 1500
                                                     / 197e12)
    # a window with one vertex on a side reads more than it computes
    t, bound = least_time_s(1, 4096, PEAKS)
    assert bound == "memory" and t == pytest.approx(4096 / 819e9)


def test_every_metric_of_the_benchmark_has_a_reader():
    import json
    import os

    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(bench_run.load_reader(m["name"]))
