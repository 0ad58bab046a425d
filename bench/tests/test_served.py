"""The readers of the server's phase timers and counters, on hand-made
``/metrics`` snapshots, and their silence on a program that lacks them."""
import pytest

from bench import run as bench_run
from bench.record import Run


def read(name, run):
    return bench_run.load_reader(name)(run)


def snap(cycles, dispatches, windows, lanes, wedges, phases):
    agg = {"push_latency_ms": {"mean": 10.0, "count": cycles},
           "dispatch_count": dispatches, "windows_dispatched": windows}
    if phases is not None:
        agg["phases_ms"] = {k: {"mean": m, "count": n}
                            for k, (m, n) in phases.items()}
        agg["executor"] = {"wedge_lanes": lanes, "wedges": wedges}
    return {"aggregate": agg}


def make_run(phases0, phases1, d0=(10, 10, 0, 0), d1=(30, 50, 4096, 1024)):
    return Run(workload="w", t0=0.0, t1=1.0, setup_s=1.0, tables={},
               server0=snap(5, *d0, phases0), server1=snap(25, *d1, phases1))


PH0 = {"queue_wait": (2.0, 40), "wal_sync": (1.0, 5), "submit": (0.5, 20),
       "estimator": (3.0, 10)}
PH1 = {"queue_wait": (3.0, 120), "wal_sync": (1.5, 25), "submit": (0.6, 60),
       "estimator": (4.0, 30)}


def test_phase_readers():
    run = make_run(PH0, PH1)
    # (3 * 120 - 2 * 40) / 80 pushes
    assert read("queue_wait_ms.live", run) == pytest.approx(280 / 80)
    assert read("wal_sync_ms.live", run) == pytest.approx(32.5 / 20)
    # per dispatch, not per span: (36 - 10) ms over 20 dispatches
    assert read("submit_ms.live", run) == pytest.approx(26 / 20)
    assert read("estimator_ms.live", run) == pytest.approx(90 / 20)
    assert read("windows_per_dispatch.live", run) == pytest.approx(40 / 20)
    assert read("wedge_pad_share.catchup", run) == pytest.approx(75.0)


def test_a_program_without_the_timers_reads_nothing():
    # the counters it had count no dispatch at latency_budget_ms 0
    run = make_run(None, None, d0=(0, 0, 0, 0), d1=(0, 0, 0, 0))
    for name in ("queue_wait_ms.live", "wal_sync_ms.live", "submit_ms.live",
                 "estimator_ms.live", "windows_per_dispatch.live",
                 "wedge_pad_share.catchup"):
        assert read(name, run) is None, name


def test_nothing_to_read_in_the_window():
    # no sparse-routed window, no dispatch, a timer that never ran
    run = make_run({"queue_wait": (2.0, 40)}, {"queue_wait": (2.0, 40)},
                   d0=(10, 10, 0, 0), d1=(10, 10, 0, 0))
    assert read("wedge_pad_share.catchup", run) is None
    assert read("windows_per_dispatch.live", run) is None
    assert read("queue_wait_ms.live", run) is None
    assert read("submit_ms.live", run) is None
