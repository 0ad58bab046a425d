"""The idle share that the program's host spans explain, on hand-made
host and device intervals (nanoseconds)."""
import numpy as np
import pytest

from bench import idle_host as H
from bench.trace import WINDOW_SPAN


def test_overlap_of_two_unions():
    a = np.array([[0.0, 10.0], [20.0, 30.0]])
    b = np.array([[5.0, 25.0], [28.0, 40.0]])
    assert H.overlap(a, b) == pytest.approx(5 + 5 + 2)
    assert H.overlap(b, a) == pytest.approx(12)
    assert H.overlap(a, np.zeros((0, 2))) == 0.0
    assert H.overlap(a, np.array([[-5.0, 50.0]])) == pytest.approx(20)
    assert H.overlap(a, np.array([[10.0, 20.0]])) == 0.0


def test_share_counts_idle_time_under_program_spans():
    # window [0, 100); the device runs [10, 30) and [60, 70)
    devices = {"/device:TPU:0": [("count", 10, 30), ("count", 60, 70)]}
    host = [("python", WINDOW_SPAN, 0, 100),
            # idle 30-40 under a program span, 5 of it also under another
            ("python", "sgrapp.estimate", 25, 40),
            ("engine", "sgrapp.wal_sync", 35, 45),
            # busy time under a span does not count
            ("engine", "sgrapp.count_wait", 60, 70),
            # JAX's own spans and spans past the window do not count
            ("python", "PjitFunction(body)", 80, 90),
            ("python", "sgrapp.decode", 95, 120)]
    # idle and under a program span: 30-45 and 95-100
    assert H.idle_host_share(devices, host) == pytest.approx(20.0)


def test_share_averages_devices_and_reads_nothing_without_work():
    devices = {"/device:TPU:0": [("a", 0, 50)],
               "/device:TPU:1": [("a", 0, 100)],
               "/device:TPU:2": [("a", 200, 300)]}     # outside: left out
    host = [("python", WINDOW_SPAN, 0, 100),
            ("python", "sgrapp.launch", 40, 80)]
    # device 0 idle under the span 50-80, device 1 never idle
    assert H.idle_host_share(devices, host) == pytest.approx(15.0)
    assert H.idle_host_share({}, host) is None
    # no program span at all: nothing the host's steps explain
    assert H.idle_host_share(devices, host[:1]) == 0.0
