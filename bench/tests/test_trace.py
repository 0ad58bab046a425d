"""The trace reduction, on a small trace recorded on one TPU v5e by
``make_trace.py``: a 2048x2048 bf16 matmul run three times inside the
window mark, with a 20 ms host span ``host_wait`` before the third."""
import os

import numpy as np
import pytest

from bench import trace as T

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "v5e_matmul.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return T.load(FIXTURE)


def test_the_fixture_has_one_device_and_the_mark(planes):
    devices, host = planes
    assert list(devices) == ["/device:TPU:0"]
    assert len(devices["/device:TPU:0"]) == 9        # 3 calls x 3 ops
    assert sum(name == T.WINDOW_SPAN for _, name, _, _ in host) == 1


def test_reduce(planes):
    devices, host = planes
    got = T.reduce(devices, host)
    (lo, hi), = [(s, e) for _, n, s, e in host if n == T.WINDOW_SPAN]
    assert got["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # the device clock reads about 1.3 ms behind the host's here, so only
    # the third call's ops (about 91 us) fall inside the mark
    inside = [min(e, hi) - max(s, lo) for _, s, e in devices["/device:TPU:0"]
              if min(e, hi) > max(s, lo)]
    assert got["busy_s"] == pytest.approx(sum(inside) * 1e-9)
    assert 5e-5 < got["busy_s"] < 2e-4
    assert got["device_ops"][0][0].startswith("%convolution_tanh_fusion")
    # the longest idle gap is the host's 20 ms wait
    label, secs = got["idle_gaps"][0]
    assert label == "python3:host_wait"
    assert 0.015 < secs < 0.025
    assert len(got["idle_gaps"]) <= T.TOP and len(got["device_ops"]) <= T.TOP


def test_union_and_clip():
    iv = np.array([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [7.0, 8.0]])
    np.testing.assert_array_equal(T._union(iv), [[0, 3], [5, 8]])
    np.testing.assert_array_equal(T._clip(iv, 1.5, 6.0),
                                  [[5, 6], [1.5, 2], [1.5, 3]])


def test_no_device_work_reads_nothing():
    assert T.reduce({}, [("python3", T.WINDOW_SPAN, 0, 10)]) is None
