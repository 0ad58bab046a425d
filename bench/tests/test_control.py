"""The control (one acked push never applied) must come out as not
correct, at a size a test can hold."""
import json
import os

import pytest

from bench.control import control_numbers

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("config,pushes", [("movielens-win8k", 16),
                                           ("netflix-win512", 24)])
def test_the_control_fails(config, pushes):
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, tenants=2)
    workload = {"movielens-win8k": "movielens-win8k.catchup",
                "netflix-win512": "netflix-win512.live"}[config]
    with open(os.path.join(BENCH, "limits", f"{workload}.json")) as f:
        limits = json.load(f)
    got = control_numbers(cfg, 3, pushes)
    assert any(got[k] > limits[k] for k in limits), got
    assert got["windows_wrong"] > 0
    assert got["estimate_rel_gap"] > limits["estimate_rel_gap"]
