"""A whole run on the CPU, past the harness's look for a chip, with the
served path broken underneath: ``correct`` must come out false for each
fault a one-chip cell can have, and true with none.

The faults: the estimator step returns its state unchanged; half of each
dispatch's windows are left out (counted as nothing); one count is altered
where the executor produces it.  The cells run on one chip, so there is no
exchange between chips to leave out.
"""
import json
import os

import numpy as np
import pytest

from bench import run as bench_run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny():
    with open(os.path.join(BENCH, "configs", "movielens-win8k.json")) as f:
        cfg = json.load(f)
    cfg.update(tenants=2, nt_w=64, push_records=64, records=4096, users=512,
               items=256)
    cfg["engine"] = dict(cfg["engine"], tier="dense", align=64)
    return cfg


TRAFFIC = {"loop": "closed", "records_ready_per_tenant": 4096,
           "warmup": {"ladder_streams": 1, "min_s": 1, "quiet_s": 1,
                      "min_windows_per_tenant": 4, "max_s": 60},
           "grace_s": 30}
BENCH_DICT = {
    "workloads": [{"name": "tiny", "config": "tiny_config",
                   "traffic": "tiny_traffic", "chips": 1}],
    "end_to_end": [{"name": "edges_per_s", "unit": "edges/s"}],
    "per_layer": []}


class Reaped:
    """A pending-counts handle whose counts pass through ``alter``."""

    def __init__(self, handle, alter):
        self.handle, self.alter = handle, alter

    def reap(self):
        return self.alter(np.array(self.handle.reap(), dtype=np.float64))


def break_counts(alter):
    def hook(server):
        ex = server.engine.executor
        submit = ex.window_counts_submit
        ex.window_counts_submit = lambda batch: Reaped(submit(batch), alter)
    return hook


def half_left_out(c):
    c[len(c) // 2:] = 0.0
    return c


def one_altered(c):
    c[0] += 1.0
    return c


def state_unchanged(server):
    server.engine._step_fn = lambda carry, xs: (carry, carry[0])


def run_tiny(hook, seed):
    return bench_run.run_cell(
        BENCH_DICT, "tiny", seed, 2.0, False, require_chip=False,
        limits=bench_run.load_json("limits", "movielens-win8k.catchup.json"),
        server_hook=hook,
        configs={"tiny_config": tiny(), "tiny_traffic": TRAFFIC})


def test_a_sound_run_is_correct():
    res = run_tiny(None, 2**31 + 99)
    assert res["correct"], res["checks"]
    assert res["metrics"]["edges_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged,
                                   break_counts(half_left_out),
                                   break_counts(one_altered)],
                         ids=["state_unchanged", "half_left_out",
                              "one_altered"])
def test_a_broken_path_is_not_correct(fault):
    res = run_tiny(fault, 2**31 + 7)
    assert not res["correct"], res["checks"]
