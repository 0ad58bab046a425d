"""The set-up's ladder warm-up: made-up windows land on the bucket rungs
they are made for, and the rungs come from the program's own plan of a
fixed sample."""
import itertools

import numpy as np
import pytest

from bench import ladder

STREAM = {"family": "synthetic_rating", "user_exponent": 1.1,
          "item_exponent": 1.2, "temporal": "uniform",
          "edges_per_timestamp": 2, "n_users": 4096, "n_items": 1024,
          "segment_records": 4096}


def executor(tier, align):
    from repro.core.executor import WindowExecutor

    return WindowExecutor(tier, align=align, snap=0)


@pytest.mark.parametrize("align,nt_w", [(64, 128), (128, 512)])
def test_made_up_windows_reach_every_sampled_rung(align, nt_w):
    ex = executor("auto", align)
    rungs = ladder.sampled_rungs(ex, STREAM, nt_w, align, 2)
    assert all(rungs) and rungs == ladder.sampled_rungs(ex, STREAM, nt_w,
                                                         align, 2)
    for key in itertools.product(*rungs):
        edges = ladder.window(*key, align)
        assert edges is not None, key
        assert np.unique(edges, axis=0).shape[0] == edges.shape[0]
        assert ladder.bucket_key(ex, ladder.pack(edges, align)) == key


def test_a_layout_that_cannot_be_made_is_refused():
    # 64 edges cannot touch 128 users
    assert ladder.window(64, 128, 64, 1024, 64) is None


def test_warm_dispatches_each_combination():
    ex = executor("dense", 64)
    got = ladder.warm(ex, STREAM, 64, 64, 1)
    assert got["missed"] == []
    assert got["warmed"] == int(np.prod([len(r) for r in got["rungs"]]))
    assert got["rungs"][3] == [0]             # dense: no wedge rung
