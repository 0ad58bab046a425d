"""The harness refuses a CPU run and a device kind missing from the peak
table."""
import json
from types import SimpleNamespace

import pytest

from bench import run as bench_run


def test_a_cpu_run_exits_non_zero_and_prints_no_result(capsys):
    rc = bench_run.main(["--workload", "movielens-win8k.catchup", "--seed",
                         "3000000001", "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out.strip() == ""
    assert "no TPU" in err


def fake(kind, platform="tpu"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_the_device_check():
    peaks = bench_run.load_json("peaks.json")
    bench_run.check_device([fake("TPU v5 lite")], 1, peaks)
    with pytest.raises(bench_run.RunError, match="not in bench/peaks.json"):
        bench_run.check_device([fake("TPU v9 imaginary")], 1, peaks)
    with pytest.raises(bench_run.RunError, match="needs 4 chips"):
        bench_run.check_device([fake("TPU v5 lite")], 4, peaks)
    with pytest.raises(bench_run.RunError, match="no TPU"):
        bench_run.check_device([fake("cpu", "cpu")], 1, peaks)


def test_the_peak_table_names_its_source():
    peaks = bench_run.load_json("peaks.json")
    assert "TPU v5e" in peaks["source"]
    for row in peaks["devices"].values():
        assert row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
    json.dumps(peaks)
