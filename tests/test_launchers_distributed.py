"""Launcher entry points + distributed counter + dry-run on a tiny mesh.

The 512-device dry-run runs via ``python -m repro.launch.dryrun``; here we
exercise the same code path on an 8-device tiny mesh in a subprocess (the
XLA device-count flag must be set before jax init, so in-process is not an
option for the test runner).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


def run(cmd, timeout=540):
    return subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                          env=ENV, cwd=REPO)


def test_train_launcher_smoke(tmp_path):
    r = run([sys.executable, "-m", "repro.launch.train", "--arch",
             "graphsage-reddit", "--smoke", "--steps", "4",
             "--ckpt", str(tmp_path / "ck"), "--ckpt_every", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout
    # restart resumes from the checkpoint
    r2 = run([sys.executable, "-m", "repro.launch.train", "--arch",
              "graphsage-reddit", "--smoke", "--steps", "6",
              "--ckpt", str(tmp_path / "ck"), "--ckpt_every", "2"])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "restored step 4" in r2.stdout


def test_serve_launcher_smoke():
    r = run([sys.executable, "-m", "repro.launch.serve", "--arch",
             "minicpm3-4b", "--smoke", "--batch", "2", "--prompt", "8",
             "--gen", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "decode" in r.stdout


@pytest.mark.parametrize("mesh", ["tiny", "tiny_multipod"])
def test_dryrun_tiny_mesh(tmp_path, mesh):
    r = run([sys.executable, "-m", "repro.launch.dryrun", "--arch", "sgrapp",
             "--shape", "win_8k", "--mesh", mesh, "--out", str(tmp_path)])
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    rec = json.load(open(tmp_path / mesh / "sgrapp__win_8k.json"))
    assert rec["status"] == "ok"
    assert rec["memory"]["temp_size_bytes"] is not None
    assert rec["hlo"]["collectives"]["total"] > 0  # the ring permutes


def test_distributed_counter_exactness_subprocess():
    """Half-ring/int8 distributed counting == sequential oracle (8 devices)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.distributed import make_distributed_window_counter
from repro.core.windows import windowize
from repro.core.sgrapp import window_exact_counts
from repro.streams import bipartite_pa_stream
from repro.launch.mesh import make_tiny_mesh
mesh = make_tiny_mesh()
s = bipartite_pa_stream(1500, seed=1, n_unique=300)
wb = windowize(s.tau, s.edge_i, s.edge_j, 50)
nw = (wb.n_windows // 2) * 2
ref = np.asarray(window_exact_counts(wb))[:nw]
for hr, wd in [(False, None), (True, jnp.int8)]:
    counter = make_distributed_window_counter(wb.n_i, wb.n_j, mesh,
                                              half_ring=hr, wire_dtype=wd)
    with mesh:
        got = np.asarray(counter(jnp.array(wb.edge_i[:nw]),
                                 jnp.array(wb.edge_j[:nw]),
                                 jnp.array(wb.valid[:nw])))
    assert np.allclose(got, ref), (hr, wd, got, ref)
print("EXACT")
"""
    r = run([sys.executable, "-c", code])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "EXACT" in r.stdout


def test_elastic_resharding_restore(tmp_path):
    """Checkpoint saved under one mesh restores onto a different mesh shape
    with different shardings (the elastic-restart path) value-exactly."""
    code = rf"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train.checkpoint import save_checkpoint, restore_checkpoint
from repro.launch.mesh import make_tiny_mesh

d = r"{str(tmp_path / 'ck')}"
mesh_a = make_tiny_mesh()
params = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
           "b": jnp.ones((16,), jnp.float32)}}
sharded = {{
    "w": jax.device_put(params["w"], NamedSharding(mesh_a, P("model", None))),
    "b": jax.device_put(params["b"], NamedSharding(mesh_a, P("data"))),
}}
save_checkpoint(d, 1, sharded)

# 'restart' on a different mesh shape with transposed layout
mesh_b = jax.make_mesh((4, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
shardings = {{
    "w": NamedSharding(mesh_b, P(None, "data")),
    "b": NamedSharding(mesh_b, P("model")),
}}
restored, _ = restore_checkpoint(d, params, shardings=shardings)
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(params["w"]))
np.testing.assert_array_equal(np.asarray(restored["b"]), np.asarray(params["b"]))
assert restored["w"].sharding.spec == P(None, "data")
print("ELASTIC_OK")
"""
    r = run([sys.executable, "-c", code])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ELASTIC_OK" in r.stdout
