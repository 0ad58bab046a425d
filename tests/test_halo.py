"""Halo-exchange message passing: partitioner + bit-exactness vs gather."""
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}

from repro.graphs.halo import build_partitioned_batch  # noqa: E402


def locality_graph(n, e, seed=0, far_frac=0.2):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = np.clip(src + rng.integers(-4, 5, e), 0, n - 1)
    far = rng.random(e) < far_frac
    dst = np.where(far, rng.integers(0, n, e), dst)
    return src, dst


def test_partitioner_structure():
    n, e, n_dev = 64, 300, 8
    src, dst = locality_graph(n, e)
    x = np.random.default_rng(1).normal(size=(n, 8)).astype(np.float32)
    labels = np.zeros(n, dtype=np.int64)
    pg = build_partitioned_batch(src, dst, x, labels, n_dev, halo=32)
    assert pg.x.shape == (n_dev, pg.n_loc, 8)
    # every kept edge's dst index is local and src_ext in the extended range
    ext_max = pg.n_loc + n_dev * pg.halo
    for d in range(n_dev):
        m = pg.edge_mask[d]
        assert (pg.edge_dst_loc[d][m] < pg.n_loc).all()
        assert (pg.edge_src_ext[d][m] < ext_max).all()
    # with a generous halo nothing is dropped
    assert pg.edge_mask.sum() == e


def test_halo_matches_gather_loss():
    """Runs on 8 forced host devices in a subprocess (device count is locked
    at jax init)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models.gnn.graphsage import SAGEConfig, init_sage, sage_loss, sage_loss_halo
from repro.graphs.halo import build_partitioned_batch
from repro.launch.mesh import make_tiny_mesh
rng = np.random.default_rng(0)
n_dev, n, e = 8, 64, 400
src = rng.integers(0, n, e)
dst = np.clip(src + rng.integers(-4, 5, e), 0, n - 1)
far = rng.random(e) < 0.2
dst = np.where(far, rng.integers(0, n, e), dst)
x = rng.normal(size=(n, 16)).astype(np.float32)
labels = rng.integers(0, 5, n)
cfg = SAGEConfig(name="s", d_in=16, d_hidden=8, n_classes=5)
params = init_sage(jax.random.PRNGKey(0), cfg)
pg = build_partitioned_batch(src, dst, x, labels, n_dev, halo=64)
mesh = make_tiny_mesh()
bh = {k: jnp.asarray(v) for k, v in pg.device_batch().items()}
with mesh:
    lh = float(jax.jit(lambda p, b: sage_loss_halo(p, b, cfg, mesh, ("data","model")))(params, bh))
br = {"x": jnp.asarray(x), "edge_src": jnp.asarray(src), "edge_dst": jnp.asarray(dst),
      "labels": jnp.asarray(labels), "label_mask": jnp.ones(n)}
lr = float(sage_loss(params, br, cfg))
assert abs(lh - lr) < 2e-5, (lh, lr)
print("HALO_EXACT")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=540, env=ENV, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "HALO_EXACT" in r.stdout


def test_eqv2_halo_matches_gather_loss():
    """EquiformerV2 halo path == gather path (8 forced host devices)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models.gnn.equiformer_v2 import EqV2Config, init_eqv2, eqv2_loss, eqv2_loss_halo
from repro.graphs.halo import build_partitioned_batch
from repro.launch.mesh import make_tiny_mesh
rng = np.random.default_rng(0)
n_dev, n, e = 8, 64, 300
src = rng.integers(0, n, e)
dst = np.clip(src + rng.integers(-4, 5, e), 0, n - 1)
x = rng.normal(size=(n, 12)).astype(np.float32)
labels = rng.integers(0, 4, n)
cfg = EqV2Config(name="e", n_layers=2, d_hidden=8, l_max=2, m_max=1,
                 n_heads=2, d_in=12, d_out=4, dtype="float32")
params = init_eqv2(jax.random.PRNGKey(0), cfg)
nc = cfg.n_coeff
pg = build_partitioned_batch(src, dst, x, labels, n_dev, halo=64)
wig_global = rng.normal(size=(e, nc, nc)).astype(np.float32) * 0.2
n_loc = pg.n_loc
order, counts = {}, [0]*n_dev
for t, d_ in enumerate(np.minimum(dst // n_loc, n_dev - 1)):
    order[(int(d_), counts[int(d_)])] = t
    counts[int(d_)] += 1
e_cap = pg.edge_src_ext.shape[1]
wig_p = np.zeros((n_dev, e_cap, nc, nc), np.float32)
for d_ in range(n_dev):
    for slot in range(min(counts[d_], e_cap)):
        wig_p[d_, slot] = wig_global[order[(d_, slot)]]
mesh = make_tiny_mesh()
bh = {k: jnp.asarray(v) for k, v in pg.device_batch().items()}
bh["wigner"] = jnp.asarray(wig_p)
with mesh:
    lh = float(jax.jit(lambda p, b: eqv2_loss_halo(p, b, cfg, mesh, ("data","model")))(params, bh))
br = {"x": jnp.asarray(x), "edge_src": jnp.asarray(src), "edge_dst": jnp.asarray(dst),
      "wigner": jnp.asarray(wig_global), "labels": jnp.asarray(labels),
      "label_mask": jnp.ones(n)}
lr = float(eqv2_loss(params, br, cfg))
assert abs(lh - lr) < 3e-5, (lh, lr)
print("EQV2_HALO_EXACT")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=540, env=ENV, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "EQV2_HALO_EXACT" in r.stdout
