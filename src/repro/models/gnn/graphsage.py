"""GraphSAGE (Hamilton et al. 2017) — mean aggregator, edge-list form.

Message passing is gather -> segment_mean -> linear (JAX-native SpMM per the
assignment).  Works over full graphs and sampler-produced padded subgraphs.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...distributed.sharding import Sharder
from ...graphs.segment import segment_mean
from ..common import Split, cross_entropy, dense_init

__all__ = ["SAGEConfig", "init_sage", "sage_forward", "sage_loss"]


@dataclass(frozen=True)
class SAGEConfig:
    name: str
    n_layers: int = 2
    d_hidden: int = 128
    d_in: int = 602
    n_classes: int = 41
    aggregator: str = "mean"
    dtype: str = "float32"


def init_sage(key, cfg: SAGEConfig) -> dict:
    ks = Split(key)
    dims = [cfg.d_in] + [cfg.d_hidden] * cfg.n_layers
    return {
        "w_self": [dense_init(ks(), a, b) for a, b in zip(dims[:-1], dims[1:])],
        "w_nbr": [dense_init(ks(), a, b) for a, b in zip(dims[:-1], dims[1:])],
        "b": [jnp.zeros((b,)) for b in dims[1:]],
        "w_out": dense_init(ks(), cfg.d_hidden, cfg.n_classes),
    }


def sage_forward(params, batch, cfg: SAGEConfig, shard: Sharder | None = None):
    shard = shard or Sharder(None)
    x = batch["x"]
    src, dst = batch["edge_src"], batch["edge_dst"]
    mask = batch.get("edge_mask")
    n = x.shape[0]
    for ws, wn, b in zip(params["w_self"], params["w_nbr"], params["b"]):
        x = shard.act(x, "flat", None)
        # project-then-gather: mean_nbr(x) @ Wn == mean_nbr(x @ Wn) (linear
        # maps commute with the mean), so the cross-shard gather moves
        # d_hidden-wide rows instead of d_in-wide ones — 4.7x less ICI on
        # reddit's 602-dim inputs (SSPerf hillclimb, graphsage cell)
        xn = x @ wn
        agg = segment_mean(xn[src], dst, n, mask)
        x = jax.nn.relu(x @ ws + agg + b)
    x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)
    return x @ params["w_out"]


def sage_loss(params, batch, cfg: SAGEConfig, shard: Sharder | None = None):
    logits = sage_forward(params, batch, cfg, shard)
    return cross_entropy(logits, batch["labels"], mask=batch.get("label_mask"))


# ---------------------------------------------------------------------------
# halo-exchange variant (SSPerf hillclimb: the collective-bound cell)
# ---------------------------------------------------------------------------

def sage_loss_halo(params, batch, cfg: SAGEConfig, mesh, axes: tuple):
    """Partitioned-layout GraphSAGE: features cross the network only through
    the per-layer halo all-to-all (graphs/halo.py), never an all-gather.

    ``batch`` uses the PartitionedGraph layout: x [N, F] (flat-sharded =
    n_loc rows per device), halo_send_idx [n_dev, n_dev, H] (dim 0 sharded),
    edge_src_ext/edge_dst_loc/edge_mask [n_dev, e_loc] (dim 0 sharded),
    labels/label_mask like x.
    """
    import functools

    from jax.sharding import PartitionSpec as P
    from ...graphs.halo import halo_exchange
    from ...graphs.segment import segment_mean as _segment_mean

    def local(x, send_idx, e_src, e_dst, e_mask, labels, lmask):
        send_idx = send_idx[0]
        e_src, e_dst, e_mask = e_src[0], e_dst[0], e_mask[0]
        labels, lmask = labels[0], lmask[0]
        n_loc = x.shape[0]
        for ws, wn, b in zip(params["w_self"], params["w_nbr"], params["b"]):
            xn = x @ wn                       # project-then-exchange
            ext = halo_exchange(xn, send_idx, axes)
            agg = _segment_mean(ext[e_src], e_dst, n_loc, e_mask)
            x = jax.nn.relu(x @ ws + agg + b)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)
        logits = x @ params["w_out"]
        lg = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
        num = jax.lax.psum(((lse - gold) * lmask).sum(), axes)
        den = jax.lax.psum(lmask.sum(), axes)
        return num / jnp.maximum(den, 1.0)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None, None), P(axes, None),
                  P(axes, None), P(axes, None), P(axes, None), P(axes, None)),
        out_specs=P(),
    )
    return fn(batch["x"], batch["halo_send_idx"], batch["edge_src_ext"],
              batch["edge_dst_loc"], batch["edge_mask"],
              batch["labels_2d"], batch["label_mask_2d"])
