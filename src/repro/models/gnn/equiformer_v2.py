"""EquiformerV2-style equivariant graph attention via eSCN convolutions
(Liao et al. 2023, arXiv:2306.12059).

Node features are spherical-harmonic coefficient stacks x [N, (L+1)^2, C]
with l_max=6.  Per edge the eSCN trick applies: rotate the source features
into the edge-aligned frame (Wigner-D block-diagonal matrix, precomputed
host-side per edge), where the SO(3) tensor-product convolution reduces to an
SO(2) convolution coupling only m <= m_max=2 — the O(L^6) -> O(L^3) reduction
the assignment's taxonomy names.  Attention weights come from the invariant
(l=0) channel via an MLP + segment softmax.

Documented simplification (DESIGN.md): the SO(2) conv mixes channels with
per-|m| weights shared across l (true eSCN also couples l-pairs); Wigner
matrices enter as inputs (host-precomputed) rather than being synthesized
in-graph.  Structure — rotate, m-restricted mix, attention, rotate back,
scatter — matches the paper.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...distributed.sharding import Sharder
from ...graphs.segment import segment_softmax, segment_sum
from ..common import Split, cross_entropy, dense_init, mlp_apply, mlp_init

__all__ = ["EqV2Config", "init_eqv2", "eqv2_forward", "eqv2_loss", "m_order_masks"]


@dataclass(frozen=True)
class EqV2Config:
    name: str
    n_layers: int = 12
    d_hidden: int = 128
    l_max: int = 6
    m_max: int = 2
    n_heads: int = 8
    d_in: int = 100
    d_out: int = 1
    # f32 default: XLA-CPU *inflates* measured temp for bf16 programs
    # (per-use f32 converts); on real TPUs flip to bfloat16 for 2x state
    dtype: str = "float32"

    @property
    def n_coeff(self) -> int:
        return (self.l_max + 1) ** 2


def m_order_masks(l_max: int, m_max: int) -> np.ndarray:
    """|m| per coefficient index (l^2 + l + m layout), clipped mask m<=m_max."""
    ms = np.zeros((l_max + 1) ** 2, dtype=np.int64)
    for l in range(l_max + 1):
        for m in range(-l, l + 1):
            ms[l * l + l + m] = abs(m)
    return ms


def init_eqv2(key, cfg: EqV2Config) -> dict:
    ks = Split(key)
    c = cfg.d_hidden
    layers = []
    for _ in range(cfg.n_layers):
        layers.append({
            # SO(2) channel mixing per |m| (m_max+1 weight sets)
            "w_so2": (jax.random.normal(ks(), (cfg.m_max + 1, c, c)) / np.sqrt(c)).astype(jnp.float32),
            "w_so2_im": (jax.random.normal(ks(), (cfg.m_max + 1, c, c)) / np.sqrt(c)).astype(jnp.float32),
            "attn_mlp": mlp_init(ks(), [2 * c, c, cfg.n_heads]),
            "node_mlp": mlp_init(ks(), [c, 2 * c, c]),
            "ln_scale": jnp.ones((c,)),
        })
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "embed": dense_init(ks(), cfg.d_in, c),
        "layers": stacked,
        "out": mlp_init(ks(), [c, c, cfg.d_out]),
    }


def eqv2_forward(params, batch, cfg: EqV2Config, shard: Sharder | None = None):
    """batch: x [N, d_in] invariant inputs, edge_src/dst [E], wigner
    [E, n_coeff, n_coeff] edge-frame rotations, masks."""
    shard = shard or Sharder(None)
    src, dst = batch["edge_src"], batch["edge_dst"]
    emask = batch.get("edge_mask")
    wig = batch["wigner"]
    n = batch["x"].shape[0]
    nc = cfg.n_coeff
    c = cfg.d_hidden

    m_of = jnp.asarray(m_order_masks(cfg.l_max, cfg.m_max))          # [nc]
    keep = (m_of <= cfg.m_max)                                       # SO(2) restriction
    # sign of m (for the +m/-m coupling): index of -m partner
    l_of = jnp.asarray([l for l in range(cfg.l_max + 1) for _ in range(2 * l + 1)])
    idx = jnp.arange(nc)
    m_signed = idx - (l_of * l_of + l_of)
    partner = l_of * l_of + l_of - m_signed                          # index of (l, -m)

    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    wig = wig.astype(dt)
    # lift invariant features into the l=0 channel
    x = jnp.zeros((n, nc, c), dt)
    x = x.at[:, 0, :].set(jnp.tanh(batch["x"].astype(jnp.float32)
                                   @ params["embed"]).astype(dt))

    def layer(x, lp):
        x = shard.act(x, "flat", None, None)
        # -- rotate into edge frames
        xe = jnp.einsum("epq,eqc->epc", wig, x[src],
                        preferred_element_type=jnp.float32).astype(x.dtype)
        # -- SO(2) conv: couple (l, m) with (l, -m), per-|m| channel mixing
        w_re = lp["w_so2"][jnp.clip(m_of, 0, cfg.m_max)].astype(x.dtype)
        w_im = lp["w_so2_im"][jnp.clip(m_of, 0, cfg.m_max)].astype(x.dtype)
        y_re = jnp.einsum("epc,pcd->epd", xe, w_re,
                          preferred_element_type=jnp.float32)
        y_im = jnp.einsum("epc,pcd->epd", xe[:, partner, :], w_im,
                          preferred_element_type=jnp.float32)
        sgn = jnp.sign(m_signed)[None, :, None].astype(jnp.float32)
        ye = jnp.where(keep[None, :, None], y_re + sgn * y_im, 0.0).astype(x.dtype)
        # -- invariant attention over incoming edges
        inv = jnp.concatenate([x[src][:, 0, :], x[dst][:, 0, :]], axis=-1)
        logits = mlp_apply(lp["attn_mlp"], inv)                      # [E, H]
        alpha = segment_softmax(logits, dst, n, emask)               # [E, H]
        alpha = alpha.mean(-1, keepdims=True)[:, None, :]            # [E,1,1]
        # -- rotate back + scatter
        msg = (jnp.einsum("eqp,epc->eqc", wig, ye) * alpha.astype(x.dtype)).astype(x.dtype)
        if emask is not None:
            msg = jnp.where(emask[:, None, None], msg, jnp.zeros((), x.dtype))
        agg = segment_sum(msg.reshape(msg.shape[0], -1), dst, n).reshape(n, nc, c)
        x = x + agg
        # -- equivariant norm + invariant MLP on l=0
        norm = jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2, axis=1, keepdims=True) + 1e-6)
        x = (x.astype(jnp.float32) / norm * lp["ln_scale"][None, None, :]).astype(x.dtype)
        x = x.at[:, 0, :].add(
            mlp_apply(lp["node_mlp"], x[:, 0, :].astype(jnp.float32)).astype(x.dtype))
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    return mlp_apply(params["out"], x[:, 0, :].astype(jnp.float32))  # invariant readout


def eqv2_loss(params, batch, cfg: EqV2Config, shard: Sharder | None = None):
    pred = eqv2_forward(params, batch, cfg, shard)
    if "labels" in batch:
        return cross_entropy(pred, batch["labels"], mask=batch.get("label_mask"))
    return jnp.mean((pred - batch["target"]).astype(jnp.float32) ** 2)


# ---------------------------------------------------------------------------
# halo-exchange variant (SSPerf: the gather formulation all-gathers the
# [N, nc, C] coefficient stacks per layer; the partitioned layout moves only
# boundary stacks — same machinery proven on GraphSAGE in graphs/halo.py)
# ---------------------------------------------------------------------------

def eqv2_loss_halo(params, batch, cfg: EqV2Config, mesh, axes: tuple):
    """Partitioned-layout EquiformerV2.

    batch: x [N, d_in] flat-sharded; halo_send_idx [n_dev, n_dev, H];
    edge_src_ext/edge_dst_loc/edge_mask [n_dev, e_loc]; wigner
    [n_dev, e_loc, nc, nc]; labels_2d/label_mask_2d [n_dev, n_loc].
    """
    from jax.sharding import PartitionSpec as P
    from ...graphs.halo import halo_exchange

    nc, c = cfg.n_coeff, cfg.d_hidden
    m_of = jnp.asarray(m_order_masks(cfg.l_max, cfg.m_max))
    keep = (m_of <= cfg.m_max)
    l_of = jnp.asarray([l for l in range(cfg.l_max + 1) for _ in range(2 * l + 1)])
    idx = jnp.arange(nc)
    m_signed = idx - (l_of * l_of + l_of)
    partner = l_of * l_of + l_of - m_signed

    def local(xin, send_idx, e_src, e_dst, e_mask, wig, labels, lmask):
        send_idx = send_idx[0]
        e_src, e_dst, e_mask, wig = e_src[0], e_dst[0], e_mask[0], wig[0]
        labels, lmask = labels[0], lmask[0]
        n_loc = xin.shape[0]
        x = jnp.zeros((n_loc, nc, c))
        x = x.at[:, 0, :].set(jnp.tanh(xin @ params["embed"]))

        def layer(x, lp):
            ext = halo_exchange(x.reshape(n_loc, nc * c), send_idx, axes)
            xs = ext[e_src].reshape(-1, nc, c)           # boundary-aware gather
            xe = jnp.einsum("epq,eqc->epc", wig, xs)
            w_re = lp["w_so2"][jnp.clip(m_of, 0, cfg.m_max)]
            w_im = lp["w_so2_im"][jnp.clip(m_of, 0, cfg.m_max)]
            y_re = jnp.einsum("epc,pcd->epd", xe, w_re)
            y_im = jnp.einsum("epc,pcd->epd", xe[:, partner, :], w_im)
            sgn = jnp.sign(m_signed)[None, :, None].astype(x.dtype)
            ye = jnp.where(keep[None, :, None], y_re + sgn * y_im, 0.0)
            inv = jnp.concatenate([xs[:, 0, :], x[e_dst][:, 0, :]], axis=-1)
            logits = mlp_apply(lp["attn_mlp"], inv)
            alpha = segment_softmax(logits, e_dst, n_loc, e_mask)
            alpha = alpha.mean(-1, keepdims=True)[:, None, :]
            msg = jnp.einsum("eqp,epc->eqc", wig, ye) * alpha
            msg = jnp.where(e_mask[:, None, None], msg, 0.0)
            agg = segment_sum(msg.reshape(msg.shape[0], -1), e_dst,
                              n_loc).reshape(n_loc, nc, c)
            x = x + agg
            norm = jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2, axis=1,
                                    keepdims=True) + 1e-6)
            x = (x.astype(jnp.float32) / norm
                 * lp["ln_scale"][None, None, :]).astype(x.dtype)
            x = x.at[:, 0, :].add(mlp_apply(lp["node_mlp"], x[:, 0, :]))
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
        pred = mlp_apply(params["out"], x[:, 0, :]).astype(jnp.float32)
        lse = jax.nn.logsumexp(pred, axis=-1)
        gold = jnp.take_along_axis(pred, labels[:, None], axis=-1)[:, 0]
        num = jax.lax.psum(((lse - gold) * lmask).sum(), axes)
        den = jax.lax.psum(lmask.sum(), axes)
        return num / jnp.maximum(den, 1.0)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None, None), P(axes, None),
                  P(axes, None), P(axes, None), P(axes, None, None, None),
                  P(axes, None), P(axes, None)),
        out_specs=P(),
    )
    return fn(batch["x"], batch["halo_send_idx"], batch["edge_src_ext"],
              batch["edge_dst_loc"], batch["edge_mask"], batch["wigner"],
              batch["labels_2d"], batch["label_mask_2d"])
