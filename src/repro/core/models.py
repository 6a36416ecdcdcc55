"""The FlowGNN model zoo: GCN, GIN, GIN+VN, GAT, PNA, DGN (paper Table II),
and the graph transformer GraphGPS (arXiv:2205.12454).

Each model is a functional (init, apply) pair built on the generic
message-passing engine. Layer counts / dims default to the paper's Sec. VI-A
configurations; everything is overridable through ``GNNConfig``.

These models are *inference-first* (the paper accelerates inference), but all
apply functions are differentiable so the same code trains (used by the
quickstart example and the loss-decreases system test).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.graph import GraphBatch
from repro.core.message_passing import (
    DEFAULT_DATAFLOW,
    DataflowConfig,
    FusableAttention,
    FusableMessage,
    FusableUpdate,
    PrecomputedGraphStats,
    _count_pass,
    fused_edge_aggregate,
    global_pool,
    precompute_graph_stats,
    propagate,
    scan_layers,
    segment_aggregate,
    segment_softmax,
)

Array = jax.Array
Params = Dict[str, Any]

# impls whose edge phase consumes the FusableMessage description
_FUSABLE_IMPLS = ("pipeline", "fused_layer")


def _stack_layers(layers):
    """Stack a homogeneous list of per-layer param pytrees on a leading axis.

    The stacked form is what the scanned forward (DESIGN.md §7) consumes:
    ``lax.scan`` slices one layer's parameters per step, so the layer loop
    compiles ONCE instead of being re-traced per layer. ``init`` keeps the
    per-layer list layout (checkpoints, dense oracles, and the training
    example index it), and apply-time stacking is a cheap device-side
    concat.
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


@dataclass(frozen=True)
class GNNConfig:
    model: str = "gin"
    num_layers: int = 5
    hidden_dim: int = 100
    node_feat_dim: int = 9          # OGB-mol style raw features
    edge_feat_dim: int = 3
    out_dim: int = 1
    heads: int = 4                  # GAT, GPS attention
    head_dim: int = 16              # GAT, GPS attention
    ffn_dim: int = 768              # GPS feed-forward width
    pe_steps: int = 16              # GPS RWSE: random-walk steps
    pe_dim: int = 20                # GPS RWSE: encoded width
    attention_slot: Optional[int] = None  # GPS: most nodes one graph may have
    pos_dim: int = 1                # DGN directional field width
    avg_log_degree: float = 1.3     # PNA's delta (from "training set")
    task: str = "graph"             # graph | node
    head_mlp: Tuple[int, ...] = ()  # extra hidden head layers (PNA/DGN)
    eps_init: float = 0.0           # GIN epsilon
    dtype: Any = jnp.float32

    def replace(self, **kw) -> "GNNConfig":
        import dataclasses
        return dataclasses.replace(self, **kw)


# Paper Sec. VI-A model configurations.
PAPER_GNN_CONFIGS: Dict[str, GNNConfig] = {
    "gcn": GNNConfig(model="gcn", num_layers=5, hidden_dim=100),
    "gin": GNNConfig(model="gin", num_layers=5, hidden_dim=100),
    "gin_vn": GNNConfig(model="gin_vn", num_layers=5, hidden_dim=100),
    "gat": GNNConfig(model="gat", num_layers=5, hidden_dim=64, heads=4, head_dim=16),
    "pna": GNNConfig(model="pna", num_layers=4, hidden_dim=80, head_mlp=(40, 20)),
    "dgn": GNNConfig(model="dgn", num_layers=4, hidden_dim=100, head_mlp=(50, 25)),
}


# ---------------------------------------------------------------------------
# param helpers
# ---------------------------------------------------------------------------

def _dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> Params:
    scale = jnp.sqrt(2.0 / (d_in + d_out))
    w = jax.random.normal(key, (d_in, d_out), dtype) * scale
    return {"w": w, "b": jnp.zeros((d_out,), dtype)}


def _dense(p: Params, x: Array) -> Array:
    return x @ p["w"] + p["b"]


def _mlp_init(key, dims, dtype=jnp.float32) -> list:
    keys = jax.random.split(key, len(dims) - 1)
    return [_dense_init(k, dims[i], dims[i + 1], dtype) for i, k in enumerate(keys)]


def _mlp(ps: list, x: Array, act=jax.nn.relu) -> Array:
    for i, p in enumerate(ps):
        x = _dense(p, x)
        if i < len(ps) - 1:
            x = act(x)
    return x


def _head_init(key, cfg: GNNConfig, d_in: int) -> list:
    dims = (d_in,) + tuple(cfg.head_mlp) + (cfg.out_dim,)
    return _mlp_init(key, dims, cfg.dtype)


def _readout(head, cfg: GNNConfig, graph: GraphBatch, x: Array,
             stats: Optional[PrecomputedGraphStats] = None) -> Array:
    if cfg.task == "node":
        return _mlp(head, x)
    pooled = global_pool(graph, x, kind="mean", stats=stats)
    out = _mlp(head, pooled)
    return jnp.where(graph.graph_mask[:, None], out, 0.0)


# ---------------------------------------------------------------------------
# GCN — SpMM-expressible family (paper uses it for the I-GCN comparison)
# ---------------------------------------------------------------------------

def gcn_init(key, cfg: GNNConfig) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 2)
    layers = []
    d = cfg.hidden_dim
    for l in range(cfg.num_layers):
        d_in = cfg.node_feat_dim if l == 0 else d
        layers.append(_dense_init(keys[l], d_in, d, cfg.dtype))
    return {"layers": layers, "head": _head_init(keys[-1], cfg, d)}


def gcn_layer(p, graph: GraphBatch, x: Array, dataflow: DataflowConfig,
              stats: PrecomputedGraphStats, *, last,
              fusable: Optional[FusableMessage] = None) -> Array:
    """One GCN layer (module-level so the wide runner can drive it per shard).

    ``stats`` must carry ``inv_sqrt_deg``; ``fusable`` may share the per-edge
    norm stream across layers (rebuilt here when absent — same values, the
    gather is cheap next to the edge sweep).
    """
    inv_sqrt = stats.inv_sqrt_deg
    self_coeff = inv_sqrt * inv_sqrt        # analytic self-loop weight
    if fusable is None and dataflow.impl in _FUSABLE_IMPLS:
        fusable = FusableMessage(
            src_weight=inv_sqrt[graph.senders] * inv_sqrt[graph.receivers])

    def message(src, dst, e, _inv=inv_sqrt, _g=graph):
        norm = _inv[_g.senders] * _inv[_g.receivers]
        return src * norm[:, None]

    def update(xv, m, _p=p):
        m = m + xv * self_coeff[:, None]      # analytic self loop
        return _dense(_p, m)

    fu = (FusableUpdate(w1=p["w"], b1=p["b"], self_coeff=self_coeff)
          if dataflow.impl == "fused_layer" else None)
    h = propagate(graph, x, message_fn=message, update_fn=update,
                  aggregate="sum", dataflow=dataflow, stats=stats,
                  fusable=fusable, fusable_update=fu)
    # position-dependent activation gated outside the (scan-invariant)
    # layer body; relu(0) == 0 so it commutes with the node mask
    return jnp.where(last, h, jax.nn.relu(h))


def gcn_apply(params, graph: GraphBatch, cfg: GNNConfig,
              dataflow: DataflowConfig = DEFAULT_DATAFLOW,
              stats: Optional[PrecomputedGraphStats] = None) -> Array:
    x = graph.node_feat.astype(cfg.dtype)
    if stats is None or stats.inv_sqrt_deg is None:
        stats = precompute_graph_stats(graph, with_self_loop_norm=True,
                                       with_graph_counts=cfg.task == "graph")
    inv_sqrt = stats.inv_sqrt_deg           # 1/sqrt(deg+1), once per graph
    self_coeff = inv_sqrt * inv_sqrt        # analytic self-loop weight

    # fusable phi: the symmetric norm is a per-edge scalar stream, shared
    # by every layer (layer-invariant — computed once per forward pass)
    fusable = None
    if dataflow.impl in _FUSABLE_IMPLS:
        fusable = FusableMessage(
            src_weight=inv_sqrt[graph.senders] * inv_sqrt[graph.receivers])

    def layer_step(xx, p, last):
        return gcn_layer(p, graph, xx, dataflow, stats, last=last,
                         fusable=fusable)

    n_layers = cfg.num_layers
    # layer 0 maps node_feat_dim -> hidden and stays unrolled; the
    # homogeneous tail scans over stacked parameters (one compiled body)
    if dataflow.scan_layers and n_layers > 1:
        x = layer_step(x, params["layers"][0], n_layers == 1)
        stacked = _stack_layers(params["layers"][1:])
        last_flags = jnp.arange(1, n_layers) == n_layers - 1

        def body(xx, pl):
            p, last = pl
            return layer_step(xx, p, last), None

        x, _ = scan_layers(body, x, (stacked, last_flags),
                           length=n_layers - 1)
    else:
        for l, p in enumerate(params["layers"]):
            x = layer_step(x, p, l == n_layers - 1)
    return _readout(params["head"], cfg, graph, x, stats)


# ---------------------------------------------------------------------------
# GIN (+ edge embeddings, Eq. 1) and GIN + Virtual Node
# ---------------------------------------------------------------------------

def _gin_layers_init(key, cfg: GNNConfig):
    keys = jax.random.split(key, cfg.num_layers)
    layers = []
    d = cfg.hidden_dim
    for l in range(cfg.num_layers):
        k1, k2, k3 = jax.random.split(keys[l], 3)
        layers.append({
            "edge_enc": _dense_init(k1, cfg.edge_feat_dim, d, cfg.dtype),
            "mlp": _mlp_init(k2, (d, 2 * d, d), cfg.dtype),
            "eps": jnp.asarray(cfg.eps_init, cfg.dtype),
        })
    return layers


def gin_init(key, cfg: GNNConfig) -> Params:
    k0, k1, k2 = jax.random.split(key, 3)
    return {
        "node_enc": _dense_init(k0, cfg.node_feat_dim, cfg.hidden_dim, cfg.dtype),
        "layers": _gin_layers_init(k1, cfg),
        "head": _head_init(k2, cfg, cfg.hidden_dim),
    }


def _gin_layer(p, graph, x, dataflow, stats=None):
    e = _dense(p["edge_enc"], graph.edge_feat)   # per-layer bond encoder

    def message(src, dst, ee, _e=e):
        return jax.nn.relu(src + _e)             # phi = ReLU(x_j + e_ji)

    def update(xx, m, _p=p):
        return _mlp(_p["mlp"], (1.0 + _p["eps"]) * xx + m)

    # fusable phi: the bond embedding is an additive edge-side input stream
    fusable = (FusableMessage(edge_term=e, activation="relu")
               if dataflow.impl in _FUSABLE_IMPLS else None)
    # fusable gamma: (1+eps) self term + the 2-layer MLP, in-kernel
    fu = None
    if dataflow.impl == "fused_layer":
        m0, m1 = p["mlp"]
        fu = FusableUpdate(w1=m0["w"], b1=m0["b"], w2=m1["w"], b2=m1["b"],
                           self_coeff=1.0 + p["eps"])
    return propagate(graph, x, message_fn=message, update_fn=update,
                     aggregate="sum", dataflow=dataflow, stats=stats,
                     fusable=fusable, fusable_update=fu)


def gin_apply(params, graph: GraphBatch, cfg: GNNConfig,
              dataflow: DataflowConfig = DEFAULT_DATAFLOW,
              stats: Optional[PrecomputedGraphStats] = None) -> Array:
    x = jax.nn.relu(_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)))
    if stats is None and cfg.task == "graph":
        stats = precompute_graph_stats(graph, with_degrees=False,
                                       with_graph_counts=True)
    if dataflow.scan_layers and cfg.num_layers > 1:
        def body(xx, p):
            return _gin_layer(p, graph, xx, dataflow, stats), None

        x, _ = scan_layers(body, x, _stack_layers(params["layers"]),
                           length=cfg.num_layers)
    else:
        for p in params["layers"]:
            x = _gin_layer(p, graph, x, dataflow, stats)
    return _readout(params["head"], cfg, graph, x, stats)


def gin_vn_init(key, cfg: GNNConfig) -> Params:
    k0, k1, k2, k3 = jax.random.split(key, 4)
    d = cfg.hidden_dim
    vn_mlps = [_mlp_init(k, (d, 2 * d, d), cfg.dtype)
               for k in jax.random.split(k3, cfg.num_layers - 1)]
    return {
        "node_enc": _dense_init(k0, cfg.node_feat_dim, d, cfg.dtype),
        "layers": _gin_layers_init(k1, cfg),
        "head": _head_init(k2, cfg, d),
        "vn_mlps": vn_mlps,
    }


def gin_vn_broadcast(graph: GraphBatch, x: Array, vn: Array) -> Array:
    """VN -> all nodes (node-local given a replicated ``vn``)."""
    x = x + vn[graph.graph_ids]
    return jnp.where(graph.node_mask[:, None], x, 0.0)


def gin_vn_update(p_vn, graph: GraphBatch, x: Array, vn: Array) -> Array:
    """All nodes -> VN: the per-graph sum pool + MLP (needs the full graph)."""
    pooled = global_pool(graph, x, kind="sum")
    vn = _mlp(p_vn, vn + pooled)
    return jnp.where(graph.graph_mask[:, None], vn, 0.0)


def gin_vn_apply(params, graph: GraphBatch, cfg: GNNConfig,
                 dataflow: DataflowConfig = DEFAULT_DATAFLOW,
                 stats: Optional[PrecomputedGraphStats] = None) -> Array:
    """GIN with a virtual node per packed graph.

    The VN's O(N) edges are never materialized: its incoming aggregation is a
    segment-sum pool and its outgoing messages are a broadcast — the dataflow
    balances automatically (paper Fig. 6, strictly cheaper here).
    """
    x = jax.nn.relu(_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)))
    if stats is None and cfg.task == "graph":
        stats = precompute_graph_stats(graph, with_degrees=False,
                                       with_graph_counts=True)
    vn = jnp.zeros((graph.n_graph_pad, cfg.hidden_dim), cfg.dtype)
    n_layers = len(params["layers"])

    def broadcast_vn(xx, vv):
        return gin_vn_broadcast(graph, xx, vv)

    def vn_update(xx, vv, p_vn):
        return gin_vn_update(p_vn, graph, xx, vv)

    if dataflow.scan_layers and n_layers > 1:
        # layers 0..L-2 (gin layer + vn exchange) are homogeneous and scan;
        # the last layer (no vn update after it) stays unrolled
        def body(carry, ps):
            xx, vv = carry
            p_layer, p_vn = ps
            xx = _gin_layer(p_layer, graph, broadcast_vn(xx, vv), dataflow,
                            stats)
            return (xx, vn_update(xx, vv, p_vn)), None

        (x, vn), _ = scan_layers(
            body, (x, vn),
            (_stack_layers(params["layers"][:-1]),
             _stack_layers(params["vn_mlps"])),
            length=n_layers - 1)
        x = _gin_layer(params["layers"][-1], graph, broadcast_vn(x, vn),
                       dataflow, stats)
    else:
        for l, p in enumerate(params["layers"]):
            x = _gin_layer(p, graph, broadcast_vn(x, vn), dataflow, stats)
            if l < n_layers - 1:
                vn = vn_update(x, vn, params["vn_mlps"][l])
    return _readout(params["head"], cfg, graph, x, stats)


# ---------------------------------------------------------------------------
# GAT — anisotropic family; MP-to-NT (gather-then-transform) dataflow
# ---------------------------------------------------------------------------

def gat_init(key, cfg: GNNConfig) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 2)
    d_hid = cfg.heads * cfg.head_dim
    layers = []
    for l in range(cfg.num_layers):
        d_in = cfg.node_feat_dim if l == 0 else d_hid
        # fresh keys per layer for w AND both attention halves (a_dst used to
        # be drawn from the shared keys[-2], making every layer's destination
        # attention identical)
        kw, ka_src, ka_dst = jax.random.split(keys[l], 3)
        layers.append({
            "w": _dense_init(kw, d_in, d_hid, cfg.dtype),
            # attention vectors a = [a_src ; a_dst], one per head
            "a_src": jax.random.normal(ka_src, (cfg.heads, cfg.head_dim), cfg.dtype) * 0.1,
            "a_dst": jax.random.normal(ka_dst, (cfg.heads, cfg.head_dim), cfg.dtype) * 0.1,
        })
    return {"layers": layers, "head": _head_init(keys[-1], cfg, d_hid)}


def gat_layer(p, graph: GraphBatch, x: Array, dataflow: DataflowConfig,
              stats: Optional[PrecomputedGraphStats], *, last) -> Array:
    """One GAT layer (module-level so the wide runner can drive it per shard).

    Heads/head_dim come from the attention-vector shapes. The per-node
    attention halves use an explicit multiply-reduce over the head dim
    rather than einsum: XLA lowers the einsum through a gemm whose
    accumulation order depends on the row count, while the elementwise
    product + axis reduction is per-row stable — required for wide
    placement, where each shard evaluates the NT side on a different
    number of rows yet must match the single-device forward bitwise.
    """
    H, Dh = p["a_src"].shape
    N = graph.n_node_pad
    h = _dense(p["w"], x).reshape(N, H, Dh)
    # per-node attention halves (computed once per node — NT side)
    alpha_src = (h * p["a_src"][None]).sum(-1)
    alpha_dst = (h * p["a_dst"][None]).sum(-1)
    if dataflow.impl in _FUSABLE_IMPLS:
        # one-launch attention: per-edge logits, leaky_relu, the flash
        # style online softmax (running max + rescaled denominator per
        # dest bank) and the weighted scatter all fold into the edge
        # sweep (DESIGN.md §6) — no seg_softmax pre-pass and no (E, H)
        # attention stream through HBM
        agg = fused_edge_aggregate(
            graph, h.reshape(N, H * Dh),
            FusableMessage(attention=FusableAttention(
                src_logits=alpha_src, dst_logits=alpha_dst)),
            kinds=("sum",), dataflow=dataflow, stats=stats)["sum"]
    else:
        logits = jax.nn.leaky_relu(
            alpha_src[graph.senders] + alpha_dst[graph.receivers],
            negative_slope=0.2)                               # (E, H)
        att = segment_softmax(logits, graph.receivers, N,
                              edge_mask=graph.edge_mask,
                              dataflow=dataflow)              # (E, H)
        msg = h[graph.senders] * att[..., None]               # (E, H, Dh)
        _count_pass()         # the gather + weight message rewrite
        agg = segment_aggregate(
            msg.reshape(-1, H * Dh), graph.receivers, N,
            kind="sum", edge_mask=graph.edge_mask, dataflow=dataflow)
    out = jnp.where(last, agg, jax.nn.elu(agg))
    return jnp.where(graph.node_mask[:, None], out, 0.0)


def gat_apply(params, graph: GraphBatch, cfg: GNNConfig,
              dataflow: DataflowConfig = DEFAULT_DATAFLOW,
              stats: Optional[PrecomputedGraphStats] = None) -> Array:
    x = graph.node_feat.astype(cfg.dtype)
    if stats is None and cfg.task == "graph":
        stats = precompute_graph_stats(graph, with_degrees=False,
                                       with_graph_counts=True)

    def layer_step(xx, p, last):
        return gat_layer(p, graph, xx, dataflow, stats, last=last)

    n_layers = cfg.num_layers
    if dataflow.scan_layers and n_layers > 1:
        x = layer_step(x, params["layers"][0], n_layers == 1)
        last_flags = jnp.arange(1, n_layers) == n_layers - 1

        def body(xx, pl):
            p, last = pl
            return layer_step(xx, p, last), None

        x, _ = scan_layers(body, x,
                           (_stack_layers(params["layers"][1:]), last_flags),
                           length=n_layers - 1)
    else:
        for l, p in enumerate(params["layers"]):
            x = layer_step(x, p, l == n_layers - 1)
    return _readout(params["head"], cfg, graph, x, stats)


# ---------------------------------------------------------------------------
# PNA — multi-aggregator (mean/std/max/min) x degree scalers (Eq. 3)
# ---------------------------------------------------------------------------

def pna_init(key, cfg: GNNConfig) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 3)
    d = cfg.hidden_dim
    layers = []
    for l in range(cfg.num_layers):
        k1, k2, k3 = jax.random.split(keys[l], 3)
        layers.append({
            "edge_enc": _dense_init(k1, cfg.edge_feat_dim, d, cfg.dtype),
            "pre": _dense_init(k2, 2 * d, d, cfg.dtype),     # phi(x_j, e)
            "post": _dense_init(k3, 12 * d + d, d, cfg.dtype),  # 4 aggs x 3 scalers + self
        })
    return {
        "node_enc": _dense_init(keys[-3], cfg.node_feat_dim, d, cfg.dtype),
        "layers": layers,
        "head": _head_init(keys[-1], cfg, d),
    }


def pna_layer(p, graph: GraphBatch, x: Array, dataflow: DataflowConfig,
              stats: PrecomputedGraphStats) -> Array:
    """One PNA layer (module-level so the wide runner can drive it per shard).

    ``stats`` must carry ``pna_scalers`` (and ``degrees`` for mean/std).
    """
    N = graph.n_node_pad
    d = p["pre"]["w"].shape[1]
    scalers = stats.pna_scalers                               # (N, 3)
    e = _dense(p["edge_enc"], graph.edge_feat)

    def message(src, dst, ee, _e=e, _p=p):
        return jax.nn.relu(_dense(_p["pre"], jnp.concatenate([src, _e], -1)))

    def update(xv, m, _p=p):
        # m = concat of 4 aggregators: (N, 4D); apply 3 scalers -> (N, 12D)
        scaled = (m[:, None, :] * scalers[:, :, None]).reshape(N, -1)
        h = _dense(_p["post"], jnp.concatenate([xv, scaled], -1))
        return jax.nn.relu(h)

    # fusable phi: the pre-linear splits into a node-side transform
    # (N rows, not E) plus an edge-side term — phi = relu(x@Ws[snd]
    # + e@We + b), exactly the per-edge linear-combine contract.
    # fusable gamma: the scaler-contraction epilogue — the four
    # statistics are derived from the kernel's accumulators and the
    # degree scalers contracted in-register (DESIGN.md §7), so under
    # impl='fused_layer' on kernel backends PNA is one launch per
    # layer too; off-kernel the pipeline edge phase + XLA gamma stays.
    fusable = None
    fu = None
    if dataflow.impl in _FUSABLE_IMPLS:
        w_pre, b_pre = p["pre"]["w"], p["pre"]["b"]
        fusable = FusableMessage(
            node_input=x @ w_pre[:d], edge_term=e @ w_pre[d:],
            bias=b_pre, activation="relu")
        if dataflow.impl == "fused_layer":
            fu = FusableUpdate(w1=p["post"]["w"], b1=p["post"]["b"],
                               scalers=scalers, out_activation="relu")

    return propagate(graph, x, message_fn=message, update_fn=update,
                     aggregate=("mean", "std", "max", "min"),
                     dataflow=dataflow, stats=stats, fusable=fusable,
                     fusable_update=fu)


def pna_apply(params, graph: GraphBatch, cfg: GNNConfig,
              dataflow: DataflowConfig = DEFAULT_DATAFLOW,
              stats: Optional[PrecomputedGraphStats] = None) -> Array:
    x = jax.nn.relu(_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)))
    if stats is None or stats.pna_scalers is None:
        # one degree sweep for the whole network: the shared degrees feed the
        # scalers AND every layer's mean/std (no per-layer count columns)
        stats = precompute_graph_stats(graph, pna_delta=cfg.avg_log_degree,
                                       with_graph_counts=cfg.task == "graph")

    def layer_step(xx, p):
        return pna_layer(p, graph, xx, dataflow, stats)

    if dataflow.scan_layers and cfg.num_layers > 1:
        def body(xx, p):
            return layer_step(xx, p), None

        x, _ = scan_layers(body, x, _stack_layers(params["layers"]),
                           length=cfg.num_layers)
    else:
        for p in params["layers"]:
            x = layer_step(x, p)
    return _readout(params["head"], cfg, graph, x, stats)


# ---------------------------------------------------------------------------
# DGN — directional aggregation guided by a node field (Laplacian-eigvec proxy)
# ---------------------------------------------------------------------------

def dgn_init(key, cfg: GNNConfig) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 2)
    d = cfg.hidden_dim
    layers = []
    for l in range(cfg.num_layers):
        layers.append({"post": _dense_init(keys[l], 2 * d + d, d, cfg.dtype)})
    return {
        "node_enc": _dense_init(keys[-2], cfg.node_feat_dim, d, cfg.dtype),
        "layers": layers,
        "head": _head_init(keys[-1], cfg, d),
    }


def dgn_lane_weights(graph: GraphBatch, stats: PrecomputedGraphStats,
                     d: int, dtype) -> Array:
    """The layer-invariant [1 | w] per-lane weight stream for DGN's phi."""
    e_pad = graph.n_edge_pad
    return jnp.concatenate(
        [jnp.ones((e_pad, d), dtype),
         jnp.broadcast_to(stats.dgn_weights[:, None], (e_pad, d))], axis=-1)


def dgn_layer(p, graph: GraphBatch, x: Array, dataflow: DataflowConfig,
              stats: PrecomputedGraphStats, *,
              lane_w: Optional[Array] = None) -> Array:
    """One DGN layer (module-level so the wide runner can drive it per shard).

    ``stats`` must carry the directional field (``dgn_weights``/``dgn_wsum``)
    and ``degrees``; ``lane_w`` may share the per-forward [1 | w] lane stream
    (rebuilt here when absent).
    """
    d = p["post"]["w"].shape[1]
    w = stats.dgn_weights                                      # (E,)
    w_sum = stats.dgn_wsum                                     # (N,)
    if lane_w is None and dataflow.impl in _FUSABLE_IMPLS:
        lane_w = dgn_lane_weights(graph, stats, d, x.dtype)

    # single-pass multi-statistic sweep: the mean aggregator and the
    # directional sum come out of ONE pass over [x_src | x_src*w]
    # (degrees and the field normalizer come precomputed via ``stats``)
    def message(src, dst, ee):
        return jnp.concatenate([src, src * w[:, None]], axis=-1)

    def update(xv, m, _p=p):
        # m = concat(sum, mean) over the stacked lanes: (N, 4D)
        m_mean = m[:, 2 * d:3 * d]
        m_dir = m[:, d:2 * d]
        m_dx = jnp.abs(m_dir - xv * w_sum[:, None])       # |B_dx X|
        h = _dense(_p["post"], jnp.concatenate([xv, m_mean, m_dx], -1))
        return jax.nn.relu(h)

    # fusable gamma: the directional-field epilogue — under
    # impl='fused_layer' on kernel backends the |s1 - x·wsum| combine
    # and the post MLP run inside the same launch as the edge sweep
    # (DESIGN.md §7), so DGN is one launch per layer too
    fus = None
    fu = None
    if dataflow.impl in _FUSABLE_IMPLS:
        fus = FusableMessage(
            node_input=jnp.concatenate([x, x], axis=-1),
            src_weight=lane_w)
        if dataflow.impl == "fused_layer":
            fu = FusableUpdate(w1=p["post"]["w"], b1=p["post"]["b"],
                               field_wsum=w_sum, out_activation="relu")

    return propagate(graph, x, message_fn=message, update_fn=update,
                     aggregate=("sum", "mean"), dataflow=dataflow,
                     stats=stats, fusable=fus, fusable_update=fu)


def dgn_apply(params, graph: GraphBatch, cfg: GNNConfig,
              dataflow: DataflowConfig = DEFAULT_DATAFLOW,
              stats: Optional[PrecomputedGraphStats] = None) -> Array:
    """mean + directional-derivative aggregators: Y = [D^-1 A X ; |B_dx X|].

    B_dx rows are built on the fly from the per-node field ``node_pos``
    (the paper feeds precomputed Laplacian eigenvectors as kernel inputs; our
    streaming generator attaches the field to each graph the same way).
    The field weights, their per-destination sums, and the degrees are all
    layer-invariant — computed once in ``precompute_graph_stats`` and shared.
    """
    x = jax.nn.relu(_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)))
    if stats is None or stats.dgn_weights is None:
        stats = precompute_graph_stats(graph, with_dgn_field=True,
                                       with_graph_counts=cfg.task == "graph")

    # fusable phi for the pipeline: [x_src | x_src*w] is the gathered row of
    # the duplicated node buffer scaled by per-lane weights [1 | w] — the
    # weight stream is layer-invariant (field only), built once per forward
    lane_w = None
    if dataflow.impl in _FUSABLE_IMPLS:
        lane_w = dgn_lane_weights(graph, stats, cfg.hidden_dim, x.dtype)

    def layer_step(xx, p):
        return dgn_layer(p, graph, xx, dataflow, stats, lane_w=lane_w)

    if dataflow.scan_layers and cfg.num_layers > 1:
        def body(xx, p):
            return layer_step(xx, p), None

        x, _ = scan_layers(body, x, _stack_layers(params["layers"]),
                           length=cfg.num_layers)
    else:
        for p in params["layers"]:
            x = layer_step(x, p)
    return _readout(params["head"], cfg, graph, x, stats)


# ---------------------------------------------------------------------------
# GraphGPS — GatedGCN local block + per-graph Transformer, RWSE positions
# (Rampášek et al., arXiv:2205.12454; DESIGN.md §13)
# ---------------------------------------------------------------------------

_BN_EPS = 1e-5      # torch.nn.BatchNorm1d's default


def _bn_init(key, d: int, dtype=jnp.float32) -> Params:
    """Inference BatchNorm: scale, shift and running statistics, seeded
    away from the identity so that folding them is exercised."""
    kg, kb, km, kv = jax.random.split(key, 4)
    return {"gamma": 1.0 + 0.1 * jax.random.normal(kg, (d,), dtype),
            "beta": 0.1 * jax.random.normal(kb, (d,), dtype),
            "mean": 0.1 * jax.random.normal(km, (d,), dtype),
            "var": jax.random.uniform(kv, (d,), dtype, 0.5, 1.5)}


def _bn(p: Params, x: Array) -> Array:
    """Inference BatchNorm folded into one affine map at trace time."""
    scale = p["gamma"] * jax.lax.rsqrt(p["var"] + _BN_EPS)
    return x * scale + (p["beta"] - p["mean"] * scale)


def gps_init(key, cfg: GNNConfig) -> Params:
    d, f = cfg.hidden_dim, cfg.ffn_dim
    if cfg.attention_slot is None:
        raise ValueError("gps needs attention_slot: the most nodes one "
                         "graph may have")
    if cfg.heads * cfg.head_dim != d:
        raise ValueError(f"gps needs heads * head_dim == hidden_dim, got "
                         f"{cfg.heads} * {cfg.head_dim} != {d}")
    if not 0 < cfg.pe_dim < d:
        raise ValueError(f"gps needs 0 < pe_dim < hidden_dim, got "
                         f"{cfg.pe_dim}")
    dt = cfg.dtype
    k_node, k_bn, k_pe, k_edge, k_layers, k_head = jax.random.split(key, 6)
    layers = []
    for k in jax.random.split(k_layers, cfg.num_layers):
        ks = jax.random.split(k, 14)
        layer = {name: _dense_init(kk, d, d, dt)
                 for name, kk in zip("ABCDE", ks[:5])}
        layer.update({
            "bn_x": _bn_init(ks[5], d, dt),
            "bn_e": _bn_init(ks[6], d, dt),
            "norm_local": _bn_init(ks[7], d, dt),
            "attn_in": _dense_init(ks[8], d, 3 * d, dt),
            "attn_out": _dense_init(ks[9], d, d, dt),
            "norm_attn": _bn_init(ks[10], d, dt),
            "ffn1": _dense_init(ks[11], d, f, dt),
            "ffn2": _dense_init(ks[12], f, d, dt),
            "norm_ffn": _bn_init(ks[13], d, dt),
        })
        layers.append(layer)
    return {
        "node_enc": _dense_init(k_node, cfg.node_feat_dim, d - cfg.pe_dim, dt),
        "pe_norm": _bn_init(k_bn, cfg.pe_steps, dt),
        "pe_enc": _dense_init(k_pe, cfg.pe_steps, cfg.pe_dim, dt),
        "edge_enc": _dense_init(k_edge, cfg.edge_feat_dim, d, dt),
        "layers": layers,
        "head": _head_init(k_head, cfg, d),
    }


class AttentionSlots(NamedTuple):
    """Where each packed graph's nodes sit in a ``(G_pad, S)`` slot grid.

    Graph ``g``'s nodes are contiguous in the batch, from
    ``start[g] = Σ_{h<g} count[h]``; node ``start[g] + p`` sits in slot
    ``(g, p)``. The counts are ``graph_node_counts``, the differences of
    the ``ends`` the batch's ``FlatLayout`` carries.
    """

    rows: Array       # (G_pad * S,) node row read into each slot (clipped)
    valid: Array      # (G_pad, S) the slot holds a node of its graph
    back: Array       # (N_pad,) flat slot of each node (0 for padding)
    pos: Array        # (N_pad,) node's place in its graph
    fits: Array       # (N_pad,) padding, or a node inside its graph's slot


def attention_slots(graph: GraphBatch, counts: Array, slot: int
                    ) -> AttentionSlots:
    n = graph.n_node_pad
    cnt = counts.astype(jnp.int32)
    start = jnp.cumsum(cnt) - cnt
    p = jnp.arange(slot, dtype=jnp.int32)
    rows = jnp.clip(start[:, None] + p[None, :], 0, n - 1).reshape(-1)
    node = jnp.arange(n, dtype=jnp.int32)
    pos = node - start[graph.graph_ids]
    inside = graph.node_mask & (pos < slot)
    back = jnp.where(inside, graph.graph_ids * slot + pos, 0)
    return AttentionSlots(rows=rows, valid=p[None, :] < cnt[:, None],
                          back=back, pos=pos,
                          fits=inside | ~graph.node_mask)


def random_walk_se(graph: GraphBatch, slots: AttentionSlots,
                   steps: int) -> Array:
    """RWSE: diag((D⁻¹A)^k), k = 1..steps, per node (N_pad, steps).

    Built from the batch's own edges, per graph over its slot: ``A[s, r]``
    counts the edges s → r and ``D`` is the out-degree, as GraphGPS's
    ``get_rw_landing_probs``. A node with no out-edge has a zero row, so
    its probabilities are 0. The products run at ``highest`` precision:
    they are sums of probabilities, not a learned map.
    """
    g, s = slots.valid.shape
    ps, pr = slots.pos[graph.senders], slots.pos[graph.receivers]
    ok = graph.edge_mask & (ps < s) & (pr < s)
    flat = (graph.graph_ids[graph.senders] * s + ps) * s + pr
    _count_pass()                 # one scatter of the edge stream
    a = jnp.zeros((g * s * s,), jnp.float32).at[
        jnp.where(ok, flat, g * s * s)].add(1.0, mode="drop")
    a = a.reshape(g, s, s)
    deg = a.sum(-1, keepdims=True)
    walk = a / jnp.where(deg > 0, deg, 1.0)

    def power(m, _):
        m = jnp.matmul(m, walk, precision=jax.lax.Precision.HIGHEST)
        return m, jnp.diagonal(m, axis1=1, axis2=2)

    _, diags = jax.lax.scan(power, jnp.broadcast_to(jnp.eye(s), walk.shape),
                            None, length=steps)            # (steps, G, S)
    pe = jnp.moveaxis(diags, 0, -1).reshape(g * s, steps)[slots.back]
    return jnp.where(graph.node_mask[:, None], pe, 0.0)


def slot_attention(p_in: Params, p_out: Params, x: Array,
                   slots: AttentionSlots, heads: int) -> Array:
    """Multi-head self-attention within each graph (GraphGPS's global
    model). The Q/K/V and output maps run on the flat node rows; only
    the scores and their weighted sum run in the (G_pad, S) slot grid,
    with the keys of empty slots masked. The softmax is float32. A query
    slot with no key (an empty graph slot) gets zero weights, so no NaN
    is made; its row is never read back."""
    d = x.shape[1]
    g, s = slots.valid.shape
    dh = d // heads
    qkv = _dense(p_in, x)[slots.rows].reshape(g, s, 3, heads, dh)
    q = qkv[:, :, 0] * (dh ** -0.5)
    k, v = qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("gqhd,gkhd->ghqk", q, k).astype(jnp.float32)
    scores = jnp.where(slots.valid[:, None, None, :], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    w = jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0))
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("ghqk,gkhd->gqhd", w.astype(x.dtype), v)
    return _dense(p_out, out.reshape(g * s, d)[slots.back])


def gps_layer(p, graph: GraphBatch, x: Array, e: Array,
              slots: AttentionSlots, dataflow: DataflowConfig,
              heads: int) -> Tuple[Array, Array]:
    """One GPS layer: the GatedGCN local block and the attention read the
    same input; their sum goes through the FFN.

        ê_ij = D x_i + E x_j + C e_ij ;  σ_ij = sigmoid(ê_ij)
        x̂_i  = A x_i + Σ_j σ_ij ⊙ B x_j / (Σ_j σ_ij + 1e-6)
        x_L  = BN₁(x + relu(BN_x(x̂)))      e' = e + relu(BN_e(ê))
        x_A  = BN₂(x + MHA(x within its graph))
        h    = x_L + x_A ;  x' = BN₃(h + W₂ relu(W₁ h))
    """
    d = x.shape[1]
    with jax.named_scope("gps.local"):
        ax = _dense(p["A"], x)
        ce = _dense(p["C"], e)

        def message(src, dst, _e):
            # rows are [B x | E x | D x]: each node mapped once, gathered;
            # e_hat = D x_i + E x_j + C e_ij
            e_hat = dst[:, 2 * d:] + src[:, d:2 * d] + ce
            gate = jax.nn.sigmoid(e_hat)
            # numerator and gate sum side by side: one segment sum
            return jnp.concatenate([gate * src[:, :d], gate], -1), e_hat

        def update(_rows, m):
            return ax + m[:, :d] / (m[:, d:] + 1e-6)

        rows = jnp.concatenate(
            [_dense(p["B"], x), _dense(p["E"], x), _dense(p["D"], x)], -1)
        x_hat, e_hat = propagate(
            graph, rows, message_fn=message, update_fn=update,
            aggregate="sum", dataflow=dataflow, edge_output=True)
        x_local = _bn(p["norm_local"], x + jax.nn.relu(_bn(p["bn_x"], x_hat)))
        e = jnp.where(graph.edge_mask[:, None],
                      e + jax.nn.relu(_bn(p["bn_e"], e_hat)), 0.0)
    with jax.named_scope("gps.attention"):
        att = slot_attention(p["attn_in"], p["attn_out"], x, slots, heads)
        # a node outside its graph's slot gets NaN, never a wrong answer
        att = jnp.where(slots.fits[:, None], att, jnp.nan)
        x_attn = _bn(p["norm_attn"], x + att)
    with jax.named_scope("gps.ffn"):
        h = x_local + x_attn
        ff = _dense(p["ffn2"], jax.nn.relu(_dense(p["ffn1"], h)))
        x = _bn(p["norm_ffn"], h + ff)
    return jnp.where(graph.node_mask[:, None], x, 0.0), e


def gps_apply(params, graph: GraphBatch, cfg: GNNConfig,
              dataflow: DataflowConfig = DEFAULT_DATAFLOW,
              stats: Optional[PrecomputedGraphStats] = None) -> Array:
    """GraphGPS with RWSE positions computed in the forward from the
    batch's edges (no graph pre-processing). Every graph must have at
    most ``cfg.attention_slot`` nodes: the engine refuses larger ones at
    ``submit``; here their answers come out NaN."""
    if stats is None or stats.graph_node_counts is None:
        stats = precompute_graph_stats(graph, with_degrees=False,
                                       with_graph_counts=True)
    slots = attention_slots(graph, stats.graph_node_counts,
                            cfg.attention_slot)
    with jax.named_scope("gps.rwse"):
        pe = random_walk_se(graph, slots, cfg.pe_steps).astype(cfg.dtype)
        pe = _dense(params["pe_enc"], _bn(params["pe_norm"], pe))
    x = jnp.concatenate(
        [_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)), pe],
        -1)
    x = jnp.where(graph.node_mask[:, None], x, 0.0)
    e = jnp.where(graph.edge_mask[:, None],
                  _dense(params["edge_enc"], graph.edge_feat.astype(cfg.dtype)),
                  0.0)

    def layer_step(xe, p):
        return gps_layer(p, graph, *xe, slots, dataflow, cfg.heads)

    if dataflow.scan_layers and cfg.num_layers > 1:
        (x, e), _ = scan_layers(lambda xe, p: (layer_step(xe, p), None),
                                (x, e), _stack_layers(params["layers"]),
                                length=cfg.num_layers)
    else:
        for p in params["layers"]:
            x, e = layer_step((x, e), p)
    return _readout(params["head"], cfg, graph, x, stats)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class GNNModel(NamedTuple):
    init: Callable[..., Params]
    apply: Callable[..., Array]


GNN_MODELS: Dict[str, GNNModel] = {
    "gcn": GNNModel(gcn_init, gcn_apply),
    "gin": GNNModel(gin_init, gin_apply),
    "gin_vn": GNNModel(gin_vn_init, gin_vn_apply),
    "gat": GNNModel(gat_init, gat_apply),
    "pna": GNNModel(pna_init, pna_apply),
    "dgn": GNNModel(dgn_init, dgn_apply),
    "gps": GNNModel(gps_init, gps_apply),
}


def make_gnn(cfg: GNNConfig) -> GNNModel:
    if cfg.model not in GNN_MODELS:
        raise KeyError(f"unknown GNN '{cfg.model}'; have {sorted(GNN_MODELS)}")
    return GNN_MODELS[cfg.model]
