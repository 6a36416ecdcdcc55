"""Dense pure-jnp oracles for the FlowGNN model zoo.

The paper guarantees end-to-end functionality by cross-checking the FPGA
implementation against PyTorch(-Geometric). We do the same: every model in
``core/models.py`` (sparse COO + segment ops + optional Pallas kernels) is
checked against the implementations here, which build an explicit dense
(N, N) adjacency and evaluate Eq. (2) with straightforward einsums.

Slow and memory-hungry by design — oracle only. Assumes no duplicate edges
(our generators are duplicate-free).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.graph import GraphBatch
from repro.core.models import _BN_EPS, GNNConfig, _dense, _mlp

Array = jax.Array


def dense_from_coo(graph: GraphBatch):
    """Return (A, E) with A: (N, N) {0,1} adjacency (A[i, j]=1 iff edge j->i),
    E: (N, N, D) dense edge features."""
    n = graph.n_node_pad
    w = graph.edge_mask.astype(jnp.float32)
    a = jnp.zeros((n, n), jnp.float32).at[graph.receivers, graph.senders].add(w)
    e = jnp.zeros((n, n, graph.edge_feat.shape[1]), jnp.float32)
    e = e.at[graph.receivers, graph.senders].add(
        graph.edge_feat * w[:, None])
    return a, e


def _mask_nodes(graph, x):
    return jnp.where(graph.node_mask[:, None], x, 0.0)


def _dense_pool_mean(graph: GraphBatch, x: Array) -> Array:
    g = graph.n_graph_pad
    onehot = jax.nn.one_hot(graph.graph_ids, g) * graph.node_mask[:, None]
    s = onehot.T @ x
    cnt = jnp.maximum(onehot.sum(0), 1.0)
    return s / cnt[:, None]


def _readout(head, cfg, graph, x):
    if cfg.task == "node":
        return _mlp(head, x)
    out = _mlp(head, _dense_pool_mean(graph, x))
    return jnp.where(graph.graph_mask[:, None], out, 0.0)


def gcn_dense(params, graph: GraphBatch, cfg: GNNConfig) -> Array:
    a, _ = dense_from_coo(graph)
    n = graph.n_node_pad
    deg = a.sum(1) + 1.0
    inv = jax.lax.rsqrt(deg)
    s_hat = inv[:, None] * (a + jnp.eye(n)) * inv[None, :]
    # padded rows/cols of A are zero; eye adds self loops to padded nodes but
    # those rows are masked at the end of each layer, matching the sparse path.
    s_hat = s_hat * graph.node_mask[:, None] * graph.node_mask[None, :]
    x = graph.node_feat.astype(cfg.dtype)
    for l, p in enumerate(params["layers"]):
        h = _dense(p, s_hat @ x)
        x = h if l == cfg.num_layers - 1 else jax.nn.relu(h)
        x = _mask_nodes(graph, x)
    return _readout(params["head"], cfg, graph, x)


def _gin_layer_dense(p, a, e_dense, x):
    e = e_dense @ p["edge_enc"]["w"] + p["edge_enc"]["b"]     # (N, N, D)
    msg = jax.nn.relu(x[None, :, :] + e)                       # (N_dst, N_src, D)
    agg = jnp.einsum("ij,ijd->id", a, msg)
    return _mlp(p["mlp"], (1.0 + p["eps"]) * x + agg)


def gin_dense(params, graph: GraphBatch, cfg: GNNConfig) -> Array:
    a, e_dense = dense_from_coo(graph)
    x = jax.nn.relu(_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)))
    for p in params["layers"]:
        x = _mask_nodes(graph, _gin_layer_dense(p, a, e_dense, x))
    return _readout(params["head"], cfg, graph, x)


def gin_vn_dense(params, graph: GraphBatch, cfg: GNNConfig) -> Array:
    a, e_dense = dense_from_coo(graph)
    x = jax.nn.relu(_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)))
    g = graph.n_graph_pad
    onehot = jax.nn.one_hot(graph.graph_ids, g) * graph.node_mask[:, None]
    vn = jnp.zeros((g, cfg.hidden_dim), cfg.dtype)
    nl = len(params["layers"])
    for l, p in enumerate(params["layers"]):
        x = _mask_nodes(graph, x + onehot @ vn)
        x = _mask_nodes(graph, _gin_layer_dense(p, a, e_dense, x))
        if l < nl - 1:
            vn = _mlp(params["vn_mlps"][l], vn + onehot.T @ x)
            vn = jnp.where(graph.graph_mask[:, None], vn, 0.0)
    return _readout(params["head"], cfg, graph, x)


def gat_dense(params, graph: GraphBatch, cfg: GNNConfig) -> Array:
    a, _ = dense_from_coo(graph)
    x = graph.node_feat.astype(cfg.dtype)
    n, h, dh = graph.n_node_pad, cfg.heads, cfg.head_dim
    for l, p in enumerate(params["layers"]):
        hh = _dense(p["w"], x).reshape(n, h, dh)
        asrc = jnp.einsum("nhd,hd->nh", hh, p["a_src"])
        adst = jnp.einsum("nhd,hd->nh", hh, p["a_dst"])
        logits = jax.nn.leaky_relu(
            asrc[None, :, :] + adst[:, None, :], negative_slope=0.2)  # (dst, src, H)
        logits = jnp.where(a[:, :, None] > 0, logits, -jnp.inf)
        att = jax.nn.softmax(logits, axis=1)
        att = jnp.where(a[:, :, None] > 0, att, 0.0)
        agg = jnp.einsum("ijh,jhd->ihd", att, hh).reshape(n, h * dh)
        x = agg if l == cfg.num_layers - 1 else jax.nn.elu(agg)
        x = _mask_nodes(graph, x)
    return _readout(params["head"], cfg, graph, x)


def pna_dense(params, graph: GraphBatch, cfg: GNNConfig) -> Array:
    a, e_dense = dense_from_coo(graph)
    x = jax.nn.relu(_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)))
    n = graph.n_node_pad
    deg = a.sum(1)
    log_deg = jnp.log(deg + 1.0)
    delta = cfg.avg_log_degree
    scalers = jnp.stack(
        [jnp.ones_like(log_deg), log_deg / delta,
         delta / jnp.maximum(log_deg, 1e-3)], axis=-1)

    for p in params["layers"]:
        e = e_dense @ p["edge_enc"]["w"] + p["edge_enc"]["b"]
        src = jnp.broadcast_to(x[None, :, :], e.shape[:2] + x.shape[-1:])
        msg = jax.nn.relu(jnp.einsum(
            "ijk,kd->ijd", jnp.concatenate([src, e], -1), p["pre"]["w"])
            + p["pre"]["b"])                                   # (dst, src, D)
        cnt = jnp.maximum(deg, 1.0)[:, None]
        s1 = jnp.einsum("ij,ijd->id", a, msg)
        mean = s1 / cnt
        s2 = jnp.einsum("ij,ijd->id", a, msg * msg)
        var = jnp.maximum(s2 / cnt - mean * mean, 0.0)
        std = jnp.sqrt(var + 1e-5)
        big = jnp.where(a[:, :, None] > 0, msg, -jnp.inf)
        mx = jnp.where(deg[:, None] > 0, jnp.max(big, 1), 0.0)
        small = jnp.where(a[:, :, None] > 0, msg, jnp.inf)
        mn = jnp.where(deg[:, None] > 0, jnp.min(small, 1), 0.0)
        m = jnp.concatenate([mean, std, mx, mn], -1)           # (N, 4D)
        scaled = (m[:, None, :] * scalers[:, :, None]).reshape(n, -1)
        x = jax.nn.relu(_dense(p["post"], jnp.concatenate([x, scaled], -1)))
        x = _mask_nodes(graph, x)
    return _readout(params["head"], cfg, graph, x)


def dgn_dense(params, graph: GraphBatch, cfg: GNNConfig) -> Array:
    a, _ = dense_from_coo(graph)
    x = jax.nn.relu(_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)))
    pos = graph.node_pos[:, 0]
    dpos = (pos[None, :] - pos[:, None]) * a                    # (dst, src)
    absnorm = jnp.abs(dpos).sum(1)
    w = dpos / jnp.maximum(absnorm, 1e-6)[:, None]
    deg = a.sum(1)
    for p in params["layers"]:
        cnt = jnp.maximum(deg, 1.0)[:, None]
        m_mean = (a @ x) / cnt
        m_dx = jnp.abs(w @ x - x * w.sum(1)[:, None])
        h = _dense(p["post"], jnp.concatenate([x, m_mean, m_dx], -1))
        x = _mask_nodes(graph, jax.nn.relu(h))
    return _readout(params["head"], cfg, graph, x)


def _bn_explicit(p, x: Array) -> Array:
    return (x - p["mean"]) / jnp.sqrt(p["var"] + _BN_EPS) * p["gamma"] \
        + p["beta"]


def gps_dense(params, graph: GraphBatch, cfg: GNNConfig) -> Array:
    """GraphGPS (arXiv:2205.12454) with each graph alone: edges are an
    (E, D) array whose endpoints are gathered by one-hot (E, N) matrices,
    attention is dense over the nodes of the graph's own block, and RWSE
    is diag((D⁻¹A)^k) by matrix powers of the (N, N) random walk.

    Departures from GraphGPS: linear node and edge encoders in place of
    the OGB atom and bond embedding tables; inference BatchNorm with its
    running statistics, written out (the program folds it into one affine
    map); no dropout. Duplicate edges count with their multiplicity.
    """
    hi = jax.lax.Precision.HIGHEST
    n = graph.n_node_pad
    w = graph.edge_mask.astype(jnp.float32)[:, None]
    src = jax.nn.one_hot(graph.senders, n) * w               # (E, N)
    dst = jax.nn.one_hot(graph.receivers, n) * w
    a = jnp.matmul(src.T, dst, precision=hi)                  # a[s, r]: s -> r
    deg = a.sum(1, keepdims=True)
    walk = a / jnp.where(deg > 0, deg, 1.0)
    m, steps = walk, []
    for _ in range(cfg.pe_steps):
        steps.append(jnp.diagonal(m))
        m = jnp.matmul(m, walk, precision=hi)
    pe = _dense(params["pe_enc"],
                _bn_explicit(params["pe_norm"], jnp.stack(steps, -1)))
    x = jnp.concatenate(
        [_dense(params["node_enc"], graph.node_feat.astype(cfg.dtype)), pe],
        -1)
    x = _mask_nodes(graph, x)
    e = _dense(params["edge_enc"], graph.edge_feat.astype(cfg.dtype)) * w
    same = ((graph.graph_ids[:, None] == graph.graph_ids[None, :])
            & graph.node_mask[None, :]) | jnp.eye(n, dtype=bool)
    heads = cfg.heads
    dh = cfg.hidden_dim // heads
    for p in params["layers"]:
        x_src = jnp.matmul(src, x, precision=hi)              # x_j per edge
        x_dst = jnp.matmul(dst, x, precision=hi)              # x_i per edge
        e_hat = _dense(p["D"], x_dst) + _dense(p["E"], x_src) \
            + _dense(p["C"], e)
        gate = jax.nn.sigmoid(e_hat) * w
        num = jnp.matmul(dst.T, gate * _dense(p["B"], x_src), precision=hi)
        den = jnp.matmul(dst.T, gate, precision=hi)
        x_hat = _dense(p["A"], x) + num / (den + 1e-6)
        x_local = _bn_explicit(
            p["norm_local"], x + jax.nn.relu(_bn_explicit(p["bn_x"], x_hat)))
        e = (e + jax.nn.relu(_bn_explicit(p["bn_e"], e_hat))) * w
        q, k, v = jnp.split(_dense(p["attn_in"], x).reshape(n, 3, heads, dh),
                            3, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q[:, 0] / jnp.sqrt(dh), k[:, 0])
        att = jax.nn.softmax(jnp.where(same[None], scores, -jnp.inf), -1)
        mha = _dense(p["attn_out"],
                     jnp.einsum("hqk,khd->qhd", att, v[:, 0]).reshape(n, -1))
        x_attn = _bn_explicit(p["norm_attn"], x + mha)
        h = x_local + x_attn
        ff = _dense(p["ffn2"], jax.nn.relu(_dense(p["ffn1"], h)))
        x = _mask_nodes(graph, _bn_explicit(p["norm_ffn"], h + ff))
    return _readout(params["head"], cfg, graph, x)


DENSE_REFS = {
    "gcn": gcn_dense,
    "gin": gin_dense,
    "gin_vn": gin_vn_dense,
    "gat": gat_dense,
    "pna": pna_dense,
    "dgn": dgn_dense,
    "gps": gps_dense,
}
