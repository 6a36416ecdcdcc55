"""Plain reference and weights for GIN (FlowGNN, arXiv:2204.13103, Table II).

A copy of the program's dense oracle ``gin_dense``: each graph alone, an
explicit (N, N) adjacency, and the layer equation written out with
einsums, no kernels, packing or segment ops. It imports nothing of the
program. ``init`` makes the weights in the layout the program serves
(``{"node_enc", "layers": [{"edge_enc", "mlp", "eps"}], "head"}``).

    x0   = relu(W_n h + b_n)
    m_i  = sum_{j -> i} relu(x_j + W_e e_ji + b_e)
    x'_i = MLP_{D -> 2D -> D}((1 + eps) x_i + m_i)
    y    = head(mean_i x_i)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Sums over edges and nodes are exact in float32, as a segment sum is; only
# the dense layers take the configuration's matmul precision.
EXACT = "highest"


def _dense_init(key, d_in, d_out):
    kw, kb = jax.random.split(key)
    scale = jnp.sqrt(2.0 / (d_in + d_out))
    return {"w": jax.random.normal(kw, (d_in, d_out), jnp.float32) * scale,
            "b": 0.1 * jax.random.normal(kb, (d_out,), jnp.float32)}


def _mlp_init(key, dims):
    keys = jax.random.split(key, len(dims) - 1)
    return [_dense_init(k, dims[i], dims[i + 1]) for i, k in enumerate(keys)]


def init(key, cfg):
    """Seeded weights in the program's layout, float32."""
    d = cfg["hidden_dim"]
    k0, k1, k2 = jax.random.split(key, 3)
    layers = []
    for k in jax.random.split(k1, cfg["num_layers"]):
        ka, kb, kc = jax.random.split(k, 3)
        layers.append({
            "edge_enc": _dense_init(ka, cfg["edge_feat_dim"], d),
            "mlp": _mlp_init(kb, (d, 2 * d, d)),
            "eps": 0.5 * jax.random.uniform(kc, (), jnp.float32),
        })
    head = (d,) + tuple(cfg["head_mlp"]) + (cfg["out_dim"],)
    return {"node_enc": _dense_init(k0, cfg["node_feat_dim"], d),
            "layers": layers, "head": _mlp_init(k2, head)}


def _dense(p, x, dot):
    return dot(x, p["w"]) + p["b"]


def _mlp(ps, x, dot):
    for i, p in enumerate(ps):
        x = _dense(p, x, dot)
        if i < len(ps) - 1:
            x = jax.nn.relu(x)
    return x


def _head(ps, x, dot):
    """The readout MLP's answer, and the magnitude of the terms its last
    layer sums, sum_k |w_k h_k| + |b|: the scale of that answer before
    cancellation, against which a gap is measured."""
    h = x
    if len(ps) > 1:
        h = jax.nn.relu(_mlp(ps[:-1], x, dot))
    last = ps[-1]
    return (_dense(last, h, dot),
            jnp.matmul(jnp.abs(h), jnp.abs(last["w"]), precision=EXACT)
            + jnp.abs(last["b"]))


def forward(params, g, cfg):
    """One graph, padded to (N, E): ``g`` holds ``node_feat`` (N, F),
    ``senders``/``receivers`` (E,), ``edge_feat`` (E, 3), ``edge_mask``
    (E,) and ``node_mask`` (N,). Computes in ``node_feat``'s dtype, every
    dense layer's matmul at the configuration's ``matmul_precision``.
    Returns the answer (out_dim,) and its magnitude (see ``_head``)."""
    dot = functools.partial(jnp.matmul, precision=cfg["matmul_precision"])
    x0 = g["node_feat"]
    dt = x0.dtype
    n = x0.shape[0]
    w = g["edge_mask"].astype(dt)
    a = jnp.zeros((n, n), dt).at[g["receivers"], g["senders"]].add(w)
    e = jnp.zeros((n, n, g["edge_feat"].shape[1]), dt)
    e = e.at[g["receivers"], g["senders"]].add(g["edge_feat"] * w[:, None])
    keep = g["node_mask"][:, None]
    x = jnp.where(keep, jax.nn.relu(_dense(params["node_enc"], x0, dot)), 0)
    for p in params["layers"]:
        msg = jax.nn.relu(x[None, :, :] + _dense(p["edge_enc"], e, dot))
        agg = jnp.einsum("ij,ijd->id", a, msg, precision=EXACT)
        x = jnp.where(keep, _mlp(p["mlp"], (1 + p["eps"]) * x + agg, dot), 0)
    pooled = x.sum(0) / jnp.maximum(keep.sum(), 1).astype(dt)
    return _head(params["head"], pooled, dot)
