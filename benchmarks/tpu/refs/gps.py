"""Plain reference and weights for GraphGPS (Rampášek et al., "Recipe for a
General, Powerful, Scalable Graph Transformer", arXiv:2205.12454), as its
ogbg-molpcba model: GatedGCN local model, a Transformer global model,
RWSE node positions, mean pooling and one linear layer.

A copy of the program's dense oracle ``gps_dense``: each graph alone, its
edges an (E, D) array whose endpoints are gathered by one-hot (E, N)
matrices, attention dense over the graph's own nodes, RWSE by matrix
powers. It imports nothing of the program. ``init`` makes the weights in
the layout the program serves (``{"node_enc", "pe_norm", "pe_enc",
"edge_enc", "layers": [{"A".."E", "bn_x", "bn_e", "norm_local",
"attn_in", "attn_out", "norm_attn", "ffn1", "ffn2", "norm_ffn"}],
"head"}``); each BN is ``{"gamma", "beta", "mean", "var"}``, its running
statistics fitted to molecules drawn from the seed (``fit_batch_norm``).

    x0   = [W_n h + b_n | W_pe BN(RWSE_1..16) + b_pe]          (364 | 20)
    e0   = W_b f + b_b
    ê_ij = D x_i + E x_j + C e_ij ;  σ_ij = sigmoid(ê_ij)
    x̂_i  = A x_i + Σ_j σ_ij ⊙ B x_j / (Σ_j σ_ij + 1e-6)
    x_L  = BN₁(x + relu(BN_x(x̂)))      e' = e + relu(BN_e(ê))
    x_A  = BN₂(x + MHA(x))
    h    = x_L + x_A ;  x' = BN₃(h + W₂ relu(W₁ h))
    y    = W_o mean_i x_i + b_o

Departures from GraphGPS: linear node and edge encoders in place of the
OGB atom and bond embedding tables; random weights, with each BN's
running statistics those of its input over 64 random molecules, as
training leaves them; no dropout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Sums over edges and nodes, the one-hot gathers and the random-walk
# powers are exact in float32, as a segment sum is; only the dense layers
# (the maps, the attention scores and their weighted sum) take the
# configuration's matmul precision.
EXACT = "highest"
BN_EPS = 1e-5


def _dense_init(key, d_in, d_out):
    kw, kb = jax.random.split(key)
    scale = jnp.sqrt(2.0 / (d_in + d_out))
    return {"w": jax.random.normal(kw, (d_in, d_out), jnp.float32) * scale,
            "b": 0.1 * jax.random.normal(kb, (d_out,), jnp.float32)}


def _bn_init(key, d):
    kg, kb = jax.random.split(key)
    return {"gamma": 1.0 + 0.1 * jax.random.normal(kg, (d,), jnp.float32),
            "beta": 0.1 * jax.random.normal(kb, (d,), jnp.float32),
            "mean": jnp.zeros((d,), jnp.float32),
            "var": jnp.ones((d,), jnp.float32)}


def init(key, cfg):
    """Seeded weights in the program's layout, float32: Glorot-normal
    matrices, N(0, 0.1) biases, BN gamma 1 + N(0, 0.1) and beta N(0, 0.1),
    and BN running statistics fitted to 64 molecules drawn from the key.

    Fitted, not drawn: with drawn statistics (mean N(0, 0.1), variance
    U(0.5, 1.5)) no BN normalises and the activations grow 3.3x a layer
    (rms 0.8 to 101 over five); two float32 orderings of this forward
    then part by a third of the bfloat16 control's gap at one-pass bf16
    matmuls, and no limit could tell a sound program from the control."""
    d, f = cfg["hidden_dim"], cfg["ffn_dim"]
    steps, pe = cfg["pe_steps"], cfg["pe_dim"]
    k_node, k_bn, k_pe, k_edge, k_layers, k_head = jax.random.split(key, 6)
    def layer(k):
        ks = jax.random.split(k, 14)
        p = {name: _dense_init(kk, d, d) for name, kk in zip("ABCDE", ks[:5])}
        p.update({
            "bn_x": _bn_init(ks[5], d), "bn_e": _bn_init(ks[6], d),
            "norm_local": _bn_init(ks[7], d),
            "attn_in": _dense_init(ks[8], d, 3 * d),
            "attn_out": _dense_init(ks[9], d, d),
            "norm_attn": _bn_init(ks[10], d),
            "ffn1": _dense_init(ks[11], d, f),
            "ffn2": _dense_init(ks[12], f, d),
            "norm_ffn": _bn_init(ks[13], d),
        })
        return p

    # one layer traced and mapped over the layers' keys: the values of a
    # loop over the layers, in a program that compiles twice as fast
    stacked = jax.vmap(layer)(jax.random.split(k_layers, cfg["num_layers"]))
    layers = [jax.tree.map(lambda a, i=i: a[i], stacked)
              for i in range(cfg["num_layers"])]
    head = (d,) + tuple(cfg["head_mlp"]) + (cfg["out_dim"],)
    head_keys = jax.random.split(k_head, len(head) - 1)
    params = {"node_enc": _dense_init(k_node, cfg["node_feat_dim"], d - pe),
              "pe_norm": _bn_init(k_bn, steps),
              "pe_enc": _dense_init(k_pe, steps, pe),
              "edge_enc": _dense_init(k_edge, cfg["edge_feat_dim"], d),
              "layers": layers,
              "head": [_dense_init(kk, head[i], head[i + 1])
                       for i, kk in enumerate(head_keys)]}
    return fit_batch_norm(params, molecules(jax.random.fold_in(key, 1), 64,
                                            cfg), cfg)


def _dense(p, x, dot):
    return dot(x, p["w"]) + p["b"]


def _bn(p, x):
    return (x - p["mean"]) / jnp.sqrt(p["var"] + BN_EPS) * p["gamma"] \
        + p["beta"]


def _exact(a, b):
    return jnp.matmul(a, b, precision=EXACT)


def _head(ps, x, dot):
    """The readout's answer, and the magnitude of the terms its last layer
    sums, sum_k |w_k h_k| + |b|: the scale of that answer before
    cancellation, against which a gap is measured."""
    h = x
    for p in ps[:-1]:
        h = jax.nn.relu(_dense(p, h, dot))
    last = ps[-1]
    return (_dense(last, h, dot),
            _exact(jnp.abs(h), jnp.abs(last["w"])) + jnp.abs(last["b"]))


def forward(params, g, cfg, norm=None):
    """One graph, padded to (N, E); see ``refs/gin.py`` for ``g``.
    Computes in ``node_feat``'s dtype, every dense layer's matmul at the
    configuration's ``matmul_precision``. Returns the answer (out_dim,)
    and its magnitude (see ``_head``).

    ``norm(p, x, rows)`` stands in for each BN when given, ``rows`` the
    mask of the rows of ``x`` that are nodes or edges (``fit_batch_norm``
    uses it); the BNs are called in the order that function fills them."""
    dot = functools.partial(jnp.matmul, precision=cfg["matmul_precision"])
    bn = (lambda p, x, rows: _bn(p, x)) if norm is None else norm
    x0 = g["node_feat"]
    dt = x0.dtype
    n = x0.shape[0]
    keep = g["node_mask"][:, None]
    w = g["edge_mask"].astype(dt)[:, None]
    src = jax.nn.one_hot(g["senders"], n, dtype=dt) * w         # (E, N)
    dst = jax.nn.one_hot(g["receivers"], n, dtype=dt) * w

    a = _exact(src.T, dst)                                     # a[s, r]
    deg = a.sum(1, keepdims=True)
    walk = a / jnp.where(deg > 0, deg, 1)
    m, steps = walk, []
    for _ in range(cfg["pe_steps"]):
        steps.append(jnp.diagonal(m))
        m = _exact(m, walk)
    pe = _dense(params["pe_enc"],
                bn(params["pe_norm"], jnp.stack(steps, -1), keep), dot)
    x = jnp.concatenate([_dense(params["node_enc"], x0, dot), pe], -1)
    x = jnp.where(keep, x, 0)
    e = _dense(params["edge_enc"], g["edge_feat"], dot) * w

    heads = cfg["heads"]
    dh = cfg["hidden_dim"] // heads
    attend = (g["node_mask"][None, :] & g["node_mask"][:, None]) \
        | jnp.eye(n, dtype=bool)
    for p in params["layers"]:
        x_src, x_dst = _exact(src, x), _exact(dst, x)
        e_hat = (_dense(p["D"], x_dst, dot) + _dense(p["E"], x_src, dot)
                 + _dense(p["C"], e, dot))
        gate = jax.nn.sigmoid(e_hat) * w
        num = _exact(dst.T, gate * _dense(p["B"], x_src, dot))
        den = _exact(dst.T, gate)
        x_hat = _dense(p["A"], x, dot) + num / (den + 1e-6)
        x_local = bn(p["norm_local"],
                     x + jax.nn.relu(bn(p["bn_x"], x_hat, keep)), keep)
        e = (e + jax.nn.relu(bn(p["bn_e"], e_hat, w))) * w

        qkv = _dense(p["attn_in"], x, dot).reshape(n, 3, heads, dh)
        q = qkv[:, 0] * jnp.asarray(dh ** -0.5, dt)
        scores = jnp.einsum("qhd,khd->hqk", q, qkv[:, 1],
                            precision=cfg["matmul_precision"])
        att = jax.nn.softmax(jnp.where(attend[None], scores, -jnp.inf), -1)
        mha = jnp.einsum("hqk,khd->qhd", att, qkv[:, 2],
                         precision=cfg["matmul_precision"]).reshape(n, -1)
        x_attn = bn(p["norm_attn"], x + _dense(p["attn_out"], mha, dot),
                    keep)

        h = x_local + x_attn
        ff = _dense(p["ffn2"], jax.nn.relu(_dense(p["ffn1"], h, dot)), dot)
        x = jnp.where(keep, bn(p["norm_ffn"], h + ff, keep), 0)
    pooled = x.sum(0) / jnp.maximum(keep.sum(), 1).astype(dt)
    return _head(params["head"], pooled, dot)


LAYER_NORMS = ("bn_x", "norm_local", "bn_e", "norm_attn", "norm_ffn")


def molecules(key, count, cfg, n=26, rings=3):
    """``count`` molecule-shaped graphs of ``n`` atoms, as ``forward``
    takes them stacked: a random recursive tree closed by ``rings`` random
    bonds (one that would join an atom to itself is masked), each bond in
    both directions, N(0, 1) atom and bond features, as the screen
    traffic's."""
    k_tree, k_ring, k_node, k_edge = jax.random.split(key, 4)
    child = jnp.broadcast_to(jnp.arange(1, n), (count, n - 1))
    parent = (jax.random.uniform(k_tree, (count, n - 1))
              * child).astype(jnp.int32)
    ends = jax.random.randint(k_ring, (count, 2, rings), 0, n)
    a = jnp.concatenate([child, ends[:, 0]], 1)
    b = jnp.concatenate([parent, ends[:, 1]], 1)
    bond = jnp.concatenate([jnp.ones((count, n - 1), bool),
                            ends[:, 0] != ends[:, 1]], 1)
    return {"node_feat": jax.random.normal(
                k_node, (count, n, cfg["node_feat_dim"])),
            "senders": jnp.concatenate([a, b], 1),
            "receivers": jnp.concatenate([b, a], 1),
            "edge_feat": jax.random.normal(
                k_edge, (count, 2 * a.shape[1], cfg["edge_feat_dim"])),
            "edge_mask": jnp.concatenate([bond, bond], 1),
            "node_mask": jnp.ones((count, n), bool)}


def fit_batch_norm(params, graphs, cfg):
    """``params`` with each BN's running mean and variance set to those of
    its input over ``graphs`` (stacked, as ``molecules`` makes them), each
    earlier BN normalising by the statistics it was given: the running
    statistics training leaves, under which each BN keeps its input at
    unit scale."""
    def one(g):
        fitted = []

        def norm(p, x, rows):
            r = rows.astype(x.dtype).reshape(-1, 1)
            count = jax.lax.psum(r.sum(), "g")
            mean = jax.lax.psum((x * r).sum(0), "g") / count
            var = jax.lax.psum((jnp.square(x - mean) * r).sum(0),
                               "g") / count
            fitted.append({"mean": mean, "var": var})
            return _bn(dict(p, **fitted[-1]), x)

        forward(params, g, cfg, norm=norm)
        return fitted

    # every graph reads the same statistics: keep the first's
    fitted = iter(jax.tree.map(lambda a: a[0],
                               jax.vmap(one, axis_name="g")(graphs)))
    out = dict(params, pe_norm=dict(params["pe_norm"], **next(fitted)))
    out["layers"] = [dict(p, **{name: dict(p[name], **next(fitted))
                                for name in LAYER_NORMS})
                     for p in params["layers"]]
    return out
