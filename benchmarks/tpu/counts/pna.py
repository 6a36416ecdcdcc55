"""Algorithmic FLOPs and bytes of one PNA forward (see ``refs/pna.py``).

Counted at a graph's real node and edge counts, whatever implements the
forward: padding, one-hot routing and re-reads are not work the algorithm
needs, so they show as a lower share of the roofline. A multiply-add is 2
FLOPs; additions, squares, comparisons for max/min and the scaler products
are 1; activations, logs and square roots are not counted. Bytes are
float32 (int32 indices): each layer reads its node input, the edge
features and both endpoint indices once and writes its output once; the
input features are read and the output written once. Intermediate
messages are not counted, as a fused forward keeps them on chip. Weights
are read once per program launch (``weight_bytes``).
"""

F32 = 4


def flops(n, e, cfg):
    d, f, fe = cfg["hidden_dim"], cfg["node_feat_dim"], cfg["edge_feat_dim"]
    total = 2 * n * f * d + n * d                     # node encoder
    per_layer = (2 * e * fe * d + e * d               # bond encoder
                 + 2 * e * 2 * d * d + e * d          # pre: 2D -> D per edge
                 + e * d                              # sum (mean)
                 + 2 * e * d                          # sum of squares
                 + 2 * e * d                          # max and min
                 + 6 * n * d                          # mean, var, std
                 + 3 * 4 * n * d                      # 3 scalers x 4 aggs
                 + 2 * n * 13 * d * d + n * d)        # post: 13D -> D
    total += cfg["num_layers"] * per_layer
    total += n * d                                    # mean pool
    dims = (d,) + tuple(cfg["head_mlp"]) + (cfg["out_dim"],)
    total += sum(2 * a * b + b for a, b in zip(dims, dims[1:]))
    return total


def bytes_moved(n, e, cfg):
    d, f, fe = cfg["hidden_dim"], cfg["node_feat_dim"], cfg["edge_feat_dim"]
    per_layer = (n * d + e * fe + n * d) * F32 + 2 * e * F32
    return n * f * F32 + cfg["num_layers"] * per_layer + cfg["out_dim"] * F32


def weight_bytes(cfg):
    d, f, fe = cfg["hidden_dim"], cfg["node_feat_dim"], cfg["edge_feat_dim"]
    layer = (fe * d + d) + (2 * d * d + d) + (13 * d * d + d)
    dims = (d,) + tuple(cfg["head_mlp"]) + (cfg["out_dim"],)
    head = sum(a * b + b for a, b in zip(dims, dims[1:]))
    return F32 * ((f * d + d) + cfg["num_layers"] * layer + head)
