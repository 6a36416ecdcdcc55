"""Algorithmic FLOPs and bytes of one GIN forward (see ``refs/gin.py``).

Counted at a graph's real node and edge counts, whatever implements the
forward: padding, one-hot routing and re-reads are not work the algorithm
needs, so they show as a lower share of the roofline. A multiply-add is 2
FLOPs; additions of biases, messages and aggregates are 1; activations
are not counted. Bytes are float32 (int32 indices): each layer reads its
node input, the edge features and both endpoint indices once and writes
its output once; the input features are read and the output written once.
Intermediate messages are not counted, as a fused forward keeps them on
chip. Weights are read once per program launch (``weight_bytes``).
"""

F32 = 4


def flops(n, e, cfg):
    d, f, fe = cfg["hidden_dim"], cfg["node_feat_dim"], cfg["edge_feat_dim"]
    total = 2 * n * f * d + n * d                     # node encoder
    per_layer = (2 * e * fe * d + e * d               # bond encoder
                 + e * d                              # relu(x_j + e_ji)
                 + e * d                              # sum over in-edges
                 + 2 * n * d                          # (1 + eps) x + m
                 + 2 * n * d * 2 * d + 2 * n * d     # D -> 2D
                 + 2 * n * 2 * d * d + n * d)        # 2D -> D
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
    layer = (fe * d + d) + (d * 2 * d + 2 * d) + (2 * d * d + d) + 1
    dims = (d,) + tuple(cfg["head_mlp"]) + (cfg["out_dim"],)
    head = sum(a * b + b for a, b in zip(dims, dims[1:]))
    return F32 * ((f * d + d) + cfg["num_layers"] * layer + head)
