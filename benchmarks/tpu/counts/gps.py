"""Algorithmic FLOPs and bytes of one GraphGPS forward (see ``refs/gps.py``).

Counted at a graph's real node and edge counts, whatever implements the
forward: padding, one-hot routing, the attention slot's empty rows and
re-reads are not work the algorithm needs, so they show as a lower share
of the roofline. Attention is counted over the graph's n² pairs, not the
slot's S², and RWSE as ``pe_steps`` products of n × n matrices. A
multiply-add is 2 FLOPs; additions, divisions, a BatchNorm's scale and
shift and the softmax's subtract, sum and divide are 1 each; activations
and exponentials are not counted. Bytes are float32 (int32 indices):
each layer reads its node and edge state and both endpoint indices once
and writes both states once; the input features are read and the output
written once. Intermediate messages, scores and the random-walk powers
are not counted, as a fused forward keeps them on chip. Weights are read
once per program launch (``weight_bytes``).
"""

F32 = 4


def _widths(cfg):
    return (cfg["hidden_dim"], cfg["ffn_dim"], cfg["node_feat_dim"],
            cfg["edge_feat_dim"], cfg["pe_steps"], cfg["pe_dim"])


def flops(n, e, cfg):
    d, f, fn, fe, k, p = _widths(cfg)
    h = cfg["heads"]
    total = (2 * n * fn * (d - p) + n * (d - p)       # node encoder
             + k * 2 * n ** 3                         # RWSE: k walk powers
             + 2 * n * k                              # BN of the k steps
             + 2 * n * k * p + n * p                  # k -> pe_dim
             + 2 * e * fe * d + e * d)                # bond encoder
    local = (4 * (2 * n * d * d + n * d)              # A, B, D, E x
             + 2 * e * d * d + e * d                  # C e
             + 2 * e * d                              # ê = Dx_i + Ex_j + Ce
             + e * d                                  # σ ⊙ Bx_j
             + 2 * e * d                              # Σ σ Bx_j and Σ σ
             + 3 * n * d                              # Ax + num / (den + ε)
             + 2 * n * d + n * d + 2 * n * d          # BN_x, + x, BN₁
             + 2 * e * d + e * d)                     # BN_e, + e
    attention = (2 * n * d * 3 * d + 3 * n * d        # Q, K, V
                 + n * d                              # q / sqrt(dh)
                 + 2 * n * n * d                      # scores
                 + 3 * h * n * n                      # softmax
                 + 2 * n * n * d                      # weighted V
                 + 2 * n * d * d + n * d              # output map
                 + n * d + 2 * n * d)                 # + x, BN₂
    ffn = (n * d                                      # x_L + x_A
           + 2 * n * d * f + n * f                    # D -> F
           + 2 * n * f * d + n * d                    # F -> D
           + n * d + 2 * n * d)                       # + h, BN₃
    total += cfg["num_layers"] * (local + attention + ffn)
    total += n * d                                    # mean pool
    dims = (d,) + tuple(cfg["head_mlp"]) + (cfg["out_dim"],)
    total += sum(2 * a * b + b for a, b in zip(dims, dims[1:]))
    return total


def bytes_moved(n, e, cfg):
    d, f, fn, fe, k, p = _widths(cfg)
    per_layer = 2 * (n * d + e * d) * F32 + 2 * e * F32
    return ((n * fn + e * fe) * F32 + 2 * e * F32
            + cfg["num_layers"] * per_layer + cfg["out_dim"] * F32)


def weight_bytes(cfg):
    d, f, fn, fe, k, p = _widths(cfg)
    bn = 4 * d                                        # gamma, beta, mean, var
    layer = (5 * (d * d + d) + 3 * bn                 # A..E, BN_x, BN_e, BN₁
             + (3 * d * d + 3 * d) + (d * d + d) + bn  # attention, BN₂
             + (d * f + f) + (f * d + d) + bn)        # FFN, BN₃
    dims = (d,) + tuple(cfg["head_mlp"]) + (cfg["out_dim"],)
    head = sum(a * b + b for a, b in zip(dims, dims[1:]))
    top = ((fn * (d - p) + d - p) + 4 * k + (k * p + p)
           + (fe * d + d))
    return F32 * (top + cfg["num_layers"] * layer + head)
