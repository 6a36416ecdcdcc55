"""Pallas TPU kernel: fused NT + message transform + scatter (the dataflow).

This is the paper's headline pipelining insight made structural on TPU: "MP
need not wait for node transformation to complete ... as soon as embedding
values are computed, they are streamed into the data queue" (Sec. III-D1).

Here the transformed embedding tile never reaches HBM at all: for each node
tile (grid step) we (1) run the NT MLP on the tile, (2) immediately apply the
GIN-style message transform phi = relu(y_src + e) for the edges whose source
lies in the tile, and (3) scatter-accumulate into the message buffer via a
one-hot routing matmul. Gather and scatter both become MXU matmuls:

    y_tile = MLP(x_tile)                              # NT
    msg    = relu(onehot_src @ y_tile + E) * sel      # phi on the fly
    out   += onehot_dst^T @ msg                       # multicast scatter

Scope: edge arrays resident in VMEM — exactly the paper's workload regime
(molecular/HEP graphs, N <= ~2k, E <= ~8k). Larger graphs fall back to the
two-kernel path (nt_mlp + mp_scatter).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _fused_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                  snd_ref, rcv_ref, mask_ref, ef_ref, out_ref, *,
                  node_tile: int, num_nodes: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # --- NT: transform this node tile (accumulate in f32 on the MXU)
    h = jnp.maximum(jax.lax.dot(
        x_ref[...].astype(jnp.float32), w1_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32) + b1_ref[...], 0.0)
    y = jax.lax.dot(h, w2_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32) + b2_ref[...]

    # --- multicast: edges whose source is in this tile consume y immediately
    e = snd_ref.shape[0]
    snd = snd_ref[...].reshape(e)
    rcv = rcv_ref[...].reshape(e)
    mask = mask_ref[...].reshape(e) != 0
    local_src = snd - t * node_tile
    sel = (local_src >= 0) & (local_src < node_tile) & mask

    lanes_src = jax.lax.broadcasted_iota(jnp.int32, (e, node_tile), 1)
    onehot_src = (lanes_src == local_src[:, None]) & sel[:, None]
    gathered = jax.lax.dot(onehot_src.astype(jnp.float32), y,
                           preferred_element_type=jnp.float32)   # (E, D)
    msg = jnp.maximum(gathered + ef_ref[...].astype(jnp.float32), 0.0)
    msg = jnp.where(sel[:, None], msg, 0.0)

    lanes_dst = jax.lax.broadcasted_iota(jnp.int32, (e, num_nodes), 1)
    onehot_dst = (lanes_dst == rcv[:, None]) & sel[:, None]
    out_ref[...] += jax.lax.dot_general(
        onehot_dst.astype(jnp.float32), msg,
        dimension_numbers=(((0,), (0,)), ((), ())),   # onehot_dst^T @ msg
        preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("node_tile", "interpret"))
def fused_nt_scatter(x: Array, w1: Array, b1: Array, w2: Array, b2: Array,
                     senders: Array, receivers: Array, edge_mask: Array,
                     edge_feat: Array, *, node_tile: int = 32,
                     interpret: bool) -> Array:
    """out[i] = sum_{e: dst(e)=i} relu(MLP(x)[src(e)] + edge_feat[e]).

    x: (N, D_in); MLP: D_in -> D_ff -> D. edge_feat: (E, D).
    N % node_tile == 0 (pad at call site).
    """
    n, d_in = x.shape
    e = senders.shape[0]
    d = w2.shape[1]
    if n % node_tile:
        raise ValueError("pad N to node_tile")
    d_ff = w1.shape[1]

    kernel = functools.partial(
        _fused_kernel, node_tile=node_tile, num_nodes=n)
    return pl.pallas_call(
        kernel,
        grid=(n // node_tile,),
        in_specs=[
            pl.BlockSpec((node_tile, d_in), lambda t: (t, 0)),   # x tile
            pl.BlockSpec((d_in, d_ff), lambda t: (0, 0)),        # w1
            pl.BlockSpec((1, d_ff), lambda t: (0, 0)),           # b1
            pl.BlockSpec((d_ff, d), lambda t: (0, 0)),           # w2
            pl.BlockSpec((1, d), lambda t: (0, 0)),              # b2
            pl.BlockSpec((e, 1), lambda t: (0, 0)),              # senders
            pl.BlockSpec((e, 1), lambda t: (0, 0)),              # receivers
            pl.BlockSpec((e, 1), lambda t: (0, 0)),              # edge mask
            pl.BlockSpec((e, d), lambda t: (0, 0)),              # edge feats
        ],
        out_specs=pl.BlockSpec((n, d), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(x, w1, b1.reshape(1, -1).astype(jnp.float32),
      w2, b2.reshape(1, -1).astype(jnp.float32),
      senders.astype(jnp.int32).reshape(e, 1),
      receivers.astype(jnp.int32).reshape(e, 1),
      edge_mask.astype(jnp.int32).reshape(e, 1),
      edge_feat)
