"""Pallas TPU kernel: the FlowGNN NT unit (input-stationary fused MLP).

The paper's NT unit computes a fully-connected layer in an *input-stationary*
fashion — "each fetched element of the input vector updates the entire output
vector" — then a finalization (activation) pass, ping-ponged between nodes.

TPU mapping: grid = (node tiles, d_in blocks). The (node_tile, d_ff) hidden
accumulator stays in VMEM while d_in blocks stream through (input-stationary
along the contraction); on the last d_in step the epilogue applies bias +
ReLU and the second layer's matmul — the "output" phase — so the hidden
matrix never round-trips to HBM. node_tile realizes P_node, the feature-lane
width of each matmul realizes P_apply.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _nt_mlp_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, out_ref, acc_ref):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w1_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _epilogue():
        h = jnp.maximum(acc_ref[...] + b1_ref[...].astype(jnp.float32), 0.0)
        out_ref[...] = (jax.lax.dot(
            h, w2_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) + b2_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("node_tile", "k_tile", "interpret"))
def nt_mlp(x: Array, w1: Array, b1: Array, w2: Array, b2: Array, *,
           node_tile: int = 128, k_tile: int = 128,
           interpret: bool) -> Array:
    """y = relu(x @ w1 + b1) @ w2 + b2 with the hidden matrix kept in VMEM.

    x: (N, D_in), w1: (D_in, D_ff), w2: (D_ff, D_out).
    N % node_tile == 0 and D_in % k_tile == 0 (pad at call site).
    """
    n, d_in = x.shape
    d_ff = w1.shape[1]
    d_out = w2.shape[1]
    if n % node_tile or d_in % k_tile:
        raise ValueError("pad N to node_tile and D_in to k_tile")

    return pl.pallas_call(
        _nt_mlp_kernel,
        grid=(n // node_tile, d_in // k_tile),
        in_specs=[
            pl.BlockSpec((node_tile, k_tile), lambda i, k: (i, k)),  # x
            pl.BlockSpec((k_tile, d_ff), lambda i, k: (k, 0)),       # w1
            pl.BlockSpec((1, d_ff), lambda i, k: (0, 0)),            # b1
            pl.BlockSpec((d_ff, d_out), lambda i, k: (0, 0)),        # w2
            pl.BlockSpec((1, d_out), lambda i, k: (0, 0)),           # b2
        ],
        out_specs=pl.BlockSpec((node_tile, d_out), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((node_tile, d_ff), jnp.float32)],
        interpret=interpret,
    )(x, w1, b1.reshape(1, -1), w2, b2.reshape(1, -1))
