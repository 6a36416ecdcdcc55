"""Pallas TPU kernel: two-pass streaming segment softmax (GAT attention).

The XLA path for per-destination edge softmax costs three sweeps over the
edge stream (segment_max, segment_sum of exps, exp-normalize with two
gathers). This kernel does it in two, flash-attention style, on the same
dest-banked layout as kernels/mp_scatter.py (DESIGN.md §4):

  Pass 1 (grid banks x edge tiles): each bank keeps a per-node *running max*
    ``m`` and an *online-rescaled denominator* ``d`` in VMEM; every edge tile
    updates both — ``d = d * exp(m_old - m_new) + sum exp(logit - m_new)`` —
    so the max and the denominator come out of ONE sweep with no
    re-normalization pass.
  Pass 2 (grid edge tiles): per-edge normalize ``exp(logit - m[dst]) /
    d[dst]``. The gather of (m, d) by destination runs as a one-hot routing
    matmul against the full (N, H) statistics held in VMEM.

Statistics are f32; output is cast back to ``logits.dtype``. Masked edges
get weight 0; destinations with no valid edges produce all-zero weights —
identical semantics to core.message_passing.segment_softmax (the jnp oracle,
mirrored in kernels/ref.py::segment_softmax_ref).

VMEM note: pass 2 holds the full (N, H) m/d plus an (edge_tile, N) route
matrix per step; fine for the paper's streaming workloads (N <= a few k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mp_scatter import (ROUTE_PRECISION, _ceil_to, _route_matrix,
                                      _route_select, pad_edge_stream)

Array = jax.Array


def _stats_kernel(recv_ref, mask_ref, logit_ref, m_ref, d_ref, *,
                  bank_size: int, edge_tile: int):
    bank = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        d_ref[...] = jnp.zeros_like(d_ref)

    logit = logit_ref[...].astype(jnp.float32)        # (edge_tile, H)
    recv = recv_ref[...].reshape(edge_tile)
    mask = mask_ref[...].reshape(edge_tile)

    sel = _route_select(
        _route_matrix(recv, mask, bank, bank_size, edge_tile))  # (tile, bank, 1)

    # per-node max of this tile: (edge_tile, bank, H) mask-select -> max
    tile = jnp.where(sel, logit[:, None, :], -jnp.inf)
    tile_max = jnp.max(tile, axis=0)                  # (bank, H)

    m_old = m_ref[...]
    d_old = d_ref[...]
    m_new = jnp.maximum(m_old, tile_max)
    # online rescale; d_old is 0 wherever m_old is -inf, so corr=0 is safe
    corr = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - m_new), 0.0)
    # exp of owned logits against the new max; unowned lanes -> exp(-inf)=0
    delta = jnp.where(sel, logit[:, None, :] - m_new[None, :, :], -jnp.inf)
    d_ref[...] = d_old * corr + jnp.sum(jnp.exp(delta), axis=0)
    m_ref[...] = m_new


def _norm_kernel(recv_ref, mask_ref, logit_ref, m_ref, d_ref, out_ref, *,
                 num_nodes: int, edge_tile: int):
    logit = logit_ref[...].astype(jnp.float32)        # (edge_tile, H)
    recv = recv_ref[...].reshape(edge_tile)
    mask = mask_ref[...].reshape(edge_tile)

    # gather per-edge (m, d) as a one-hot routing matmul over all nodes;
    # m is -inf for empty destinations, which would poison the matmul
    # (0 * -inf = nan), so it is sanitized first and validity is recovered
    # from d > 0 (a destination with any valid edge has d > 0).
    m = m_ref[...]
    m_clean = jnp.where(jnp.isfinite(m), m, 0.0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (edge_tile, num_nodes), 1)
    route = (lanes == recv[:, None]).astype(jnp.float32)
    dn = (((1,), (0,)), ((), ()))                     # route @ stats
    gm = jax.lax.dot_general(route, m_clean, dimension_numbers=dn,
                             precision=ROUTE_PRECISION,
                             preferred_element_type=jnp.float32)
    gd = jax.lax.dot_general(route, d_ref[...], dimension_numbers=dn,
                             precision=ROUTE_PRECISION,
                             preferred_element_type=jnp.float32)

    valid = (mask != 0)[:, None] & (gd > 0.0)
    shifted = jnp.where(valid, logit - gm, -jnp.inf)
    out_ref[...] = jnp.exp(shifted) / jnp.maximum(gd, 1e-16)


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "edge_tile", "num_banks", "interpret"),
)
def seg_softmax(logits: Array, receivers: Array, edge_mask: Array,
                num_nodes: int, *, edge_tile: int = 128, num_banks: int = 4,
                interpret: bool) -> Array:
    """Streaming per-destination softmax. logits: (E,) or (E, H)."""
    squeeze = logits.ndim == 1
    e = logits.shape[0]
    # 1-D logit streams are normalized to (E_pad, 1) by pad_edge_stream
    logits, recv2, mask2, e_pad = pad_edge_stream(
        logits, receivers, edge_mask, edge_tile)
    h = logits.shape[1]
    n_pad = _ceil_to(num_nodes, num_banks)
    bank_size = n_pad // num_banks
    n_edge_blocks = e_pad // edge_tile

    stats = functools.partial(
        _stats_kernel, bank_size=bank_size, edge_tile=edge_tile)
    m, d = pl.pallas_call(
        stats,
        grid=(num_banks, n_edge_blocks),
        in_specs=[
            pl.BlockSpec((edge_tile, 1), lambda b, t: (t, 0)),   # receivers
            pl.BlockSpec((edge_tile, 1), lambda b, t: (t, 0)),   # mask
            pl.BlockSpec((edge_tile, h), lambda b, t: (t, 0)),   # logits
        ],
        out_specs=[
            pl.BlockSpec((bank_size, h), lambda b, t: (b, 0)),
            pl.BlockSpec((bank_size, h), lambda b, t: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, h), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, h), jnp.float32),
        ],
        interpret=interpret,
    )(recv2, mask2, logits)

    norm = functools.partial(
        _norm_kernel, num_nodes=n_pad, edge_tile=edge_tile)
    out = pl.pallas_call(
        norm,
        grid=(n_edge_blocks,),
        in_specs=[
            pl.BlockSpec((edge_tile, 1), lambda t: (t, 0)),      # receivers
            pl.BlockSpec((edge_tile, 1), lambda t: (t, 0)),      # mask
            pl.BlockSpec((edge_tile, h), lambda t: (t, 0)),      # logits
            pl.BlockSpec((n_pad, h), lambda t: (0, 0)),          # m
            pl.BlockSpec((n_pad, h), lambda t: (0, 0)),          # d
        ],
        out_specs=pl.BlockSpec((edge_tile, h), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((e_pad, h), jnp.float32),
        interpret=interpret,
    )(recv2, mask2, logits, m, d)

    out = out[:e].astype(logits.dtype)
    return out[:, 0] if squeeze else out
