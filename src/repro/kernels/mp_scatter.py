"""Pallas TPU kernels: the FlowGNN MP unit (dest-banked scatter-aggregate).

FPGA -> TPU adaptation of the paper's multi-queue multicast (Fig. 5):

  * Each *bank* (grid dim 0) owns a contiguous range of destination nodes —
    the "MP unit owns its own memory bank" rule, so banks never conflict.
  * Edges stream through in raw COO order (grid dim 1), ``edge_tile`` at a
    time — zero preprocessing, any edge order.
  * Scatter is reformulated as a dense one-hot *routing matmul* so it runs on
    the MXU: ``acc += route^T @ msg`` where ``route[e, n] = (dst_e == n)``.
    Random BRAM writes (FPGA) become dense 128-lane matmuls (TPU); edges not
    owned by the bank contribute zero rows. This trades redundant compare
    lanes for fully dense, conflict-free accumulation — the core
    rethink-for-MXU decision (DESIGN.md §2).
  * The bank accumulator lives in VMEM across all edge steps (output block
    revisited); Pallas double-buffers the edge-block DMA against the matmul,
    which is the TPU analogue of the NT->MP FIFO decoupling.

``mp_scatter`` is the plain scatter-sum unit. ``mp_scatter_multi`` is the
single-pass *multi-statistic* unit (DESIGN.md §3): the same edge-tile stream
feeds several VMEM accumulators at once — f32 sum and sum-of-squares through
the MXU routing matmul, per-destination count from the route column sums, and
max/min through mask-select — so every statistic a PNA-style layer needs
comes out of ONE sweep over the raw edge stream, exactly the paper's
"one stream, many statistics" MP-unit dataflow.

Block shapes map the paper's knobs: num_banks = P_edge, edge_tile = edges per
MP step, and the (bank_size x D) accumulator tile realizes P_scatter lanes.
Accumulation is always float32; outputs are cast back to ``msg.dtype``.

VMEM note: the max/min mask-select materializes an
(edge_tile, bank_size, D) select per step; size banks/tiles so
``edge_tile * bank_size * D * 4B`` fits alongside the accumulators.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# Statistic names in the fixed output order of mp_scatter_multi.
MULTI_STATS = ("sum", "sumsq", "count", "max", "min")

# Precision of every routing matmul (one-hot gather / scatter): the f32
# contract. Mosaic's default contract rounds f32 operands toward bf16, so
# a one-hot gather would hand back bf16-rounded rows and a scatter-sum
# would add bf16-rounded messages — errors the XLA segment-op path never
# makes. A one-hot row times an f32 value is exact under the f32 contract.
ROUTE_PRECISION = jax.lax.Precision.HIGHEST


def _route_matrix(recv, mask, bank, bank_size, edge_tile):
    """Boolean one-hot routing matrix (edge_tile, bank_size) for this bank."""
    local = recv - bank * bank_size
    own = (local >= 0) & (local < bank_size) & (mask != 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (edge_tile, bank_size), 1)
    return (lanes == local[:, None]) & own[:, None]


def _route_select(route_b):
    """(edge_tile, bank_size, 1) boolean select from a routing matrix,
    built through f32: the TPU compiler refuses the reshape of a boolean
    vector."""
    return route_b.astype(jnp.float32)[:, :, None] > 0.0


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def pad_edge_stream(msg: Array, receivers: Array, edge_mask: Array,
                    edge_tile: int):
    """Pad the raw edge stream to a multiple of ``edge_tile``.

    Extra slots get masked-out edges pointing at node 0. ``msg`` may be
    (E, D) or a 1-D (E,) stream (per-edge scalars: softmax logits, edge
    weights) — 1-D streams come back in the (E_pad, 1) layout the kernels
    expect. Returns (msg, recv2, mask2, e_pad) with receivers/mask already
    int32-reshaped to (E_pad, 1).
    """
    if msg.ndim not in (1, 2):
        raise ValueError(
            f"pad_edge_stream expects (E,) or (E, D) streams, got "
            f"shape {msg.shape}")
    e = msg.shape[0]
    e_pad = _ceil_to(e, edge_tile)
    if e_pad != e:
        pad = e_pad - e
        msg = jnp.pad(msg, (0, pad) if msg.ndim == 1
                      else ((0, pad), (0, 0)))
        receivers = jnp.pad(receivers, (0, pad))
        edge_mask = jnp.pad(edge_mask.astype(bool), (0, pad))
    if msg.ndim == 1:
        msg = msg.reshape(e_pad, 1)
    recv2 = receivers.astype(jnp.int32).reshape(e_pad, 1)
    mask2 = edge_mask.astype(jnp.int32).reshape(e_pad, 1)
    return msg, recv2, mask2, e_pad


def _mp_scatter_kernel(recv_ref, mask_ref, msg_ref, out_ref, *,
                       bank_size: int, edge_tile: int):
    bank = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    msg = msg_ref[...].astype(jnp.float32)            # (edge_tile, D)
    recv = recv_ref[...].reshape(edge_tile)           # (edge_tile,)
    mask = mask_ref[...].reshape(edge_tile)

    route = _route_matrix(recv, mask, bank, bank_size, edge_tile)
    out_ref[...] += jax.lax.dot_general(
        route.astype(jnp.float32), msg,
        dimension_numbers=(((0,), (0,)), ((), ())),   # route^T @ msg
        precision=ROUTE_PRECISION,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "node_tile", "edge_tile", "num_banks",
                     "interpret"),
)
def mp_scatter(msg: Array, receivers: Array, edge_mask: Array,
               num_nodes: int, *, node_tile: int = 8, edge_tile: int = 128,
               num_banks: int = 4, interpret: bool) -> Array:
    """Scatter-sum `msg` (E, D) into (num_nodes, D) via dest-banked routing.

    Accumulates in float32, returns ``msg.dtype``. E is padded internally to
    a multiple of ``edge_tile`` (masked edges) and ``num_nodes`` to a
    multiple of ``num_banks`` (unaddressed rows), so uneven sizes are fine.
    """
    e, d = msg.shape
    msg, recv2, mask2, e_pad = pad_edge_stream(
        msg, receivers, edge_mask, edge_tile)
    n_pad = _ceil_to(num_nodes, num_banks)
    bank_size = n_pad // num_banks
    n_edge_blocks = e_pad // edge_tile

    kernel = functools.partial(
        _mp_scatter_kernel, bank_size=bank_size, edge_tile=edge_tile)

    out = pl.pallas_call(
        kernel,
        grid=(num_banks, n_edge_blocks),
        in_specs=[
            pl.BlockSpec((edge_tile, 1), lambda b, t: (t, 0)),   # receivers
            pl.BlockSpec((edge_tile, 1), lambda b, t: (t, 0)),   # mask
            pl.BlockSpec((edge_tile, d), lambda b, t: (t, 0)),   # messages
        ],
        out_specs=pl.BlockSpec((bank_size, d), lambda b, t: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret,
    )(recv2, mask2, msg)
    return out[:num_nodes].astype(msg.dtype)


# ---------------------------------------------------------------------------
# Single-pass multi-statistic MP unit
# ---------------------------------------------------------------------------

def _mp_scatter_multi_kernel(recv_ref, mask_ref, msg_ref, *out_refs,
                             bank_size: int, edge_tile: int, stats):
    bank = pl.program_id(0)
    refs = dict(zip(stats, out_refs))

    @pl.when(pl.program_id(1) == 0)
    def _init():
        for name, ref in refs.items():
            if name == "max":
                ref[...] = jnp.full_like(ref, -jnp.inf)
            elif name == "min":
                ref[...] = jnp.full_like(ref, jnp.inf)
            else:
                ref[...] = jnp.zeros_like(ref)

    msg = msg_ref[...].astype(jnp.float32)            # (edge_tile, D)
    recv = recv_ref[...].reshape(edge_tile)
    mask = mask_ref[...].reshape(edge_tile)

    route_b = _route_matrix(recv, mask, bank, bank_size, edge_tile)
    route = route_b.astype(jnp.float32)
    dn = (((0,), (0,)), ((), ()))                     # route^T @ rhs

    if "sum" in refs:
        refs["sum"][...] += jax.lax.dot_general(
            route, msg, dimension_numbers=dn, precision=ROUTE_PRECISION,
            preferred_element_type=jnp.float32)
    if "sumsq" in refs:
        refs["sumsq"][...] += jax.lax.dot_general(
            route, msg * msg, dimension_numbers=dn,
            precision=ROUTE_PRECISION, preferred_element_type=jnp.float32)
    if "count" in refs:
        refs["count"][...] += jnp.sum(route, axis=0)[:, None]
    if "max" in refs or "min" in refs:
        sel = _route_select(route_b)                  # (edge_tile, bank, 1)
        if "max" in refs:
            tile = jnp.where(sel, msg[:, None, :], -jnp.inf)
            refs["max"][...] = jnp.maximum(refs["max"][...],
                                           jnp.max(tile, axis=0))
        if "min" in refs:
            tile = jnp.where(sel, msg[:, None, :], jnp.inf)
            refs["min"][...] = jnp.minimum(refs["min"][...],
                                           jnp.min(tile, axis=0))


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "node_tile", "edge_tile", "num_banks",
                     "stats", "interpret"),
)
def mp_scatter_multi(msg: Array, receivers: Array, edge_mask: Array,
                     num_nodes: int, *, stats, node_tile: int = 8,
                     edge_tile: int = 128, num_banks: int = 4,
                     interpret: bool):
    """One edge-stream sweep feeding multiple per-node accumulators.

    ``stats`` is a subset of MULTI_STATS. Returns ``{name: f32 array}``:
    sum/sumsq/max/min are (num_nodes, D), count is (num_nodes, 1). max/min
    of empty destinations come back +-inf (callers substitute their neutral).

    Unlike ``mp_scatter`` this wrapper pads internally: E is padded to a
    multiple of ``edge_tile`` with masked edges and ``num_nodes`` to a
    multiple of ``num_banks`` with unaddressed rows, so uneven bank/tile
    sizes are fine.
    """
    stats = tuple(s for s in MULTI_STATS if s in stats)
    if not stats:
        raise ValueError("stats must name at least one accumulator")
    e, d = msg.shape
    msg, recv2, mask2, e_pad = pad_edge_stream(
        msg, receivers, edge_mask, edge_tile)
    n_pad = _ceil_to(num_nodes, num_banks)
    bank_size = n_pad // num_banks
    n_edge_blocks = e_pad // edge_tile

    widths = {"sum": d, "sumsq": d, "count": 1, "max": d, "min": d}
    out_shapes = [jax.ShapeDtypeStruct((n_pad, widths[s]), jnp.float32)
                  for s in stats]
    out_specs = [
        pl.BlockSpec((bank_size, widths[s]), lambda b, t: (b, 0))
        for s in stats
    ]

    kernel = functools.partial(
        _mp_scatter_multi_kernel, bank_size=bank_size, edge_tile=edge_tile,
        stats=stats)

    outs = pl.pallas_call(
        kernel,
        grid=(num_banks, n_edge_blocks),
        in_specs=[
            pl.BlockSpec((edge_tile, 1), lambda b, t: (t, 0)),   # receivers
            pl.BlockSpec((edge_tile, 1), lambda b, t: (t, 0)),   # mask
            pl.BlockSpec((edge_tile, d), lambda b, t: (t, 0)),   # messages
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(recv2, mask2, msg)
    return {s: o[:num_nodes] for s, o in zip(stats, outs)}
