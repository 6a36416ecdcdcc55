"""The FlowGNN-banked MoE data path composed from the Pallas primitives.

This is the structural answer to the olmoe hillclimb (EXPERIMENTS.md
§Perf): expressed in XLA ops, sort-based dispatch moves (T*k, d) tensors
through HBM five times per layer; expressed as dest-banked kernels, the
scatter/gather stay VMEM-resident per bank tile.

    dispatch: buf  = mp_scatter(x[token_ids], slot, own, E_loc * C)
    combine:  out  = mp_scatter(w * gather_rows(y, slot), token_ids, T)

Validated against the jnp dispatch used by nn/moe.py (tests); compiled
execution requires a real TPU (interpret mode on CPU is correctness-only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.gather_rows import gather_rows
from repro.kernels.mp_scatter import mp_scatter

Array = jax.Array


def moe_dispatch(x: Array, token_ids: Array, slot: Array, own: Array,
                 num_slots: int, *, edge_tile: int = 128,
                 num_banks: int = 4, interpret: bool) -> Array:
    """Build the (num_slots, d) expert buffer from routed tokens.

    x: (T, d); token_ids/slot/own: (T*k,) — raw router output order,
    zero preprocessing (any order works; slots are unique per `own`).
    """
    msg = x[jnp.clip(token_ids, 0, x.shape[0] - 1)]
    return mp_scatter(msg, slot, own, num_slots, edge_tile=edge_tile,
                      num_banks=num_banks, interpret=interpret)


def moe_combine(y: Array, token_ids: Array, slot: Array, own: Array,
                weights: Array, num_tokens: int, *, edge_tile: int = 128,
                num_banks: int = 4, interpret: bool) -> Array:
    """out[t] = sum_assignments w * y[slot]: banked gather then banked
    scatter-add back to tokens."""
    gathered = gather_rows(y, slot, own, idx_tile=edge_tile,
                           num_banks=num_banks, interpret=interpret)
    msg = gathered * weights[:, None].astype(gathered.dtype)
    return mp_scatter(msg, token_ids, own, num_tokens, edge_tile=edge_tile,
                      num_banks=num_banks, interpret=interpret)
