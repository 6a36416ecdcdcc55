"""Pallas TPU kernel: banked row gather (the MP unit's mirror image).

``out[i] = y[idx[i]]`` for idx in raw arrival order — the *multicast read*
side of the FlowGNN adapter. Together with mp_scatter this completes the
dest-banked MoE data path on TPU (EXPERIMENTS.md §Perf, olmoe):

    dispatch:  buf = mp_scatter(x_sorted, slot)        # banked scatter
    expert FFN on buf
    combine:   out = mp_scatter(w * gather_rows(y, slot), token_ids)

Grid = (index blocks, source banks); each step mask-selects the bank's
rows via a one-hot routing matmul (route @ y_bank on the MXU), exactly the
dense-select-over-random-access trade described in DESIGN.md §2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _gather_kernel(idx_ref, mask_ref, y_ref, out_ref, *,
                   bank_size: int, idx_tile: int):
    bank = pl.program_id(1)

    @pl.when(bank == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...].reshape(idx_tile)
    mask = mask_ref[...].reshape(idx_tile)
    local = idx - bank * bank_size
    own = (local >= 0) & (local < bank_size) & (mask != 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (idx_tile, bank_size), 1)
    route = (lanes == local[:, None]) & own[:, None]
    out_ref[...] += jax.lax.dot(
        route.astype(jnp.float32), y_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("idx_tile", "num_banks", "interpret"))
def gather_rows(y: Array, idx: Array, mask: Array, *, idx_tile: int = 128,
                num_banks: int = 4, interpret: bool) -> Array:
    """out[i] = y[idx[i]] (masked rows -> 0). y: (N, D); idx/mask: (S,).

    S % idx_tile == 0 and N % num_banks == 0 (pad at the call site).
    """
    n, d = y.shape
    s = idx.shape[0]
    if s % idx_tile or n % num_banks:
        raise ValueError("pad S to idx_tile and N to num_banks")
    bank_size = n // num_banks

    kernel = functools.partial(_gather_kernel, bank_size=bank_size,
                               idx_tile=idx_tile)
    return pl.pallas_call(
        kernel,
        grid=(s // idx_tile, num_banks),
        in_specs=[
            pl.BlockSpec((idx_tile, 1), lambda i, b: (i, 0)),     # idx
            pl.BlockSpec((idx_tile, 1), lambda i, b: (i, 0)),     # mask
            pl.BlockSpec((bank_size, d), lambda i, b: (b, 0)),    # y bank
        ],
        out_specs=pl.BlockSpec((idx_tile, d), lambda i, b: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, d), jnp.float32),
        interpret=interpret,
    )(idx.astype(jnp.int32).reshape(s, 1),
      mask.astype(jnp.int32).reshape(s, 1), y)


def gather_rows_ref(y: Array, idx: Array, mask: Array) -> Array:
    out = y[jnp.clip(idx, 0, y.shape[0] - 1)].astype(jnp.float32)
    return jnp.where(mask[:, None], out, 0.0)
