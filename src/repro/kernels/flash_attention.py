"""Pallas TPU kernel: flash attention (online softmax) for the LM substrate.

Supports causal masking, gemma2-style local windows, and logit softcapping.
q tiles of (q_tile, head_dim) stream over kv blocks; the running max /
denominator / output accumulator live in VMEM scratch, so the (Sq, Sk) logits
matrix never materializes. Grid = (batch*heads, q tiles, kv blocks) with the
kv dim innermost so scratch persists across kv steps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  q_tile: int, kv_tile: int, sk: int, sq: int,
                  causal: bool, window: Optional[int],
                  softcap: Optional[float], scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (q_tile, d)
    k = k_ref[0].astype(jnp.float32)                    # (kv_tile, d)
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (q_tile, kv_tile)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    # absolute positions; query ends aligned with key ends (decode-friendly)
    q_pos = qi * q_tile + jax.lax.broadcasted_iota(
        jnp.int32, (q_tile, kv_tile), 0) + (sk - sq)
    k_pos = ki * kv_tile + jax.lax.broadcasted_iota(
        jnp.int32, (q_tile, kv_tile), 1)
    mask = jnp.ones((q_tile, kv_tile), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                                 # (q_tile, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                              # (q_tile, kv_tile)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_tile", "kv_tile",
                     "interpret"),
)
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    q_tile: int = 128, kv_tile: int = 128,
                    interpret: bool) -> Array:
    """q: (B, H, Sq, D); k/v: (B, H, Sk, D). Sq % q_tile == Sk % kv_tile == 0."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if sq % q_tile or sk % kv_tile:
        raise ValueError("pad sequence lengths to tile sizes")
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)

    kernel = functools.partial(
        _flash_kernel, q_tile=q_tile, kv_tile=kv_tile, sk=sk, sq=sq,
        causal=causal, window=window, softcap=softcap, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // q_tile, sk // kv_tile),
        in_specs=[
            pl.BlockSpec((1, q_tile, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, kv_tile, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, kv_tile, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_tile, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_tile, 1), jnp.float32),   # running max
            pltpu.VMEM((q_tile, 1), jnp.float32),   # running denom
            pltpu.VMEM((q_tile, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
