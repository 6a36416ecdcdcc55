"""Pallas TPU kernel: the fused gather-phi-scatter edge pipeline.

This is the whole edge phase of a FlowGNN layer in ONE kernel launch
(DESIGN.md §6). The paper's NT and MP units are decoupled by FIFOs and
overlap fully, so an edge is gathered, transformed by phi, and scattered
without the message matrix ever reaching off-chip memory (Fig. 4b/5). The
unfused TPU path loses that: ``x[senders]`` materializes an (E, D) gather,
``message_fn`` writes an (E, D) message buffer, and the scatter kernel
reads it back — three HBM round-trips over the edge stream where the paper
does zero. Here, per edge tile:

  1. **gather** — source rows are pulled from the *resident* (N, D) node
     buffer (held in VMEM across all grid steps) via a one-hot gather
     matmul on the MXU: ``src = onehot_src @ y``;
  2. **phi** — the fusable message transform (DESIGN.md §6: per-edge scale
     of the gathered row, an additive per-edge term, a bias, and an
     activation) is applied in-register;
  3. **scatter** — the multi-statistic accumulators of the single-pass MP
     unit are fed directly: sum / sum-of-squares through the dest-banked
     routing matmul, count from the route column sums, and max / min via
     the *keyed* routing formulation below.

The (E, D) message matrix never exists; ``count_edge_passes()`` sees one
pass for the whole layer step.

Keyed max/min (closes the ROADMAP item): instead of the ±inf boolean
mask-select of ``mp_scatter_multi``, the routing matrix doubles as a finite
*additive key* — ``key = (route - 1) · BIG`` is 0 for owned edges and
``-BIG`` otherwise, so ``max_e(msg[e, d] + key[e, n])`` selects the owned
maximum with a broadcast add that shares the already-built route matrix,
keeps all arithmetic finite (no -inf · 0 hazards), and lets empty
destinations be recovered from the streamed count / precomputed degrees
rather than an ``isfinite`` sweep. Exact while |msg| stays far below BIG
(1e30; any value below ulp(BIG)/2 ≈ 7e22 is absorbed exactly).

VMEM sizing rule (DESIGN.md §6): a grid step holds the resident node
buffer (N_pad × D), the gather route (edge_tile × N_pad), and — when max or
min is requested — the keyed select working set (edge_tile × bank_size × D),
all f32. Size ``edge_tile`` / ``num_banks`` so
``4B · edge_tile · (N_pad + bank_size · D)`` fits alongside the
accumulators; the gather is re-issued per bank (dense compute traded for
zero HBM traffic, the same trade as DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mp_scatter import (MULTI_STATS, ROUTE_PRECISION, _ceil_to,
                                      _route_matrix, pad_edge_stream)

Array = jax.Array

# Finite keyed-select offset. Messages must stay well below ulp(BIG)/2
# (≈ 7e22) in magnitude for the keyed max/min to be exact — comfortably
# true for any finite activation a GNN layer produces.
BIG = 1e30

# Online-softmax carry accumulators, appended to the requested stats when
# attention is on: per dest-node per head, the running keyed max and the
# online-rescaled denominator (flash attention's (m, l) pair, DESIGN.md §6).
ATT_STATS = ("att_max", "att_denom")


def _head_lanes(d: int, heads: int) -> Array:
    """(1, d) map of each lane to its head: lane // head_dim."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, d), 1) // (d // heads)


def _expand_heads(v: Array, lane_head: Array) -> Array:
    """(R, H) -> (R, H·head_dim): each head's column over its lanes.

    A per-head select rather than a product with an (R, H, head_dim)
    reshape of the accumulator, which the TPU compiler refuses for
    head_dim < 128 lanes; the values are the same."""
    out = jnp.zeros((v.shape[0], lane_head.shape[1]), v.dtype)
    for h in range(v.shape[1]):
        out = jnp.where(lane_head == h, v[:, h:h + 1], out)
    return out


def _gather_phi_tile(y_ref, snd, valid, sw_ref, et_ref, b_ref, *,
                     edge_tile: int, n_pad: int, sw_mode: str, head_dim: int,
                     activation: str):
    """Gather the tile's source rows + apply the fusable phi, in-register.

    Shared between ``mp_pipeline`` and the fused-layer kernel
    (kernels/layer_fused.py). ``sw_mode='head'`` expands (edge_tile, H)
    attention lanes to (edge_tile, H·head_dim) *inside* the kernel — GAT's
    per-head broadcast never materializes on the host. Returns
    ``(msg, g_route)`` so callers can reuse the gather route for other
    node-side streams (the attention source halves).
    """
    # --- gather: one-hot matmul against the resident node buffer (MXU).
    # Masked edges get an all-zero route row, so they gather zeros.
    lanes = jax.lax.broadcasted_iota(jnp.int32, (edge_tile, n_pad), 1)
    g_route = ((lanes == snd[:, None]) & valid[:, None]).astype(jnp.float32)
    src = jax.lax.dot(g_route, y_ref[...].astype(jnp.float32),
                      precision=ROUTE_PRECISION,
                      preferred_element_type=jnp.float32)   # (edge_tile, D)

    # --- phi, in-register (masked rows may hold garbage from the additive
    # terms; the scatter routes and keys exclude them everywhere).
    msg = src
    if sw_mode == "head":
        sw = sw_ref[...].astype(jnp.float32)         # (edge_tile, H)
        heads = sw.shape[1]
        sw = jnp.broadcast_to(sw[:, :, None], (edge_tile, heads, head_dim))
        msg = msg * sw.reshape(edge_tile, heads * head_dim)
    elif sw_mode != "none":
        msg = msg * sw_ref[...].astype(jnp.float32)  # (tile,1) broadcasts
    if et_ref is not None:
        msg = msg + et_ref[...].astype(jnp.float32)
    if b_ref is not None:
        msg = msg + b_ref[...]
    if activation == "relu":
        msg = jnp.maximum(msg, 0.0)
    return msg, g_route


def _src_weight_mode(src_weight, d: int):
    """Classify a src_weight stream: scalar (E,), full (E, D), or per-head
    (E, H) with H | D — broadcast across head_dim lanes in-kernel."""
    if src_weight.ndim == 1:
        return "scalar", 0
    h = src_weight.shape[1]
    if h == d:
        return "full", 0
    if h and d % h == 0:
        return "head", d // h
    raise ValueError(
        f"src_weight width {h} must equal D={d} or divide it (per-head)")


def _mp_pipeline_kernel(*refs, bank_size: int, edge_tile: int, n_pad: int,
                        stats, sw_mode: str, head_dim: int, has_et: bool,
                        has_bias: bool, activation: str,
                        att_heads: int = 0, att_slope: float = 0.2):
    it = iter(refs)
    snd_ref, recv_ref, mask_ref = next(it), next(it), next(it)
    sw_ref = next(it) if sw_mode != "none" else None
    et_ref = next(it) if has_et else None
    b_ref = next(it) if has_bias else None
    as_ref = next(it) if att_heads else None
    ad_in_ref = next(it) if att_heads else None
    y_ref = next(it)
    out = dict(zip(stats, it))

    bank = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        for name, ref in out.items():
            if name in ("max", "att_max"):
                ref[...] = jnp.full_like(ref, -BIG)
            elif name == "min":
                ref[...] = jnp.full_like(ref, BIG)
            else:
                ref[...] = jnp.zeros_like(ref)

    snd = snd_ref[...].reshape(edge_tile)
    recv = recv_ref[...].reshape(edge_tile)
    mask = mask_ref[...].reshape(edge_tile)
    valid = mask != 0

    msg, g_route = _gather_phi_tile(
        y_ref, snd, valid, sw_ref, et_ref, b_ref, edge_tile=edge_tile,
        n_pad=n_pad, sw_mode=sw_mode, head_dim=head_dim,
        activation=activation)

    # --- scatter: dest-banked multi-statistic accumulation.
    route_b = _route_matrix(recv, mask, bank, bank_size, edge_tile)
    route = route_b.astype(jnp.float32)
    dn = (((0,), (0,)), ((), ()))                    # route^T @ rhs
    if att_heads:
        # flash-style online softmax, folded into the edge sweep
        # (DESIGN.md §6): the gather route pulls the per-node source
        # attention half, the scatter route the destination half; the
        # keyed logits share the finite-additive-key trick of max/min, so
        # unowned lanes sit at -BIG and the per-(bank, head) running max
        # m and denominator d obey the flash recurrence
        #     m' = max(m, tile_max);  d' = d·exp(m - m') + Σ exp(l - m')
        # with the weighted numerator (the "sum" accumulator) rescaled by
        # the same exp(m - m') carry. The min(·, 0) clamp is exact for
        # owned lanes (m' ≥ their logit by construction) and stops the
        # exp from overflowing on unowned -BIG lanes before the route
        # zeroes them.
        a_s = jax.lax.dot(g_route, as_ref[...].astype(jnp.float32),
                          precision=ROUTE_PRECISION,
                          preferred_element_type=jnp.float32)  # (tile, H)
        a_d = jax.lax.dot(route, ad_in_ref[...].astype(jnp.float32),
                          precision=ROUTE_PRECISION,
                          preferred_element_type=jnp.float32)  # (tile, H)
        logits = a_s + a_d
        logits = jnp.where(logits >= 0.0, logits, att_slope * logits)
        key = (route - 1.0) * BIG                    # (tile, bank)
        # one head at a time on (tile, bank) planes: the TPU compiler
        # refuses the (tile, H·head_dim) -> (tile, H, head_dim) reshape a
        # per-head einsum needs, so no 3-D per-head value is formed. The
        # carries are read and written transposed, (H, bank), so each
        # head's row lines up with the bank lanes of its keyed plane.
        lane_head = _head_lanes(msg.shape[1], att_heads)
        head_row = jax.lax.broadcasted_iota(
            jnp.int32, (att_heads, bank_size), 0)
        m_old = out["att_max"][...].T                # (H, bank)
        d_old = out["att_denom"][...].T
        m_new, d_new, corr = m_old, d_old, m_old
        num = jnp.zeros(out["sum"].shape, jnp.float32)
        for h in range(att_heads):
            keyed = logits[:, h:h + 1] + key         # (tile, bank)
            m_h = jnp.maximum(m_old[h:h + 1],
                              jnp.max(keyed, axis=0, keepdims=True))
            corr_h = jnp.exp(m_old[h:h + 1] - m_h)   # (1, bank), ≤ 1
            p = jnp.exp(jnp.minimum(keyed - m_h, 0.0)) * route
            d_h = (d_old[h:h + 1] * corr_h
                   + jnp.sum(p, axis=0, keepdims=True))
            m_new = jnp.where(head_row == h, m_h, m_new)
            d_new = jnp.where(head_row == h, d_h, d_new)
            corr = jnp.where(head_row == h, corr_h, corr)
            num = num + jax.lax.dot_general(
                p, jnp.where(lane_head == h, msg, 0.0),
                dimension_numbers=dn, precision=ROUTE_PRECISION,
                preferred_element_type=jnp.float32)
        out["att_max"][...] = m_new.T
        out["att_denom"][...] = d_new.T
        out["sum"][...] = (out["sum"][...] * _expand_heads(corr.T, lane_head)
                           + num)

        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _att_normalize():
            # per-bank normalization epilogue: the rescaled numerator is
            # divided by the final denominator; empty destinations
            # (denom 0) come back as exact zeros
            den = out["att_denom"][...]
            wgt = jnp.where(den > 0.0,
                            1.0 / jnp.maximum(den, 1e-16), 0.0)
            out["sum"][...] = out["sum"][...] * _expand_heads(wgt, lane_head)
    elif "sum" in out:
        out["sum"][...] += jax.lax.dot_general(
            route, msg, dimension_numbers=dn, precision=ROUTE_PRECISION,
            preferred_element_type=jnp.float32)
    if "sumsq" in out:
        out["sumsq"][...] += jax.lax.dot_general(
            route, msg * msg, dimension_numbers=dn,
            precision=ROUTE_PRECISION, preferred_element_type=jnp.float32)
    if "count" in out:
        out["count"][...] += jnp.sum(route, axis=0)[:, None]
    if "max" in out or "min" in out:
        # keyed select: 0 for owned lanes, -BIG otherwise — shares the
        # route matrix, stays finite, and the broadcast *add* replaces the
        # ±inf boolean mask-select of mp_scatter_multi.
        key = (route - 1.0) * BIG                    # (edge_tile, bank)
        if "max" in out:
            out["max"][...] = jnp.maximum(
                out["max"][...],
                jnp.max(msg[:, None, :] + key[:, :, None], axis=0))
        if "min" in out:
            out["min"][...] = jnp.minimum(
                out["min"][...],
                jnp.min(msg[:, None, :] - key[:, :, None], axis=0))


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "stats", "activation", "att_slope",
                     "edge_tile", "num_banks", "interpret"),
)
def mp_pipeline(x: Array, senders: Array, receivers: Array, edge_mask: Array,
                num_nodes: int, *, stats, src_weight: Array = None,
                edge_term: Array = None, bias: Array = None,
                activation: str = "none", att_src: Array = None,
                att_dst: Array = None, att_slope: float = 0.2,
                edge_tile: int = 128, num_banks: int = 4,
                interpret: bool):
    """One-launch edge phase: gather + fusable phi + multi-stat scatter.

    ``x`` is the (num_nodes, D) node buffer; phi for edge e is

        act( x[senders[e]] * src_weight[e] + edge_term[e] + bias )

    with ``src_weight`` per-edge scalars (E,), full-width (E, D), or
    per-head lanes (E, H) with H | D (broadcast across head_dim in-register
    — GAT's attention expansion without the host-side (E, H·Dh) stream),
    and each of the three terms optional. ``stats`` is a subset of
    MULTI_STATS; returns ``{name: f32 array}`` with sum/sumsq/max/min of
    shape (num_nodes, D) and count (num_nodes, 1). max/min of empty
    destinations come back ∓BIG (finite; recover validity from count or
    degrees — see the module docstring). Uneven E / num_nodes are padded
    internally, like ``mp_scatter_multi``.

    ``att_src``/``att_dst`` (N, H) switch on the in-sweep online softmax
    (DESIGN.md §6): per edge the attention logit is
    ``leaky_relu(att_src[snd] + att_dst[recv], att_slope)`` per head, the
    per-(dest, head) running max and online-rescaled denominator are
    carried in the accumulator flash-attention style, and the "sum"
    statistic becomes the softmax-weighted per-head aggregation —
    normalized in a per-bank epilogue on the last edge tile, still ONE
    launch. The carries come back as extra ``att_max`` (empty dests at
    -BIG) / ``att_denom`` (empty dests at 0) entries, both (N, H).
    Attention restricts ``stats`` to ("sum",) plus an optional "count".
    """
    stats = tuple(s for s in MULTI_STATS if s in stats)
    if not stats:
        raise ValueError("stats must name at least one accumulator")
    if activation not in ("none", "relu"):
        raise ValueError(f"unsupported activation '{activation}'")
    if (att_src is None) != (att_dst is None):
        raise ValueError("att_src and att_dst must be given together")
    n, d = x.shape
    if n != num_nodes:
        raise ValueError(f"node buffer has {n} rows, expected {num_nodes}")
    att_heads = 0
    if att_src is not None:
        if "sum" not in stats or set(stats) - {"sum", "count"}:
            raise ValueError(
                "attention supports stats ('sum',) plus optional 'count', "
                f"got {stats}")
        if att_src.shape != att_dst.shape or att_src.shape[0] != num_nodes:
            raise ValueError(
                f"attention halves must both be ({num_nodes}, H), got "
                f"{att_src.shape} / {att_dst.shape}")
        att_heads = att_src.shape[1]
        if att_heads == 0 or d % att_heads != 0:
            raise ValueError(
                f"attention head count {att_heads} must divide D={d}")
    e = senders.shape[0]
    e_pad = _ceil_to(e, edge_tile)
    n_pad = _ceil_to(num_nodes, num_banks)
    bank_size = n_pad // num_banks

    # pad the edge streams (masked slots) and the node buffer (zero rows)
    _, snd2, _, _ = pad_edge_stream(senders, senders, edge_mask, edge_tile)
    _, recv2, mask2, _ = pad_edge_stream(
        receivers, receivers, edge_mask, edge_tile)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))

    sw_mode, head_dim = "none", 0
    inputs = [snd2, recv2, mask2]
    in_specs = [pl.BlockSpec((edge_tile, 1), lambda b, t: (t, 0))] * 3
    if src_weight is not None:
        sw2 = pad_edge_stream(src_weight, receivers, edge_mask, edge_tile)[0]
        sw_mode, head_dim = _src_weight_mode(src_weight, d)
        inputs.append(sw2)
        in_specs.append(
            pl.BlockSpec((edge_tile, sw2.shape[1]), lambda b, t: (t, 0)))
    if edge_term is not None:
        et2 = pad_edge_stream(edge_term, receivers, edge_mask, edge_tile)[0]
        inputs.append(et2)
        in_specs.append(pl.BlockSpec((edge_tile, d), lambda b, t: (t, 0)))
    if bias is not None:
        inputs.append(bias.astype(jnp.float32).reshape(1, d))
        in_specs.append(pl.BlockSpec((1, d), lambda b, t: (0, 0)))
    if att_heads:
        a_s = att_src.astype(jnp.float32)
        a_d = att_dst.astype(jnp.float32)
        if n_pad != n:
            a_s = jnp.pad(a_s, ((0, n_pad - n), (0, 0)))
            a_d = jnp.pad(a_d, ((0, n_pad - n), (0, 0)))
        # the source half rides the resident gather route; the destination
        # half streams per bank alongside the accumulators
        inputs.append(a_s)
        in_specs.append(pl.BlockSpec((n_pad, att_heads), lambda b, t: (0, 0)))
        inputs.append(a_d)
        in_specs.append(
            pl.BlockSpec((bank_size, att_heads), lambda b, t: (b, 0)))
    inputs.append(x)                                   # resident node buffer
    in_specs.append(pl.BlockSpec((n_pad, d), lambda b, t: (0, 0)))

    if att_heads:
        stats = stats + ATT_STATS
    widths = {"sum": d, "sumsq": d, "count": 1, "max": d, "min": d,
              "att_max": att_heads, "att_denom": att_heads}
    out_shapes = [jax.ShapeDtypeStruct((n_pad, widths[s]), jnp.float32)
                  for s in stats]
    out_specs = [pl.BlockSpec((bank_size, widths[s]), lambda b, t: (b, 0))
                 for s in stats]

    kernel = functools.partial(
        _mp_pipeline_kernel, bank_size=bank_size, edge_tile=edge_tile,
        n_pad=n_pad, stats=stats, sw_mode=sw_mode, head_dim=head_dim,
        has_et=edge_term is not None, has_bias=bias is not None,
        activation=activation, att_heads=att_heads, att_slope=att_slope)

    outs = pl.pallas_call(
        kernel,
        grid=(num_banks, e_pad // edge_tile),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(*inputs)
    return {s: o[:num_nodes] for s, o in zip(stats, outs)}


def mp_pipeline_ref(x: Array, senders: Array, receivers: Array,
                    edge_mask: Array, num_nodes: int, stats, *,
                    src_weight: Array = None, edge_term: Array = None,
                    bias: Array = None, activation: str = "none",
                    att_src: Array = None, att_dst: Array = None,
                    att_slope: float = 0.2):
    """Pure-jnp oracle for ``mp_pipeline`` (raw f32 accumulators).

    Mirrors the kernel contract exactly, including the finite ∓BIG
    neutral for empty-destination max/min and the attention carries
    (``att_max`` at -BIG / ``att_denom`` at 0 for empty destinations,
    softmax-weighted normalized "sum").
    """
    msg = apply_fusable_phi(x, senders, src_weight=src_weight,
                            edge_term=edge_term, bias=bias,
                            activation=activation)
    own = edge_mask[:, None]
    out = {}
    if att_src is not None:
        e_n, d = msg.shape
        heads = att_src.shape[1]
        hd = d // heads
        logits = (jnp.take(att_src, senders, axis=0)
                  + jnp.take(att_dst, receivers, axis=0)).astype(jnp.float32)
        logits = jnp.where(logits >= 0.0, logits, att_slope * logits)
        m = jnp.maximum(jax.ops.segment_max(
            jnp.where(own, logits, -BIG), receivers,
            num_segments=num_nodes), -BIG)
        p = jnp.where(own, jnp.exp(logits - jnp.take(m, receivers, axis=0)),
                      0.0)
        denom = jax.ops.segment_sum(p, receivers, num_segments=num_nodes)
        num = jax.ops.segment_sum(
            (p[:, :, None] * msg.reshape(e_n, heads, hd)).reshape(e_n, d),
            receivers, num_segments=num_nodes)
        wgt = jnp.where(denom > 0.0, 1.0 / jnp.maximum(denom, 1e-16), 0.0)
        out["sum"] = (num.reshape(num_nodes, heads, hd)
                      * wgt[:, :, None]).reshape(num_nodes, d)
        out["att_max"] = m
        out["att_denom"] = denom
    elif "sum" in stats:
        out["sum"] = jax.ops.segment_sum(
            jnp.where(own, msg, 0.0), receivers, num_segments=num_nodes)
    if "sumsq" in stats:
        m0 = jnp.where(own, msg, 0.0)
        out["sumsq"] = jax.ops.segment_sum(
            m0 * m0, receivers, num_segments=num_nodes)
    if "count" in stats:
        out["count"] = jax.ops.segment_sum(
            edge_mask.astype(jnp.float32)[:, None], receivers,
            num_segments=num_nodes)
    if "max" in stats:
        mx = jax.ops.segment_max(
            jnp.where(own, msg, -BIG), receivers, num_segments=num_nodes)
        out["max"] = jnp.maximum(mx, -BIG)     # untouched rows: -inf -> -BIG
    if "min" in stats:
        mn = jax.ops.segment_min(
            jnp.where(own, msg, BIG), receivers, num_segments=num_nodes)
        out["min"] = jnp.minimum(mn, BIG)
    return out


def apply_fusable_phi(x: Array, senders: Array, *, src_weight: Array = None,
                      edge_term: Array = None, bias: Array = None,
                      activation: str = "none") -> Array:
    """The fusable phi as plain jnp: act(x[snd] * sw + et + b), in f32.

    Shared by ``mp_pipeline_ref`` and the CPU mirror of the pipeline path
    in ``core.message_passing.fused_edge_aggregate`` so both sides apply
    the terms in the identical order (bitwise-parity contract).
    """
    msg = jnp.take(x, senders, axis=0).astype(jnp.float32)
    if src_weight is not None:
        sw = src_weight.astype(jnp.float32)
        if sw.ndim == 1:
            msg = msg * sw[:, None]
        else:
            mode, head_dim = _src_weight_mode(sw, msg.shape[1])
            if mode == "head":
                # per-head lanes (GAT): broadcast across head_dim via a
                # reshape — bitwise-identical to the unfused
                # ``h[senders] * att[..., None]`` multiply, with no
                # host-side (E, H·Dh) expansion
                e_n, d_n = msg.shape
                msg = (msg.reshape(e_n, sw.shape[1], head_dim)
                       * sw[:, :, None]).reshape(e_n, d_n)
            else:
                msg = msg * sw
    if edge_term is not None:
        msg = msg + edge_term.astype(jnp.float32)
    if bias is not None:
        msg = msg + bias.astype(jnp.float32)
    if activation == "relu":
        msg = jnp.maximum(msg, 0.0)
    elif activation != "none":
        raise ValueError(f"unsupported activation '{activation}'")
    return msg
