"""Pallas TPU kernel: the layer-fused NT+MP step — a whole GNN layer in ONE
launch.

``mp_pipeline`` (DESIGN.md §6) fused the *edge phase* — gather, phi, every
statistic — into one kernel, but the layer was still two dispatches: the
pipeline produced the aggregated (N, D) buffer, wrote it to HBM, and a
separate NT dispatch (``nt_mlp`` or an XLA matmul) read it back to apply
the update. FlowGNN's headline claim is stronger: the NT and MP units of
adjacent layers pipeline against each other with *no inter-layer
materialization* (Fig. 4b). This kernel closes that gap (DESIGN.md §7):

  grid = (num_banks, edge_tiles); per bank the edge stream is swept once
  into VMEM accumulators (gather matmul + fusable phi + routing matmul,
  exactly the mp_pipeline stages), and on the bank's LAST edge tile the NT
  epilogue runs in-register on the still-resident accumulators. Two
  epilogue forms:

  **self_mlp** (GIN, GIN-VN, GCN) — one sum accumulator:

      z   = acc + self_coeff * x_bank          # GIN's (1+eps)x, GCN's self loop
      h   = z @ w1 + b1                        # update matmul (MXU)
      h   = relu(h) @ w2 + b2                  # optional second MLP layer
      out = act_out(h)

  **scalers** (PNA's Eq. 3 contraction) — sum/sumsq/keyed-max/keyed-min
  accumulators plus the shared degree stream:

      mean = s1/deg ; std = sqrt(max(s2/deg - mean², 0) + 1e-5)
      m    = concat(mean, std, max, min)                     # (bank, 4D)
      z    = concat(x_bank, s_0·m, ..., s_{S-1}·m)           # degree scalers
      out  = act_out( mlp(z) )

  Either way the aggregated message buffer never reaches HBM — the only
  (N, ·) write of the whole layer is the final output. ``node_input``
  (PNA's pre-linear node-side transform) swaps the resident gather buffer
  while the self/concat rows still come from the carry ``x``.

  **field** (DGN's directional |·| combine) — one sum accumulator over
  the stacked [x | x·w-lane] gather buffer (width 2·D_x):

      mean = s1[:, :D_x] / deg
      dx   = |s1[:, D_x:] - x_bank · field_wsum|     # |B_dx X| closed in-register
      out  = act_out( mlp( concat(x_bank, mean, dx) ) )

GAT's attention-weighted aggregate has no update matmul; it runs the
attention-fused ``mp_pipeline`` (online softmax in the edge sweep) as its
one launch under ``impl='fused_layer'`` — see
``core.message_passing.propagate``.

VMEM sizing: on top of the ``mp_pipeline`` working set (resident node
buffer N_pad × D, gather route edge_tile × N_pad), a grid step holds the
(bank_size, D) f32 accumulator (×4 for the scalers form, plus the keyed
select tensor edge_tile × bank_size × D) and the update weights (D_in ×
D_ff and D_ff × D_out). With the paper's hidden sizes (D ≤ 128, D_ff ≤
13D) the weights are a few hundred KB — far below the route/buffer terms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mp_pipeline import (BIG, _gather_phi_tile,
                                       _src_weight_mode, apply_fusable_phi)
from repro.kernels.mp_scatter import (ROUTE_PRECISION, _ceil_to, _route_matrix,
                                      pad_edge_stream)

Array = jax.Array


def _layer_fused_kernel(*refs, bank_size: int, edge_tile: int, n_pad: int,
                        sw_mode: str, head_dim: int, has_et: bool,
                        has_phi_bias: bool, phi_activation: str,
                        self_mode: str, two_layer: bool,
                        out_activation: str, epilogue: str, n_scalers: int,
                        d_x: int = 0):
    it = iter(refs)
    snd_ref, recv_ref, mask_ref = next(it), next(it), next(it)
    sw_ref = next(it) if sw_mode != "none" else None
    et_ref = next(it) if has_et else None
    pb_ref = next(it) if has_phi_bias else None
    y_ref = next(it)                                  # resident (n_pad, D)
    # the bank's own slice of the carry x (self term / epilogue concat)
    needs_xb = self_mode != "none" or epilogue in ("scalers", "field")
    xb_ref = next(it) if needs_xb else None
    sc_ref = next(it) if self_mode != "none" else None
    scal_ref = next(it) if epilogue == "scalers" else None
    deg_ref = next(it) if epilogue in ("scalers", "field") else None
    wsum_ref = next(it) if epilogue == "field" else None
    w1_ref, b1_ref = next(it), next(it)
    w2_ref = next(it) if two_layer else None
    b2_ref = next(it) if two_layer else None
    out_ref = next(it)
    scratch = list(it)                                # VMEM accumulators

    @pl.when(pl.program_id(1) == 0)
    def _init():
        if epilogue == "scalers":
            acc_s, acc_sq, acc_mx, acc_mn = scratch
            acc_s[...] = jnp.zeros_like(acc_s)
            acc_sq[...] = jnp.zeros_like(acc_sq)
            acc_mx[...] = jnp.full_like(acc_mx, -BIG)
            acc_mn[...] = jnp.full_like(acc_mn, BIG)
        else:
            scratch[0][...] = jnp.zeros_like(scratch[0])

    snd = snd_ref[...].reshape(edge_tile)
    recv = recv_ref[...].reshape(edge_tile)
    mask = mask_ref[...].reshape(edge_tile)
    valid = mask != 0

    msg, _ = _gather_phi_tile(
        y_ref, snd, valid, sw_ref, et_ref, pb_ref, edge_tile=edge_tile,
        n_pad=n_pad, sw_mode=sw_mode, head_dim=head_dim,
        activation=phi_activation)

    route = _route_matrix(recv, mask, pl.program_id(0), bank_size,
                          edge_tile).astype(jnp.float32)
    dn = (((0,), (0,)), ((), ()))                     # route^T @ msg
    if epilogue == "scalers":
        acc_s, acc_sq, acc_mx, acc_mn = scratch
        acc_s[...] += jax.lax.dot_general(
            route, msg, dimension_numbers=dn, precision=ROUTE_PRECISION,
            preferred_element_type=jnp.float32)
        acc_sq[...] += jax.lax.dot_general(
            route, msg * msg, dimension_numbers=dn,
            precision=ROUTE_PRECISION, preferred_element_type=jnp.float32)
        # keyed max/min (mp_pipeline's finite additive-key formulation)
        key = (route - 1.0) * BIG                     # (edge_tile, bank)
        acc_mx[...] = jnp.maximum(
            acc_mx[...], jnp.max(msg[:, None, :] + key[:, :, None], axis=0))
        acc_mn[...] = jnp.minimum(
            acc_mn[...], jnp.min(msg[:, None, :] - key[:, :, None], axis=0))
    else:
        scratch[0][...] += jax.lax.dot_general(
            route, msg, dimension_numbers=dn, precision=ROUTE_PRECISION,
            preferred_element_type=jnp.float32)

    def _mlp_out(z):
        h = jax.lax.dot(z, w1_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        h = h + b1_ref[...].astype(jnp.float32)
        if two_layer:
            h = jnp.maximum(h, 0.0)
            h = jax.lax.dot(h, w2_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            h = h + b2_ref[...].astype(jnp.float32)
        if out_activation == "relu":
            h = jnp.maximum(h, 0.0)
        out_ref[...] = h.astype(out_ref.dtype)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _nt_epilogue():
        # the bank's aggregation is complete: run the update in-register
        # on the still-resident accumulators (the NT unit folded in).
        if epilogue == "scalers":
            acc_s, acc_sq, acc_mx, acc_mn = scratch
            deg = deg_ref[...].astype(jnp.float32)        # (bank, 1)
            rdenom = 1.0 / jnp.maximum(deg, 1.0)
            mean = acc_s[...] * rdenom
            var = jnp.maximum(acc_sq[...] * rdenom - mean * mean, 0.0)
            std = jnp.sqrt(var + 1e-5)
            nonempty = deg > 0.0
            mx = acc_mx[...]
            mn = acc_mn[...]
            mx = jnp.where(nonempty & (mx > -BIG), mx, 0.0)
            mn = jnp.where(nonempty & (mn < BIG), mn, 0.0)
            m = jnp.concatenate([mean, std, mx, mn], axis=-1)  # (bank, 4D)
            sc = scal_ref[...].astype(jnp.float32)             # (bank, S)
            z = jnp.concatenate(
                [xb_ref[...].astype(jnp.float32)]
                + [m * sc[:, k:k + 1] for k in range(n_scalers)], axis=-1)
        elif epilogue == "field":
            # DGN's |·| directional combine (DESIGN.md §7): the single sum
            # accumulator carries the stacked [x_src | x_src·w] lanes; the
            # mean half is degree-normalized and the directional half
            # closes the derivative |Σ w·x_src - x·Σw| in-register
            acc = scratch[0][...]
            deg = deg_ref[...].astype(jnp.float32)            # (bank, 1)
            rdenom = 1.0 / jnp.maximum(deg, 1.0)
            xb = xb_ref[...].astype(jnp.float32)
            mean = acc[:, :d_x] * rdenom
            dx = jnp.abs(acc[:, d_x:] - xb * wsum_ref[...].astype(
                jnp.float32))
            z = jnp.concatenate([xb, mean, dx], axis=-1)
        else:
            z = scratch[0][...]
            if self_mode == "scalar":
                z = z + sc_ref[0, 0] * xb_ref[...].astype(jnp.float32)
            elif self_mode == "node":
                z = z + xb_ref[...].astype(jnp.float32) * sc_ref[...]
        _mlp_out(z)


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "phi_activation", "out_activation",
                     "edge_tile", "num_banks", "interpret"),
)
def layer_fused(x: Array, senders: Array, receivers: Array, edge_mask: Array,
                num_nodes: int, *, w1: Array, b1: Array,
                node_input: Array = None, src_weight: Array = None,
                edge_term: Array = None, phi_bias: Array = None,
                phi_activation: str = "none", self_coeff=None,
                scalers: Array = None, degrees: Array = None,
                field_wsum: Array = None,
                w2: Array = None, b2: Array = None,
                out_activation: str = "none", edge_tile: int = 128,
                num_banks: int = 4, interpret: bool) -> Array:
    """One-launch GNN layer: gather + phi + aggregate + NT update.

    Per edge, phi is the fusable form of ``mp_pipeline``
    (``act(y[snd] * src_weight + edge_term + phi_bias)`` with ``y`` the
    resident gather buffer — ``node_input`` or ``x``); per node the update
    is either the self-term form

        out = act_out( mlp( sum_agg + self_coeff * x ) )

    with ``self_coeff`` None, a scalar (GIN's 1+eps), or a per-node (N,)
    vector (GCN's self-loop norm), or — with ``scalers`` (N, S) and the
    shared masked in-``degrees`` (N,) — the PNA scaler-contraction form

        m   = concat(mean, std, max, min)          # derived in-register
        out = act_out( mlp( concat(x, s_0*m, ..., s_{S-1}*m) ) )

    or — with ``field_wsum`` (N,) and ``degrees`` — DGN's directional
    field form: the gather buffer is the stacked [x | x·w-lane] pair
    (width 2·D_x) and the epilogue derives

        out = act_out( mlp( concat(x, s1[:, :D_x]/deg,
                                   |s1[:, D_x:] - x·field_wsum|) ) )

    from the single sum accumulator. ``mlp`` is one dense layer (w1, b1)
    or two with a ReLU between (w1, b1, w2, b2). Returns
    (num_nodes, D_out) in ``x.dtype``. Uneven E / num_nodes are padded
    internally.
    """
    if phi_activation not in ("none", "relu"):
        raise ValueError(f"unsupported activation '{phi_activation}'")
    if out_activation not in ("none", "relu"):
        raise ValueError(f"unsupported activation '{out_activation}'")
    if (w2 is None) != (b2 is None):
        raise ValueError("w2 and b2 must be given together")
    if sum(p is not None for p in (self_coeff, scalers, field_wsum)) > 1:
        raise ValueError(
            "self_coeff, scalers and field_wsum are mutually exclusive")
    if (scalers is not None or field_wsum is not None) and degrees is None:
        raise ValueError(
            "the scalers/field epilogues need the shared degrees")
    n, d_x = x.shape
    if n != num_nodes:
        raise ValueError(f"node buffer has {n} rows, expected {num_nodes}")
    y = x if node_input is None else node_input
    if y.shape[0] != num_nodes:
        raise ValueError(
            f"node_input has {y.shape[0]} rows, expected {num_nodes}")
    d = y.shape[1]                        # message / accumulator width
    epilogue = ("scalers" if scalers is not None
                else "field" if field_wsum is not None else "self_mlp")
    n_scalers = 0
    if epilogue == "scalers":
        n_scalers = scalers.shape[1]
        d_in = d_x + n_scalers * 4 * d
    elif epilogue == "field":
        if d != 2 * d_x:
            raise ValueError(
                f"the field epilogue expects a stacked gather buffer of "
                f"width 2·{d_x}, got {d}")
        d_in = d_x + d
    else:
        d_in = d
    if w1.shape[0] != d_in:
        raise ValueError(
            f"w1 contracts over {w1.shape[0]}, epilogue '{epilogue}' "
            f"expects {d_in}")
    e = senders.shape[0]
    e_pad = _ceil_to(e, edge_tile)
    n_pad = _ceil_to(num_nodes, num_banks)
    bank_size = n_pad // num_banks
    d_out = (w2 if w2 is not None else w1).shape[1]
    two_layer = w2 is not None

    _, snd2, _, _ = pad_edge_stream(senders, senders, edge_mask, edge_tile)
    _, recv2, mask2, _ = pad_edge_stream(
        receivers, receivers, edge_mask, edge_tile)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        y = x if node_input is None else jnp.pad(y, ((0, n_pad - n), (0, 0)))

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
    if phi_bias is not None:
        inputs.append(phi_bias.astype(jnp.float32).reshape(1, d))
        in_specs.append(pl.BlockSpec((1, d), lambda b, t: (0, 0)))
    inputs.append(y)                                  # resident gather buffer
    in_specs.append(pl.BlockSpec((n_pad, d), lambda b, t: (0, 0)))

    self_mode = "none"
    if self_coeff is not None:
        sc = jnp.asarray(self_coeff, jnp.float32)
        if sc.ndim == 0:
            self_mode = "scalar"
            sc = sc.reshape(1, 1)
            sc_spec = pl.BlockSpec((1, 1), lambda b, t: (0, 0))
        elif sc.shape == (num_nodes,):
            self_mode = "node"
            if n_pad != num_nodes:
                sc = jnp.pad(sc, (0, n_pad - num_nodes))
            sc = sc.reshape(n_pad, 1)
            sc_spec = pl.BlockSpec((bank_size, 1), lambda b, t: (b, 0))
        else:
            raise ValueError(
                f"self_coeff must be scalar or ({num_nodes},), got "
                f"shape {sc.shape}")
        # the bank's own slice of the carry, for the self term
        inputs.append(x)
        in_specs.append(pl.BlockSpec((bank_size, d_x), lambda b, t: (b, 0)))
        inputs.append(sc)
        in_specs.append(sc_spec)
    elif epilogue == "scalers":
        # the carry rows join the concat; scalers + degrees stream per bank
        inputs.append(x)
        in_specs.append(pl.BlockSpec((bank_size, d_x), lambda b, t: (b, 0)))
        scal = jnp.asarray(scalers, jnp.float32)
        if scal.shape[0] != num_nodes:
            raise ValueError(
                f"scalers has {scal.shape[0]} rows, expected {num_nodes}")
        deg = jnp.asarray(degrees, jnp.float32).reshape(num_nodes, 1)
        if n_pad != num_nodes:
            scal = jnp.pad(scal, ((0, n_pad - num_nodes), (0, 0)))
            deg = jnp.pad(deg, ((0, n_pad - num_nodes), (0, 0)))
        inputs.append(scal)
        in_specs.append(
            pl.BlockSpec((bank_size, n_scalers), lambda b, t: (b, 0)))
        inputs.append(deg)
        in_specs.append(pl.BlockSpec((bank_size, 1), lambda b, t: (b, 0)))
    elif epilogue == "field":
        # the carry rows join the concat; degrees + field weight sums
        # stream per bank
        inputs.append(x)
        in_specs.append(pl.BlockSpec((bank_size, d_x), lambda b, t: (b, 0)))
        deg = jnp.asarray(degrees, jnp.float32).reshape(num_nodes, 1)
        wsum = jnp.asarray(field_wsum, jnp.float32).reshape(num_nodes, 1)
        if n_pad != num_nodes:
            deg = jnp.pad(deg, ((0, n_pad - num_nodes), (0, 0)))
            wsum = jnp.pad(wsum, ((0, n_pad - num_nodes), (0, 0)))
        inputs.append(deg)
        in_specs.append(pl.BlockSpec((bank_size, 1), lambda b, t: (b, 0)))
        inputs.append(wsum)
        in_specs.append(pl.BlockSpec((bank_size, 1), lambda b, t: (b, 0)))

    d_ff = w1.shape[1]
    inputs += [w1, b1.astype(jnp.float32).reshape(1, d_ff)]
    in_specs += [pl.BlockSpec((d_in, d_ff), lambda b, t: (0, 0)),
                 pl.BlockSpec((1, d_ff), lambda b, t: (0, 0))]
    if two_layer:
        inputs += [w2, b2.astype(jnp.float32).reshape(1, d_out)]
        in_specs += [pl.BlockSpec((d_ff, d_out), lambda b, t: (0, 0)),
                     pl.BlockSpec((1, d_out), lambda b, t: (0, 0))]

    kernel = functools.partial(
        _layer_fused_kernel, bank_size=bank_size, edge_tile=edge_tile,
        n_pad=n_pad, sw_mode=sw_mode, head_dim=head_dim,
        has_et=edge_term is not None, has_phi_bias=phi_bias is not None,
        phi_activation=phi_activation, self_mode=self_mode,
        two_layer=two_layer, out_activation=out_activation,
        epilogue=epilogue, n_scalers=n_scalers, d_x=d_x)

    n_acc = 4 if epilogue == "scalers" else 1
    out = pl.pallas_call(
        kernel,
        grid=(num_banks, e_pad // edge_tile),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bank_size, d_out), lambda b, t: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bank_size, d), jnp.float32)
                        for _ in range(n_acc)],
        interpret=interpret,
    )(*inputs)
    return out[:num_nodes]


def layer_fused_ref(x: Array, senders: Array, receivers: Array,
                    edge_mask: Array, num_nodes: int, *, w1: Array, b1: Array,
                    node_input: Array = None, src_weight: Array = None,
                    edge_term: Array = None, phi_bias: Array = None,
                    phi_activation: str = "none", self_coeff=None,
                    scalers: Array = None, degrees: Array = None,
                    field_wsum: Array = None,
                    w2: Array = None, b2: Array = None,
                    out_activation: str = "none") -> Array:
    """Pure-jnp oracle for ``layer_fused`` (identical contract)."""
    y = x if node_input is None else node_input
    msg = apply_fusable_phi(y, senders, src_weight=src_weight,
                            edge_term=edge_term, bias=phi_bias,
                            activation=phi_activation)
    own = edge_mask[:, None]
    if field_wsum is not None:
        if degrees is None:
            raise ValueError("the field epilogue needs the shared degrees")
        d_x = x.shape[1]
        s1 = jax.ops.segment_sum(jnp.where(own, msg, 0.0), receivers,
                                 num_segments=num_nodes)
        deg = jnp.asarray(degrees, jnp.float32)[:, None]
        rdenom = 1.0 / jnp.maximum(deg, 1.0)
        xf = x.astype(jnp.float32)
        mean = s1[:, :d_x] * rdenom
        dx = jnp.abs(s1[:, d_x:]
                     - xf * jnp.asarray(field_wsum, jnp.float32)[:, None])
        z = jnp.concatenate([xf, mean, dx], axis=-1)
    elif scalers is not None:
        if degrees is None:
            raise ValueError("the scalers epilogue needs the shared degrees")
        m0 = jnp.where(own, msg, 0.0)
        s1 = jax.ops.segment_sum(m0, receivers, num_segments=num_nodes)
        s2 = jax.ops.segment_sum(m0 * m0, receivers, num_segments=num_nodes)
        mx = jnp.maximum(jax.ops.segment_max(
            jnp.where(own, msg, -BIG), receivers, num_segments=num_nodes),
            -BIG)
        mn = jnp.minimum(jax.ops.segment_min(
            jnp.where(own, msg, BIG), receivers, num_segments=num_nodes),
            BIG)
        deg = jnp.asarray(degrees, jnp.float32)[:, None]
        rdenom = 1.0 / jnp.maximum(deg, 1.0)
        mean = s1 * rdenom
        var = jnp.maximum(s2 * rdenom - mean * mean, 0.0)
        std = jnp.sqrt(var + 1e-5)
        nonempty = deg > 0.0
        mx = jnp.where(nonempty & (mx > -BIG), mx, 0.0)
        mn = jnp.where(nonempty & (mn < BIG), mn, 0.0)
        m = jnp.concatenate([mean, std, mx, mn], axis=-1)
        sc = jnp.asarray(scalers, jnp.float32)
        z = jnp.concatenate(
            [x.astype(jnp.float32)]
            + [m * sc[:, k:k + 1] for k in range(sc.shape[1])], axis=-1)
    else:
        z = jax.ops.segment_sum(jnp.where(own, msg, 0.0),
                                receivers, num_segments=num_nodes)
        if self_coeff is not None:
            sc = jnp.asarray(self_coeff, jnp.float32)
            z = z + x.astype(jnp.float32) * (sc if sc.ndim == 0
                                             else sc[:, None])
    h = z @ w1.astype(jnp.float32) + b1.astype(jnp.float32)
    if w2 is not None:
        h = jnp.maximum(h, 0.0) @ w2.astype(jnp.float32)
        h = h + b2.astype(jnp.float32)
    if out_activation == "relu":
        h = jnp.maximum(h, 0.0)
    return h.astype(x.dtype)
