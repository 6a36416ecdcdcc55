"""FlowGNN's generic message-passing engine (paper Eq. 2), TPU-adapted.

    x_i^{l+1} = gamma( x_i^l,  A_{j in N(i)}  phi(x_i^l, x_j^l, e_{j,i}^l) )

The engine exposes:

  * ``propagate``                — one NT+MP step with pluggable phi / A / gamma,
  * ``segment_aggregate``        — the MP unit: permutation-invariant aggregation
                                   over raw COO destinations (sum/mean/max/min/std),
  * ``segment_multi_aggregate``  — the *single-pass* multi-statistic MP unit:
                                   all requested kinds from one sweep over the
                                   edge stream (DESIGN.md §3),
  * ``segment_softmax``          — edge softmax for anisotropic models (GAT),
  * ``FusableMessage`` / ``fused_edge_aggregate`` — the *pipeline* contract:
                                   phi described as a per-edge linear combine
                                   so the whole edge phase (gather + phi +
                                   every statistic) runs as one launch with
                                   no (E, D) message buffer (DESIGN.md §6),
  * ``FusableUpdate`` / ``scan_layers`` — the *layer-fused* contract
                                   (DESIGN.md §7): gamma described as a
                                   self-term + dense MLP so the NT update
                                   folds into the pipeline kernel (one launch
                                   per layer), and a ``lax.scan`` wrapper over
                                   stacked layer parameters that keeps
                                   ``count_edge_passes`` honest,
  * ``PrecomputedGraphStats``    — per-graph structure statistics (degrees,
                                   normalizers, PNA scalers, DGN field
                                   weights) computed once per forward pass
                                   and shared across layers (DESIGN.md §5),
  * ``DataflowConfig``           — the paper's four parallelism knobs, remapped to
                                   TPU tile shapes (see DESIGN.md §2), plus the
                                   implementation selector used by the Fig. 9
                                   ablation (twopass / unfused / fused / kernel).

Implementation notes (FPGA -> TPU adaptation):
  * The paper merges scatter and gather into one pass over edges writing into
    an O(N) message buffer. ``segment_aggregate`` is exactly that merged pass;
    XLA lowers it to a single scatter-add (O(N) live memory, messages are
    fused away when ``impl='fused'``).
  * The paper's MP unit accumulates *all* per-destination statistics while the
    edge stream flows past once (Fig. 5). ``segment_multi_aggregate`` restores
    that property on TPU: the moment statistics (sum / count / sum-of-squares)
    are stacked into one widened segment-sum — a single edge sweep — and
    mean/var/std are derived algebraically; max/min keep their own combiner.
    With ``impl='kernel'`` the whole bundle (moments *and* max/min) runs as
    one Pallas edge-tile stream (kernels/mp_scatter.py::mp_scatter_multi).
  * The multi-queue multicast adapter (each MP unit owns a destination bank)
    becomes the *banked* formulation: destinations are tiled into
    ``num_banks`` contiguous banks; each bank accumulates its own edges with
    dense mask-select math. ``impl='kernel'`` runs it as a Pallas kernel
    (kernels/mp_scatter.py); ``banked_segment_sum`` is the pure-jnp mirror
    used for CPU ablations and as the kernel oracle.
  * ``count_edge_passes()`` counts sweeps over the edge stream at trace time,
    so the Fig. 9 ablation can report the paper's headline dataflow property
    (passes-over-edges) and benchmarks can guard against regressions.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.graph import GraphBatch

Array = jax.Array

_NEUTRAL = {
    "sum": 0.0,
    "mean": 0.0,
    "max": -jnp.inf,
    "min": jnp.inf,
    "std": 0.0,
    "var": 0.0,
}

AGG_KINDS = tuple(_NEUTRAL.keys())

# Kinds derivable from the streamed moments (sum, count, sum-of-squares).
MOMENT_KINDS = ("sum", "mean", "var", "std")


# ---------------------------------------------------------------------------
# Edge-pass accounting (trace-time): the paper's "one pass over the stream"
# property, made measurable. Each segment reduction / kernel launch / full
# per-edge rewrite of the x-dependent message stream counts as one pass
# (x-independent side streams — edge encodings, attention lanes, field
# weights — are NT-side stream preparation and are not counted).
# ---------------------------------------------------------------------------

@dataclass
class EdgePassStats:
    passes: int = 0


class _EdgePassScope(threading.local):
    """Per-thread active counter (None when no block is open)."""

    def __init__(self):
        self.active: Optional[EdgePassStats] = None


_EDGE_PASS_SCOPE = _EdgePassScope()


def _count_pass(n: int = 1) -> None:
    st = _EDGE_PASS_SCOPE.active
    if st is not None:
        st.passes += n


@contextmanager
def count_edge_passes():
    """Count edge-stream sweeps issued while tracing inside the block.

    Counting happens at Python trace time, so trace the function of interest
    inside the block (e.g. ``jax.eval_shape(fn, *args)`` or an un-jitted
    call); cached jit re-executions count nothing.

    Counters are *thread-local*: concurrent traces (e.g. the
    ``GraphStreamEngine`` dispatcher thread compiling a bucket while user
    code counts its own trace) never corrupt each other. Nesting in one
    thread is rejected — a nested block would silently steal the outer
    block's sweeps, so it raises instead.
    """
    if _EDGE_PASS_SCOPE.active is not None:
        raise RuntimeError(
            "count_edge_passes() does not nest: a counting block is "
            "already open in this thread")
    st = EdgePassStats()
    _EDGE_PASS_SCOPE.active = st
    try:
        yield st
    finally:
        _EDGE_PASS_SCOPE.active = None


@contextmanager
def _uncounted():
    """Suspend pass counting (one fused launch = one pass, whatever the
    mirror implementation issues internally)."""
    st = _EDGE_PASS_SCOPE.active
    _EDGE_PASS_SCOPE.active = None
    try:
        yield
    finally:
        _EDGE_PASS_SCOPE.active = st


def scan_layers(body, init, xs, *, length: int):
    """``lax.scan`` over stacked layer parameters, pass-accounting aware.

    The scanned forward (DESIGN.md §7) traces the layer body ONCE, so the
    sweeps ``count_edge_passes`` records during that single trace are the
    *per-layer* count; this wrapper multiplies them by the number of scanned
    steps so trace-time accounting keeps reporting the paper's per-forward
    passes-over-edges figure regardless of execution strategy.
    """
    st = _EDGE_PASS_SCOPE.active
    before = st.passes if st is not None else 0
    carry, ys = jax.lax.scan(body, init, xs, length=length)
    if st is not None and length > 1:
        st.passes += (st.passes - before) * (length - 1)
    return carry, ys


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PrecomputedGraphStats:
    """Graph-level statistics computed once per forward pass.

    The paper's MP unit accumulates per-destination state on the fly; several
    models additionally need *graph structure* statistics (degrees, degree
    normalizers, PNA scalers, the DGN field weights) that are functions of the
    topology only — recomputing them per layer costs one edge sweep each time.
    This bundle is produced once by :func:`precompute_graph_stats` and threaded
    through ``propagate`` (and directly into ``segment_multi_aggregate``) so
    every layer shares the same arrays.

    All fields are optional: a model requests only what it uses, and ``None``
    fields vanish from the pytree (no dead device buffers).

      degrees       (N,)   masked in-degree per destination node
      inv_sqrt_deg  (N,)   1/sqrt(degree + 1) — GCN's self-loop normalizer
      pna_scalers   (N, 3) [identity, amplification, attenuation] (Eq. 3)
      dgn_weights   (E,)   normalized directional field weight per edge
      dgn_wsum      (N,)   per-destination sum of dgn_weights (layer-invariant
                           part of the |B_dx X| derivative)
      graph_node_counts (G_pad,)  valid nodes per packed graph — shared by
                           every mean readout (``global_pool``) instead of
                           re-issuing a node-mask segment-sum per pool
    """

    degrees: Optional[Array] = None
    inv_sqrt_deg: Optional[Array] = None
    pna_scalers: Optional[Array] = None
    dgn_weights: Optional[Array] = None
    dgn_wsum: Optional[Array] = None
    graph_node_counts: Optional[Array] = None


def precompute_graph_stats(
    graph: GraphBatch,
    *,
    with_degrees: bool = True,
    with_self_loop_norm: bool = False,
    pna_delta: Optional[float] = None,
    with_dgn_field: bool = False,
    with_graph_counts: bool = False,
    degrees: Optional[Array] = None,
) -> PrecomputedGraphStats:
    """Compute the per-graph statistics bundle (one sweep per family).

    ``pna_delta`` is the PNA normalization constant (``cfg.avg_log_degree``).
    Sweeps issued here are counted by ``count_edge_passes`` — they are real
    passes over the edge stream, just hoisted out of the layer loop.

    ``degrees`` may be supplied to skip the degree sweep: wide placement
    (distributed/wide.py) injects exact *global* in-degrees per shard, since
    halo rows have no local in-edges but their degree normalizers (GCN
    ``inv_sqrt_deg``, PNA scalers) must match the owner's values bitwise.
    In-degree counts are exact small integers in f32, so the injected values
    equal what the masked segment-sum would produce on the owning shard.
    """
    need_deg = with_degrees or with_self_loop_norm or pna_delta is not None
    if need_deg and degrees is None:
        _count_pass()
        degrees = jax.ops.segment_sum(
            graph.edge_mask.astype(jnp.float32), graph.receivers,
            num_segments=graph.n_node_pad)
    elif not need_deg:
        degrees = None
    inv_sqrt_deg = None
    if with_self_loop_norm:
        inv_sqrt_deg = jax.lax.rsqrt(degrees + 1.0)
    pna_scalers = None
    if pna_delta is not None:
        log_deg = jnp.log(degrees + 1.0)
        pna_scalers = jnp.stack([
            jnp.ones_like(log_deg),
            log_deg / pna_delta,
            pna_delta / jnp.maximum(log_deg, 1e-3),
        ], axis=-1)
    dgn_weights = dgn_wsum = None
    if with_dgn_field:
        pos = graph.node_pos[:, 0]
        dpos = pos[graph.senders] - pos[graph.receivers]
        _count_pass()
        absnorm = jax.ops.segment_sum(
            jnp.where(graph.edge_mask, jnp.abs(dpos), 0.0), graph.receivers,
            num_segments=graph.n_node_pad)
        dgn_weights = dpos / jnp.maximum(absnorm[graph.receivers], 1e-6)
        _count_pass()
        dgn_wsum = jax.ops.segment_sum(
            jnp.where(graph.edge_mask, dgn_weights, 0.0), graph.receivers,
            num_segments=graph.n_node_pad)
    graph_node_counts = None
    if with_graph_counts:
        # node-stream sweep (not an edge pass): valid nodes per packed graph
        graph_node_counts = jax.ops.segment_sum(
            graph.node_mask.astype(jnp.float32), graph.graph_ids,
            num_segments=graph.n_graph_pad)
    return PrecomputedGraphStats(
        degrees=degrees, inv_sqrt_deg=inv_sqrt_deg, pna_scalers=pna_scalers,
        dgn_weights=dgn_weights, dgn_wsum=dgn_wsum,
        graph_node_counts=graph_node_counts)


@dataclass(frozen=True)
class DataflowConfig:
    """Paper knobs -> TPU tiles.

    P_node    -> node_tile    (nodes per NT grid step / bank row-tile)
    P_edge    -> num_banks    (MP units == destination-node banks)
    P_apply   -> apply_tile   (embedding lanes per NT step)
    P_scatter -> scatter_tile (edge-feature lanes per MP step)

    ``single_pass`` selects the multi-statistic MP unit: when True (default)
    multi-kind aggregation streams the edges once and derives mean/var/std
    from shared moments; when False it falls back to the per-kind loop
    (kept for the Fig. 9 pass-count ablation).

    ``impl='pipeline'`` is the fused gather-phi-scatter edge pipeline
    (DESIGN.md §6): layers that describe phi through ``FusableMessage``
    run their whole edge phase — gather, transform, every statistic — as
    one launch with no (E, D) message buffer (1 edge pass). Layers with an
    arbitrary ``message_fn`` fall back to the ``fused`` behaviour.

    ``impl='fused_layer'`` goes one further (DESIGN.md §7): layers that
    also describe gamma through ``FusableUpdate`` fold the update matmul +
    bias + activation into the pipeline kernel, so the whole NT+MP layer
    step is literally one launch and the aggregated message buffer never
    reaches HBM. Layers without a fusable update keep the pipeline edge
    phase and run gamma as a separate (XLA-fused) stage.

    ``scan_layers`` selects the scanned stacked-parameter forward
    (DESIGN.md §7): the homogeneous layer stack runs as a single
    ``lax.scan`` — one trace, one compiled body, node buffer resident
    across layers — instead of a per-layer unrolled Python loop. The scan
    body computes the same op sequence as one unrolled layer (tails from
    an identical input match bitwise); whole-forward outputs can still
    drift by ~1 ulp against the unrolled program because XLA fuses the
    two programs differently. Cross-program parity checks (e.g. the wide
    placement tests) therefore pin ``scan_layers=False`` on both sides;
    ``False`` also keeps the unrolled loop for ablation.
    """

    node_tile: int = 8
    num_banks: int = 4
    apply_tile: int = 128
    scatter_tile: int = 128
    edge_tile: int = 128          # edges streamed per MP grid step (kernel)
    # twopass | unfused | fused | banked | kernel | pipeline | fused_layer
    impl: str = "fused"
    single_pass: bool = True      # fuse multi-kind aggregation into one sweep
    scan_layers: bool = True      # lax.scan over stacked layer params

    def replace(self, **kw) -> "DataflowConfig":
        import dataclasses
        return dataclasses.replace(self, **kw)


DEFAULT_DATAFLOW = DataflowConfig()


@dataclass(frozen=True)
class FusableAttention:
    """An edge softmax the pipeline kernel folds INTO the sweep (DESIGN.md §6).

    Describes GAT-style additive attention through its per-node halves:

        logit_e = leaky_relu( src_logits[senders[e]]
                              + dst_logits[receivers[e]], slope )   # (H,)
        weight  = softmax over each destination's incoming edges, per head

    On the kernel path the softmax runs flash-attention style inside the
    fused gather-phi-scatter sweep — a per-(dest, head) running max and an
    online-rescaled denominator carried in the VMEM accumulator, with a
    per-bank normalization epilogue — so the logits, exp-rescale, weighted
    scatter and epilogue are ONE launch (no 2-sweep softmax pre-pass). The
    jnp mirror computes the identical 2-pass ``segment_softmax`` weights
    and stays bitwise-equal to the unfused model path.

      src_logits  (N, H)  per-node source attention half (NT side)
      dst_logits  (N, H)  per-node destination attention half
      slope       float   leaky_relu negative slope (GAT uses 0.2)
    """

    src_logits: Array
    dst_logits: Array
    slope: float = 0.2


@dataclass(frozen=True)
class FusableMessage:
    """A phi the pipeline kernel can apply in-register (DESIGN.md §6).

    Describes the message transform as a per-edge linear combine of the
    gathered source row and an edge-feature term, plus bias and activation:

        phi_e = act( node_input[senders[e]] * src_weight[e]
                     + edge_term[e] + bias )

    All fields optional; ``None`` terms vanish. This covers the whole model
    zoo: GCN (per-edge scalar norm), GIN (additive edge embedding + relu),
    PNA (the pre-linear split into a node-side transform + edge-side term),
    GAT's attention-weighted scatter, and DGN's stacked directional columns.
    Arbitrary ``message_fn``s that don't fit stay on the unfused path —
    ``propagate`` falls back automatically when ``fusable`` is ``None``.

      node_input  (N, D)  pre-transformed node buffer (defaults to ``x``);
                          node-side matmuls (PNA's W_src) belong here — NT
                          work on N rows instead of E rows
      src_weight  (E,) or (E, D)  multiplicative per-edge weight on the
                          gathered row (GCN norm, precomputed edge weights)
      edge_term   (E, D)  additive per-edge term (edge embeddings); an
                          x-independent input stream, not a message buffer
      bias        (D,)    additive bias
      activation  str     'none' | 'relu'
      attention   :class:`FusableAttention`  in-sweep online softmax
                          weighting of the phi output (GAT); restricts the
                          aggregation to ``kinds=('sum',)`` and is mutually
                          exclusive with ``src_weight``
    """

    node_input: Optional[Array] = None
    src_weight: Optional[Array] = None
    edge_term: Optional[Array] = None
    bias: Optional[Array] = None
    activation: str = "none"
    attention: Optional[FusableAttention] = None


# the multi-statistic bundle the scaler-epilogue form consumes, in the
# concat order PNA's update expects (Eq. 3)
PNA_STAT_KINDS = ("mean", "std", "max", "min")


@dataclass(frozen=True)
class FusableUpdate:
    """A gamma the layer-fused kernel can run in-register (DESIGN.md §7).

    Two epilogue forms are covered. The **self-term + MLP** form:

        x' = act_out( mlp( m + self_coeff * x ) )

    where ``m`` is the layer's (sum-)aggregated message buffer, still
    resident in the kernel's VMEM accumulator when the update runs — the
    GIN family (self_coeff = 1+eps, 2-layer MLP) and GCN (self_coeff =
    the per-node self-loop norm, 1 dense layer). And the **scaler
    contraction** form (``scalers`` set), PNA's Eq. 3 update:

        m  = concat(mean, std, max, min)                  # (N, 4D), in-VMEM
        x' = act_out( mlp( concat(x, s_0*m, .., s_{S-1}*m) ) )

    where ``scalers`` are the per-node degree scalers ((N, S), layer-
    invariant, from ``PrecomputedGraphStats``): the kernel derives the
    four statistics from its sum/sumsq/keyed-max/keyed-min accumulators
    and contracts the scalers in-register, so PNA's whole layer is one
    launch too. And the **directional field** form (``field_wsum`` set),
    DGN's absolute-value combine over the stacked [x | x·w-lane] buffer:

        x' = act_out( mlp( concat(x, s1[:, :D_x]/deg,
                                  |s1[:, D_x:] - x·field_wsum|) ) )

    where ``field_wsum`` is the per-destination sum of the directional
    field weights (layer-invariant, from ``PrecomputedGraphStats``): the
    kernel closes the ``|B_dx X|`` derivative on its single sum
    accumulator, so DGN's layer is one launch too. Updates with no matmul
    at all (GAT) instead run the attention-fused pipeline
    (:class:`FusableAttention`) as their one launch.

      self_coeff  scalar or (N,)  weight on the residual self term (None
                                  drops it; mutually exclusive with
                                  ``scalers``/``field_wsum``)
      scalers     (N, S)          per-node degree scalers: selects the
                                  scaler-contraction epilogue (aggregate
                                  kinds must be ``PNA_STAT_KINDS`` and
                                  shared ``stats.degrees`` must be present)
      field_wsum  (N,)            per-destination field-weight sums:
                                  selects the directional-field epilogue
                                  (aggregate kinds must be
                                  ``('sum', 'mean')``, ``stats.degrees``
                                  must be present, and the fusable phi
                                  must gather the stacked 2·D_x buffer)
      w1, b1      (D_in, D_ff), (D_ff,)   first dense layer (D_in = D for
                                  the self form, D + S·4·D for scalers,
                                  3·D_x for the field form)
      w2, b2      (D_ff, D_out), (D_out,)  optional second layer; a ReLU
                                  is applied between the two
      out_activation  'none' | 'relu'   final activation. Layer-position-
                                  dependent activations (GCN's no-relu
                                  last layer) are gated *outside* the
                                  kernel so the scanned body stays
                                  layer-invariant.
    """

    w1: Array
    b1: Array
    self_coeff: Optional[Union[Array, float]] = None
    scalers: Optional[Array] = None
    field_wsum: Optional[Array] = None
    w2: Optional[Array] = None
    b2: Optional[Array] = None
    out_activation: str = "none"


# Test hook: force the Pallas pipeline kernel (interpret mode off-TPU)
# instead of the jnp mirror in fused_edge_aggregate.
_FORCE_PIPELINE_KERNEL = False


def _pipeline_uses_kernel() -> bool:
    return _FORCE_PIPELINE_KERNEL or jax.default_backend() == "tpu"


def fused_edge_aggregate(
    graph: GraphBatch,
    x: Array,
    fusable: FusableMessage,
    *,
    kinds: Sequence[str],
    dataflow: DataflowConfig = DEFAULT_DATAFLOW,
    stats: Optional[PrecomputedGraphStats] = None,
) -> Dict[str, Array]:
    """The fused gather-phi-scatter edge phase: ONE pass, no (E, D) buffer.

    On TPU this is one ``mp_pipeline`` kernel launch (gather matmul from
    the resident node buffer, phi in-register, all statistics accumulated
    — DESIGN.md §6). Elsewhere it runs the fused jnp mirror: the identical
    op sequence under the caller's trace, which XLA fuses and which stays
    bitwise-equal to the unfused path for the same phi formulation.

    Returns ``{kind: (N, D) array}`` like ``segment_multi_aggregate``.
    """
    kinds = tuple(kinds)
    if not kinds:
        raise ValueError("kinds must be non-empty")
    for k in kinds:
        if k not in AGG_KINDS:
            raise ValueError(f"unknown aggregation '{k}'")
    if fusable.attention is not None:
        if kinds != ("sum",):
            raise ValueError(
                f"attention-fused aggregation requires kinds=('sum',), "
                f"got {kinds}")
        if fusable.src_weight is not None:
            raise ValueError(
                "attention and src_weight are mutually exclusive")
    y = x if fusable.node_input is None else fusable.node_input
    degrees = stats.degrees if stats is not None else None
    out_dtype = y.dtype

    _count_pass()                 # the whole edge phase is one launch
    with _uncounted():
        if _pipeline_uses_kernel():
            return _pipeline_kernel_stats(
                graph, y, fusable, kinds, dataflow, degrees, out_dtype)
        from repro.kernels.mp_pipeline import apply_fusable_phi
        src_weight = fusable.src_weight
        if fusable.attention is not None:
            # the mirror computes the 2-pass softmax weights with the
            # exact op sequence of the unfused model path (bitwise-parity
            # contract); the kernel path above folds the softmax into the
            # sweep instead
            att = fusable.attention
            logits = jax.nn.leaky_relu(
                att.src_logits[graph.senders]
                + att.dst_logits[graph.receivers],
                negative_slope=att.slope)
            src_weight = segment_softmax(
                logits, graph.receivers, graph.n_node_pad,
                edge_mask=graph.edge_mask)
        msg = apply_fusable_phi(
            y, graph.senders, src_weight=src_weight,
            edge_term=fusable.edge_term, bias=fusable.bias,
            activation=fusable.activation).astype(out_dtype)
        inner = dataflow.replace(impl="fused")
        if len(kinds) == 1:
            return {kinds[0]: segment_aggregate(
                msg, graph.receivers, graph.n_node_pad, kind=kinds[0],
                edge_mask=graph.edge_mask, dataflow=inner, degrees=degrees)}
        return segment_multi_aggregate(
            msg, graph.receivers, graph.n_node_pad, kinds=kinds,
            edge_mask=graph.edge_mask, dataflow=inner, degrees=degrees)


def _pipeline_kernel_stats(graph, y, fusable, kinds, dataflow, degrees,
                           out_dtype) -> Dict[str, Array]:
    """Run mp_pipeline and derive the requested kinds from raw accumulators."""
    from repro.kernels import ops as kops
    from repro.kernels.mp_pipeline import BIG

    want_moments = any(k in ("mean", "var", "std") for k in kinds)
    want = {
        "sum": "sum" in kinds or want_moments,
        "sumsq": any(k in ("var", "std") for k in kinds),
        "max": "max" in kinds,
        "min": "min" in kinds,
        # count doubles as empty-destination validity for max/min when no
        # precomputed degrees are shared
        "count": degrees is None and (want_moments or "max" in kinds
                                      or "min" in kinds),
    }
    att = fusable.attention
    raw = kops.mp_pipeline(
        y, graph.senders, graph.receivers, graph.edge_mask,
        graph.n_node_pad, stats=tuple(s for s, w in want.items() if w),
        src_weight=fusable.src_weight, edge_term=fusable.edge_term,
        bias=fusable.bias, activation=fusable.activation,
        att_src=None if att is None else att.src_logits,
        att_dst=None if att is None else att.dst_logits,
        att_slope=0.2 if att is None else att.slope,
        edge_tile=dataflow.edge_tile, num_banks=dataflow.num_banks)
    deg = degrees if degrees is not None else raw.get("count")
    if deg is not None and deg.ndim == 2:
        deg = deg[:, 0]
    mx, mn = raw.get("max"), raw.get("min")
    # keyed accumulators are finite: empty destinations sit at the ∓BIG
    # neutral and validity comes from the count/degrees stream
    nonempty = None if deg is None else (deg > 0)[:, None]
    return _derive_kinds(
        kinds, s1=raw.get("sum"), s2=raw.get("sumsq"), deg=deg,
        mx=mx, mn=mn,
        mx_valid=None if mx is None else nonempty & (mx > -BIG),
        mn_valid=None if mn is None else nonempty & (mn < BIG),
        out_dtype=out_dtype)


def _derive_kinds(kinds, *, s1, s2, deg, mx, mn, mx_valid, mn_valid,
                  out_dtype) -> Dict[str, Array]:
    """Derive the requested statistics from raw f32 accumulators.

    Shared finalization tail of ``segment_multi_aggregate`` and the
    pipeline kernel path, so the moment algebra (mean/var/std epsilon) and
    the empty-destination neutralization can never diverge between them.
    ``mx_valid``/``mn_valid`` mark destinations whose max/min is real (the
    ±inf paths use isfinite, the keyed kernel uses count/degrees > 0).
    """
    out: Dict[str, Array] = {}
    if any(k in ("mean", "var", "std") for k in kinds):
        rdenom = (1.0 / jnp.maximum(deg, 1.0).astype(jnp.float32))[:, None]
        mean = s1 * rdenom
    if any(k in ("var", "std") for k in kinds):
        var = jnp.maximum(s2 * rdenom - mean * mean, 0.0)
    for k in kinds:
        if k == "sum":
            out[k] = s1.astype(out_dtype)
        elif k == "mean":
            out[k] = mean.astype(out_dtype)
        elif k == "var":
            out[k] = var.astype(out_dtype)
        elif k == "std":
            out[k] = jnp.sqrt(var + 1e-5).astype(out_dtype)
        elif k == "max":
            out[k] = jnp.where(mx_valid, mx, 0.0).astype(out_dtype)
        elif k == "min":
            out[k] = jnp.where(mn_valid, mn, 0.0).astype(out_dtype)
    return out


# ---------------------------------------------------------------------------
# MP unit: segment aggregation over raw COO destinations
# ---------------------------------------------------------------------------

def _masked(msg: Array, edge_mask: Array, kind: str) -> Array:
    fill = _NEUTRAL[kind]
    m = edge_mask[:, None] if msg.ndim == 2 else edge_mask
    if fill == 0.0:
        return jnp.where(m, msg, 0.0)
    return jnp.where(m, msg, fill)


def segment_aggregate(
    msg: Array,
    receivers: Array,
    num_nodes: int,
    *,
    kind: str = "sum",
    edge_mask: Optional[Array] = None,
    dataflow: DataflowConfig = DEFAULT_DATAFLOW,
    degrees: Optional[Array] = None,
) -> Array:
    """Aggregate per-edge messages ``msg`` (E, D) into per-node buffers (N, D).

    Permutation-invariant by construction; works on raw (unsorted) COO.
    """
    if kind not in AGG_KINDS:
        raise ValueError(f"unknown aggregation '{kind}'")
    if edge_mask is None:
        edge_mask = jnp.ones(msg.shape[0], dtype=bool)

    if dataflow.impl in ("kernel", "banked") and kind == "sum":
        _count_pass()
        if dataflow.impl == "kernel":
            from repro.kernels import ops as kops
            return kops.mp_scatter(
                msg, receivers, edge_mask, num_nodes,
                node_tile=dataflow.node_tile,
                edge_tile=dataflow.edge_tile,
                num_banks=dataflow.num_banks,
            )
        return banked_segment_sum(
            msg, receivers, num_nodes,
            num_banks=dataflow.num_banks, edge_mask=edge_mask)

    if dataflow.impl == "kernel":
        # every non-sum kind runs through the multi-statistic kernel so
        # impl='kernel' covers all of AGG_KINDS with one code path.
        return segment_multi_aggregate(
            msg, receivers, num_nodes, kinds=(kind,), edge_mask=edge_mask,
            dataflow=dataflow, degrees=degrees)[kind]

    msgm = _masked(msg, edge_mask, kind)
    if kind == "sum":
        _count_pass()
        return jax.ops.segment_sum(msgm, receivers, num_segments=num_nodes)
    if kind == "max":
        _count_pass()
        out = jax.ops.segment_max(msgm, receivers, num_segments=num_nodes)
        return jnp.where(jnp.isfinite(out), out, 0.0)
    if kind == "min":
        _count_pass()
        out = jax.ops.segment_min(msgm, receivers, num_segments=num_nodes)
        return jnp.where(jnp.isfinite(out), out, 0.0)

    # mean / var / std need on-the-fly degrees (no preprocessing).
    if degrees is None:
        _count_pass()
        degrees = jax.ops.segment_sum(
            edge_mask.astype(msg.dtype), receivers, num_segments=num_nodes)
    denom = jnp.maximum(degrees, 1.0)[:, None]
    _count_pass()
    s1 = jax.ops.segment_sum(msgm, receivers, num_segments=num_nodes)
    mean = s1 / denom
    if kind == "mean":
        return mean
    _count_pass()
    s2 = jax.ops.segment_sum(msgm * msgm, receivers, num_segments=num_nodes)
    var = jnp.maximum(s2 / denom - mean * mean, 0.0)
    if kind == "var":
        return var
    return jnp.sqrt(var + 1e-5)


def segment_multi_aggregate(
    msg: Array,
    receivers: Array,
    num_nodes: int,
    *,
    kinds: Sequence[str],
    edge_mask: Optional[Array] = None,
    dataflow: DataflowConfig = DEFAULT_DATAFLOW,
    degrees: Optional[Array] = None,
) -> Dict[str, Array]:
    """All requested statistics from a single pass over the edge stream.

    The single-pass multi-statistic MP unit (paper Fig. 5 / Eq. 2): instead of
    one edge sweep per aggregation kind, the moment statistics are stacked
    into one widened segment-sum —

        [ msg | msg*msg | 1 ]  --segment_sum-->  [ s1 | s2 | count ]

    — and mean / var / std are derived algebraically (var = s2/n - mean^2,
    std = sqrt(var + 1e-5), degree-0 rows are 0). max / min need a different
    combiner: in the jnp paths they cost one extra sweep each; with
    ``impl='kernel'`` every statistic is accumulated by one Pallas edge-tile
    stream (kernels/mp_scatter.py::mp_scatter_multi), preserving the paper's
    "one stream, many statistics" dataflow exactly.

    Accumulation is float32 regardless of ``msg.dtype``; outputs are cast
    back to ``msg.dtype``. ``degrees`` (masked in-degrees) may be passed in
    to share an already-computed count. Returns ``{kind: (N, D) array}``.
    """
    kinds = tuple(kinds)
    if not kinds:
        raise ValueError("kinds must be non-empty")
    for k in kinds:
        if k not in AGG_KINDS:
            raise ValueError(f"unknown aggregation '{k}'")
    if msg.ndim != 2:
        raise ValueError(
            f"segment_multi_aggregate expects 2-D messages, got {msg.shape}")
    if edge_mask is None:
        edge_mask = jnp.ones(msg.shape[0], dtype=bool)
    out_dtype = msg.dtype

    want_moments = any(k in ("mean", "var", "std") for k in kinds)
    want_sum = "sum" in kinds or want_moments
    want_sumsq = any(k in ("var", "std") for k in kinds)
    want_max = "max" in kinds
    want_min = "min" in kinds
    need_count = want_moments and degrees is None

    s1 = s2 = cnt = mx = mn = None
    if dataflow.impl == "kernel":
        from repro.kernels import ops as kops
        raw = kops.mp_scatter_multi(
            msg, receivers, edge_mask, num_nodes,
            want_sum=want_sum, want_sumsq=want_sumsq, want_count=need_count,
            want_max=want_max, want_min=want_min,
            node_tile=dataflow.node_tile, edge_tile=dataflow.edge_tile,
            num_banks=dataflow.num_banks)
        _count_pass()                      # one edge stream, all statistics
        s1 = raw.get("sum")
        s2 = raw.get("sumsq")
        cnt = raw["count"][:, 0] if need_count else None
        mx = raw.get("max")
        mn = raw.get("min")
    else:
        msgf = msg.astype(jnp.float32)
        if dataflow.impl == "banked":
            # banked mirror routes edges by bank-local index; mask with where
            recv_m = receivers
            msgf = jnp.where(edge_mask[:, None], msgf, 0.0)
        else:
            # divert masked edges to an out-of-range segment: XLA drops
            # out-of-bound scatter updates, which masks without touching the
            # (E, D) messages (cheaper than two full-width `where`s)
            recv_m = jnp.where(edge_mask, receivers, num_nodes)
        parts = []
        if want_sum:
            parts.append(("s1", msgf))
        if want_sumsq:
            parts.append(("s2", msgf * msgf))
        if need_count:
            # two identical count columns keep the stacked width even
            # (odd-width scatters vectorize poorly on CPU)
            parts.append(("cnt", jnp.ones((msg.shape[0], 2), jnp.float32)))
        if parts:
            stacked = (jnp.concatenate([p for _, p in parts], axis=-1)
                       if len(parts) > 1 else parts[0][1])
            if dataflow.impl == "banked":
                agg = banked_segment_sum(
                    stacked, recv_m, num_nodes,
                    num_banks=dataflow.num_banks, edge_mask=edge_mask)
            else:
                agg = jax.ops.segment_sum(
                    stacked, recv_m, num_segments=num_nodes)
            _count_pass()                  # the single moment sweep
            off = 0
            got = {}
            for name, p in parts:
                got[name] = agg[:, off:off + p.shape[-1]]
                off += p.shape[-1]
            s1 = got.get("s1")
            s2 = got.get("s2")
            cnt = got["cnt"][:, 0] if need_count else None
        if want_max:
            _count_pass()
            if dataflow.impl == "banked":
                mx = jax.ops.segment_max(
                    _masked(msgf, edge_mask, "max"), recv_m,
                    num_segments=num_nodes)
            else:
                mx = jax.ops.segment_max(msgf, recv_m,
                                         num_segments=num_nodes)
        if want_min:
            _count_pass()
            if dataflow.impl == "banked":
                mn = jax.ops.segment_min(
                    _masked(msgf, edge_mask, "min"), recv_m,
                    num_segments=num_nodes)
            else:
                mn = jax.ops.segment_min(msgf, recv_m,
                                         num_segments=num_nodes)

    deg = degrees if degrees is not None else cnt
    return _derive_kinds(
        kinds, s1=s1, s2=s2, deg=deg, mx=mx, mn=mn,
        mx_valid=None if mx is None else jnp.isfinite(mx),
        mn_valid=None if mn is None else jnp.isfinite(mn),
        out_dtype=out_dtype)


def banked_segment_sum(
    msg: Array,
    receivers: Array,
    num_nodes: int,
    *,
    num_banks: int,
    edge_mask: Optional[Array] = None,
) -> Array:
    """Pure-jnp mirror of the dest-banked MP-unit layout (kernel oracle).

    Destination nodes are split into ``num_banks`` contiguous banks
    ("MP unit b owns nodes [b*bank, (b+1)*bank)"), exactly the multicast
    ownership rule of Fig. 5. Each bank accumulates only its own edges via a
    dense mask — conflict-free, edge-order independent.

    ``msg`` may be (E, D) or (E,) — 1-D messages (e.g. softmax denominators,
    edge weights) are aggregated per-scalar and returned as (N,).
    """
    if msg.ndim not in (1, 2):
        raise ValueError(
            f"banked_segment_sum expects (E,) or (E, D) messages, got "
            f"shape {msg.shape}")
    squeeze = msg.ndim == 1
    if squeeze:
        msg = msg[:, None]
    if edge_mask is None:
        edge_mask = jnp.ones(msg.shape[0], dtype=bool)
    if num_nodes % num_banks != 0:
        raise ValueError("num_nodes must divide into banks (pad the batch)")
    bank = num_nodes // num_banks
    msgm = jnp.where(edge_mask[:, None], msg, 0.0)

    def one_bank(b):
        local = receivers - b * bank
        own = (local >= 0) & (local < bank) & edge_mask
        local = jnp.clip(local, 0, bank - 1)
        return jax.ops.segment_sum(
            jnp.where(own[:, None], msgm, 0.0), local, num_segments=bank)

    banks = jax.vmap(one_bank)(jnp.arange(num_banks))  # (B, bank, D)
    out = banks.reshape(num_nodes, msg.shape[1])
    return out[:, 0] if squeeze else out


def segment_softmax(
    logits: Array,
    receivers: Array,
    num_nodes: int,
    *,
    edge_mask: Optional[Array] = None,
    dataflow: Optional[DataflowConfig] = None,
) -> Array:
    """Per-destination softmax over incoming edges (GAT attention weights).

    logits: (E,) or (E, H). Returns normalized weights of the same shape.

    With ``dataflow.impl == 'kernel'`` this runs the two-pass streaming
    Pallas kernel (kernels/seg_softmax.py): pass 1 keeps a per-bank running
    max + online-rescaled denominator, pass 2 exp-normalizes each edge tile —
    2 edge sweeps instead of the 3 sweeps (segment_max, segment_sum,
    normalize-with-gathers) the XLA path below issues.
    """
    if edge_mask is None:
        edge_mask = jnp.ones(logits.shape[0], dtype=bool)
    if dataflow is not None and dataflow.impl == "kernel":
        from repro.kernels import ops as kops
        _count_pass(2)
        return kops.seg_softmax(
            logits, receivers, edge_mask, num_nodes,
            edge_tile=dataflow.edge_tile, num_banks=dataflow.num_banks)
    m = edge_mask if logits.ndim == 1 else edge_mask[:, None]
    neg = jnp.where(m, logits, -jnp.inf)
    _count_pass()
    seg_max = jax.ops.segment_max(neg, receivers, num_segments=num_nodes)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = jnp.where(m, logits - seg_max[receivers], -jnp.inf)
    e = jnp.where(m, jnp.exp(shifted), 0.0)
    _count_pass()
    denom = jax.ops.segment_sum(e, receivers, num_segments=num_nodes)
    denom = jnp.maximum(denom, 1e-16)
    _count_pass()
    return e / denom[receivers]


# ---------------------------------------------------------------------------
# The generic NT + MP step (Eq. 2)
# ---------------------------------------------------------------------------

def propagate(
    graph: GraphBatch,
    x: Array,
    *,
    message_fn: Callable[[Array, Array, Array], Array],
    update_fn: Callable[[Array, Array], Array],
    aggregate: Union[str, Sequence[str]] = "sum",
    edge_feat: Optional[Array] = None,
    dataflow: DataflowConfig = DEFAULT_DATAFLOW,
    stats: Optional[PrecomputedGraphStats] = None,
    fusable: Optional[FusableMessage] = None,
    fusable_update: Optional[FusableUpdate] = None,
    edge_output: bool = False,
) -> Union[Array, Tuple[Array, Array]]:
    """One message-passing layer.

    message_fn(x_src, x_dst, e)  -> (E, D)      # phi — scatter phase
    aggregate                    -> A           # gather phase (merged)
    update_fn(x, m)              -> (N, D_out)  # gamma — node transformation

    ``stats`` (see :class:`PrecomputedGraphStats`) shares per-graph degrees
    across layers: degree-normalized kinds (mean/var/std) then skip their
    per-layer count sweep / count columns entirely.

    Multi-kind ``aggregate`` (the PNA path) runs through the single-pass
    multi-statistic MP unit by default (``dataflow.single_pass``): one edge
    sweep for the moment statistics, shared degrees, max/min alongside —
    instead of one full sweep (plus degree/moment side-sweeps) per kind.

    ``fusable`` (see :class:`FusableMessage`) is the pipeline contract:
    with ``impl='pipeline'`` the whole edge phase — gather, phi, every
    statistic — runs as one launch and the (E, D) message matrix never
    materializes (1 edge pass). Without a fusable description the layer
    falls back to the unfused path below, whose gather + phi per-edge
    rewrite costs its own pass over the stream.

    ``impl='twopass'`` mimics the paper's *non-pipelined* baseline (Fig. 4a):
    the full message matrix is forced to materialize (optimization barrier)
    before aggregation. The default fused path lets XLA fuse phi into the
    scatter epilogue — the compiler-level analogue of NT/MP overlap.

    ``fusable_update`` (see :class:`FusableUpdate`) is the layer-fused
    contract: with ``impl='fused_layer'`` and both descriptions present,
    the *whole layer* — gather, phi, aggregation, update MLP — runs as one
    launch on the kernel path (kernels/layer_fused.py) and as one fused
    jnp region (via ``update_fn``, bitwise-equal to the unfused path) on
    the mirror. Layers with only a fusable phi keep the pipeline edge
    phase; layers with neither fall back to the unfused path.

    ``edge_output=True`` is for layers that carry an edge state: then
    ``message_fn`` returns ``(msg, edge_out)`` and ``propagate`` returns
    ``(node_out, edge_out)``, so the per-edge value the message phase
    computed (GatedGCN's pre-activation) comes back from the same sweep
    instead of being recomputed. Such a layer has no fusable form.
    """
    kinds = (aggregate,) if isinstance(aggregate, str) else tuple(aggregate)
    if edge_output and fusable is not None:
        raise ValueError("edge_output has no fusable form: pass "
                         "fusable=None")
    if dataflow.impl in ("pipeline", "fused_layer") and fusable is not None:
        fu = fusable_update
        if (dataflow.impl == "fused_layer" and fu is not None
                and fu.scalers is None and fu.field_wsum is None
                and kinds == ("sum",) and fusable.attention is None
                and fusable.node_input is None and _pipeline_uses_kernel()):
            # the one-launch layer step: NT epilogue inside the kernel
            _count_pass()
            with _uncounted():
                from repro.kernels import ops as kops
                out = kops.layer_fused(
                    x, graph.senders, graph.receivers, graph.edge_mask,
                    graph.n_node_pad, w1=fu.w1, b1=fu.b1,
                    src_weight=fusable.src_weight,
                    edge_term=fusable.edge_term, phi_bias=fusable.bias,
                    phi_activation=fusable.activation,
                    self_coeff=fu.self_coeff, w2=fu.w2, b2=fu.b2,
                    out_activation=fu.out_activation,
                    edge_tile=dataflow.edge_tile,
                    num_banks=dataflow.num_banks)
            return jnp.where(graph.node_mask[:, None], out, 0.0)
        if (dataflow.impl == "fused_layer" and fu is not None
                and fu.scalers is not None and kinds == PNA_STAT_KINDS
                and stats is not None and stats.degrees is not None
                and _pipeline_uses_kernel()):
            # the scaler-contraction one-launch layer step (PNA): the four
            # statistics are derived from the kernel's accumulators and
            # the degree scalers contracted in-register (DESIGN.md §7)
            _count_pass()
            with _uncounted():
                from repro.kernels import ops as kops
                out = kops.layer_fused(
                    x, graph.senders, graph.receivers, graph.edge_mask,
                    graph.n_node_pad, w1=fu.w1, b1=fu.b1,
                    node_input=fusable.node_input,
                    src_weight=fusable.src_weight,
                    edge_term=fusable.edge_term, phi_bias=fusable.bias,
                    phi_activation=fusable.activation,
                    scalers=fu.scalers, degrees=stats.degrees,
                    w2=fu.w2, b2=fu.b2,
                    out_activation=fu.out_activation,
                    edge_tile=dataflow.edge_tile,
                    num_banks=dataflow.num_banks)
            return jnp.where(graph.node_mask[:, None], out, 0.0)
        if (dataflow.impl == "fused_layer" and fu is not None
                and fu.field_wsum is not None and kinds == ("sum", "mean")
                and stats is not None and stats.degrees is not None
                and fusable.node_input is not None
                and _pipeline_uses_kernel()):
            # the directional-field one-launch layer step (DGN): plain and
            # field-weighted message lanes accumulate side by side and the
            # |s1 - x·wsum| combine + update MLP run in the epilogue
            _count_pass()
            with _uncounted():
                from repro.kernels import ops as kops
                out = kops.layer_fused(
                    x, graph.senders, graph.receivers, graph.edge_mask,
                    graph.n_node_pad, w1=fu.w1, b1=fu.b1,
                    node_input=fusable.node_input,
                    src_weight=fusable.src_weight,
                    edge_term=fusable.edge_term, phi_bias=fusable.bias,
                    phi_activation=fusable.activation,
                    field_wsum=fu.field_wsum, degrees=stats.degrees,
                    w2=fu.w2, b2=fu.b2,
                    out_activation=fu.out_activation,
                    edge_tile=dataflow.edge_tile,
                    num_banks=dataflow.num_banks)
            return jnp.where(graph.node_mask[:, None], out, 0.0)
        agg_stats = fused_edge_aggregate(
            graph, x, fusable, kinds=kinds, dataflow=dataflow, stats=stats)
        m = (agg_stats[kinds[0]] if len(kinds) == 1 else
             jnp.concatenate([agg_stats[k] for k in kinds], axis=-1))
        out = update_fn(x, m)
        return jnp.where(graph.node_mask[:, None], out, 0.0)

    ef = graph.edge_feat if edge_feat is None else edge_feat
    src = jnp.take(x, graph.senders, axis=0)
    dst = jnp.take(x, graph.receivers, axis=0)
    msg = message_fn(src, dst, ef)
    if edge_output:
        msg, edge_out = msg
    _count_pass()                 # the gather + phi (E, D) message rewrite

    if dataflow.impl == "twopass":
        msg = jax.lax.optimization_barrier(msg)

    degrees = stats.degrees if stats is not None else None
    if len(kinds) == 1:
        m = segment_aggregate(
            msg, graph.receivers, graph.n_node_pad,
            kind=kinds[0], edge_mask=graph.edge_mask, dataflow=dataflow,
            degrees=degrees)
    elif dataflow.single_pass:
        agg_stats = segment_multi_aggregate(
            msg, graph.receivers, graph.n_node_pad,
            kinds=kinds, edge_mask=graph.edge_mask, dataflow=dataflow,
            degrees=degrees)
        m = jnp.concatenate([agg_stats[k] for k in kinds], axis=-1)
    else:
        # legacy per-kind loop, kept for the Fig. 9 pass-count ablation
        aggs = [
            segment_aggregate(
                msg, graph.receivers, graph.n_node_pad,
                kind=k, edge_mask=graph.edge_mask, dataflow=dataflow,
                degrees=degrees)
            for k in kinds
        ]
        m = jnp.concatenate(aggs, axis=-1)
    out = update_fn(x, m)
    out = jnp.where(graph.node_mask[:, None], out, 0.0)
    return (out, edge_out) if edge_output else out


def global_pool(graph: GraphBatch, x: Array, *, kind: str = "mean",
                stats: Optional[PrecomputedGraphStats] = None) -> Array:
    """Graph-level readout: pool node embeddings per packed graph (G_pad, D).

    ``stats.graph_node_counts`` (when shared) supplies the per-graph node
    counts for the mean, so repeated pools in one forward pass stop
    re-issuing the node-mask segment-sum.
    """
    xm = jnp.where(graph.node_mask[:, None], x, 0.0)
    s = jax.ops.segment_sum(xm, graph.graph_ids, num_segments=graph.n_graph_pad)
    if kind == "sum":
        return s
    if stats is not None and stats.graph_node_counts is not None:
        cnt = stats.graph_node_counts.astype(x.dtype)
    else:
        cnt = jax.ops.segment_sum(
            graph.node_mask.astype(x.dtype), graph.graph_ids,
            num_segments=graph.n_graph_pad)
    return s / jnp.maximum(cnt, 1.0)[:, None]
