"""Admission validation: cheap vectorized checks on arriving graphs.

Defense layer 1 of the serving stack (DESIGN.md §9). The paper's workload
is *untrusted by construction* — raw COO edge lists straight off the wire
with zero preprocessing — so a malformed graph must be rejected at
``GraphStreamEngine.submit``, before it is packed next to healthy
neighbors. Past admission, an out-of-range edge index is undefined
behavior inside the jit'd scatter (XLA clamps or drops silently — wrong
answers, not errors), and a NaN feature poisons every co-packed graph's
aggregation until the output-validation gate quarantines the wrong
victims. Catching both here costs a handful of vectorized numpy
reductions per arrival (~microseconds, off the device path) and converts
"my whole batch failed" into ``InvalidGraph`` on exactly the bad request.

``check_graph`` returns a reason string (``None`` = admissible) so the
engine can attach its request id; ``validate_graph`` is the raising form
for callers outside the engine (benches, data loaders).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.errors import InvalidGraph


def _indices_below(idx: np.ndarray, n_nodes: int) -> bool:
    """Whether every entry of the non-empty integer array ``idx`` lies in
    ``[0, n_nodes)``, by one reduction: viewed as the unsigned type of its
    width a negative entry reads ``>= 2**(bits - 1)``, which no signed
    entry in range reaches."""
    dt = idx.dtype
    if dt.kind == "i":
        n_nodes = min(n_nodes, 1 << (8 * dt.itemsize - 1))
        idx = idx.view(dt.str.replace("i", "u"))
    return int(np.maximum.reduce(idx)) < n_nodes


def check_graph(node_feat, senders, receivers, edge_feat=None,
                node_pos=None, *, node_feat_dim: Optional[int] = None,
                edge_feat_dim: Optional[int] = None,
                pos_dim: Optional[int] = None,
                require_finite: bool = False) -> Optional[str]:
    """Why this raw COO graph is inadmissible, or ``None`` if it is fine.

    Checks, in order of how badly the failure would corrupt a packed
    batch downstream:

    * shapes: ``node_feat`` is a non-empty 2-D array; ``senders`` /
      ``receivers`` are 1-D and the same length (zero edges is legal —
      an isolated node is a real molecule);
    * index dtypes: signed or unsigned integer (a float edge list
      silently truncates; ``bool`` and ``timedelta64`` are refused too);
    * index range: every sender/receiver in ``[0, n_nodes)`` — the check
      that prevents cross-graph reads after packing offsets are applied.
      An admissible graph pays one reduction per index array; the range
      the message quotes is computed only for a graph that fails it;
    * feature widths vs the model config (``node_feat_dim`` /
      ``edge_feat_dim`` / ``pos_dim`` — pass ``None`` to skip one);
      ``edge_feat`` rows must match the edge count;
    * ``require_finite``: no NaN/Inf in any float payload (opt-in knob:
      some models legitimately embed sentinel infinities upstream).
    """
    node_feat = np.asarray(node_feat)
    if node_feat.ndim != 2:
        return f"node_feat must be 2-D (nodes x features), got " \
               f"shape {node_feat.shape}"
    n_nodes = node_feat.shape[0]
    if n_nodes == 0:
        return "graph has zero nodes"
    if node_feat_dim is not None and node_feat.shape[1] != node_feat_dim:
        return (f"node_feat width {node_feat.shape[1]} != model's "
                f"node_feat_dim {node_feat_dim}")

    senders = np.asarray(senders)
    receivers = np.asarray(receivers)
    if senders.ndim != 1 or receivers.ndim != 1:
        return "senders/receivers must be 1-D edge index arrays"
    if senders.shape[0] != receivers.shape[0]:
        return (f"senders ({senders.shape[0]}) and receivers "
                f"({receivers.shape[0]}) disagree on the edge count")
    if senders.size:
        if (senders.dtype.kind not in "iu"
                or receivers.dtype.kind not in "iu"):
            return (f"edge indices must be integers, got "
                    f"{senders.dtype}/{receivers.dtype}")
        if not (_indices_below(senders, n_nodes)
                and _indices_below(receivers, n_nodes)):
            lo = min(int(senders.min()), int(receivers.min()))
            hi = max(int(senders.max()), int(receivers.max()))
            return (f"edge index out of range: [{lo}, {hi}] not within "
                    f"[0, {n_nodes})")

    n_edges = senders.shape[0]
    if edge_feat is not None:
        edge_feat = np.asarray(edge_feat)
        if edge_feat.ndim != 2 or edge_feat.shape[0] != n_edges:
            return (f"edge_feat must be ({n_edges}, D), got "
                    f"shape {edge_feat.shape}")
        if edge_feat_dim is not None and edge_feat.shape[1] != edge_feat_dim:
            return (f"edge_feat width {edge_feat.shape[1]} != model's "
                    f"edge_feat_dim {edge_feat_dim}")
    if node_pos is not None:
        node_pos = np.asarray(node_pos)
        if node_pos.ndim != 2 or node_pos.shape[0] != n_nodes:
            return (f"node_pos must be ({n_nodes}, P), got "
                    f"shape {node_pos.shape}")
        if pos_dim is not None and node_pos.shape[1] != pos_dim:
            return (f"node_pos width {node_pos.shape[1]} != model's "
                    f"pos_dim {pos_dim}")

    if require_finite:
        for name, arr in (("node_feat", node_feat), ("edge_feat", edge_feat),
                          ("node_pos", node_pos)):
            if arr is not None and not bool(np.all(np.isfinite(arr))):
                return f"{name} contains non-finite values"
    return None


def check_budget(num_nodes: int, num_edges: int, *,
                 node_budget: Optional[int] = None,
                 edge_budget: Optional[int] = None,
                 wide_enabled: bool = False) -> Optional[str]:
    """Why this graph exceeds the single-device serving budget, or ``None``.

    The budget is the largest compiled bucket one executor serves
    (``max(GraphStreamEngine.buckets)`` node slots, plus an optional edge
    bound). A graph over budget is *admissible only under wide placement*;
    with wide disabled the engine raises :class:`GraphTooLarge` from the
    reason returned here, naming the enabling knob so the caller knows the
    graph is servable, just not on one device.
    """
    if node_budget is not None and num_nodes > node_budget:
        return (f"graph has {num_nodes} nodes > largest single-device "
                f"bucket {node_budget}"
                + ("" if wide_enabled else
                   " and wide placement is disabled (wide=True splits it "
                   "across the executor pool)"))
    if edge_budget is not None and num_edges > edge_budget:
        return (f"graph has {num_edges} edges > single-device edge "
                f"budget {edge_budget}"
                + ("" if wide_enabled else
                   " and wide placement is disabled (wide=True splits it "
                   "across the executor pool)"))
    return None


def check_graph_nodes(num_nodes: int, limit: Optional[int]) -> Optional[str]:
    """Why this graph has more nodes than its model serves in one graph,
    or ``None``. A model that attends within a slot of ``attention_slot``
    rows per graph (GPS) refuses a larger graph rather than serve it
    wrong; no bucket or wide split can hold it."""
    if limit is not None and num_nodes > limit:
        return (f"graph has {num_nodes} nodes > the model's limit of "
                f"{limit} nodes per graph (attention_slot)")
    return None


def validate_graph(node_feat, senders, receivers, edge_feat=None,
                   node_pos=None, *, node_feat_dim: Optional[int] = None,
                   edge_feat_dim: Optional[int] = None,
                   pos_dim: Optional[int] = None,
                   require_finite: bool = False) -> None:
    """Raise ``InvalidGraph`` when :func:`check_graph` finds a reason."""
    reason = check_graph(node_feat, senders, receivers, edge_feat, node_pos,
                         node_feat_dim=node_feat_dim,
                         edge_feat_dim=edge_feat_dim, pos_dim=pos_dim,
                         require_finite=require_finite)
    if reason is not None:
        raise InvalidGraph(reason)
