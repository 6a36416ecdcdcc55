"""Admission's fast path (``check_graph`` and ``GraphStreamEngine.submit``).

``check_graph`` tests each index array by one reduction, on its unsigned
view. ``oracle_check_graph`` below is the form it replaced, a min and a
max over each index array, kept here as the reference: on every case
and on a seeded sweep of random graphs both give the same reason, or
both ``None``. The one deliberate difference, ``timedelta64`` indices,
has a test of its own.

``submit`` takes its request id from a counter and admits under one
acquisition of the engine lock: four client threads submitting into one
engine get distinct, dense ids, every future resolves to its own graph's
answer, bitwise, and the pending counts return to zero.
"""

import sys
import threading
from typing import Optional

import jax
import numpy as np
import pytest

from repro.core.engine import GraphStreamEngine
from repro.core.models import PAPER_GNN_CONFIGS, make_gnn
from repro.core.validate import check_graph
from repro.data.graphs import molhiv_like


def _is_int_dtype(a: np.ndarray) -> bool:
    return np.issubdtype(np.asarray(a).dtype, np.integer)


def oracle_check_graph(node_feat, senders, receivers, edge_feat=None,
                       node_pos=None, *, node_feat_dim: Optional[int] = None,
                       edge_feat_dim: Optional[int] = None,
                       pos_dim: Optional[int] = None,
                       require_finite: bool = False) -> Optional[str]:
    """``check_graph`` as it was before the fast path (the reference)."""
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
        if not _is_int_dtype(senders) or not _is_int_dtype(receivers):
            return (f"edge indices must be integers, got "
                    f"{senders.dtype}/{receivers.dtype}")
        lo = min(int(senders.min()), int(receivers.min()))
        hi = max(int(senders.max()), int(receivers.max()))
        if lo < 0 or hi >= n_nodes:
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


DIMS = dict(node_feat_dim=9, edge_feat_dim=3, pos_dim=2)
INDEX_DTYPES = (np.int8, np.int16, np.int32, np.int64,
                np.uint8, np.uint16, np.uint32, np.uint64)


def _graph(n=6, e=8, dtype=np.int32, seed=0):
    """A sound graph: (node_feat, senders, receivers, edge_feat, node_pos)."""
    r = np.random.default_rng(seed)
    return [r.normal(size=(n, 9)).astype(np.float32),
            r.integers(0, n, e).astype(dtype),
            r.integers(0, n, e).astype(dtype),
            r.normal(size=(e, 3)).astype(np.float32),
            r.normal(size=(n, 2)).astype(np.float32)]


def _with(field: int, value, **kw):
    g = _graph(**kw)
    g[field] = value(g) if callable(value) else value
    return g


def _set(field: int, at: int, value):
    def edit(g):
        a = g[field].copy()
        a[at] = value
        return a
    return edit


def _cases():
    cases = {"sound": (_graph(), {})}
    for dt in INDEX_DTYPES:
        name = np.dtype(dt).name
        top = np.iinfo(dt).max
        cases[f"sound-{name}"] = (_graph(dtype=dt), {})
        for field, side in ((1, "sender"), (2, "receiver")):
            cases[f"{side}-equal-n-{name}"] = (
                _with(field, _set(field, 3, 6), dtype=dt), {})
            cases[f"{side}-dtype-max-{name}"] = (
                _with(field, _set(field, 0, top), dtype=dt), {})
            if np.dtype(dt).kind == "i":
                cases[f"{side}-negative-{name}"] = (
                    _with(field, _set(field, 5, -1), dtype=dt), {})
                cases[f"{side}-dtype-min-{name}"] = (
                    _with(field, _set(field, 2, np.iinfo(dt).min),
                          dtype=dt), {})
    # more nodes than a signed byte reaches: a negative int8 index viewed
    # as uint8 (255) is below n, so the bound must come from the width
    for dt in (np.int8, np.uint8):
        name = np.dtype(dt).name
        cases[f"wide-graph-{name}"] = (_graph(n=300, dtype=dt), {})
    cases["wide-graph-negative-int8"] = (
        _with(1, _set(1, 4, -1), n=300, dtype=np.int8), {})
    cases["wide-graph-dtype-min-int8"] = (
        _with(2, _set(2, 0, -128), n=300, dtype=np.int8), {})
    cases["bool-senders"] = (_with(1, lambda g: g[1] > 2), {})
    g = _graph()
    cases["bool-both"] = (g[:1] + [g[1] > 2, g[2] > 2] + g[3:], {})
    cases["float-senders"] = (_with(1, lambda g: g[1].astype(np.float32)),
                              {})
    cases["float-receivers"] = (_with(2, lambda g: g[2].astype(np.float64)),
                                {})
    g = _graph(e=0)
    g[1] = g[2] = np.zeros(0, np.float32)
    cases["float-no-edges"] = (g, {})
    cases["no-edges"] = (_graph(e=0), {})
    wide = np.random.default_rng(2).integers(0, 6, 24)
    for dt in (np.int32, np.int64, np.uint32):
        name = np.dtype(dt).name
        cases[f"strided-sound-{name}"] = (
            _with(1, wide.astype(dt)[::3]), {})
        bad = wide.astype(dt)
        bad[9] = 6
        cases[f"strided-out-of-range-{name}"] = (_with(2, bad[::3]), {})
    neg = wide.astype(np.int64)
    neg[6] = -3
    cases["strided-negative"] = (_with(1, neg[::3]), {})
    cases["reversed-view"] = (_with(1, lambda g: g[1][::-1]), {})
    cases["big-endian"] = (_with(1, lambda g: g[1].astype(">i4")), {})
    cases["big-endian-negative"] = (
        _with(1, lambda g: np.array([-1] + [0] * 7, ">i8")), {})
    cases["2d-senders"] = (_with(1, lambda g: g[1].reshape(2, 4)), {})
    cases["2d-receivers"] = (_with(2, lambda g: g[2].reshape(4, 2)), {})
    cases["unequal-lengths"] = (_with(2, lambda g: g[2][:5]), {})
    cases["zero-nodes"] = (_with(0, np.zeros((0, 9), np.float32)), {})
    cases["1d-node-feat"] = (_with(0, np.zeros(6, np.float32)), {})
    cases["node-width"] = (_with(0, np.zeros((6, 7), np.float32)), {})
    cases["edge-width"] = (_with(3, np.zeros((8, 5), np.float32)), {})
    cases["pos-width"] = (_with(4, np.zeros((6, 3), np.float32)), {})
    cases["edge-rows"] = (_with(3, np.zeros((7, 3), np.float32)), {})
    cases["edge-1d"] = (_with(3, np.zeros(8, np.float32)), {})
    cases["pos-rows"] = (_with(4, np.zeros((5, 2), np.float32)), {})
    cases["no-optional-fields"] = (_graph()[:3] + [None, None], {})
    for field, name in ((0, "node_feat"), (3, "edge_feat"), (4, "node_pos")):
        for bad in (np.nan, np.inf, -np.inf):
            g = _with(field, _set(field, (1, 0), bad))
            cases[f"non-finite-{name}-{bad}"] = (g, {"require_finite": True})
            cases[f"non-finite-{name}-{bad}-unchecked"] = (g, {})
    cases["out-of-range-and-non-finite"] = (
        _with(1, _set(1, 0, 99)), {"require_finite": True})
    cases["lists"] = ([[[0.0] * 9] * 3, [0, 1, 2], [2, 1, 0],
                       [[0.0] * 3] * 3, [[0.0] * 2] * 3], {})
    cases["lists-out-of-range"] = ([[[0.0] * 9] * 3, [0, 1, 3], [2, 1, 0],
                                    None, None], {})
    return cases


CASES = _cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_check_graph_gives_the_oracles_reason(name):
    graph, kw = CASES[name]
    for dims in (DIMS, {}):
        want = oracle_check_graph(*graph, **dims, **kw)
        assert check_graph(*graph, **dims, **kw) == want


def test_cases_cover_accepts_and_each_kind_of_refusal():
    """The table above is not all one branch: it holds sound graphs and
    each reason ``check_graph`` gives."""
    reasons = {oracle_check_graph(*g, **DIMS, **kw)
               for g, kw in CASES.values()}
    assert None in reasons
    for start in ("node_feat must be 2-D", "graph has zero nodes",
                  "node_feat width", "senders/receivers must be 1-D",
                  "senders (", "edge indices must be integers",
                  "edge index out of range", "edge_feat must be",
                  "edge_feat width", "node_pos must be", "node_pos width",
                  "node_feat contains", "edge_feat contains",
                  "node_pos contains"):
        assert any(r is not None and r.startswith(start) for r in reasons), \
            start


def test_check_graph_matches_the_oracle_on_random_graphs():
    """1,200 seeded graphs of random size, index dtype, range and layout;
    indices are drawn from a little below 0 to a little above n, clipped
    to what the dtype holds, so both sides of each bound are hit."""
    r = np.random.default_rng(20261020)
    dtypes = INDEX_DTYPES + (np.bool_, np.float32, np.float64)
    outcomes = {"accepted": 0, "refused": 0}
    for _ in range(1200):
        n = int(r.choice([1, 2, 5, 30, 127, 128, 129, 200, 255, 256, 300]))
        e = int(r.integers(0, 40))
        dt = np.dtype(dtypes[r.integers(len(dtypes))])
        lo, hi = -3, n + 2
        if dt.kind in "iu":
            lo, hi = max(lo, np.iinfo(dt).min), min(hi, np.iinfo(dt).max)
        stride = int(r.choice([1, 1, 2, 3]))
        idx = []
        for _ in range(2):
            v = r.integers(lo, hi + 1, e * stride)
            if r.random() < 0.5:
                v = np.clip(v, 0, n - 1)            # mostly in range
            idx.append(v.astype(dt)[::stride])
        graph = [r.normal(size=(n, 9)).astype(np.float32), *idx,
                 r.normal(size=(e, 3)).astype(np.float32), None]
        if r.random() < 0.05:
            graph[3][..., 0] = np.nan
        kw = {"require_finite": bool(r.random() < 0.5)}
        want = oracle_check_graph(*graph, **DIMS, **kw)
        assert check_graph(*graph, **DIMS, **kw) == want, (n, e, dt, stride)
        outcomes["accepted" if want is None else "refused"] += 1
    assert min(outcomes.values()) > 200, outcomes


def test_timedelta_indices_are_refused():
    """The one difference from the oracle: ``np.issubdtype`` counts
    ``timedelta64`` as an integer and admitted it; admission now asks
    for a signed or unsigned integer dtype."""
    g = _graph()
    td = g[1].astype("m8[ns]")
    assert oracle_check_graph(g[0], td, g[2]) is None
    assert check_graph(g[0], td, g[2]) == (
        "edge indices must be integers, got timedelta64[ns]/int32")


def _small_gin():
    cfg = PAPER_GNN_CONFIGS["gin"]
    cfg = cfg.replace(num_layers=2, hidden_dim=16,
                      head_mlp=(8,) if cfg.head_mlp else ())
    return cfg, make_gnn(cfg).init(jax.random.PRNGKey(0), cfg)


def _serve(cfg, params, graphs, threads: int):
    """Every graph through one engine from ``threads`` client threads
    (thread k submits graphs k, k + threads, ...). Returns each graph's
    answer, the request ids the scheduler received, and the engine."""
    # one graph a batch: each answer comes from a program that holds that
    # graph alone, whatever the threads' interleaving, so answers compare
    # bitwise; a cap of 32 makes the clients wait at backpressure too
    eng = GraphStreamEngine(cfg, params, max_batch=1, max_pending=32)
    ids = []
    add = eng._scheduler.add

    def recording_add(queue, item, now=None):
        ids.append(item.payload.req_id)        # under the engine lock
        return add(queue, item, now=now)

    eng._scheduler.add = recording_add
    futs = [None] * len(graphs)
    errors = []

    def client(k):
        try:
            for i in range(k, len(graphs), threads):
                g = graphs[i]
                futs[i] = eng.submit(g.node_feat, g.senders, g.receivers,
                                     g.edge_feat)
        except Exception as exc:               # reported by the test
            errors.append(exc)

    workers = [threading.Thread(target=client, args=(k,))
               for k in range(threads)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=300)
    finally:
        sys.setswitchinterval(switch)
    assert not any(w.is_alive() for w in workers)
    assert not errors, errors
    outs = [np.asarray(f.result(timeout=300)) for f in futs]
    return outs, ids, eng


def test_concurrent_submits_get_dense_ids_and_their_own_answers():
    cfg, params = _small_gin()
    graphs = list(molhiv_like(seed=5, n_graphs=800))
    want, _, eng1 = _serve(cfg, params, graphs, threads=1)
    eng1.close(timeout=60)
    outs, ids, eng = _serve(cfg, params, graphs, threads=4)
    try:
        assert sorted(ids) == list(range(800))
        eng.drain(timeout=60)
        assert eng._pending == 0
        assert set(eng._pending_by_queue.values()) == {0}
        assert not eng._requests
    finally:
        eng.close(timeout=60)
    for i, (o, w) in enumerate(zip(outs, want)):
        np.testing.assert_array_equal(o, w, err_msg=f"graph {i}")
