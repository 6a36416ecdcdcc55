"""GraphGPS (GatedGCN + per-graph Transformer, RWSE) on the CPU at a small
size: 2 layers, hidden 32, 4 heads of 8, slot 16, 4 random-walk steps.

Tolerances: the program and ``gps_dense`` compute the same float32
arithmetic in another order (segment sums against one-hot matmuls, slot
attention against dense attention, folded against explicit BatchNorm),
and the answers are O(10), so they agree to a few 1e-6 absolute; 1e-4
absolute and relative leaves room for that order and nothing more.
RWSE is exact rational arithmetic rounded in float32: 1e-6.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.message_passing as mp
from repro.core.engine import GraphStreamEngine
from repro.core.errors import InvalidRequest
from repro.core.graph import build_graph_batch, concat_raw_graphs
from repro.core.message_passing import DataflowConfig, precompute_graph_stats
from repro.core.models import (GNNConfig, attention_slots, make_gnn,
                               random_walk_se)
from repro.core.pyg_ref import gps_dense
from repro.data.graphs import sized_stream

CFG = GNNConfig(model="gps", num_layers=2, hidden_dim=32, heads=4,
                head_dim=8, ffn_dim=64, pe_steps=4, pe_dim=8,
                attention_slot=16, out_dim=3)
TOL = dict(atol=1e-4, rtol=1e-4)


def small_graphs(n, seed=0):
    """Molecule-shaped graphs of 4..16 nodes: all fit the slot of 16."""
    out = [g for g in sized_stream(seed=seed, n_graphs=4 * n, n_mean=10.0,
                                   n_std=3.0)
           if g.node_feat.shape[0] <= CFG.attention_slot]
    return out[:n]


def params(seed=0):
    return make_gnn(CFG).init(jax.random.PRNGKey(seed), CFG)


def alone(p, g):
    b = build_graph_batch(g.node_feat, g.senders, g.receivers,
                          edge_feat=g.edge_feat, node_pad=32, edge_pad=64)
    return np.asarray(gps_dense(p, b, CFG))[0]


def args(g):
    return g.node_feat, g.senders, g.receivers, g.edge_feat


@pytest.mark.parametrize("max_batch", [1, 4])
def test_engine_serves_the_dense_reference(max_batch):
    p, graphs = params(), small_graphs(8)
    with GraphStreamEngine(CFG, p, max_batch=max_batch, max_wait_ms=50.0,
                           eager_flush=False, buckets=(32, 64, 128)) as eng:
        futs = [eng.submit(*args(g)) for g in graphs]
        eng.drain(timeout=120)
        outs = [f.result(timeout=5) for f in futs]
        assert max(eng.stats.batch_sizes) == max_batch
    for g, o in zip(graphs, outs):
        np.testing.assert_allclose(o, alone(p, g), **TOL)


def test_empty_graph_slots_are_finite_and_equal_graphs_alone():
    p, graphs = params(1), small_graphs(3, seed=1)
    b = build_graph_batch(**concat_raw_graphs(graphs), node_pad=64,
                          edge_pad=128, graph_pad=8)
    out = np.asarray(make_gnn(CFG).apply(p, b, CFG))
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[3:], 0.0)
    for i, g in enumerate(graphs):
        np.testing.assert_allclose(out[i], alone(p, g), **TOL)


def test_node_permutation_within_a_graph_leaves_the_answer():
    p, g = params(2), small_graphs(1, seed=2)[0]
    n = g.node_feat.shape[0]
    perm = np.random.default_rng(0).permutation(n)   # new label of node i
    inv = np.argsort(perm)
    b0 = build_graph_batch(g.node_feat, g.senders, g.receivers,
                           edge_feat=g.edge_feat, node_pad=32, edge_pad=64)
    b1 = build_graph_batch(g.node_feat[inv], perm[g.senders],
                           perm[g.receivers], edge_feat=g.edge_feat,
                           node_pad=32, edge_pad=64)
    model = make_gnn(CFG)
    np.testing.assert_allclose(model.apply(p, b1, CFG),
                               model.apply(p, b0, CFG), **TOL)


def _undirected(pairs):
    a = np.array(pairs, np.int32)
    return np.concatenate([a[:, 0], a[:, 1]]), np.concatenate([a[:, 1], a[:, 0]])


def _numpy_rwse(n, snd, rcv, steps):
    a = np.zeros((n, n))
    np.add.at(a, (snd, rcv), 1.0)
    deg = a.sum(1, keepdims=True)
    walk = a / np.where(deg > 0, deg, 1.0)
    return np.stack([np.diag(np.linalg.matrix_power(walk, k))
                     for k in range(1, steps + 1)], -1)


@pytest.mark.parametrize("shape", ["path", "even_cycle", "star"])
def test_rwse_is_the_random_walks_return_probabilities(shape):
    n, steps = 6, 8
    pairs = {"path": [(i, i + 1) for i in range(n - 1)],
             "even_cycle": [(i, (i + 1) % n) for i in range(n)],
             "star": [(0, i) for i in range(1, n)]}[shape]
    snd, rcv = _undirected(pairs)
    # a second graph and an isolated node behind it: slots stay apart
    b = build_graph_batch(np.zeros((n + 3, 1), np.float32),
                          np.concatenate([snd, [n, n + 1]]),
                          np.concatenate([rcv, [n + 1, n]]),
                          node_pad=16, edge_pad=32, graph_pad=4,
                          graph_offsets=np.array([0, n, n + 3]))
    stats = precompute_graph_stats(b, with_degrees=False,
                                   with_graph_counts=True)
    pe = np.asarray(random_walk_se(
        b, attention_slots(b, stats.graph_node_counts, 8), steps))
    want = _numpy_rwse(n, snd, rcv, steps)
    np.testing.assert_allclose(pe[:n], want, atol=1e-6)
    if shape == "even_cycle":
        np.testing.assert_array_equal(pe[:n, 0::2], 0.0)   # odd steps
    np.testing.assert_allclose(pe[n:n + 2], [[0, 1] * 4] * 2, atol=1e-6)
    np.testing.assert_array_equal(pe[n + 2:], 0.0)         # isolated, padding


def test_graph_above_the_slot_is_refused():
    rng = np.random.default_rng(0)
    n = CFG.attention_slot + 1
    snd, rcv = _undirected([(i, i + 1) for i in range(n - 1)])
    ef = rng.normal(size=(snd.shape[0], 3)).astype(np.float32)
    with GraphStreamEngine(CFG, params(), validate_inputs=False) as eng:
        with pytest.raises(InvalidRequest, match="attention_slot"):
            eng.submit(rng.normal(size=(n, 9)).astype(np.float32), snd, rcv,
                       ef)
        assert eng.stats.invalid_rejects == 1


def test_gps_needs_an_attention_slot():
    cfg = GNNConfig(model="gps", hidden_dim=32, heads=4, head_dim=8,
                    pe_dim=8)
    assert cfg.attention_slot is None
    with pytest.raises(ValueError, match="attention_slot"):
        make_gnn(cfg).init(jax.random.PRNGKey(0), cfg)


def test_graph_above_the_slot_applied_directly_answers_nan():
    snd, rcv = _undirected([(i, i + 1) for i in range(19)])
    b = build_graph_batch(np.ones((20, 9), np.float32), snd, rcv,
                          edge_feat=np.ones((38, 3), np.float32),
                          node_pad=32, edge_pad=64)
    assert np.all(np.isnan(make_gnn(CFG).apply(params(), b, CFG)))


@pytest.mark.parametrize("impl", ["kernel", "pipeline", "fused_layer"])
def test_forced_pallas_impl_serves_the_reference(impl):
    """gps declares no FusableMessage: the pipeline impls fall back to the
    XLA edge phase, and ``kernel`` sums the gated messages with the
    scatter kernel (interpret mode here); each serves the same answers."""
    p, graphs = params(3), small_graphs(4, seed=3)
    mp._FORCE_PIPELINE_KERNEL = True
    try:
        with GraphStreamEngine(CFG, p, dataflow=DataflowConfig(impl=impl),
                               max_batch=4, max_wait_ms=50.0,
                               eager_flush=False, buckets=(64,)) as eng:
            futs = [eng.submit(*args(g)) for g in graphs]
            eng.drain(timeout=120)
            outs = [f.result(timeout=5) for f in futs]
    finally:
        mp._FORCE_PIPELINE_KERNEL = False
    for g, o in zip(graphs, outs):
        np.testing.assert_allclose(o, alone(p, g), **TOL)


def test_lowered_forward_names_the_gps_blocks():
    b = build_graph_batch(**concat_raw_graphs(small_graphs(2)), node_pad=32,
                          edge_pad=64, graph_pad=2)
    fwd = jax.jit(lambda p, g: make_gnn(CFG).apply(p, g, CFG))
    text = fwd.lower(params(), b).as_text(debug_info=True)
    for scope in ("gps.rwse", "gps.local", "gps.attention", "gps.ffn"):
        assert scope in text, scope


def test_propagate_hands_back_the_edge_output():
    g = small_graphs(1)[0]
    b = build_graph_batch(g.node_feat, g.senders, g.receivers,
                          edge_feat=g.edge_feat, node_pad=32, edge_pad=64)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(32, 4)).astype(np.float32))
    out, e_out = mp.propagate(
        b, x, message_fn=lambda s, d, e: (s, s - d), update_fn=lambda x, m: m,
        aggregate="sum", edge_output=True)
    np.testing.assert_allclose(e_out, x[b.senders] - x[b.receivers])
    want = np.zeros((32, 4), np.float32)
    np.add.at(want, np.asarray(b.receivers)[np.asarray(b.edge_mask)],
              np.asarray(x)[np.asarray(b.senders)[np.asarray(b.edge_mask)]])
    np.testing.assert_allclose(out, want, atol=1e-6)
    with pytest.raises(ValueError, match="edge_output"):
        mp.propagate(b, x, message_fn=None, update_fn=None,
                     edge_output=True, fusable=mp.FusableMessage())
