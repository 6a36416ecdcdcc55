"""The serving path's flat input: one int32 buffer per batch, one transfer.

``FlatLayout.pack`` fills the buffer on the host and ``unflatten`` rebuilds
the batch inside the program; the rebuilt ``GraphBatch`` must hold the same
bits as ``PackedBatch.build()`` for every field, and the served outputs
must be bitwise those of the jitted forward on the host-built batch.
"""

import jax
import numpy as np
import pytest

from repro.core.engine import GraphStreamEngine
from repro.core.graph import FlatLayout, GraphBatch
from repro.core.models import PAPER_GNN_CONFIGS, make_gnn
from repro.core.packing import PackedBatch, PackItem
from repro.data.graphs import hep_like, molhiv_like

from conftest import run_with_devices

MODELS = sorted(PAPER_GNN_CONFIGS)


def small_cfg(name):
    cfg = PAPER_GNN_CONFIGS[name]
    return cfg.replace(num_layers=2, hidden_dim=16,
                       head_mlp=(8,) if cfg.head_mlp else ())


def _item(g, edge=True, pos=True) -> PackItem:
    return PackItem(node_feat=g.node_feat, senders=g.senders,
                    receivers=g.receivers,
                    edge_feat=g.edge_feat if edge else None,
                    node_pos=g.node_pos if pos else None)


def _isolated(seed: int, n: int = 3, bare: bool = False) -> PackItem:
    """``n`` nodes and no edges at all: every node has degree 0."""
    r = np.random.default_rng(seed)
    return PackItem(node_feat=r.normal(size=(n, 9)).astype(np.float32),
                    senders=np.zeros(0, np.int32),
                    receivers=np.zeros(0, np.int32),
                    edge_feat=None if bare else np.zeros((0, 3), np.float32),
                    node_pos=(None if bare else
                              r.normal(size=(n, 1)).astype(np.float32)))


def _batch(case: str) -> PackedBatch:
    if case == "trigger":
        g = next(hep_like(seed=1, n_graphs=1))
        return PackedBatch([_item(g, pos=False)], 64, 1024, 1)
    if case == "screen":
        # 24 of 32 graph slots: some without node_pos, some without edge
        # features, one made only of degree-0 nodes
        gs = list(molhiv_like(seed=2, n_graphs=23))
        items = [_item(g, edge=i % 5 != 1, pos=i % 3 != 0)
                 for i, g in enumerate(gs)]
        items.insert(7, _isolated(3))
        return PackedBatch(items, 1024, 2048, 32)
    gs = list(molhiv_like(seed=4, n_graphs=2))
    bare = case == "small_bare"
    items = [_item(gs[0], edge=not bare, pos=not bare),
             _isolated(5, n=2, bare=bare),
             _item(gs[1], edge=not bare, pos=False)]
    return PackedBatch(items, 64, 128, 4)


def _layout(pb: PackedBatch, cfg) -> FlatLayout:
    return FlatLayout(*pb.bucket, cfg.node_feat_dim, cfg.edge_feat_dim,
                      cfg.pos_dim)


def _loop_padding(pb: PackedBatch) -> dict:
    """The padding rules written as a plain loop: the reference for
    ``padding_fields``, which both batch forms share."""
    offs = pb.graph_offsets()
    n, e = int(offs[-1]), sum(it.num_edges for it in pb.items)
    gids = np.zeros(pb.node_pad, np.int32)
    for g in range(pb.num_graphs):
        gids[offs[g]:offs[g + 1]] = g
    gids[n:] = min(pb.num_graphs, pb.graph_pad - 1)
    return {"node_mask": np.arange(pb.node_pad) < n,
            "edge_mask": np.arange(pb.edge_pad) < e, "graph_ids": gids,
            "graph_mask": np.arange(pb.graph_pad) < pb.num_graphs}


CASES = ("trigger", "screen", "small", "small_bare")


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("name", MODELS)
def test_unflatten_is_bitwise_the_host_build(name, case):
    cfg = PAPER_GNN_CONFIGS[name]
    if case == "small_bare":       # a model that takes no edge features
        cfg = cfg.replace(edge_feat_dim=1)
    pb = _batch(case)
    layout = _layout(pb, cfg)
    words = layout.pack(pb.items)
    assert words.dtype == np.int32 and words.shape == (layout.size,)
    got = jax.jit(layout.unflatten)(words)
    want = pb.build(pos_dim=cfg.pos_dim)
    assert pb.num_graphs < pb.graph_pad or case == "trigger"
    for f in GraphBatch.__dataclass_fields__:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert (a.dtype, a.shape) == (b.dtype, b.shape), f
        assert a.tobytes() == b.tobytes(), f
    for f, ref in _loop_padding(pb).items():
        np.testing.assert_array_equal(np.asarray(getattr(got, f)), ref)


def test_pack_writes_a_new_buffer_every_call():
    pb = _batch("small")
    layout = _layout(pb, PAPER_GNN_CONFIGS["gin"])
    a, b = layout.pack(pb.items), layout.pack(pb.items)
    assert a is not b and not np.shares_memory(a, b)
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("field", ["edge_feat", "node_pos"])
def test_wrong_width_fails_the_batch_without_admission(field):
    """With admission off nothing stops a field of the wrong width before
    the pack: the pack must refuse it, not serve zeros or broadcast rows,
    also beside a graph that lacks the field."""
    cfg = small_cfg("gin").replace(pos_dim=2)
    params = make_gnn(cfg).init(jax.random.PRNGKey(0), cfg)
    bad, good = molhiv_like(seed=11, n_graphs=2)
    narrow = {"edge_feat": bad.edge_feat, "node_pos": None}
    narrow[field] = getattr(bad, field)[:, :1]
    assert narrow[field].shape[1] < getattr(cfg, "edge_feat_dim"
                                            if field == "edge_feat"
                                            else "pos_dim")
    layout = FlatLayout(1024, 2048, 2, cfg.node_feat_dim, cfg.edge_feat_dim,
                        cfg.pos_dim)
    with pytest.raises(ValueError):
        layout.pack([PackItem(bad.node_feat, bad.senders, bad.receivers,
                              **narrow), _item(good, pos=False)])
    with GraphStreamEngine(cfg, params, validate_inputs=False, max_batch=2,
                           max_wait_ms=100.0, eager_flush=False) as eng:
        fut = eng.submit(bad.node_feat, bad.senders, bad.receivers, **narrow)
        ok = eng.submit(good.node_feat, good.senders, good.receivers,
                        good.edge_feat)
        with pytest.raises(Exception):
            fut.result(timeout=300)
        assert np.all(np.isfinite(ok.result(timeout=300)))


def _recording_engine(name, **kw):
    cfg = small_cfg(name)
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    eng = GraphStreamEngine(cfg, params, **kw)
    built = []
    build = eng._build_batch

    def record(pb):
        built.append(pb)
        return build(pb)
    eng._build_batch = record
    return eng, model, params, built


@pytest.mark.parametrize("max_batch", [1, 4])
@pytest.mark.parametrize("name", MODELS)
def test_served_outputs_bitwise_equal_jit_apply(name, max_batch):
    graphs = list(molhiv_like(seed=6, n_graphs=8))
    eng, model, params, built = _recording_engine(
        name, max_batch=max_batch, max_wait_ms=100.0, eager_flush=False)
    with eng:
        futs = [eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                           g.node_pos) for g in graphs]
        eng.drain(timeout=300)
        served = {id(f): f.result(timeout=5) for f in futs}
    fwd = jax.jit(lambda p, g: model.apply(p, g, eng.cfg, eng.dataflow))
    assert max(len(pb.items) for pb in built) == max_batch
    checked = 0
    for pb in built:
        out = np.asarray(fwd(params, pb.build(pos_dim=eng.cfg.pos_dim)))
        for it, ref in zip(pb.items, eng._split_outputs(pb, out)):
            got = served[id(it.payload.future)]
            assert got.tobytes() == np.asarray(ref).tobytes()
            checked += 1
    assert checked == len(graphs)


def test_one_transfer_per_batch_of_the_layout_size():
    graphs = list(molhiv_like(seed=8, n_graphs=10))
    eng, _, params, built = _recording_engine(
        "gin", max_batch=4, max_wait_ms=100.0, eager_flush=False)
    with eng:
        eng.warmup_all()                  # warm-up is not serving traffic
        assert eng.stats.h2d_transfers == 0
        built.clear()
        for g in graphs:
            eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                       g.node_pos)
        eng.drain(timeout=300)
        s = eng.stats
        assert s.h2d_transfers == len(s.batch_sizes) == len(built) >= 3
        assert s.h2d_bytes == sum(4 * eng._layout(pb.bucket).size
                                  for pb in built)
        summary = s.summary()
        assert summary["h2d_transfers"] == s.h2d_transfers
        assert summary["h2d_bytes"] == s.h2d_bytes
        # the served program stays one module under its traced name
        ex = eng._executors[0]
        for key, run in ex.compiled.items():
            text = run.lower(ex.params, eng._synthetic_batch(*key)).as_text()
            assert "module @jit_flowgnn_forward" in text


def test_each_batch_input_lives_on_its_executors_device():
    # four dispatch threads count their puts under one lock: a short
    # switch interval makes a lost update likely if the lock were missing
    out = run_with_devices("""
import sys
import jax, numpy as np
from repro.core.engine import GraphStreamEngine
from repro.core.models import PAPER_GNN_CONFIGS, make_gnn
from repro.data.graphs import molhiv_like

cfg = PAPER_GNN_CONFIGS["gin"].replace(num_layers=2, hidden_dim=16)
params = make_gnn(cfg).init(jax.random.PRNGKey(0), cfg)
eng = GraphStreamEngine(cfg, params, max_batch=2, max_wait_ms=50.0)
assert eng.num_devices == 4
sys.setswitchinterval(1e-5)
seen = []
for ex in eng._executors:
    program = ex._program_fn
    def check(e, key, g, program=program):
        seen.append((e.device, g.devices(), g.committed))
        return program(e, key, g)
    ex._program_fn = check
graphs = list(molhiv_like(seed=9, n_graphs=48))
futs = [eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                   g.node_pos) for g in graphs]
eng.drain(timeout=300)
assert all(np.all(np.isfinite(f.result(timeout=5))) for f in futs)
assert all(devs == {dev} and committed for dev, devs, committed in seen)
assert len(seen) == len(eng.stats.batch_sizes) == eng.stats.h2d_transfers
used = {dev for dev, _, _ in seen}
eng.close()
print("DEVICES", len(used))
""", n=4)
    assert int(out.split("DEVICES")[1]) >= 2
