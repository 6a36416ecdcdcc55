"""The async multi-queue serving engine: futures, packing equivalence,
autotuning, warmup coverage, and honest statistics."""

import json

import jax
import numpy as np
import pytest

from repro.core.engine import GraphStreamEngine, StreamStats
from repro.core.models import PAPER_GNN_CONFIGS, make_gnn
from repro.data.graphs import molhiv_like

MODELS = sorted(PAPER_GNN_CONFIGS)


def small_cfg(name):
    cfg = PAPER_GNN_CONFIGS[name]
    return cfg.replace(num_layers=2, hidden_dim=16,
                       head_mlp=(8,) if cfg.head_mlp else ())


def _make_engine(name, **kw):
    cfg = small_cfg(name)
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return GraphStreamEngine(cfg, params, **kw)


@pytest.mark.parametrize("name", MODELS)
def test_packed_serving_matches_batch1(name):
    """THE acceptance property: per-graph results from packed multi-graph
    serving == batch-size-1 serving, for every model."""
    graphs = list(molhiv_like(seed=3, n_graphs=8))
    args = [(g.node_feat, g.senders, g.receivers, g.edge_feat, g.node_pos)
            for g in graphs]

    with _make_engine(name, max_batch=1) as solo:
        base = [solo.process(*a) for a in args]
    with _make_engine(name, max_batch=4, max_wait_ms=50.0,
                      eager_flush=False) as packed:
        futs = [packed.submit(*a) for a in args]
        packed.drain(timeout=120)
        outs = [f.result(timeout=5) for f in futs]
        assert max(packed.stats.batch_sizes) > 1     # actually packed
    for b, o in zip(base, outs):
        np.testing.assert_allclose(b, o, atol=1e-5, rtol=1e-5)


def test_futures_resolve_per_graph_and_stats_record():
    graphs = list(molhiv_like(seed=0, n_graphs=10))
    with _make_engine("gin", max_batch=4, max_wait_ms=5.0) as eng:
        g0 = graphs[0]
        eng.warmup(g0.node_feat, g0.senders, g0.receivers, g0.edge_feat,
                   g0.node_pos)
        futs = [eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                           g.node_pos) for g in graphs]
        eng.drain(timeout=120)
        outs = [f.result(timeout=5) for f in futs]
        assert all(o.shape == (1,) for o in outs)
        assert len(eng.stats.latencies_s) == 10       # warmup excluded
        assert len(eng.stats.queue_wait_s) == 10
        assert sum(eng.stats.batch_sizes) == 10
        s = eng.stats.summary()
        assert {"p50_ms", "p90_ms", "p99_ms", "queue_wait_mean_ms",
                "device_mean_ms", "throughput_gps",
                "mean_batch_size"} <= set(s.keys())


def test_node_task_unpacks_per_graph_rows():
    cfg = small_cfg("gcn").replace(task="node")
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    graphs = list(molhiv_like(seed=1, n_graphs=4))
    with GraphStreamEngine(cfg, params, max_batch=4,
                           max_wait_ms=50.0, eager_flush=False) as eng:
        futs = [eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                           g.node_pos) for g in graphs]
        eng.drain(timeout=120)
        for g, f in zip(graphs, futs):
            out = f.result(timeout=5)
            assert out.shape == (g.node_feat.shape[0], cfg.out_dim)


def test_submit_rejects_missing_edge_features():
    with _make_engine("gin") as eng:      # gin expects 3-dim edge features
        g = next(molhiv_like(seed=0, n_graphs=1))
        with pytest.raises(ValueError):
            eng.submit(g.node_feat, g.senders, g.receivers, None, g.node_pos)


def test_autotune_picks_and_persists(tmp_path):
    cache = tmp_path / "autotune.json"
    g = next(molhiv_like(seed=0, n_graphs=1))
    with _make_engine("gin", max_batch=1, autotune=True,
                      autotune_cache=str(cache)) as eng:
        eng.process(g.node_feat, g.senders, g.receivers, g.edge_feat,
                    g.node_pos)
        report = eng.autotune_report()
        assert len(report) == 1
        (entry,) = report.values()
        assert entry["source"] == "autotuned"
        assert entry["num_banks"] >= 1 and entry["edge_tile"] >= 8
        assert len(entry["candidates_us"]) >= 2
    saved = json.loads(cache.read_text())
    # schema tag plus one workload-fingerprint section holding one bucket
    sections = {k: v for k, v in saved.items() if k != "__schema__"}
    assert len(sections) == 1
    (section,) = sections.values()
    assert len(section) == 1

    # a fresh engine loads the cache and skips the candidate search
    with _make_engine("gin", max_batch=1, autotune=True,
                      autotune_cache=str(cache)) as eng2:
        eng2.process(g.node_feat, g.senders, g.receivers, g.edge_feat,
                     g.node_pos)
        (entry2,) = eng2.autotune_report().values()
        assert entry2["source"] == "cache"
        assert (entry2["num_banks"], entry2["edge_tile"]) == (
            entry["num_banks"], entry["edge_tile"])


def test_autotune_candidates_include_pipeline_and_cache_roundtrips_impl(
        tmp_path):
    """The candidate set offers the fused gather-phi-scatter pipeline, and
    a cached impl='pipeline' winner survives the JSON round-trip."""
    cache = tmp_path / "autotune.json"
    g = next(molhiv_like(seed=0, n_graphs=1))
    with _make_engine("gin", max_batch=1, autotune=True,
                      autotune_cache=str(cache)) as eng:
        key = (64, 128, 1)
        cands = eng._candidate_dataflows(key)
        assert any(df.impl == "pipeline" for df in cands)
        assert cands[0].impl == eng.dataflow.impl
        eng.process(g.node_feat, g.senders, g.receivers, g.edge_feat,
                    g.node_pos)
        (entry,) = eng.autotune_report().values()
        # the pipeline candidate was timed alongside the (banks, tile) ones
        assert any(name.endswith("_pipeline")
                   for name in entry["candidates_us"])
        base = eng.process(g.node_feat, g.senders, g.receivers, g.edge_feat,
                           g.node_pos)

    # force a pipeline winner into the cache section and reload it
    saved = json.loads(cache.read_text())
    (section,) = (v for k, v in saved.items() if k != "__schema__")
    (bucket_entry,) = section.values()
    bucket_entry["impl"] = "pipeline"
    cache.write_text(json.dumps(saved))
    with _make_engine("gin", max_batch=1, autotune=True,
                      autotune_cache=str(cache)) as eng2:
        out = eng2.process(g.node_feat, g.senders, g.receivers, g.edge_feat,
                           g.node_pos)
        (entry2,) = eng2.autotune_report().values()
        assert entry2["source"] == "cache"
        assert entry2["impl"] == "pipeline"
    np.testing.assert_allclose(base, out, atol=1e-5, rtol=1e-5)


def test_autotune_report_names_failed_candidates(monkeypatch):
    """A candidate the compiler refuses stays out of the race, and its
    reason shows in the report instead of the candidate simply vanishing."""
    g = next(molhiv_like(seed=0, n_graphs=1))
    with _make_engine("gin", max_batch=1, autotune=True) as eng:
        make_run = eng._make_run

        def refusing(df, key):
            if df.impl != "pipeline":
                return make_run(df, key)

            def run(params, graph):
                raise RuntimeError("kernel refused by the compiler")
            return run

        monkeypatch.setattr(eng, "_make_run", refusing)
        out = eng.process(g.node_feat, g.senders, g.receivers, g.edge_feat,
                          g.node_pos)
        assert np.all(np.isfinite(out))
        (entry,) = eng.autotune_report().values()
        (name, reason), = entry["failed"].items()
        assert name.endswith("_pipeline")
        assert reason == "RuntimeError: kernel refused by the compiler"
        assert name not in entry["candidates_us"]
        assert entry["candidates_us"]          # the others were timed


def test_warmup_all_precompiles_configured_buckets():
    with _make_engine("gin", buckets=(32, 64), max_batch=2) as eng:
        keys = eng.warmup_all()
        assert set(keys) == {(32, 64, 2), (64, 128, 2)}
        assert set(eng._compiled) == set(keys)
        assert set(eng.edge_passes) == set(keys)
        # a stream hit on a warmed bucket compiles nothing new
        g = next(molhiv_like(seed=0, n_graphs=1))
        eng.process(g.node_feat, g.senders, g.receivers, g.edge_feat,
                    g.node_pos)
        assert set(eng._compiled) == set(keys)
        assert len(eng.stats.latencies_s) == 1


def test_stream_stats_batch_aware_throughput():
    s = StreamStats(latencies_s=[0.2, 0.2, 0.2, 0.2],
                    queue_wait_s=[0.1, 0.1, 0.1, 0.1],
                    device_s=[0.1], batch_sizes=[4])
    out = s.summary()
    # 4 graphs in one 100 ms device batch -> 40 graphs/s, not 10 batches/s,
    # and not the 20/s the per-graph-latency ratio would claim
    assert out["throughput_gps"] == pytest.approx(40.0)
    assert out["mean_batch_size"] == pytest.approx(4.0)
    assert out["p90_ms"] == pytest.approx(200.0)
    assert out["queue_wait_mean_ms"] == pytest.approx(100.0)


def test_close_rejects_new_work():
    eng = _make_engine("gin")
    g = next(molhiv_like(seed=0, n_graphs=1))
    eng.process(g.node_feat, g.senders, g.receivers, g.edge_feat, g.node_pos)
    eng.close()
    with pytest.raises(RuntimeError):
        eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                   g.node_pos)
