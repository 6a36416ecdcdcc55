"""The paper's real-time scenario end to end: consecutive small graphs at
batch size 1, zero preprocessing, workload-agnostic.

Streams two workloads (MolHIV-like molecules and HEP-like kNN point
clouds) through the SAME compiled engine — no recompilation per graph,
graphs processed in raw arrival order — and compares against the dense
Eq.-2 baseline, mirroring the paper's Table V methodology. The final demo
serves two tenants (a saturated bulk queue and a latency-sensitive one)
through the scheduler/executor split (DESIGN.md §5); run it with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to watch the
executor pool spread the load.

Run:  PYTHONPATH=src python examples/gnn_streaming.py [--graphs 50]
"""

import argparse
from pathlib import Path

import jax

from benchmarks.common import time_fn
from repro.core.compile_cache import enable_compile_cache
from repro.core.engine import GraphStreamEngine
from repro.core.graph import build_graph_batch
from repro.core.models import PAPER_GNN_CONFIGS, make_gnn
from repro.core.pyg_ref import DENSE_REFS
from repro.core.scheduler import QueueConfig
from repro.data.graphs import hep_like, molhiv_like


def stream(model_name: str, gen, dataset: str, n: int):
    cfg = PAPER_GNN_CONFIGS[model_name]
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    graphs = list(gen(seed=0, n_graphs=n))
    g0 = graphs[0]

    # dense baseline (what a framework without the sparse engine does)
    gb = build_graph_batch(g0.node_feat, g0.senders, g0.receivers,
                           edge_feat=g0.edge_feat, node_pad=128,
                           edge_pad=1024, node_pos=g0.node_pos)
    dense = jax.jit(lambda p, g: DENSE_REFS[cfg.model](p, g, cfg))
    t_dense = time_fn(dense, params, gb)

    eng = GraphStreamEngine(cfg, params)
    eng.warmup(g0.node_feat, g0.senders, g0.receivers, g0.edge_feat,
               g0.node_pos)
    for g in graphs:
        eng.process(g.node_feat, g.senders, g.receivers, g.edge_feat,
                    g.node_pos)
    s = eng.stats.summary()
    eng.close()
    print(f"[{model_name} | {dataset}] dense={t_dense*1e3:8.2f} ms  "
          f"flowgnn p50={s['p50_ms']:7.2f} ms  p99={s['p99_ms']:7.2f} ms  "
          f"speedup={t_dense*1e3/s['p50_ms']:5.1f}x  "
          f"throughput={s['throughput_gps']:6.1f} graphs/s")


def stream_packed(model_name: str, n: int, max_batch: int = 16):
    """The multi-queue path: async submission, adaptive packing, futures."""
    cfg = PAPER_GNN_CONFIGS[model_name]
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    graphs = list(molhiv_like(seed=0, n_graphs=n))
    with GraphStreamEngine(cfg, params, max_batch=max_batch,
                           max_wait_ms=10.0, eager_flush=False) as eng:
        g0 = graphs[0]
        eng.warmup(g0.node_feat, g0.senders, g0.receivers, g0.edge_feat,
                   g0.node_pos)
        futs = [eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                           g.node_pos) for g in graphs]
        eng.drain(timeout=300)
        preds = [f.result() for f in futs]
        s = eng.stats.summary()
    print(f"[{model_name} | molhiv packed x{max_batch}] "
          f"p50={s['p50_ms']:7.2f} ms  "
          f"mean_batch={s['mean_batch_size']:5.1f}  "
          f"throughput={s['throughput_gps']:6.1f} graphs/s  "
          f"({len(preds)} futures resolved)")


def stream_two_tenants(model_name: str, n: int):
    """Multi-tenant serving: a saturated bulk tenant next to a
    latency-sensitive one, on the same engine (and, with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, the same
    executor pool). Weighted-fair draining keeps the latency queue's tail
    bounded even though its graphs arrive AFTER the whole bulk backlog.
    """
    cfg = PAPER_GNN_CONFIGS[model_name]
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    graphs = list(molhiv_like(seed=0, n_graphs=n))
    queues = [
        QueueConfig("bulk", weight=1.0, max_wait_ms=20.0, max_batch=16),
        QueueConfig("latency", weight=16.0, max_wait_ms=1.0, max_batch=2),
    ]
    with GraphStreamEngine(cfg, params, queues=queues,
                           eager_flush=False) as eng:
        # warm every bucket x per-queue graph_pad x executor up front, so
        # the printed tail latencies measure the WFQ bound, not jit compile
        eng.warmup_all()
        bulk = [eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                           g.node_pos, queue="bulk")
                for g in graphs for _ in range(3)]
        lat = [eng.submit(g.node_feat, g.senders, g.receivers, g.edge_feat,
                          g.node_pos, queue="latency")
               for g in graphs[: max(n // 4, 4)]]
        eng.drain(timeout=600)
        for f in bulk + lat:
            f.result()
        s = eng.stats.summary()
    for q in ("bulk", "latency"):
        sq = s["queues"][q]
        print(f"[{model_name} | tenant={q:8s}] n={int(sq['count']):4d}  "
              f"p50={sq['p50_ms']:8.2f} ms  p90={sq['p90_ms']:8.2f} ms")
    devs = s.get("devices", {})
    if len(devs) > 1:
        served = ", ".join(f"{d}:{int(v['count'])}" for d, v in devs.items())
        print(f"  executor pool ({len(devs)} devices): {served}  "
              f"aggregate={s['aggregate_gps']:.1f} graphs/s")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=30)
    args = ap.parse_args()
    enable_compile_cache(Path(__file__).resolve().parents[1])
    for m in ("gin", "gcn", "gat"):
        stream(m, molhiv_like, "molhiv", args.graphs)
    stream("gin", hep_like, "hep", max(args.graphs // 3, 5))
    stream_packed("gin", max(args.graphs, 32))
    stream_two_tenants("gin", max(args.graphs, 32))
