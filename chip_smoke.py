"""Chip smoke test: the FlowGNN serving path end to end on a TPU.

One chip (the default): serves the paper's six GNNs at their published
widths (``PAPER_GNN_CONFIGS``: GCN, GIN, GIN-VN at 5x100, GAT at 5 layers of
4x16 heads, PNA at 4x80, DGN at 4x100) through ``GraphStreamEngine.submit``,
once under the default dataflow and once under ``impl="fused_layer"`` (the
one-launch Pallas layer kernels). Traffic per run: 64 molecule graphs
submitted together (packed into batches of 8) and 8 HEP trigger graphs at
batch 1. Every output is checked against the dense f32 references
(``DENSE_REFS``) evaluated on the host CPU.

Four chips (``--chips 4``): only the paths that exist across chips — the
six-model molecule stream on a 4-executor pool against a 1-executor
engine, and the wide gang (one oversized graph split over 4 chips)
against the single-device forward.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four chips

It exits non-zero, and prints no result, when JAX finds no TPU. The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Default-precision f32 matmuls on the TPU (XLA's ``x @ w`` and the update
# MLP inside the fused-layer kernel) round their operands to bf16, unit
# roundoff 2^-9. Rounding the dense layers' operands to bf16 in a CPU
# emulation of these six models on this traffic gives errors up to 1.3e-2
# of the output scale (PNA); 5e-2 leaves 4x margin above that. Error is
# max|served - ref| / max|ref| per (model, dataflow): GIN outputs reach
# ~2e4 and GIN-VN ~1e11 with random weights, so a per-element relative
# error would be dominated by outputs near zero.
REF_TOL = 5e-2

# Wide gang vs the single-device forward: both sides run the same op
# sequence with the same bf16 operand rounding, so they differ only where
# XLA orders f32 accumulation differently for a shard's rows than for the
# whole graph's — 1e-7 relative per op, lifted to a bf16 step only for the
# rare element that sits on a rounding boundary. Outputs are per node, so
# a wrong halo row moves its neighbours' outputs by the order of their
# own scale rather than being diluted by a mean over the graph.
WIDE_TOL = 1e-3

# Edge sweeps per forward under impl="fused_layer": one launch per layer,
# plus the structure sweeps hoisted out of the layer loop (the in-degree
# sweep behind GCN's norm, PNA's scalers and DGN's mean; DGN's two
# directional-field sweeps).
HOISTED_SWEEPS = {"gcn": 1, "gin": 0, "gin_vn": 0, "gat": 0, "pna": 1,
                  "dgn": 3}

# Zero on a clean run: anything else means a fallback absorbed a failure.
CLEAN_STATS = ("failed", "retries", "quarantined", "breaker_trips",
               "executor_deaths", "audit_mismatches")


class SmokeFailure(RuntimeError):
    """A smoke check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def tpu_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform: "
              f"{devices[0].platform})", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"chip_smoke: {chips} chips asked, JAX sees {len(devices)}",
              file=sys.stderr)
        return None
    return devices


# ---------------------------------------------------------------------------
# traffic and references
# ---------------------------------------------------------------------------

def traffic(seed: int):
    from repro.data.graphs import hep_like, molhiv_like
    return (list(molhiv_like(seed=seed, n_graphs=64)),
            list(hep_like(seed=seed + 2, n_graphs=8)))


def dense_refs(name, cfg, params, graphs, cpu):
    """Each graph alone through the dense oracle, f32 on the CPU backend."""
    import jax
    from repro.core.graph import build_graph_batch, pad_bucket
    from repro.core.pyg_ref import DENSE_REFS

    fn = jax.jit(lambda p, b: DENSE_REFS[name](p, b, cfg))
    p_cpu = jax.device_put(params, cpu)
    outs = []
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        for g in graphs:
            b = build_graph_batch(
                g.node_feat, g.senders, g.receivers, edge_feat=g.edge_feat,
                node_pad=pad_bucket(g.node_feat.shape[0]),
                edge_pad=pad_bucket(g.senders.shape[0]), node_pos=g.node_pos)
            outs.append(np.asarray(fn(p_cpu, b))[0])
    return outs


def scaled_error(got, want):
    """(max abs error, max abs error over the largest |want|)."""
    err = max(float(np.abs(np.asarray(a) - b).max())
              for a, b in zip(got, want))
    scale = max(float(np.abs(b).max()) for b in want)
    return err, err / max(scale, 1e-30)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_engine(cfg, params, df, devices, **kw):
    from repro.core.engine import GraphStreamEngine
    from repro.core.scheduler import QueueConfig
    # molecules flush on count (full batches of 8, not timing-dependent
    # partial ones); the trigger queue flushes every graph on arrival
    queues = (QueueConfig("molecules", max_batch=8, max_wait_ms=1000.0),
              QueueConfig("trigger", max_batch=1, max_wait_ms=0.0))
    return GraphStreamEngine(cfg, params, dataflow=df, devices=devices,
                             queues=queues, eager_flush=False, **kw)


def serve(engine, mols, heps, record=True):
    """Submit the molecules together, then the trigger graphs one by one."""
    def args(g):
        return g.node_feat, g.senders, g.receivers, g.edge_feat, g.node_pos

    futs = [engine.submit(*args(g), queue="molecules", record=record)
            for g in mols]
    outs_h = [engine.submit(*args(g), queue="trigger",
                            record=record).result(timeout=600)
              for g in heps]
    engine.drain(timeout=600)
    return [f.result(timeout=60) for f in futs] + outs_h


def check_stats(engine, label):
    st = engine.stats
    for f in CLEAN_STATS:
        check(getattr(st, f) == 0, f"{label}: stats.{f} = {getattr(st, f)}")


def check_clean(engine, df, label):
    check_stats(engine, label)
    served = set(engine._served_impl.values())
    check(served == {df.impl},
          f"{label}: served impls {served}, asked for {df.impl!r}")
    for key, entry in engine.autotune_report().items():
        check(entry["source"] == "default" and "failed" not in entry,
              f"{label}: bucket {key} not on the asked dataflow: {entry}")


def check_edge_passes(engine, cfg, label):
    """One edge pass per layer, plus the hoisted structure sweeps."""
    want = cfg.num_layers + HOISTED_SWEEPS[cfg.model]
    for key in engine._compiled:
        passes = engine.edge_passes[key]
        check(passes == want,
              f"{label}: bucket {key} makes {passes} edge passes, "
              f"expected {want}")


def check_custom_call(engine, label):
    """Every served program holds a compiled Pallas (Mosaic) kernel."""
    ex = engine._executors[0]
    for key, run in engine._compiled.items():
        text = run.lower(ex.params, engine._synthetic_batch(*key)) \
            .compile().as_text()
        check("tpu_custom_call" in text,
              f"{label}: bucket {key} compiled without a Pallas kernel")


def gather_probe():
    """Is the kernels' one-hot gather exact on the MXU? Every destination
    gets exactly one edge, so the pipeline's max statistic must hand back
    the gathered source rows bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    n, d = 1024, 100
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    snd = rng.permutation(n).astype(np.int32)
    rcv = np.arange(n, dtype=np.int32)
    out = kops.mp_pipeline(jnp.asarray(x), jnp.asarray(snd), jnp.asarray(rcv),
                           jnp.ones((n,), bool), n, stats=("max",))
    err = float(np.abs(np.asarray(out["max"]) - x[snd]).max())
    print(f"gather probe (mp_pipeline, {n}x{d} f32): max abs error {err!r}")
    check(err == 0.0, f"one-hot gather is not exact: max abs error {err}")


def smoke_one_chip(devices, seed, cpu):
    import jax
    from repro.core.message_passing import DataflowConfig
    from repro.core.models import PAPER_GNN_CONFIGS, make_gnn

    gather_probe()
    mols, heps = traffic(seed)
    for name, cfg in PAPER_GNN_CONFIGS.items():
        model = make_gnn(cfg)
        params = model.init(jax.random.PRNGKey(seed), cfg)
        refs = dense_refs(name, cfg, params, mols + heps, cpu)
        served = {}
        for df in (DataflowConfig(), DataflowConfig(impl="fused_layer")):
            label = f"{name}/{df.impl}"
            engine = make_engine(cfg, params, df, devices[:1])
            try:
                t0 = time.perf_counter()
                warm = serve(engine, mols, heps, record=False)
                compile_s = time.perf_counter() - t0
                buckets = sorted(engine._compiled)
                print(f"{label}: warm pass (compiles {len(buckets)} "
                      f"buckets {buckets}) {compile_s:.1f} s", flush=True)
                outs = serve(engine, mols, heps)
                check(sorted(engine._compiled) == buckets,
                      f"{label}: the served pass compiled new buckets")
                check_clean(engine, df, label)
                if df.impl == "fused_layer":
                    check_edge_passes(engine, cfg, label)
                    check_custom_call(engine, label)
            finally:
                engine.close()
            err, rel = scaled_error(warm + outs, refs + refs)
            print(f"{label}: {len(outs)} graphs, max abs error {err:.3e}, "
                  f"scaled {rel:.3e} (limit {REF_TOL})", flush=True)
            check(rel <= REF_TOL, f"{label}: error {rel:.3e} > {REF_TOL}")
            served[df.impl] = outs
        err, rel = scaled_error(served["fused_layer"], served["fused"])
        print(f"{name}: fused_layer vs default dataflow max abs difference "
              f"{err:.3e}, scaled {rel:.3e}", flush=True)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def smoke_pool(devices, seed, cpu):
    """Molecule stream, 4-executor pool vs 1 executor: bitwise (same
    program, same batches, identical chips)."""
    import jax
    from repro.core.message_passing import DataflowConfig
    from repro.core.models import PAPER_GNN_CONFIGS, make_gnn

    mols, _ = traffic(seed)
    df = DataflowConfig(impl="fused_layer")
    for name, cfg in PAPER_GNN_CONFIGS.items():
        params = make_gnn(cfg).init(jax.random.PRNGKey(seed), cfg)
        refs = dense_refs(name, cfg, params, mols, cpu)
        outs = {}
        pool = len(devices)
        for k in (1, pool):
            label = f"{name}/pool{k}"
            engine = make_engine(cfg, params, df, devices[:k])
            try:
                outs[k] = serve(engine, mols, [])
                check_clean(engine, df, label)
                per_dev = {dev: int(sum(s.batch_sizes))
                           for dev, s in engine.stats.by_device.items()}
            finally:
                engine.close()
            print(f"{label}: graphs served per device {per_dev}", flush=True)
            check(len(per_dev) == k and min(per_dev.values()) > 0,
                  f"{label}: not every device served graphs: {per_dev}")
        same = all(np.array_equal(a, b) for a, b in zip(outs[1], outs[pool]))
        check(same, f"{name}: {pool}-executor outputs differ from 1-executor")
        err, rel = scaled_error(outs[pool], refs)
        print(f"{name}/pool: {pool}-executor == 1-executor bitwise; vs dense "
              f"reference max abs error {err:.3e}, scaled {rel:.3e}",
              flush=True)
        check(rel <= REF_TOL, f"{name}/pool: error {rel:.3e} > {REF_TOL}")


def smoke_wide(devices, seed):
    """mesh_like graphs too large for one executor, on a 4-chip gang vs
    the single-device unrolled forward."""
    import jax
    from repro.core.engine import GraphStreamEngine
    from repro.core.graph import build_graph_batch, pad_bucket
    from repro.core.message_passing import DataflowConfig
    from repro.core.models import PAPER_GNN_CONFIGS, make_gnn
    from repro.data.graphs import mesh_like

    graphs = list(mesh_like(seed=seed + 4, n_graphs=2, n_nodes=2000))
    df = DataflowConfig(scan_layers=False)
    for name, base in PAPER_GNN_CONFIGS.items():
        cfg = base.replace(task="node")
        model = make_gnn(cfg)
        params = model.init(jax.random.PRNGKey(seed), cfg)
        single = jax.jit(lambda p, b: model.apply(p, b, cfg, df))
        engine = GraphStreamEngine(cfg, params, dataflow=df,
                                   devices=devices[:4], wide=True, wide_k=4)
        try:
            futs = [engine.submit(g.node_feat, g.senders, g.receivers,
                                  g.edge_feat, g.node_pos) for g in graphs]
            outs = [f.result(timeout=600) for f in futs]
            engine.drain(timeout=600)
            check_stats(engine, f"{name}/wide4")
            check(set(engine.stats.by_device) == {"wide[4]"},
                  f"{name}/wide4: graphs not served by the 4-chip gang: "
                  f"{sorted(engine.stats.by_device)}")
        finally:
            engine.close()
        refs = []
        for g in graphs:
            n, e = g.node_feat.shape[0], g.senders.shape[0]
            b = build_graph_batch(g.node_feat, g.senders, g.receivers,
                                  edge_feat=g.edge_feat,
                                  node_pad=pad_bucket(n),
                                  edge_pad=pad_bucket(e), node_pos=g.node_pos)
            refs.append(np.asarray(single(
                jax.device_put(params, devices[0]),
                jax.device_put(b, devices[0])))[:n])
        err, rel = scaled_error(outs, refs)
        print(f"{name}/wide4: {len(graphs)} graphs of "
              f"{graphs[0].node_feat.shape[0]} nodes, max abs error vs "
              f"single device {err:.3e}, scaled {rel:.3e} "
              f"(limit {WIDE_TOL})", flush=True)
        check(rel <= WIDE_TOL, f"{name}/wide4: error {rel:.3e} > {WIDE_TOL}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = tpu_devices(args.chips)
    if devices is None:
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.core.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache(ROOT)}", flush=True)
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    if args.chips == 1:
        smoke_one_chip(devices, args.seed, cpu)
    else:
        smoke_pool(devices[:4], args.seed, cpu)
        smoke_wide(devices[:4], args.seed)
    print(f"smoke passed in {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
