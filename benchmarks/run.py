# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark orchestrator.

  PYTHONPATH=src python -m benchmarks.run            # all tables, small sizes
  PYTHONPATH=src python -m benchmarks.run table7     # one table
  PYTHONPATH=src python -m benchmarks.run kernels    # micro-benchmarks only
  PYTHONPATH=src python -m benchmarks.run stream     # serving engine sweep

Alongside the CSV on stdout, kernel-level rows (``kernel.*``) are written to
``BENCH_kernels.json`` as a machine-readable ``{name: us_per_call}`` map
(plus the derived annotations) so the perf trajectory — in particular the
single-pass vs per-kind multi-aggregation comparison — can be tracked
across PRs. The ``stream`` target additionally writes ``BENCH_stream.json``
(p50/p99 latency and batch-aware graphs/s at batch sizes 1/8/64/256, plus
the per-bucket autotuned dataflow knobs, the chaos-goodput row, and the
``overload``/``drift``/``degraded`` sections behind the
``check_regression.py --stream`` SLO gates) and ``BENCH_overload_trace.json`` (the replayed trace plus all
three overload-run summaries — the CI artifact).
"""

import json
import sys
from pathlib import Path

from benchmarks.common import Csv
from benchmarks import kernel_bench, paper_tables, stream_bench
from repro.core.compile_cache import enable_compile_cache

_ROOT = Path(__file__).resolve().parent.parent
BENCH_JSON = _ROOT / "BENCH_kernels.json"
BENCH_STREAM_JSON = _ROOT / "BENCH_stream.json"

_STREAM_PAYLOAD = {}

# CI uploads this as the trace-replay artifact (per-event arrival schedule
# + per-run engine summaries for all three overload runs)
OVERLOAD_TRACE_JSON = _ROOT / "BENCH_overload_trace.json"


def _run_stream(csv: Csv) -> None:
    _STREAM_PAYLOAD.update(stream_bench.stream_sweep(csv))
    _STREAM_PAYLOAD["overload"] = stream_bench.overload_bench(
        csv, trace_out=str(OVERLOAD_TRACE_JSON))
    _STREAM_PAYLOAD["drift"] = stream_bench.drift_bench(csv)
    _STREAM_PAYLOAD["degraded"] = stream_bench.degraded_bench(csv)
    _STREAM_PAYLOAD["wide"] = stream_bench.wide_bench(csv)


TABLES = {
    "table5": lambda csv: paper_tables.table5_hep_latency(csv, n_graphs=12),
    "table6": lambda csv: paper_tables.table6_energy(csv, n_graphs=12),
    "fig7": lambda csv: paper_tables.fig7_batch_sweep(csv),
    "fig9": lambda csv: paper_tables.fig9_ablation(csv),
    "fig10": lambda csv: paper_tables.fig10_dse(csv),
    "table7": lambda csv: paper_tables.table7_imbalance(csv),
    "table8": lambda csv: paper_tables.table8_gcn_small(csv),
    "kernels": lambda csv: (kernel_bench.mp_paths(csv),
                            kernel_bench.multi_agg_paths(csv),
                            kernel_bench.pipeline_paths(csv),
                            kernel_bench.fused_layer_paths(csv),
                            kernel_bench.attention_fused_paths(csv),
                            kernel_bench.edge_pass_paths(csv),
                            kernel_bench.vs_segment_ops_paths(csv),
                            kernel_bench.forward_trace_paths(csv),
                            kernel_bench.softmax_paths(csv),
                            kernel_bench.attention_paths(csv)),
    "stream": _run_stream,
}


def main() -> None:
    enable_compile_cache(_ROOT)
    names = sys.argv[1:] or list(TABLES)
    csv = Csv()
    print("name,us_per_call,derived")
    for name in names:
        TABLES[name](csv)
    print(f"# {len(csv.rows)} rows")

    kernel_rows = [r for r in csv.records if r["name"].startswith("kernel.")]
    if kernel_rows:
        payload = {
            "us_per_call": {r["name"]: r["us_per_call"] for r in kernel_rows},
            "derived": {r["name"]: r["derived"] for r in kernel_rows
                        if r["derived"]},
        }
        BENCH_JSON.write_text(json.dumps(payload, indent=2, sort_keys=True)
                              + "\n")
        print(f"# wrote {BENCH_JSON.name} ({len(kernel_rows)} kernel rows)")

    if _STREAM_PAYLOAD:
        BENCH_STREAM_JSON.write_text(
            json.dumps(_STREAM_PAYLOAD, indent=2, sort_keys=True) + "\n")
        print(f"# wrote {BENCH_STREAM_JSON.name} "
              f"(batches {sorted(_STREAM_PAYLOAD['batch'], key=int)})")


if __name__ == "__main__":
    main()
