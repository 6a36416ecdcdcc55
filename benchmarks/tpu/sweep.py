"""Find the knee of an open-loop cell: one engine, a rising rate.

For each rate, in one process and on one warmed engine: the cell's traffic
at that rate for ``--seconds`` (after the mix's warm-up), then the latency
percentiles of the requests due in the window and the share of them still
unresolved when the window closed. The knee is the highest rate at which
completions keep pace with arrivals and p99 stays within the limit
derived from the lowest rate (see PERF.md). One JSON line per rate.

    python3 benchmarks/tpu/sweep.py --workload gin.trigger \
        --rates 50 200 400 800 --seconds 4
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import numpy as np
    import harness
    import traffic as traffic_lib
    from repro.core.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload, ROOT)
    if cell.mix["loop"] != "open":
        print("sweep: only open-loop cells have a rate", file=sys.stderr)
        return 2
    try:
        devices = harness.tpu_devices(cell.chips)[:cell.chips]
    except harness.NoAccelerator as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    cell.mix["rate_hz"] = args.rates[0]
    _, tr, engine = harness.set_up(cell, args.seed, args.seconds, devices)
    try:
        for rate in args.rates:
            cell.mix["rate_hz"] = rate
            tr = traffic_lib.Traffic(cell.mix, args.seed, args.seconds,
                                     node_dim=cell.config["node_feat_dim"])
            w = harness.serve(engine, cell, tr, args.seconds)
            run, _, _ = harness.summarize(cell, tr, w, devices, 0.0, 0)
            rec = w.rec
            due = (rec.t_due >= w.t0) & (rec.t_due < w.t_end)
            open_at_end = due & ~(rec.t_done < w.t_end)
            lat = run.latency_s * 1e3
            print(json.dumps({
                "rate_hz": rate, "attempted": run.attempted,
                "failed": run.failed,
                "open_at_end": int(open_at_end.sum()),
                "p50_ms": float(np.percentile(lat, 50)),
                "p90_ms": float(np.percentile(lat, 90)),
                "p99_ms": float(np.percentile(lat, 99)),
                "max_ms": float(lat.max()),
                "lateness_p99_ms": float(
                    np.percentile(run.lateness_s, 99) * 1e3),
                "submit_mean_ms": float(run.submit_s.mean() * 1e3),
                "queue_wait_mean_ms": float(
                    np.mean(run.queue_wait_s) * 1e3),
                "compiled_in_window": w.compiled_in_window,
                "t": time.perf_counter()}), flush=True)
    finally:
        engine.close(timeout=harness.GRACE_S)
    return 0


if __name__ == "__main__":
    sys.exit(main())
