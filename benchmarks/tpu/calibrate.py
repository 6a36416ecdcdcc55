"""Readings that a cell's check limit is set from, on the chip.

For each seed, in one process: a short window of the cell's own traffic
(the same set-up, sample and reference as a benchmark run), then

- ``program``: ``mean_rel_gap`` of the answers the window served against
  the reference, in float32 at the configuration's matmul precision;
- ``control``: the same number for the reference itself, put in the
  program's place and computed in bfloat16, the precision below float32;
- for the record, each one's widest gap, quantiles and every gap, and
  the program's widest gap against the reference with every dense matmul
  at ``highest`` precision.

The limit goes between the largest program reading and the smallest
control reading (see PERF.md). One JSON line per seed on standard output.

    python3 benchmarks/tpu/calibrate.py --workload gin.trigger \
        --seeds 101 102 103 --seconds 3
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
QUANTILES = (50, 75, 90, 95, 99)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import dataclasses

    import numpy as np
    import harness
    from repro.core.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload, ROOT)
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoAccelerator as exc:
        print(f"calibrate: {exc}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    for seed in args.seeds:
        t0 = time.perf_counter()
        run, params, tr, sample, served = harness.measure(
            cell, seed=seed, seconds=args.seconds, trace=False,
            devices=devices, t_start=t0, log=lambda m: None)
        dev = devices[0]
        ref, mag = harness.reference_outputs(cell, params, tr, sample, dev)
        served = np.stack([np.asarray(s) for s in served])
        low, _ = harness.reference_outputs(cell, params, tr, sample, dev,
                                           control=True)
        highest = dataclasses.replace(
            cell, config=dict(cell.config, matmul_precision="highest"))
        ref_hi, mag_hi = harness.reference_outputs(highest, params, tr,
                                                   sample, dev)
        gaps = {"program": harness.relative_gaps(served, ref, mag),
                "control": harness.relative_gaps(low, ref, mag)}
        row = {"workload": cell.name, "seed": seed, "sample": len(sample),
               "attempted": run.attempted, "failed": run.failed,
               "program": float(gaps["program"].mean()),
               "control": float(gaps["control"].mean()),
               "program_max_vs_highest": float(harness.relative_gaps(
                   served, ref_hi, mag_hi).max())}
        for name, g in gaps.items():
            row[f"{name}_max"] = float(g.max())
            row[f"{name}_quantiles"] = {
                q: float(np.percentile(g, q)) for q in QUANTILES}
            row[f"{name}_gaps"] = g.tolist()
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
