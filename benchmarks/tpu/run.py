"""FlowGNN serving benchmark on the TPU: one run of one cell.

    python3 benchmarks/tpu/run.py --workload gin.trigger --seed 7 \
        --seconds 10 --trace 0

(``python3 -m benchmarks.tpu.run ...`` from the repository root works
too.) The cell, its configuration, traffic and metrics are read from
``BENCHMARK.json`` and the files under ``benchmarks/tpu/``. The run
serves through ``GraphStreamEngine.submit`` for ``--seconds`` after its
set-up, checks a sample of the window's answers against the plain
reference, and prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``), and ``compared``, each number of the
check beside its limit. The same numbers are the last lines of standard
error.

It exits 2, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program (``src/repro``) is not
beside the benchmark.
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


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"the program is not beside the benchmark: no {ROOT / 'src'}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import harness

    cell = harness.load_cell(args.workload, ROOT)
    log(f"{time.perf_counter() - T_START:.3f} s: imported")
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoAccelerator as exc:
        log(str(exc))
        return 2
    log(f"{time.perf_counter() - T_START:.3f} s: {len(devices)} "
        f"{devices[0].device_kind} found")
    from repro.core.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), devices=devices,
                           t_start=T_START, log=log)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
