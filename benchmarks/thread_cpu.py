"""Host CPU per graph served, split by thread, for cells of the TPU
benchmark.

Runs a cell's set-up and window as ``benchmarks/tpu/run.py`` does (no
check, no result line) and reads every thread's CPU time
(``/proc/self/task/*/stat``, user + system) every ``SAMPLE_S``; the
split covers the span between the first and the last reading inside the
window. Prints one JSON line per cell and seed: microseconds of CPU per
graph completed in that span for the client thread (the harness and
``GraphStreamEngine.submit``), the engine's dispatch, completion and
placer threads, every other thread, and ``submit`` alone (the client's
CPU inside ``submit``, by ``time.thread_time``, over the whole window).

    python3 benchmarks/thread_cpu.py --workload gin.screen pna.screen \
        --seeds 1 2 --seconds 10

Needs the chip: like ``run.py`` it exits 2 where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT / "src"), str(HERE / "tpu")]

import harness  # noqa: E402

# coarse, so that reading /proc adds next to nothing to the interpreter
# time being measured
SAMPLE_S = 1.0
ROLES = (("client", "MainThread:"), ("dispatch", "flowgnn-dispatch-"),
         ("completer", "flowgnn-complete-"), ("placer", "flowgnn-placer"))


def thread_cpu() -> dict:
    """``{"<name>:<tid>": user + system CPU s}`` for every thread of this
    process."""
    tick = os.sysconf("SC_CLK_TCK")
    names = {t.native_id: t.name for t in threading.enumerate()}
    out = {}
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        fields = stat[stat.rindex(")") + 2:].split()
        name = names.get(int(tid), stat[stat.index("(") + 1:stat.rindex(")")])
        out[f"{name}:{tid}"] = (int(fields[11]) + int(fields[12])) / tick
    return out


class Sampler(threading.Thread):
    """Every thread's CPU time, every ``SAMPLE_S``."""

    def __init__(self):
        super().__init__(name="thread-cpu-sampler", daemon=True)
        self.stop_flag = threading.Event()
        self.samples = []           # (perf_counter, thread_cpu())

    def run(self):
        while not self.stop_flag.is_set():
            self.samples.append((time.perf_counter(), thread_cpu()))
            self.stop_flag.wait(SAMPLE_S)

    def inside(self, t0: float, t1: float) -> tuple:
        """The first and the last sample taken within ``[t0, t1]``."""
        within = [s for s in self.samples if t0 <= s[0] <= t1]
        if len(within) < 2:
            raise RuntimeError(f"fewer than two samples in a {t1 - t0:.1f} s"
                               f" window; lengthen --seconds")
        return within[0], within[-1]


def role(key: str) -> str:
    """The role of the thread a snapshot's ``<name>:<tid>`` key names."""
    for r, prefix in ROLES:
        if key.startswith(prefix):
            return r
    return "other"


def split(engine, cell, tr, seconds: float) -> dict:
    """One window with the sampler beside it: CPU µs per graph by role."""
    submit_cpu = [0.0]
    served = engine.submit

    def timed_submit(*args, **kwargs):
        t = time.thread_time()
        try:
            return served(*args, **kwargs)
        finally:
            submit_cpu[0] += time.thread_time() - t

    engine.submit = timed_submit
    sampler = Sampler()
    sampler.start()
    try:
        w = harness.serve(engine, cell, tr, seconds)
    finally:
        sampler.stop_flag.set()
        sampler.join()
        engine.submit = served
    (ta, a), (tb, b) = sampler.inside(w.t0, w.t_end)
    rec = w.rec
    graphs = int((rec.ok & (rec.t_done >= ta) & (rec.t_done < tb)).sum())
    by_role = {}
    for key, cpu in b.items():
        r = role(key)
        by_role[r] = by_role.get(r, 0.0) + cpu - a.get(key, 0.0)
    sent = ~(rec.t_sub0 != rec.t_sub0)           # not NaN
    n_sent = int(sent.sum())
    us = {r: by_role.get(r, 0.0) / graphs * 1e6
          for r in ("client", "dispatch", "completer", "placer", "other")}
    return {"graphs": graphs, "graphs_per_s": graphs / (tb - ta),
            "sampled_s": tb - ta, "cpu_us_per_graph": us,
            "total_us_per_graph": sum(us.values()),
            "submit_us_per_call": submit_cpu[0] / n_sent * 1e6,
            "submit_wall_us_mean": float(
                (rec.t_sub1 - rec.t_sub0)[sent].mean() * 1e6)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    for name in args.workload:
        cell = harness.load_cell(name, ROOT)
        try:
            devices = harness.tpu_devices(cell.chips)[:cell.chips]
        except harness.NoAccelerator as exc:
            print(f"thread_cpu: {exc}", file=sys.stderr)
            return 2
        for seed in args.seeds:
            _, tr, engine = harness.set_up(cell, seed, args.seconds, devices)
            try:
                row = split(engine, cell, tr, args.seconds)
            finally:
                engine.close(timeout=harness.GRACE_S)
            print(json.dumps({"workload": name, "seed": seed, **row}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
