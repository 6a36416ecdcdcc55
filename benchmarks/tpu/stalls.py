"""Find where a cell's window stalls: a watcher thread beside the window.

Runs the cell's set-up and window as a benchmark run does (no check, no
result line) with one more thread that wakes every ``TICK_S`` and reports
two kinds of stall:

- ``process``: the watcher itself woke more than ``STALL_S`` late: the
  interpreter lock was held elsewhere, or the process got no CPU. Each
  thread's CPU time and run-queue wait (``/proc/self/task``),
  the host's stolen time (``/proc/stat``) and the control group's
  throttled time since the last snapshot (every ``SNAPSHOT_EVERY_S``)
  say which;
- ``submit``: the main thread sat inside one ``engine.submit`` call for
  more than ``STALL_S`` while the watcher ran; its stack is sampled.

One JSON line per seed on standard output.

    python3 benchmarks/tpu/stalls.py --workload gin.trigger --seeds 1 2 \
        --seconds 10
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

TICK_S = 0.005
STALL_S = 0.05
SNAPSHOT_EVERY_S = 0.25


def _host_s() -> dict:
    """Seconds the host took from this machine (``steal``, all CPUs) and
    that this control group was held back by its CPU quota."""
    out = {"steal": 0.0, "throttled": 0.0}
    with open("/proc/stat") as f:
        out["steal"] = int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    try:
        with open("/sys/fs/cgroup/cpu.stat") as f:
            for line in f:
                key, value = line.split()
                if key == "throttled_usec":
                    out["throttled"] = int(value) / 1e6
    except OSError:
        pass
    return out


class _Threads:
    """Each thread's CPU time (``stat``, in clock ticks) and run-queue wait
    (``schedstat``, where the kernel keeps it), in seconds."""

    def __init__(self):
        self.tick = os.sysconf("SC_CLK_TCK")

    def snapshot(self) -> dict:
        py = {t.native_id: t.name for t in threading.enumerate()}
        out = {}
        for tid in os.listdir("/proc/self/task"):
            try:
                with open(f"/proc/self/task/{tid}/stat") as f:
                    stat = f.read()
            except OSError:
                continue
            comm = stat[stat.index("(") + 1:stat.rindex(")")]
            fields = stat[stat.rindex(")") + 2:].split()
            wait = 0.0
            try:
                with open(f"/proc/self/task/{tid}/schedstat") as f:
                    wait = int(f.read().split()[1]) / 1e9
            except (OSError, IndexError):
                pass
            name = py.get(int(tid), comm)
            out[f"{name}:{tid}"] = (
                (int(fields[11]) + int(fields[12])) / self.tick, wait)
        return out


def _delta(before: dict, after: dict, floor: float = 0.005) -> dict:
    """Per thread (cpu s, run-queue wait s) between two snapshots, the
    busiest first, and the totals."""
    d = {k: (after[k][0] - before.get(k, (0, 0))[0],
             after[k][1] - before.get(k, (0, 0))[1]) for k in after}
    top = {k: [round(c, 4), round(w, 4)]
           for k, (c, w) in sorted(d.items(), key=lambda kv: -kv[1][0])
           if c >= floor or w >= floor}
    return {"cpu_s": sum(c for c, _ in d.values()),
            "wait_s": sum(w for _, w in d.values()), "threads": top}


def _submit_frame(frame):
    """The harness's ``_submit`` frame on this stack, if any."""
    while frame is not None:
        if frame.f_code.co_name == "_submit":
            return frame
        frame = frame.f_back
    return None


class Watcher(threading.Thread):
    def __init__(self, main_ident: int):
        super().__init__(name="stall-watcher", daemon=True)
        self.main_ident = main_ident
        self.stop_flag = threading.Event()
        self.stalls = []

    def run(self):
        threads = _Threads()
        t_prev = time.perf_counter()
        snap, host, t_snap = threads.snapshot(), _host_s(), t_prev
        sub_frame, sub_since, sub_stall = None, 0.0, None
        while not self.stop_flag.is_set():
            time.sleep(TICK_S)
            now = time.perf_counter()
            gap = now - t_prev
            if gap > STALL_S or now - t_snap >= SNAPSHOT_EVERY_S:
                snap1, host1 = threads.snapshot(), _host_s()
                if gap > STALL_S:
                    self.stalls.append({
                        "kind": "process", "at_s": t_prev - T_START,
                        "ms": gap * 1e3, "since_snapshot_s": now - t_snap,
                        **{f"{k}_s": host1[k] - host[k] for k in host},
                        **_delta(snap, snap1)})
                snap, host, t_snap = snap1, host1, now
            frame = sys._current_frames().get(self.main_ident)
            sf = _submit_frame(frame)
            if sf is not sub_frame:
                if sub_stall is not None:
                    sub_stall["ms"] = (now - sub_since) * 1e3
                    self.stalls.append(sub_stall)
                sub_frame, sub_since, sub_stall = sf, now, None
            elif (sf is not None and sub_stall is None
                  and now - sub_since > STALL_S):
                sub_stall = {"kind": "submit", "at_s": sub_since - T_START,
                             "stack": traceback.format_stack(frame)[-8:]}
            t_prev = now
        if sub_stall is not None:
            sub_stall["ms"] = (time.perf_counter() - sub_since) * 1e3
            self.stalls.append(sub_stall)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import numpy as np
    import harness
    from repro.core.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload, ROOT)
    try:
        devices = harness.tpu_devices(cell.chips)[:cell.chips]
    except harness.NoAccelerator as exc:
        print(f"stalls: {exc}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    for seed in args.seeds:
        params, tr, engine = harness.set_up(cell, seed, args.seconds,
                                            devices)
        watcher = Watcher(threading.main_thread().ident)
        watcher.start()
        try:
            w = harness.serve(engine, cell, tr, args.seconds)
        finally:
            watcher.stop_flag.set()
            watcher.join()
            engine.close(timeout=harness.GRACE_S)
        run, _, _ = harness.summarize(cell, tr, w, devices, 0.0, 0)
        row = {"workload": cell.name, "seed": seed,
               "window_at_s": w.t0 - T_START, "window_s": w.t_end - w.t0,
               "gc_in_window": w.gc_pauses,
               "compiles_in_window": w.compiled_in_window,
               "stalls": [s for s in watcher.stalls
                          if w.t0 - 1.0 <= s["at_s"] + T_START < w.t_end]}
        if len(run.latency_s):
            row["latency_p50_ms"] = float(np.percentile(run.latency_s, 50)
                                          * 1e3)
            row["lateness_max_ms"] = float(run.lateness_s.max() * 1e3)
        elif run.completed:
            row["graphs_per_s"] = run.completed / run.seconds
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
