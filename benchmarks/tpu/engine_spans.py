"""The engine's own spans in a profiler trace, joined per batch and per
request, and the stage split they give.

    python3 benchmarks/tpu/engine_spans.py --workload gin.trigger \
        --seed 7 --seconds 10             # serve one cell, trace, report
    python3 benchmarks/tpu/engine_spans.py --xplane <file.xplane.pb>

The program records one ``flowgnn.*`` span per stage of a request's path
(``src/repro/core/spans.py``), in the same ``.xplane.pb`` as the device
planes and on their clock. This module reads them with their thread and
ids beside each chip's busy union (``trace_reduce``), and joins them:

- a batch is the spans that carry its dispatch id (``batch``); its
  ``flowgnn.place`` names its requests (``reqs``) and its device (``dev``);
- a request is its ``flowgnn.submit`` (``req``) joined to the batch that
  carried it, where the trace holds the whole path: submit, place, build,
  launch, device wait, fetch, unpack and resolve. Its in-flight interval
  runs from its submit's start to its batch's resolve's end;
- hand-offs are the three gaps where a request passes between threads:
  submit end -> place start (client -> placer), place end -> build start
  (placer -> dispatch), launch end (stage end where the batch waited for
  the double buffer) -> device wait start (dispatch -> completer). A
  negative gap, where the next thread started first, counts as 0.

Spans are wall-clock: a stage's span includes the time its thread waited
for the interpreter lock inside it. A trace taken with JAX's Python tracer
on (the profiler's default, ``python_tracer_level`` 1) times every Python
call and slows the host path many times over; the ``--workload`` mode
turns it off for its window unless ``--python-tracer 1``.

The benchmark's per-layer metrics do not read these numbers yet: its
harness reduces the trace without the engine's spans and deletes the file.
"""

from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from trace_reduce import (Interval, clip, find_xplane, gaps,  # noqa: E402
                          read_xplane, total, union)

PREFIX = "flowgnn."
SUBMIT = "flowgnn.submit"
PLACE = "flowgnn.place"
BUILD = "flowgnn.build"
LAUNCH = "flowgnn.launch"
STAGE = "flowgnn.stage"
DEVICE_WAIT = "flowgnn.device_wait"
FETCH = "flowgnn.fetch"
UNPACK = "flowgnn.unpack"
RESOLVE = "flowgnn.resolve"
# the stages every batch on a request's path passes (stage only where the
# double buffer was full)
PATH = (PLACE, BUILD, LAUNCH, DEVICE_WAIT, FETCH, UNPACK, RESOLVE)
COMPLETION = (FETCH, UNPACK, RESOLVE)
# host work: the top-level spans, without the waits (stage, device wait)
# and the children (submit.validate inside submit, compile inside launch)
WORK = (SUBMIT, PLACE, BUILD, LAUNCH, FETCH, UNPACK, RESOLVE)
UNTRACED = "untraced host"


class Span(NamedTuple):
    name: str
    start: int                  # ns
    end: int
    thread: Tuple[str, int]     # (host plane, line index on it)
    ids: Dict[str, object]      # the span's stats: batch, req, reqs, ...


@dataclass
class EngineSpans:
    """A trace's engine spans and what the chips did meanwhile."""

    events: List[Span]                  # sorted by start
    busy: Dict[int, List[Interval]]     # per chip, busy union in the window
    window_ns: Interval


def read_spans(path: str) -> Optional[EngineSpans]:
    """The engine spans of one trace file; ``None`` where it holds none
    or no device operation ran."""
    from jax.profiler import ProfileData
    chips, _, window = read_xplane(path)
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    events.append(Span(ev.name, s, s + int(ev.duration_ns),
                                       (plane.name, k), dict(list(ev.stats))))
    starts = [s for c in chips.values() for _, s, _ in c.ops]
    if not events or not starts:
        return None
    if window is None:
        window = (min(starts), max(e for c in chips.values()
                                   for _, _, e in c.ops))
    lo, hi = window
    busy = {i: union(clip(((s, e) for _, s, e in c.ops), lo, hi))
            for i, c in chips.items()}
    return EngineSpans(sorted(events, key=lambda e: (e.start, e.end)),
                       busy, window)


def _ids(value) -> Tuple[int, ...]:
    """A ``reqs`` stat: one id reads back as a number, more as ``a;b``."""
    return tuple(int(v) for v in str(value).split(";") if v != "")


@dataclass
class Batch:
    id: int
    stages: Dict[str, Span] = field(default_factory=dict)
    reqs: Tuple[int, ...] = ()
    dev: Optional[int] = None

    def ms(self, *names: str) -> float:
        return sum(self.stages[n].end - self.stages[n].start
                   for n in names) * 1e-6


@dataclass
class Request:
    id: int
    submit: Span
    batch: Batch

    @property
    def inflight(self) -> Interval:
        return (self.submit.start, self.batch.stages[RESOLVE].end)

    def handoff_intervals(self) -> List[Interval]:
        st = self.batch.stages
        ready = st[STAGE].end if STAGE in st else st[LAUNCH].end
        return [(self.submit.end, st[PLACE].start),
                (st[PLACE].end, st[BUILD].start),
                (ready, st[DEVICE_WAIT].start)]

    def handoffs_ns(self) -> List[int]:
        return [max(e - s, 0) for s, e in self.handoff_intervals()]

    def coverage(self) -> float:
        """Share of the in-flight interval that the request's own spans
        and its hand-offs cover (overlaps once)."""
        lo, hi = self.inflight
        own = [self.submit] + list(self.batch.stages.values())
        parts = [(s.start, s.end) for s in own] + self.handoff_intervals()
        return total(union(clip(parts, lo, hi))) / max(hi - lo, 1)


def batches(sp: EngineSpans) -> Dict[int, Batch]:
    """Every batch whose id a span carries, with the spans that carry it
    (the first of each stage)."""
    out: Dict[int, Batch] = {}
    for ev in sp.events:
        bid = ev.ids.get("batch")
        if not isinstance(bid, int):
            continue
        b = out.setdefault(bid, Batch(bid))
        b.stages.setdefault(ev.name, ev)
        if ev.name == PLACE:
            b.reqs = _ids(ev.ids.get("reqs", ""))
            dev = ev.ids.get("dev")
            b.dev = dev if isinstance(dev, int) else None
    return out


def requests(sp: EngineSpans, by_batch: Dict[int, Batch]) -> List[Request]:
    """The requests whose whole path lies in the trace, by submit order."""
    carrier = {r: b for b in by_batch.values()
               if all(n in b.stages for n in PATH) for r in b.reqs}
    out = []
    for ev in sp.events:
        if ev.name != SUBMIT or not isinstance(ev.ids.get("req"), int):
            continue
        b = carrier.get(ev.ids["req"])
        if b is not None:
            out.append(Request(ev.ids["req"], ev, b))
    return out


def _devices(by_batch: Dict[int, Batch]) -> Dict[int, Optional[int]]:
    """Each batch's device: from its place span, or else from the device
    of the other batches its dispatch thread launched."""
    by_thread = {b.stages[LAUNCH].thread: b.dev for b in by_batch.values()
                 if b.dev is not None and LAUNCH in b.stages}
    return {bid: (b.dev if b.dev is not None else
                  by_thread.get(b.stages[LAUNCH].thread)
                  if LAUNCH in b.stages else None)
            for bid, b in by_batch.items()}


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two merged unions."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def one_clock(sp: EngineSpans, by_batch: Dict[int, Batch]
              ) -> Dict[int, float]:
    """Per chip, the share of its busy time in the window that lies inside
    some batch's [launch start, device wait end] on that chip's executor
    (the chip plane's index is the device id)."""
    dev_of = _devices(by_batch)
    out = {}
    for chip, busy in sorted(sp.busy.items()):
        launched = union((b.stages[LAUNCH].start, b.stages[DEVICE_WAIT].end)
                         for bid, b in by_batch.items()
                         if dev_of[bid] == chip and LAUNCH in b.stages
                         and DEVICE_WAIT in b.stages)
        busy_ns = total(busy)
        if busy_ns:
            out[chip] = total(_intersect(busy, launched)) / busy_ns
    return out


def clock_offset(sp: EngineSpans, by_batch: Dict[int, Batch],
                 reach_ns: int = 5_000_000, step_ns: int = 10_000
                 ) -> Dict[int, Dict[str, float]]:
    """Per chip, how far the device plane's clock sits from the host's:
    the device events shifted by ``d`` (within ``reach_ns``, in steps of
    ``step_ns``) put the largest share of busy time inside the chip's
    launch-to-device-wait spans at ``best_ns``; ``range_ns`` is every
    offset that puts 99% of it there (``None`` if none does)."""
    dev_of = _devices(by_batch)
    out = {}
    for chip, busy in sorted(sp.busy.items()):
        launched = union((b.stages[LAUNCH].start, b.stages[DEVICE_WAIT].end)
                         for bid, b in by_batch.items()
                         if dev_of[bid] == chip and LAUNCH in b.stages
                         and DEVICE_WAIT in b.stages)
        busy_ns = total(busy)
        if not busy_ns or not launched:
            continue
        shares = {}
        for d in range(-reach_ns, reach_ns + 1, step_ns):
            moved = [(s + d, e + d) for s, e in busy]
            shares[d] = total(_intersect(moved, launched)) / busy_ns
        best = max(shares, key=lambda d: (shares[d], -abs(d)))
        ok = [d for d, v in shares.items() if v >= 0.99]
        out[chip] = {"best_ns": best, "share_at_best": shares[best],
                     "share_at_0": shares[0],
                     "range_ns": [min(ok), max(ok)] if ok else None}
    return out


def idle_in_flight(sp: EngineSpans, reqs: List[Request],
                   by_batch: Dict[int, Batch]
                   ) -> Tuple[int, Dict[str, int]]:
    """Device-idle time in the window while at least one request placed on
    that chip is in flight, summed over chips (ns), and the same time split
    by the engine span running at each instant (the one that started last,
    on any thread; ``untraced host`` where none runs)."""
    lo, hi = sp.window_ns
    dev_of = _devices(by_batch)
    events = sp.events
    starts = [ev.start for ev in events]
    longest = max((ev.end - ev.start for ev in events), default=0)
    split: Dict[str, int] = {}
    for chip, busy in sp.busy.items():
        flying = union(clip((r.inflight for r in reqs
                             if dev_of[r.batch.id] == chip), lo, hi))
        for s, e in _intersect(gaps(busy, lo, hi), flying):
            near = [ev for ev in
                    events[bisect.bisect_left(starts, s - longest):
                           bisect.bisect_left(starts, e)] if ev.end > s]
            cuts = sorted({s, e} | {t for ev in near
                                    for t in (ev.start, ev.end) if s < t < e})
            for a, b in zip(cuts, cuts[1:]):
                running = [ev for ev in near if ev.start <= a and ev.end >= b]
                name = (max(running, key=lambda ev: (ev.start, ev.name)).name
                        if running else UNTRACED)
                split[name] = split.get(name, 0) + (b - a)
    return sum(split.values()), split


def work_ns(sp: EngineSpans) -> Dict[str, int]:
    """Host work per top-level span name in the window, summed over
    threads (ns)."""
    lo, hi = sp.window_ns
    out = {n: 0 for n in WORK}
    for ev in sp.events:
        if ev.name in out:
            out[ev.name] += max(min(ev.end, hi) - max(ev.start, lo), 0)
    return out


def report(sp: EngineSpans, graphs: Optional[int] = None) -> Dict:
    """The stage split of one traced window, in ms: per batch (build,
    launch, completion), per request (hand-offs, device idle while in
    flight, and the median request's coverage), per graph (host work; over
    ``graphs`` completed in the window, else the requests placed), and
    each chip's one-clock share."""
    by = batches(sp)
    reqs = requests(sp, by)
    out: Dict = {"batches": len(by), "requests": len(reqs),
                 "one_clock": one_clock(sp, by),
                 "clock_offset": clock_offset(sp, by)}
    for key, names in (("build_ms", (BUILD,)), ("launch_ms", (LAUNCH,)),
                       ("completion_ms", COMPLETION)):
        got = [b.ms(*names) for b in by.values()
               if all(n in b.stages for n in names)]
        out[key] = sum(got) / len(got) if got else None
    if reqs:
        n = len(reqs)
        parts = [sum(r.handoffs_ns()[k] for r in reqs) / n * 1e-6
                 for k in range(3)]
        out["handoff_ms"] = sum(parts)
        out["handoff_parts_ms"] = dict(zip(
            ("submit_place", "place_build", "launch_device_wait"), parts))
        out["median_coverage"] = statistics.median(
            r.coverage() for r in reqs)
        idle, split = idle_in_flight(sp, reqs, by)
        out["inflight_idle_ms"] = idle / n * 1e-6
        out["inflight_idle_split_ms"] = {
            k: v / n * 1e-6 for k, v in
            sorted(split.items(), key=lambda kv: -kv[1])}
    work = work_ns(sp)
    n_graphs = graphs or sum(len(b.reqs) for b in by.values()) or None
    if n_graphs:
        out["host_ms_per_graph"] = sum(work.values()) / n_graphs * 1e-6
        out["host_parts_ms_per_graph"] = {
            k: v / n_graphs * 1e-6 for k, v in work.items()}
    return out


def _split_window(w, loop: str) -> Dict[str, float]:
    """The window's end-to-end metric before the trace started and while
    it ran: the median latency of the requests due in each part (open
    loop), or the graphs completed per second in each (closed loop)."""
    import numpy as np
    rec = w.rec
    tt0, tt1 = w.t_trace
    out = {}
    for part, (a, b) in (("untraced", (w.t0, tt0)), ("traced", (tt0, tt1))):
        if loop == "open":
            due = (rec.t_due >= a) & (rec.t_due < b) & rec.ok
            lat = (rec.t_done - rec.t_due)[due]
            out[f"latency_p50_ms.{part}"] = (
                float(np.percentile(lat, 50)) * 1e3 if len(lat) else None)
        else:
            done = rec.ok & (rec.t_done >= a) & (rec.t_done < b)
            out[f"graphs_per_s.{part}"] = float(done.sum()) / (b - a)
    return out


def _serve_traced(workload: str, seed: int, seconds: float,
                  python_tracer: bool, keep: Optional[str], log
                  ) -> Dict:
    """One window of a cell with the profiler on over its last seconds,
    as the benchmark's ``--trace 1`` run takes it: the report, the
    window's end-to-end metric outside and inside the trace, and the
    device."""
    import shutil
    import jax
    root = HERE.parents[1]
    sys.path.insert(0, str(root / "src"))
    import harness
    cell = harness.load_cell(workload, root)
    devices = harness.tpu_devices(cell.chips)[:cell.chips]
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache(root)
    start_trace = jax.profiler.start_trace
    if not python_tracer:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace = (
            lambda d: start_trace(d, profiler_options=opts))
    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory() as d:
        try:
            params, tr, engine = harness.set_up(cell, seed, seconds, devices,
                                                log)
            try:
                w = harness.serve(engine, cell, tr, seconds, d)
            finally:
                engine.close(timeout=harness.GRACE_S)
        finally:
            jax.profiler.start_trace = start_trace
        run, _, _ = harness.summarize(cell, tr, w, devices,
                                      w.t0 - t_start, 0)
        path = find_xplane(d)
        if keep:
            shutil.copy(path, keep)
        sp = read_spans(path)
    graphs = None if run.trace_nodes is None else len(run.trace_nodes)
    return {"report": None if sp is None else report(sp, graphs),
            "end_to_end": _split_window(w, cell.mix["loop"]),
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--xplane")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", help="copy the trace file here")
    args = ap.parse_args(argv)
    if args.xplane:
        sp = read_spans(args.xplane)
        out = {"report": None if sp is None else report(sp)}
    elif args.workload:
        out = {"workload": args.workload, "seed": args.seed,
               "python_tracer": args.python_tracer}
        out.update(_serve_traced(
            args.workload, args.seed, args.seconds, bool(args.python_tracer),
            args.keep,
            lambda m: print(f"spans: {m}", file=sys.stderr, flush=True)))
    else:
        ap.error("give --xplane or --workload")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
