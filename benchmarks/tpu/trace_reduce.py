"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Device planes are ``/device:TPU:<i>``. On each, the ``XLA Ops`` line holds
one event per operation that ran and the ``XLA Modules`` line one event per
program launch. The engine launches no compute program in the window other
than its jitted forwards, so module time on the TPU planes is the
forward's device time; the reduction takes every module and says so.

The traced window is the benchmark's own host span ``bench.trace_window``
(opened right after the profiler starts, closed right before it stops);
without it, the window runs from the first to the last device event.

- busy: the union of a chip's op intervals inside the window (overlapping
  ops count once); idle is the rest of the window.
- top ops: device seconds per op name, summed over the chips.
- idle gaps: each gap in a chip's busy union is named by the host event
  that overlaps it most among the PjRt execute and transfer events and the
  benchmark's ``bench.*`` spans, where that event covers at least half of
  the gap, and ``untraced host`` otherwise (the host ran nothing the trace
  records: Python code in the engine has no spans); seconds are summed
  per name.
- op names are the HLO instruction's name (``fusion.72``); ``while`` is
  the scanned layer loop and encloses the ops of its body.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.trace_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host events that say what the host was doing while a chip idled
HOST_ACTIVITY = re.compile(
    r"(^bench\.)|(Execute)|(Transfer)|(BufferFromHost)|(ToLiteral)|"
    r"(CopyToDevice)|(CopyRawToHost)|(TransferToInfeed)")

Interval = Tuple[int, int]   # [start_ns, end_ns)


def op_name(text: str) -> str:
    """``%fusion.72 = f32[64,100]{...} fusion(...)`` -> ``fusion.72``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals; empty ones are dropped."""
    out: List[List[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi) that ``busy`` (a merged union) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


@dataclass
class Chip:
    ops: List[Tuple[str, int, int]] = field(default_factory=list)
    modules: List[Interval] = field(default_factory=list)


@dataclass
class TraceSummary:
    window_ns: Interval
    busy_s: Dict[int, float]          # per chip
    module_s: Dict[int, float]        # per chip
    launches: Dict[int, int]          # modules started in the window
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def read_xplane(path: str):
    """(chips, host events, window) from one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    chips: Dict[int, Chip] = {}
    host: List[Tuple[str, int, int]] = []
    window: Optional[Interval] = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = chips.setdefault(int(m.group(1)), Chip())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip.ops.extend(
                        (op_name(ev.name), int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events)
                elif line.name == MODULES_LINE:
                    chip.modules.extend(
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = int(ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW_SPAN:
                        window = (s, e)
                    elif HOST_ACTIVITY.search(ev.name):
                        host.append((ev.name, s, e))
    return chips, host, window


def reduce_events(chips: Dict[int, Chip], host: List[Tuple[str, int, int]],
                  window: Optional[Interval], top: int = 10
                  ) -> Optional[TraceSummary]:
    """The window's busy, module and idle numbers; ``None`` where no
    operation ran on a device."""
    if not any(c.ops for c in chips.values()):
        return None
    if window is None:
        starts = [s for c in chips.values() for _, s, _ in c.ops]
        ends = [e for c in chips.values() for _, _, e in c.ops]
        window = (min(starts), max(ends))
    lo, hi = window
    busy_s, module_s, launches = {}, {}, {}
    per_op: Dict[str, int] = {}
    gap_by: Dict[str, int] = {}
    host_sorted = sorted(host, key=lambda h: h[1])
    host_starts = [h[1] for h in host_sorted]
    longest = max((e - s for _, s, e in host_sorted), default=0)
    for idx, chip in sorted(chips.items()):
        ops = [(name, max(s, lo), min(e, hi)) for name, s, e in chip.ops
               if min(e, hi) > max(s, lo)]
        busy = union((s, e) for _, s, e in ops)
        busy_s[idx] = total(busy) * 1e-9
        module_s[idx] = total(clip(chip.modules, lo, hi)) * 1e-9
        launches[idx] = sum(1 for s, _ in chip.modules if lo <= s < hi)
        for name, s, e in ops:
            per_op[name] = per_op.get(name, 0) + (e - s)
        for gs, ge in gaps(busy, lo, hi):
            name = _host_activity(host_sorted, host_starts, longest, gs, ge)
            gap_by[name] = gap_by.get(name, 0) + (ge - gs)

    def ranked(d):
        return [(k, v * 1e-9) for k, v in
                sorted(d.items(), key=lambda kv: (-kv[1], kv[0]))[:top]]

    return TraceSummary(window_ns=(lo, hi), busy_s=busy_s,
                        module_s=module_s, launches=launches,
                        device_ops=ranked(per_op), idle_gaps=ranked(gap_by))


def _host_activity(host: List[Tuple[str, int, int]], starts: List[int],
                   longest: int, gs: int, ge: int) -> str:
    """The host event (sorted by start) that overlaps [gs, ge) most, if
    it covers at least half of it; else ``untraced host``."""
    best, best_ov = "untraced host", (ge - gs) // 2 - 1
    lo = bisect.bisect_left(starts, gs - longest)
    hi = bisect.bisect_left(starts, ge)
    for name, s, e in host[lo:hi]:
        ov = min(e, ge) - max(s, gs)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce_trace(path: str, top: int = 10) -> Optional[TraceSummary]:
    chips, host, window = read_xplane(path)
    return reduce_events(chips, host, window, top=top)


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    from pathlib import Path
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return str(found[-1])
