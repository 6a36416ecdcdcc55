"""One benchmark run of one cell: set-up, measured window, check, result.

Everything about a cell is data found by name:

- ``BENCHMARK.json``: the cell (configuration, traffic mix, chips) and
  the metrics it reports;
- ``configs/<config>.json``: the model as it is run;
- ``refs/<config>.py``: its weights (``init``) and plain reference
  (``forward``);
- ``counts/<config>.py``: its algorithmic FLOPs and bytes;
- ``mixes/<traffic>.json``: the traffic, overlaid by ``cells/<cell>.json``
  (the cell's own rate and the limits of its check);
- ``metrics/<metric>.py``: one reader per metric, ``read(run)``.

The window drives the served entry, ``GraphStreamEngine.submit`` and the
future it returns, under the engine's default dataflow. Timing is on the
client's clock; per-layer numbers come from a traced run of their own.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import queue as queue_lib
import tempfile
import time
from dataclasses import dataclass, fields
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# the benchmark's own modules, beside this file on ``sys.path``
import traffic as traffic_lib  # noqa: E402
import trace_reduce  # noqa: E402
from peaks import peak  # noqa: E402

# The traced window: the last TRACE_S seconds of the measured window, or
# its second half if that is shorter. A few seconds hold thousands of
# launches, and the trace file stays small.
TRACE_S = 2.0
# How long after the window closes a request may still resolve. Late is
# late, not wrong; only a request that never resolves is missing.
GRACE_S = 60.0
# Graphs per call of the vmapped reference.
REF_CHUNK = 32


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file by path (file names may hold dots)."""
    name = "bench_" + "_".join(path.relative_to(HERE).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, float]
    ref: Any
    counts: Any
    metrics: List[Dict[str, Any]]       # every metric in BENCHMARK.json

    def reported(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports in a run of this kind: every
        end-to-end metric without ``workloads`` or that lists the cell, and
        every per-layer metric that lists the cell."""
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.metrics if m["kind"] == kind
                and self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    config = load_json(HERE / "configs" / f"{w['config']}.json")
    mix = dict(load_json(HERE / "mixes" / f"{w['traffic']}.json"))
    own = HERE / "cells" / f"{name}.json"
    cell_data = load_json(own) if own.exists() else {}
    limits = cell_data.pop("limits", {})
    mix.update(cell_data)
    metrics = ([dict(m, kind="end_to_end") for m in bench["end_to_end"]]
               + [dict(m, kind="per_layer") for m in bench["per_layer"]])
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                limits=limits,
                ref=load_module(HERE / "refs" / f"{w['config']}.py"),
                counts=load_module(HERE / "counts" / f"{w['config']}.py"),
                metrics=metrics)


def tpu_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU found (JAX platform: "
                            f"{devices[0].platform})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devices)}")
    return devices


def gnn_config(config: Dict[str, Any]):
    """The program's ``GNNConfig`` for a configuration file."""
    import jax.numpy as jnp
    from repro.core.models import GNNConfig
    names = {f.name for f in fields(GNNConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    kw["head_mlp"] = tuple(kw.get("head_mlp", ()))
    kw["dtype"] = getattr(jnp, config["dtype"])
    return GNNConfig(**kw)


def weight_key(seed: int):
    import jax
    state = np.random.SeedSequence(
        (int(seed), 0x77656967)).generate_state(1)[0]
    return jax.random.PRNGKey(int(state))


def make_weights(cell: Cell, seed: int, device):
    """The cell's weights from the seed, made on ``device`` in one call."""
    import jax
    init = jax.jit(lambda k: cell.ref.init(k, cell.config))
    with jax.default_device(device):
        return jax.block_until_ready(init(weight_key(seed)))


# ---------------------------------------------------------------------------
# the measured run
# ---------------------------------------------------------------------------

@dataclass
class Run:
    """What one run measured; the metric readers take their numbers here.

    ``attempted``/``latency_s``/``lateness_s``: requests due in the window
    (open loop), or in flight during it (closed loop). ``completed*``:
    requests resolved inside it. ``submit_s``, ``queue_wait_s``,
    ``batch_sizes``, ``graphs_by_device``: what the client and the engine
    recorded inside it, up to the trace's start in a traced run.
    ``trace*``: the traced part of the window.
    """

    cell: Cell
    device_kind: str
    chips: int
    seconds: float
    setup_s: float
    attempted: int
    failed: int
    latency_s: np.ndarray           # per attempted request (open loop)
    lateness_s: np.ndarray          # due -> submit called (open loop)
    completed: int                  # resolved OK inside the window
    completed_nodes: np.ndarray
    completed_edges: np.ndarray
    submit_s: np.ndarray            # host time inside each submit call
    queue_wait_s: List[float]
    batch_sizes: List[int]
    graphs_by_device: Dict[str, int]
    memory_peak_bytes: int
    trace: Optional[trace_reduce.TraceSummary] = None
    trace_nodes: Optional[np.ndarray] = None   # graphs done in the trace
    trace_edges: Optional[np.ndarray] = None

    @property
    def peak(self) -> Dict[str, float]:
        return peak(self.device_kind)

    @property
    def max_batch(self) -> int:
        return int(self.cell.mix["queue"]["max_batch"])

    def flops(self, nodes, edges) -> float:
        """Algorithmic FLOPs of these graphs (counted per graph)."""
        return float(np.sum(self.cell.counts.flops(
            np.asarray(nodes, np.float64), np.asarray(edges, np.float64),
            self.cell.config)))

    def bytes_moved(self, nodes, edges) -> float:
        """Algorithmic bytes of these graphs, without weights."""
        return float(np.sum(self.cell.counts.bytes_moved(
            np.asarray(nodes, np.float64), np.asarray(edges, np.float64),
            self.cell.config)))


class _Recorder:
    """Client-side clocks and results, one slot per request."""

    def __init__(self, n: int):
        self.t_due = np.full(n, np.nan)
        self.t_sub0 = np.full(n, np.nan)
        self.t_sub1 = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.out: Dict[int, np.ndarray] = {}
        self.done_q: "queue_lib.SimpleQueue[int]" = queue_lib.SimpleQueue()
        self.n_sent = 0

    def callback(self, i: int) -> Callable:
        def _cb(fut):
            self.t_done[i] = time.perf_counter()
            if fut.exception() is None:
                self.ok[i] = True
                self.out[i] = fut.result()
            self.done_q.put(i)
        return _cb


def _engine(cell: Cell, params, devices, tr: "traffic_lib.Traffic"):
    from repro.core.engine import GraphStreamEngine
    from repro.core.scheduler import QueueConfig
    q = cell.mix["queue"]
    engine = GraphStreamEngine(
        gnn_config(cell.config), params, devices=list(devices),
        queues=(QueueConfig("bench", **q),))
    pairs = traffic_lib.batch_buckets(
        int(q["max_batch"]), q.get("max_nodes"), q.get("max_edges"),
        tr.pool, engine.buckets)
    engine.warmup_all(pairs)
    return engine


@dataclass
class Window:
    """What the client and the engine recorded around one window."""

    rec: "_Recorder"
    t0: float
    t_end: float
    t_wait_end: float
    t_trace: List[float]
    queue_wait_s: List[float]
    batch_sizes: List[int]
    graphs_by_device: Dict[str, int]
    compiled_in_window: int
    buckets_in_window: Dict[str, int]     # batches served per bucket
    gc_pauses: List[tuple]          # (generation, seconds) in the window


def set_up(cell: Cell, seed: int, seconds: float, devices,
           log: Callable[[str], None] = lambda m: None):
    """Weights, traffic and a warmed engine for one run."""
    log("set-up starts")
    params = make_weights(cell, seed, devices[0])
    log("weights made")
    tr = traffic_lib.Traffic(cell.mix, seed, seconds,
                             node_dim=cell.config["node_feat_dim"])
    log(f"traffic made: {len(tr.pool)} pool graphs, {len(tr)} requests")
    engine = _engine(cell, params, devices, tr)
    log(f"buckets warmed: {sorted(engine.autotune_report())}")
    # Tracing and compiling the bucket programs leaves some 10^5 Python
    # objects that live as long as the process. A full collection walks
    # them all and stalls every thread for tens of milliseconds; a
    # long-running server moves them out of the collector's reach once it
    # is warm, and so does the benchmark.
    gc.collect()
    gc.freeze()
    return params, tr, engine


def serve(engine, cell: Cell, tr, seconds: float,
          trace_dir: Optional[str] = None) -> Window:
    """Drive the cell's traffic through ``engine`` for one window, then
    wait for every request sent (up to ``GRACE_S``)."""
    import jax
    served0 = _served_buckets(engine)
    rec = _Recorder(len(tr))
    pauses = _GcPauses()
    gc.callbacks.append(pauses)
    compiles = _CompileEvents()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    t_trace: List[float] = []
    win = _TraceWindow(trace_dir, t_trace)
    spans = trace_dir is not None
    if cell.mix["loop"] == "open":
        t0, t_end, marks = _open_loop(engine, tr, rec, seconds, win, spans)
    else:
        t0, t_end, marks = _closed_loop(engine, tr, rec, seconds, cell.chips,
                                        win, spans)
    served1 = _served_buckets(engine)
    t_wait_end = _wait_all(rec, t_end + GRACE_S)
    gc.callbacks.remove(pauses)
    jax.monitoring.unregister_event_duration_listener(compiles)
    st = engine.stats
    by_dev = {}
    for dev, s in st.by_device.items():
        b0 = marks["dev0"].get(dev, 0)
        b1 = marks["dev1"].get(dev, len(s.batch_sizes))
        by_dev[dev] = int(sum(s.batch_sizes[b0:b1]))
    return Window(rec=rec, t0=t0, t_end=t_end, t_wait_end=t_wait_end,
                  t_trace=t_trace,
                  queue_wait_s=list(st.queue_wait_s[marks["qw0"]:
                                                    marks["qw1"]]),
                  batch_sizes=list(st.batch_sizes[marks["bs0"]:marks["bs1"]]),
                  graphs_by_device=by_dev,
                  compiled_in_window=compiles.inside(t0, t_end),
                  buckets_in_window={k: n - served0.get(k, 0)
                                     for k, n in served1.items()
                                     if n > served0.get(k, 0)},
                  gc_pauses=pauses.inside(t0, t_end))


def summarize(cell: Cell, tr, w: Window, devices, setup_s: float,
              memory_peak: int):
    """The window as the metric readers see it, plus the indices of the
    requests completed in it and every request's (nodes, edges)."""
    rec = w.rec
    t0, t_end = w.t0, w.t_end
    t_host = w.t_trace[0] if w.t_trace else t_end   # see _TraceWindow
    done_in = rec.ok & (rec.t_done >= t0) & (rec.t_done < t_end)
    sizes = np.array([(s.n_nodes, s.n_edges) for s in tr.pool])[tr.order]
    if cell.mix["loop"] == "open":
        due = rec.t_due
        att = (due >= t0) & (due < t_end)
        lat = np.where(rec.ok, rec.t_done - due,
                       np.maximum(w.t_wait_end - due, 0.0))[att]
        lateness = (rec.t_sub0 - due)[att]
        submit = (rec.t_sub1 - rec.t_sub0)[att & (due < t_host)]
    else:
        # attempted: sent before the window closed and not resolved
        # before it opened
        sent = ~np.isnan(rec.t_sub0)
        att = sent & (rec.t_sub0 < t_end) & ~(rec.t_done < t0)
        lat = np.zeros(0)
        lateness = np.zeros(0)
        in_win = sent & (rec.t_sub0 >= t0) & (rec.t_sub0 < t_host)
        submit = (rec.t_sub1 - rec.t_sub0)[in_win]
    idx_done = np.nonzero(done_in)[0]
    run = Run(cell=cell, device_kind=devices[0].device_kind,
              chips=cell.chips, seconds=t_end - t0, setup_s=setup_s,
              attempted=int(att.sum()), failed=int((att & ~rec.ok).sum()),
              latency_s=lat, lateness_s=lateness,
              completed=int(done_in.sum()),
              completed_nodes=sizes[idx_done, 0],
              completed_edges=sizes[idx_done, 1], submit_s=submit,
              queue_wait_s=w.queue_wait_s, batch_sizes=w.batch_sizes,
              graphs_by_device=w.graphs_by_device,
              memory_peak_bytes=memory_peak)
    if len(w.t_trace) == 2:
        tt0, tt1 = w.t_trace
        in_tr = rec.ok & (rec.t_done >= tt0) & (rec.t_done < tt1)
        run.trace_nodes = sizes[in_tr, 0]
        run.trace_edges = sizes[in_tr, 1]
    return run, idx_done, sizes


def measure(cell: Cell, *, seed: int, seconds: float, trace: bool,
            devices, t_start: float, log: Callable[[str], None]):
    """Set-up and one window; the engine is closed on return. Returns the
    run, the weights, the traffic, and the sampled requests with their
    served answers."""
    devices = list(devices)[:cell.chips]
    params, tr, engine = set_up(
        cell, seed, seconds, devices,
        lambda m: log(f"{time.perf_counter() - t_start:.3f} s: {m}"))
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    try:
        w = serve(engine, cell, tr, seconds,
                  trace_dir.name if trace else None)
        memory_peak = _memory_peak(devices)
    finally:
        engine.close(timeout=GRACE_S)
    del engine
    full = [d for g, d in w.gc_pauses if g == 2]
    log(f"garbage collections in the window: {len(w.gc_pauses)}, of them "
        f"{len(full)} full, longest full {max(full, default=0.0)!r} s")
    log(f"batches per bucket in the window: {w.buckets_in_window}")
    if w.compiled_in_window:
        log(f"warning: {w.compiled_in_window} tracing or compiling steps "
            f"inside the window")
    run, idx_done, sizes = summarize(cell, tr, w, devices, w.t0 - t_start,
                                     memory_peak)
    if len(run.lateness_s):
        lt = run.lateness_s
        log(f"generator lateness (due -> submit): p50 "
            f"{float(np.percentile(lt, 50)) * 1e3!r} ms, p99 "
            f"{float(np.percentile(lt, 99)) * 1e3!r} ms, max "
            f"{float(lt.max()) * 1e3!r} ms")
    if trace:
        run.trace = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(trace_dir.name))
        trace_dir.cleanup()
    sample = _sample(seed, idx_done, sizes, int(cell.mix["sample"]))
    served = [w.rec.out[i] for i in sample]
    return run, params, tr, sample, served


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float,
             log: Callable[[str], None] = lambda s: None) -> Dict[str, Any]:
    """One run: set-up, window, optional trace, check. Returns the result
    line's object (with ``compared`` last)."""
    run, params, tr, sample, served = measure(
        cell, seed=seed, seconds=seconds, trace=trace, devices=devices,
        t_start=t_start, log=log)
    # the check runs after the window, with the engine closed
    gap = compare(cell, params, tr, sample, served, devices[0])
    return result(cell, run, devices, trace, gap, log)


class _GcPauses:
    """Python's garbage collections: (start, generation, seconds)."""

    def __init__(self):
        self.log: List[tuple] = []
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.log.append((self._t, info["generation"],
                             time.perf_counter() - self._t))

    def inside(self, t0: float, t1: float) -> List[tuple]:
        return [(g, d) for t, g, d in self.log if t0 <= t < t1]


class _CompileEvents:
    """JAX's tracing, lowering and compiling steps, by the time each
    ended: any of them inside the window means a program was made there."""

    PREFIX = "/jax/core/compile/"

    def __init__(self):
        self.ends: List[float] = []

    def __call__(self, event: str, duration_secs: float, **_):
        if event.startswith(self.PREFIX):
            self.ends.append(time.perf_counter())

    def inside(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.ends)


class _TraceWindow:
    """Starts the profiler and the window span at ``begin`` and stops both
    at the end of the window; never due when not tracing. ``marks`` holds
    the engine's counters where the trace started: starting the profiler
    stalls every thread for a moment, so the host metrics of a traced run
    end there."""

    def __init__(self, log_dir: Optional[str], t_marks: List[float]):
        self.log_dir = log_dir
        self.t_marks = t_marks
        self.ann = None
        self.marks: Optional[Dict[str, Any]] = None
        self.begin = math.inf

    def arm(self, t0: float, t_end: float) -> None:
        if self.log_dir is not None:
            self.begin = t_end - min(TRACE_S, (t_end - t0) / 2)

    def due(self, now: float) -> bool:
        return self.ann is None and now >= self.begin

    def start(self, engine):
        import jax
        self.marks = _marks(engine)
        jax.profiler.start_trace(self.log_dir)
        self.ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self.ann.__enter__()
        self.t_marks.append(time.perf_counter())

    def stop(self):
        import jax
        if self.ann is None:
            return
        self.t_marks.append(time.perf_counter())
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


def _submit(engine, tr, rec, i, spans: bool):
    args = tr.request(i)
    rec.t_sub0[i] = time.perf_counter()
    if spans:
        import jax
        with jax.profiler.TraceAnnotation("bench.submit"):
            fut = engine.submit(*args, queue="bench")
    else:
        fut = engine.submit(*args, queue="bench")
    rec.t_sub1[i] = time.perf_counter()
    fut.add_done_callback(rec.callback(i))


def _served_buckets(engine) -> Dict[str, int]:
    """Batches served so far per bucket (the engine's load report)."""
    return {k: v["load"]["batches"]
            for k, v in engine.autotune_report().items() if "load" in v}


def _marks(engine) -> Dict[str, Any]:
    st = engine.stats
    return {"qw": len(st.queue_wait_s), "bs": len(st.batch_sizes),
            "dev": {d: len(s.batch_sizes) for d, s in st.by_device.items()}}


def _open_loop(engine, tr, rec, seconds, win, spans):
    """Requests sent on their Poisson schedule, whatever came back."""
    due = tr.due_s
    t_base = time.perf_counter() + 0.01
    t0 = t_base + tr.warm_s
    t_end = t0 + seconds
    win.arm(t0, t_end)
    m0 = None
    for i in range(len(due)):
        t_due = t_base + due[i]
        rec.t_due[i] = t_due
        if m0 is None and t_due >= t0:
            _sleep_until(t0)
            m0 = _marks(engine)
        if win.due(t_due):
            _sleep_until(win.begin)
            win.start(engine)
        _sleep_until(t_due)
        _submit(engine, tr, rec, i, spans)
    rec.n_sent = len(due)
    if win.due(t_end):
        _sleep_until(win.begin)
        win.start(engine)
    _sleep_until(t_end)
    m1 = win.marks or _marks(engine)
    win.stop()
    return t0, t_end, _join_marks(m0 or m1, m1)


def _closed_loop(engine, tr, rec, seconds, chips, win, spans):
    """A fixed number of requests in flight: each completion sends the
    next request."""
    outstanding = int(tr.mix["outstanding_per_chip"]) * chips
    t_base = time.perf_counter()
    t0 = t_base + tr.warm_s
    t_end = t0 + seconds
    win.arm(t0, t_end)
    nxt = 0
    for _ in range(outstanding):
        _submit(engine, tr, rec, nxt, spans)
        nxt += 1
    m0 = None
    while True:
        now = time.perf_counter()
        if m0 is None and now >= t0:
            m0 = _marks(engine)
        if win.due(now):
            win.start(engine)
        if now >= t_end:
            break
        wake = min(t_end, t0 if m0 is None else t_end,
                   win.begin if win.ann is None else t_end)
        try:
            rec.done_q.get(timeout=max(wake - now, 0.0))
        except queue_lib.Empty:
            continue
        if nxt >= len(tr):
            raise RuntimeError("the closed loop ran out of distinct requests")
        _submit(engine, tr, rec, nxt, spans)
        nxt += 1
    rec.n_sent = nxt
    m1 = win.marks or _marks(engine)
    win.stop()
    return t0, t_end, _join_marks(m0 or m1, m1)


def _join_marks(m0, m1):
    return {"qw0": m0["qw"], "qw1": m1["qw"], "bs0": m0["bs"],
            "bs1": m1["bs"], "dev0": m0["dev"], "dev1": m1["dev"]}


def _sleep_until(t: float) -> None:
    dt = t - time.perf_counter()
    if dt > 0:
        time.sleep(dt)


def _wait_all(rec: _Recorder, deadline: float) -> float:
    """Wait until every submitted request resolved, or the deadline."""
    while True:
        now = time.perf_counter()
        if not np.isnan(rec.t_done[:rec.n_sent]).any() or now >= deadline:
            return now
        time.sleep(0.01)


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ---------------------------------------------------------------------------
# correctness: served outputs against the plain reference
# ---------------------------------------------------------------------------

def _sample(seed: int, idx_done: np.ndarray, sizes: np.ndarray,
            n: int) -> List[int]:
    """Up to ``n`` requests completed in the window, drawn from the seed,
    always with the largest graph among them."""
    if len(idx_done) == 0:
        return []
    rng = traffic_lib.stream(seed, "sample")
    longest = int(idx_done[np.argmax(sizes[idx_done, 0])])
    rest = idx_done[idx_done != longest]
    pick = rng.choice(rest, size=min(n - 1, len(rest)), replace=False)
    return [longest] + sorted(int(i) for i in pick)


def reference_outputs(cell: Cell, params, tr, sample: List[int], device,
                      control: bool = False):
    """The plain reference over each sampled request alone: the answers
    (S, out_dim) and their magnitudes (see ``refs/*.py``), float64.

    The reference computes in the configuration's ``dtype`` (float32), its
    dense layers at the configuration's ``matmul_precision``. The control
    (``control=True``) is the same reference in the precision below it:
    weights, inputs and every activation in bfloat16."""
    import jax
    import jax.numpy as jnp

    pool = [tr.structure(i) for i in sample]
    n_pad = traffic_lib.pad_pow2(max(s.n_nodes for s in pool), 8)
    e_pad = traffic_lib.pad_pow2(max(s.n_edges for s in pool), 8)
    dtype = "bfloat16" if control else cell.config["dtype"]
    with jax.default_device(device):
        p = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    fn = jax.jit(jax.vmap(lambda g: cell.ref.forward(p, g, cell.config)))
    outs, mags = [], []
    for c in range(0, len(sample), REF_CHUNK):
        g = _padded(tr, sample[c:c + REF_CHUNK], n_pad, e_pad)
        g["node_feat"] = g["node_feat"].astype(dtype)
        g["edge_feat"] = g["edge_feat"].astype(dtype)
        with jax.default_device(device):
            y, m = fn(g)
        outs.append(np.asarray(y, np.float64))
        mags.append(np.asarray(m, np.float64))
    return np.concatenate(outs), np.concatenate(mags)


def _padded(tr, idx: List[int], n_pad: int, e_pad: int) -> Dict:
    k = len(idx)
    f = tr.node_dim
    fe = tr.pool[0].edge_feat.shape[1]
    g = {"node_feat": np.zeros((k, n_pad, f), np.float32),
         "senders": np.zeros((k, e_pad), np.int32),
         "receivers": np.zeros((k, e_pad), np.int32),
         "edge_feat": np.zeros((k, e_pad, fe), np.float32),
         "edge_mask": np.zeros((k, e_pad), bool),
         "node_mask": np.zeros((k, n_pad), bool)}
    for r, i in enumerate(idx):
        s = tr.structure(i)
        n, e = s.n_nodes, s.n_edges
        g["node_feat"][r, :n] = tr.features(i)
        g["senders"][r, :e] = s.senders
        g["receivers"][r, :e] = s.receivers
        g["edge_feat"][r, :e] = s.edge_feat
        g["edge_mask"][r, :e] = True
        g["node_mask"][r, :n] = True
    return g


def relative_gaps(served: np.ndarray, ref: np.ndarray, mag: np.ndarray
                  ) -> np.ndarray:
    """Each answer's gap to the reference's over its magnitude: the sum of
    the absolute terms of the reference's last layer, the scale at which
    rounding inside the forward shows."""
    served = np.asarray(served, np.float64).reshape(ref.shape)
    return (np.abs(served - ref) / np.maximum(mag, 1e-30)).ravel()


def mean_gap(served: np.ndarray, ref: np.ndarray, mag: np.ndarray) -> float:
    """The check's number: the mean of the sampled answers' gaps.

    Served at the configuration's precision, most answers equal the
    reference's to float32 rounding; on a few graphs in a hundred an
    operand lands on the other side of a bfloat16 step and the answer
    moves by up to a few 1e-3. Computed in bfloat16, every answer moves
    by about 1e-3. So the widest gap cannot tell the two apart, and the
    mean can; one answer altered by a hundredth of its magnitude still
    moves the mean of 256 by about 4e-5."""
    return float(relative_gaps(served, ref, mag).mean())


def compare(cell: Cell, params, tr, sample: List[int], served, device
            ) -> Optional[float]:
    if not sample:
        return None
    ref, mag = reference_outputs(cell, params, tr, sample, device)
    return mean_gap(np.stack([np.asarray(s) for s in served]), ref, mag)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def metric_value(run: Run, metric: Dict[str, Any]) -> Optional[float]:
    reader = load_module(HERE / "metrics" / f"{metric['name']}.py")
    v = reader.read(run)
    return None if v is None else float(v)


def result(cell: Cell, run: Run, devices, trace: bool,
           gap: Optional[float], log: Callable[[str], None]
           ) -> Dict[str, Any]:
    metrics = {}
    for m in cell.reported(trace):
        v = metric_value(run, m)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out: Dict[str, Any] = {"attempted": run.attempted, "failed": run.failed,
                           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.mean_busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in run.trace.device_ops],
            "idle_gaps": [[k, v] for k, v in run.trace.idle_gaps]}
    limit = cell.limits["mean_rel_gap"]
    compared = {
        "failed_requests": {"value": run.failed, "limit": 0},
        "mean_rel_gap": {"value": gap, "limit": limit},
    }
    correct = (run.attempted > 0 and run.failed == 0 and gap is not None
               and gap <= limit)
    out = {"correct": bool(correct), **out, "compared": compared}
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    return out
