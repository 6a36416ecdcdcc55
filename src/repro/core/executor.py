"""Per-device executor: the processing-element half of the serving stack.

The paper's architecture drains its queue bank into parallel processing
elements with no global synchronization (GenGNN scales the same
decomposition across PEs). Here a ``DeviceExecutor`` is one PE: it owns
exactly one ``jax.Device``, a params copy committed to that device, a
per-bucket compiled-program cache, and its own dispatch/complete thread
pair with a depth-2 staging queue — so host packing for batch k+2 overlaps
device execution of batch k *per device*, and D devices run D independent
pipelines (DESIGN.md §5).

The executor knows nothing about queues, futures, stats, or autotuning:
the engine injects

  * ``build_fn(pb)``                 — PackedBatch -> the program's
    input on this executor's device (host packing plus one transfer,
    runs on this executor's dispatch thread),
  * ``program_fn(ex, key, graph)``   — returns the jitted program for a
    bucket on THIS executor (the engine's compile/autotune cache,
    namespaced per device),
  * ``on_complete(ex, done)``        — called from this executor's
    completer thread with a ``CompletedBatch`` (results or error); the
    engine resolves futures and records stats there,
  * ``on_fatal(ex, exc)``            — a worker loop died unexpectedly,
  * ``fault_hook(site, ex, pb)``     — optional chaos-testing hook
    (``core/faults.py``) called at the ``'dispatch'`` and ``'complete'``
    sites; it may raise (injected failure/crash) or sleep (stall).

Failure semantics (DESIGN.md §8): a worker-loop death marks the executor
``dead``, fails the batch it was holding plus everything queued behind it
with ``ExecutorDead`` (every future resolves; nothing is stranded on the
staging pipe), and reports through ``on_fatal`` so the engine's
supervisor can take this executor out of rotation and re-place the failed
work on survivors. ``stop(timeout=...)`` bounds every join, so a wedged
worker can never block shutdown; ``mark_dead`` is the engine watchdog's
entry point for executors that are stuck rather than crashed.

Each stage of a batch opens a span on the profiler's clock
(``core/spans.py``): build, launch (with compile on a program's first
call here) and stage on the dispatch thread; device wait, fetch and
unpack on the completer, all carrying the batch's dispatch id.

``backlog`` (graphs submitted here and not yet completed) is what the
engine's least-backlog placement reads; ``device_s`` in ``CompletedBatch``
is *marginal* device-busy time per executor, so overlapped batches on one
device are not double-counted and per-device throughput sums honestly.
"""

from __future__ import annotations

import queue
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import spans
from repro.core.errors import ExecutorDead
from repro.core.packing import PackedBatch

BucketKey = Tuple[int, int, int]

_SENTINEL = object()


@dataclass
class _InFlight:
    """A dispatched batch waiting for this executor's device."""

    queue: str
    batch: PackedBatch
    out: Any
    t_build_start: float
    t_dispatch: float
    params_version: int = 0


@dataclass
class CompletedBatch:
    """Everything the engine needs to resolve one batch.

    ``params_version`` is the executor's params version *at dispatch
    time* — a hot ``update_params`` promoting mid-flight never changes
    which weights an already-dispatched batch ran on, and the engine's
    shadow auditor replays the batch against the matching host copy.
    """

    queue: str
    batch: PackedBatch
    results: Optional[List[np.ndarray]]       # None iff err is set
    err: Optional[BaseException]
    t_build_start: float
    t_dispatch: float
    t_ready: float
    device_s: float                            # marginal device-busy time
    params_version: int = 0


class DeviceExecutor:
    """One device's double-buffered dispatch/complete pipeline."""

    def __init__(self, *, device, index: int, params,
                 build_fn: Callable[[PackedBatch], Any],
                 program_fn: Callable[["DeviceExecutor", BucketKey, Any], Any],
                 unpack_fn: Callable[[PackedBatch, np.ndarray],
                                     List[np.ndarray]],
                 on_complete: Callable[["DeviceExecutor", CompletedBatch],
                                       None],
                 on_fatal: Callable[["DeviceExecutor", BaseException], None],
                 fault_hook: Optional[Callable[[str, "DeviceExecutor",
                                                PackedBatch], None]] = None):
        self.device = device
        self.index = index
        # (replica committed to ``device``, version) swapped as ONE
        # reference by hot reload, so a dispatch snapshot can never pair
        # old weights with a new version number
        self._params_v: Tuple[Any, int] = (params, 0)
        self.label = f"{device.platform}:{device.id}"
        # per-device program namespace: {bucket: jitted program}. The
        # engine's ``_compiled`` facade merges these for the observable
        # compile-count surface. ``touched`` maps each bucket to its last
        # engine-wide touch sequence number — the LRU order the engine's
        # cold-program eviction reads when ``max_cached_programs`` bounds
        # this namespace (DESIGN.md §5).
        self.compiled: Dict[BucketKey, Any] = {}
        self.touched: Dict[BucketKey, int] = {}
        # programs called at least once here: the first call traces and
        # compiles (weak, so an evicted program is still freed)
        self._ran: "weakref.WeakSet[Any]" = weakref.WeakSet()

        self._build_fn = build_fn
        self._program_fn = program_fn
        self._unpack_fn = unpack_fn
        self._on_complete = on_complete
        self._on_fatal = on_fatal
        self._fault_hook = fault_hook

        self._inbox: "queue.Queue[Any]" = queue.Queue()
        # depth-2 staging = the double buffer: one batch executing, one
        # dispatched behind it; a third dispatch blocks until completion
        self._staging: "queue.Queue[Any]" = queue.Queue(maxsize=2)
        self._backlog = 0
        self._queued_batches = 0
        self._lock = threading.Lock()
        self._dispatcher: Optional[threading.Thread] = None
        self._completer: Optional[threading.Thread] = None
        self._stopped = False
        self._dead = False        # a worker loop died; fail, don't block

    # -- lifecycle --------------------------------------------------------

    def start(self) -> None:
        if self._dispatcher is not None:
            return
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name=f"flowgnn-dispatch-{self.label}")
        self._completer = threading.Thread(
            target=self._complete_loop, daemon=True,
            name=f"flowgnn-complete-{self.label}")
        self._dispatcher.start()
        self._completer.start()

    def stop(self, timeout: Optional[float] = None) -> bool:
        """Finish queued work, then stop both threads. Idempotent, and
        safe after a worker-loop death (no deadlock on a full staging
        queue; leftover batches fail rather than strand).

        With ``timeout`` every join is bounded: a wedged worker thread —
        stuck inside a device computation, say — is declared dead instead
        of blocking shutdown forever, and everything it still held fails
        with ``ExecutorDead``. Returns True iff both threads exited
        cleanly within the budget.
        """
        if self._dispatcher is None or self._stopped:
            return not self._dead
        self._stopped = True
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)

        def _left() -> Optional[float]:
            return (None if deadline is None
                    else max(deadline - time.perf_counter(), 0.0))

        self._inbox.put(_SENTINEL)
        self._dispatcher.join(_left())
        if self._dispatcher.is_alive():
            self.mark_dead(ExecutorDead(
                "executor dispatch thread wedged during stop",
                executor_index=self.index))
            return False
        while True:
            try:
                self._staging.put(_SENTINEL, timeout=1.0)
                break
            except queue.Full:
                if self._dead:       # completer is gone; drain below
                    break
                left = _left()
                if left is not None and left <= 0.0:
                    self.mark_dead(ExecutorDead(
                        "executor staging pipe wedged during stop",
                        executor_index=self.index))
                    return False
        self._completer.join(_left())
        if self._completer.is_alive():
            self.mark_dead(ExecutorDead(
                "executor completer thread wedged during stop",
                executor_index=self.index))
            return False
        self._drain_queues(ExecutorDead(
            "executor stopped after worker death",
            executor_index=self.index))
        return not self._dead

    def mark_dead(self, exc: Optional[BaseException] = None) -> None:
        """Declare this executor dead without waiting for its threads
        (the engine watchdog's stuck-executor path, and wedged-stop).

        Worker loops fail fast once ``_dead`` is set; everything queued
        here resolves with ``exc`` immediately. The batch a wedged thread
        is *currently* holding cannot be reached from here — the engine's
        in-flight registry supersedes it (a late completion is ignored).
        """
        if exc is None:
            exc = ExecutorDead("executor marked dead",
                               executor_index=self.index)
        self._dead = True
        self._drain_queues(exc)

    # -- versioned params (hot reload, DESIGN.md §9) ---------------------

    @property
    def params(self) -> Any:
        return self._params_v[0]

    @property
    def params_version(self) -> int:
        return self._params_v[1]

    def set_params(self, params, version: int) -> None:
        """Install a new committed replica at ``version``.

        A single reference store (GIL-atomic): every dispatch AFTER this
        runs the new weights; a batch already past its snapshot finishes
        on the old replica, whose buffers stay alive exactly as long as
        some in-flight batch still references them.
        """
        self._params_v = (params, int(version))

    # -- placement interface ---------------------------------------------

    @property
    def backlog(self) -> int:
        """Graphs submitted to this executor and not yet completed."""
        with self._lock:
            return self._backlog

    @property
    def queued_batches(self) -> int:
        """Batches submitted here and not yet completed (building + staged
        + executing + inbox). The placer bounds this at ``PIPELINE_DEPTH``
        so excess backlog queues in the *fair* scheduler, not in a FIFO
        inbox where tenant weights no longer apply."""
        with self._lock:
            return self._queued_batches

    # one building on the dispatch thread + two in the staging double
    # buffer + one completing: enough to keep the device saturated with
    # zero inbox FIFO wait beyond it
    PIPELINE_DEPTH = 4

    @property
    def has_capacity(self) -> bool:
        return not self._dead and self.queued_batches < self.PIPELINE_DEPTH

    @property
    def idle(self) -> bool:
        return self.backlog == 0

    @property
    def dead(self) -> bool:
        return self._dead

    def submit(self, queue_name: str, pb: PackedBatch) -> None:
        """Hand one flushed batch to this executor (engine placer thread)."""
        with self._lock:
            self._backlog += pb.num_graphs
            self._queued_batches += 1
        if self._dead:       # worker died since placement: fail, don't strand
            self._fail_batch(queue_name, pb, self._dead_exc())
            return
        self._inbox.put((queue_name, pb))
        if self._dead:       # raced a dying worker past its drain: re-drain
            self._drain_queues(self._dead_exc())

    def warm(self, key: BucketKey, g) -> None:
        """Compile (and run once) the bucket's program on this device."""
        run = self._program_fn(self, key, g)
        jax.block_until_ready(self._call(run, key, self.params, g))

    def _call(self, run, key: BucketKey, params, g):
        """``run(params, g)``; a program's first call here traces and
        compiles it, inside a ``flowgnn.compile`` span."""
        if run in self._ran:
            return run(params, g)
        with spans.span(spans.COMPILE, bucket=spans.bucket_name(key)):
            out = run(params, g)
        self._ran.add(run)
        return out

    # -- worker loops -----------------------------------------------------

    def _dead_exc(self) -> ExecutorDead:
        return ExecutorDead("executor worker died",
                            executor_index=self.index)

    def _finish(self, done: CompletedBatch) -> None:
        with self._lock:
            self._backlog -= done.batch.num_graphs
            self._queued_batches -= 1
        self._on_complete(self, done)

    def _fail_batch(self, queue_name: str, pb: PackedBatch,
                    exc: BaseException) -> None:
        t = time.perf_counter()
        self._finish(CompletedBatch(
            queue=queue_name, batch=pb, results=None, err=exc,
            t_build_start=t, t_dispatch=t, t_ready=t, device_s=0.0))

    def _drain_queues(self, exc: BaseException) -> None:
        """Fail every batch still sitting in inbox/staging (worker death:
        their futures must resolve and stop() must not block)."""
        for q in (self._staging, self._inbox):
            while True:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
                if item is _SENTINEL:
                    continue
                if isinstance(item, _InFlight):
                    self._fail_batch(item.queue, item.batch, exc)
                else:
                    self._fail_batch(item[0], item[1], exc)

    def _loop_fatal(self, exc: BaseException,
                    current: Optional[Tuple[str, PackedBatch]] = None
                    ) -> None:
        # a worker loop died unexpectedly: mark the executor dead (the
        # surviving loop fails work instead of blocking on the pipe), fail
        # the batch THIS loop was holding plus everything still queued
        # here — no future is ever left unresolved — then tell the engine
        self._dead = True
        if current is not None:
            self._fail_batch(current[0], current[1], exc)
        self._drain_queues(exc)
        self._on_fatal(self, exc)

    def _dispatch_loop(self) -> None:
        current: Optional[Tuple[str, PackedBatch]] = None
        try:
            while True:
                # drop the last batch before waiting for the next: its
                # requests must not stay alive while this thread idles
                item = pb = g = out = inflight = None
                item = self._inbox.get()
                if item is _SENTINEL:
                    return
                queue_name, pb = item
                current = (queue_name, pb)
                if self._dead:
                    self._fail_batch(queue_name, pb, self._dead_exc())
                    current = None
                    continue
                t_build = time.perf_counter()
                sp = spans.tracer()
                bid = pb.dispatch_id
                try:
                    if self._fault_hook is not None:
                        self._fault_hook("dispatch", self, pb)
                    with sp(spans.BUILD, batch=bid):
                        g = self._build_fn(pb)
                    with sp(spans.LAUNCH, batch=bid):
                        run = self._program_fn(self, pb.bucket, g)
                        # one snapshot pins this batch to its dispatch-time
                        # params version (hot reload swaps the pair
                        # atomically)
                        params, pver = self._params_v
                        # asynchronous dispatch
                        out = self._call(run, pb.bucket, params, g)
                except Exception as exc:        # bad batch: report, stay up
                    t = time.perf_counter()
                    self._finish(CompletedBatch(
                        queue=queue_name, batch=pb, results=None, err=exc,
                        t_build_start=t_build, t_dispatch=t, t_ready=t,
                        device_s=0.0))
                    current = None
                    continue
                # blocks while two batches are already staged (the double
                # buffer): host packing overlaps device execution. The
                # dead-check breaks the wait so a crashed completer cannot
                # wedge this thread on a full pipe.
                inflight = _InFlight(queue_name, pb, out, t_build,
                                     time.perf_counter(),
                                     params_version=pver)
                with sp(spans.STAGE, batch=bid):
                    while True:
                        if self._dead:
                            self._fail_batch(queue_name, pb,
                                             self._dead_exc())
                            break
                        try:
                            self._staging.put(inflight, timeout=0.2)
                            break
                        except queue.Full:
                            continue
                current = None
        except BaseException as exc:
            self._loop_fatal(exc, current)
            raise

    def _complete_loop(self) -> None:
        last_ready = 0.0
        current: Optional[Tuple[str, PackedBatch]] = None
        try:
            while True:
                item = results = ready = out_np = None      # as above
                item = self._staging.get()
                if item is _SENTINEL:
                    return
                current = (item.queue, item.batch)
                err: Optional[Exception] = None
                results: Optional[List[np.ndarray]] = None
                sp = spans.tracer()
                bid = item.batch.dispatch_id
                try:
                    if self._fault_hook is not None:
                        self._fault_hook("complete", self, item.batch)
                    with sp(spans.DEVICE_WAIT, batch=bid):
                        ready = jax.block_until_ready(item.out)
                    with sp(spans.FETCH, batch=bid):
                        out_np = np.asarray(ready)
                    with sp(spans.UNPACK, batch=bid):
                        results = self._unpack_fn(item.batch, out_np)
                except Exception as exc:
                    err = exc
                t_ready = time.perf_counter()
                # marginal device time on THIS device: overlapped batches
                # in the staging pipe are not double-counted
                device_s = t_ready - max(item.t_dispatch, last_ready)
                last_ready = t_ready
                current = None      # _finish resolves it (even if the
                # engine callback then raises, the batch is accounted)
                self._finish(CompletedBatch(
                    queue=item.queue, batch=item.batch, results=results,
                    err=err, t_build_start=item.t_build_start,
                    t_dispatch=item.t_dispatch, t_ready=t_ready,
                    device_s=device_s, params_version=item.params_version))
        except BaseException as exc:
            self._loop_fatal(exc, current)
            raise
