"""Real-time multi-queue streaming inference engine (the serving facade).

The paper's extended title is "Universal GNN Inference via Multi-Queue
Streaming": a bank of independent queues drains into parallel processing
elements with no global synchronization. Since the scheduler/executor
split (DESIGN.md §5) this module is a thin facade over exactly that
decomposition:

  * a ``BatchScheduler`` (``core/scheduler.py``) — named multi-tenant
    queues with weighted-fair draining, each layered over its own
    ``GraphPacker`` with per-queue ``max_wait`` deadlines and batch
    budgets; a bulk tenant cannot starve a latency-sensitive one;
  * a ``DeviceExecutor`` pool (``core/executor.py``) — one executor per
    ``jax.devices()`` entry, each owning a committed params replica, its
    own per-bucket compiled-program namespace, and its own double-buffered
    dispatch/complete thread pair; a placer thread assigns each flushed
    batch to the executor with the least backlog;
  * this facade — ``submit`` returns a ``Future`` per graph that resolves
    *incrementally* the moment its batch completes on whichever device
    served it (streaming results: ``drain`` is backpressure, not a
    results barrier); ``process``/``drain``/``close``/``warmup_all`` keep
    their original signatures, and ``StreamStats`` adds per-queue and
    per-device breakdowns next to the global figures.

Result parity is part of the contract: the same graph produces the
identical output whichever queue it entered through and whichever device
served it (the executors run the same program on committed replicas;
tests/test_scheduler_executor.py pins 1-device vs N-device streams
bitwise). Per-bucket autotuning is shared across the (homogeneous) pool
and its JSON cache is namespaced by backend + device kind so winners
tuned on one topology are never silently replayed on another.

On top of that sits the failure-semantics layer (DESIGN.md §8): every
submission is tracked in a request registry so its Future resolves
*exactly once* no matter which failure path fires; failed batches retry
with bounded exponential backoff on a different executor, then bisect
(same bucket — no recompile, bitwise-stable survivors) until the poison
graph is isolated and only ITS future fails (``PoisonGraph``); a
non-finite output quarantines its graph instead of returning garbage;
dead executors leave the rotation (``pool_degraded``), their work
re-places on survivors, and they optionally respawn; per-request
deadlines shed expired work before dispatch (``DeadlineExceeded``) and an
in-flight watchdog fails batches stuck inside an executor; ``drain`` and
``close`` accept timeouts after which remaining futures fail with
``ExecutorDead`` rather than strand. Chaos is injectable and seeded
(``core/faults.py``) so all of this is reproducibly testable.
"""

from __future__ import annotations

import heapq
import itertools
import json
import os
import queue as queue_lib
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import spans
from repro.core.errors import (BatchFailed, DeadlineExceeded, EngineClosed,
                               EngineError, ExecutorDead, GraphTooLarge,
                               InvalidGraph, InvalidRequest, ParamUpdateFailed,
                               PoisonGraph, UnknownQueue)
from repro.core.executor import CompletedBatch, DeviceExecutor
from repro.core.faults import FaultInjector
from repro.core.graph import FlatLayout, pad_bucket
from repro.core.message_passing import (DEFAULT_DATAFLOW, DataflowConfig,
                                        count_edge_passes)
from repro.core.models import GNNConfig, make_gnn
from repro.core.packing import PackedBatch, PackItem
from repro.core.scheduler import BatchScheduler, QueueConfig
from repro.core.validate import check_budget, check_graph, check_graph_nodes
from repro.distributed.sharding import (device_kind, params_compatible,
                                        replicate_params)
from repro.distributed.wide import (WidePlan, WidePlanError, build_wide_forward,
                                    plan_wide, stack_shard_arrays, wide_mesh)

BucketKey = Tuple[int, int, int]        # (node_pad, edge_pad, graph_pad)

DEFAULT_QUEUE = "default"


@dataclass
class StreamStats:
    """Per-graph latency plus queue/device breakdowns.

    ``latencies_s``/``queue_wait_s`` have one entry per *graph*;
    ``device_s``/``batch_sizes`` have one entry per dispatched *batch*
    (``device_s`` is marginal device-busy time per executor, so overlapped
    batches on one device are not double counted and
    ``sum(batch_sizes)/sum(device_s)`` is graphs per device-busy-second —
    across a pool, the per-device average). ``by_queue``/``by_device``
    hold the same shape of stats sliced per tenant queue and per executor
    device; ``aggregate_gps`` in ``summary()`` is the pool-level wall
    figure (graphs / span from first dispatch to last completion).

    Failure accounting (DESIGN.md §8): ``retries`` counts batch
    re-placements (transient retry, executor-death requeue, and each
    bisection half), ``quarantined`` counts graphs failed as poison
    (exhausted retries or non-finite output), ``shed_deadline`` counts
    graphs dropped before dispatch because their deadline passed,
    ``failed`` counts futures resolved with an error for any reason.
    ``executor_deaths``/``respawns`` track supervision; ``pool_degraded``
    is sticky-true from the first death until a respawn restores the full
    pool.

    Load accounting (DESIGN.md §5): ``preemptions`` counts bulk batches
    split by a priority tenant's preempt window, ``retunes`` counts
    drift-triggered re-autotunes, and ``program_evictions`` counts compiled
    programs dropped by the per-executor LRU cap — none of these are
    failures; they are how the engine absorbs traffic it was not tuned
    for, surfaced so overload benches and tests can assert they fired.

    Defense accounting (DESIGN.md §9): ``invalid_rejects`` counts graphs
    rejected at admission validation (``InvalidGraph``),
    ``audits``/``audit_mismatches``/``audit_dropped`` track the shadow
    auditor (sampled re-execution on the jnp mirror),
    ``breaker_trips``/``breaker_probes`` track the per-bucket impl
    circuit breaker's demotions and cooldown re-probes, and
    ``param_updates``/``param_rollbacks`` count hot parameter reloads
    promoted vs rejected (canary failure / incompatible tree).

    Transfer accounting (DESIGN.md §5): ``h2d_transfers``/``h2d_bytes``
    count the host→device puts of dispatched batches — one per batch, of
    its ``FlatLayout`` buffer.
    """

    latencies_s: List[float] = field(default_factory=list)
    queue_wait_s: List[float] = field(default_factory=list)
    device_s: List[float] = field(default_factory=list)
    batch_sizes: List[int] = field(default_factory=list)
    t_first_dispatch: Optional[float] = None
    t_last_done: Optional[float] = None
    by_queue: Dict[str, "StreamStats"] = field(default_factory=dict)
    by_device: Dict[str, "StreamStats"] = field(default_factory=dict)
    retries: int = 0
    quarantined: int = 0
    shed_deadline: int = 0
    failed: int = 0
    executor_deaths: int = 0
    respawns: int = 0
    pool_degraded: bool = False
    preemptions: int = 0
    retunes: int = 0
    program_evictions: int = 0
    invalid_rejects: int = 0
    audits: int = 0
    audit_mismatches: int = 0
    audit_dropped: int = 0
    breaker_trips: int = 0
    breaker_probes: int = 0
    param_updates: int = 0
    param_rollbacks: int = 0
    h2d_transfers: int = 0
    h2d_bytes: int = 0

    def record_batch(self, *, latencies: Sequence[float],
                     queue_waits: Sequence[float], device_s: float,
                     batch_size: int, t_dispatch: float, t_done: float,
                     queue: Optional[str] = None,
                     device: Optional[str] = None) -> None:
        self.latencies_s.extend(latencies)
        self.queue_wait_s.extend(queue_waits)
        self.device_s.append(device_s)
        self.batch_sizes.append(batch_size)
        if self.t_first_dispatch is None or t_dispatch < self.t_first_dispatch:
            self.t_first_dispatch = t_dispatch
        if self.t_last_done is None or t_done > self.t_last_done:
            self.t_last_done = t_done
        if queue is not None:
            self.by_queue.setdefault(queue, StreamStats()).record_batch(
                latencies=latencies, queue_waits=queue_waits,
                device_s=device_s, batch_size=batch_size,
                t_dispatch=t_dispatch, t_done=t_done)
        if device is not None:
            self.by_device.setdefault(device, StreamStats()).record_batch(
                latencies=latencies, queue_waits=queue_waits,
                device_s=device_s, batch_size=batch_size,
                t_dispatch=t_dispatch, t_done=t_done)

    def record_failure(self, *, queue: Optional[str] = None, retries: int = 0,
                       quarantined: int = 0, shed: int = 0, failed: int = 0
                       ) -> None:
        self.retries += retries
        self.quarantined += quarantined
        self.shed_deadline += shed
        self.failed += failed
        if queue is not None:
            self.by_queue.setdefault(queue, StreamStats()).record_failure(
                retries=retries, quarantined=quarantined, shed=shed,
                failed=failed)

    @property
    def _has_failures(self) -> bool:
        return bool(self.retries or self.quarantined or self.shed_deadline
                    or self.failed or self.executor_deaths or self.respawns
                    or self.pool_degraded)

    @property
    def _has_load_events(self) -> bool:
        return bool(self.preemptions or self.retunes
                    or self.program_evictions)

    @property
    def _has_defense_events(self) -> bool:
        return bool(self.invalid_rejects or self.audits
                    or self.audit_mismatches or self.audit_dropped
                    or self.breaker_trips or self.breaker_probes
                    or self.param_updates or self.param_rollbacks)

    def summary(self) -> Dict[str, Any]:
        if not self.latencies_s:
            if (not self._has_failures and not self._has_load_events
                    and not self._has_defense_events):
                return {}
            out: Dict[str, Any] = {}
            self._failure_summary(out)
            self._load_summary(out)
            self._defense_summary(out)
            return out
        arr = np.array(self.latencies_s)
        out: Dict[str, Any] = {
            "count": float(arr.size),
            "mean_ms": float(arr.mean() * 1e3),
            "p50_ms": float(np.percentile(arr, 50) * 1e3),
            "p90_ms": float(np.percentile(arr, 90) * 1e3),
            "p99_ms": float(np.percentile(arr, 99) * 1e3),
        }
        if self.queue_wait_s:
            qw = np.array(self.queue_wait_s)
            out["queue_wait_mean_ms"] = float(qw.mean() * 1e3)
            out["queue_wait_p99_ms"] = float(np.percentile(qw, 99) * 1e3)
        if self.device_s and sum(self.device_s) > 0:
            # batch-aware throughput: graphs per second of device-busy time,
            # NOT batches/s and NOT inflated by per-graph queue waits.
            out["device_mean_ms"] = float(np.mean(self.device_s) * 1e3)
            out["throughput_gps"] = float(
                sum(self.batch_sizes) / sum(self.device_s))
            out["mean_batch_size"] = float(np.mean(self.batch_sizes))
        else:
            out["throughput_gps"] = float(arr.size / arr.sum())
        if (self.t_first_dispatch is not None
                and self.t_last_done is not None
                and self.t_last_done > self.t_first_dispatch):
            # pool-level wall throughput: with D busy executors this is
            # ~D x the per-device figure (the multi-device acceptance
            # metric); on one device it tracks throughput_gps.
            out["aggregate_gps"] = float(
                sum(self.batch_sizes)
                / (self.t_last_done - self.t_first_dispatch))
        if self.h2d_transfers:
            out["h2d_transfers"] = int(self.h2d_transfers)
            out["h2d_bytes"] = int(self.h2d_bytes)
        self._failure_summary(out)
        self._load_summary(out)
        self._defense_summary(out)
        if self.by_queue:
            out["queues"] = {name: s.summary()
                             for name, s in sorted(self.by_queue.items())}
        if self.by_device:
            out["devices"] = {name: s.summary()
                              for name, s in sorted(self.by_device.items())}
        return out

    def _failure_summary(self, out: Dict[str, Any]) -> None:
        if not self._has_failures:
            return
        out["retries"] = int(self.retries)
        out["quarantined_graphs"] = int(self.quarantined)
        out["shed_deadline"] = int(self.shed_deadline)
        out["failed"] = int(self.failed)
        out["executor_deaths"] = int(self.executor_deaths)
        out["respawns"] = int(self.respawns)
        out["pool_degraded"] = bool(self.pool_degraded)

    def _load_summary(self, out: Dict[str, Any]) -> None:
        if not self._has_load_events:
            return
        out["preemptions"] = int(self.preemptions)
        out["retunes"] = int(self.retunes)
        out["program_evictions"] = int(self.program_evictions)

    def _defense_summary(self, out: Dict[str, Any]) -> None:
        if not self._has_defense_events:
            return
        out["invalid_graphs"] = int(self.invalid_rejects)
        out["audits"] = int(self.audits)
        out["audit_mismatches"] = int(self.audit_mismatches)
        out["audit_dropped"] = int(self.audit_dropped)
        out["breaker_trips"] = int(self.breaker_trips)
        out["breaker_probes"] = int(self.breaker_probes)
        out["param_updates"] = int(self.param_updates)
        out["param_rollbacks"] = int(self.param_rollbacks)


@dataclass
class _Request:
    """Engine-side payload attached to each PackItem.

    ``req_id`` keys the engine's request registry — the single authority
    over whether a future is still outstanding, which is what makes
    resolution exactly-once across every completion/failure path.
    ``deadline_t`` is an absolute ``perf_counter`` deadline (``None`` =
    no deadline).
    """

    future: Future
    record: bool
    req_id: int = -1
    queue: str = DEFAULT_QUEUE
    deadline_t: Optional[float] = None
    dispatched: bool = False     # on a device now: not sheddable


@dataclass
class _Inflight:
    """One placed batch in the engine's in-flight registry (watchdog)."""

    queue: str
    batch: PackedBatch
    ex: "DeviceExecutor"
    t_placed: float


@dataclass
class _WideRequest:
    """One oversized graph awaiting (or holding) a K-executor gang.

    Wide requests bypass the packer — an oversized graph is its own
    "batch" by construction — but share the request registry, per-queue
    admission caps, and stats with narrow traffic. ``plan`` is computed at
    ``submit`` (one O(E) numpy pass; also where over-budget graphs are
    rejected as ``GraphTooLarge``), so the placer only has to find a gang
    window. ``attempts``/``requeues`` mirror the narrow batch retry
    bookkeeping: a transient failure retries on a fresh gang with backoff;
    a gang-member death re-places the whole gang without charging the
    retry budget.
    """

    req: _Request
    plan: WidePlan
    node_feat: np.ndarray
    edge_feat: Optional[np.ndarray]
    node_pos: Optional[np.ndarray]
    t_arrival: float
    attempts: int = 0
    requeues: int = 0


@dataclass
class _BucketLoad:
    """Per-bucket running traffic stats driving drift re-autotune (§5).

    EWMAs (window = ``drift_window`` batches) of the batch fill, the
    marginal device time, and the inter-completion gap (an arrival-rate
    proxy) are compared against the *tuned envelope*: ``tuned_device_s``
    is the autotune winner's timed best, ``tuned_fill`` the fill of the
    first batch served after (re)tuning — the regime the winner was picked
    for. When traffic leaves that envelope (device time inflated beyond
    ``drift_device_factor``, or fill drifted beyond ``drift_fill_factor``
    either way) the bucket's winner is invalidated and the next batch
    re-runs the autotune search — bounded by ``max_retunes`` per bucket
    and ``drift_cooldown_s`` between tunes, so a noisy bucket can never
    thrash the compile lock.
    """

    batches: int = 0
    graphs: int = 0
    ewma_fill: Optional[float] = None
    ewma_device_s: Optional[float] = None
    ewma_gap_s: Optional[float] = None
    last_seen_t: Optional[float] = None
    tuned_fill: Optional[float] = None
    tuned_device_s: Optional[float] = None
    batches_since_tune: int = 0
    last_tune_t: float = float("-inf")
    retunes: int = 0
    last_reason: Optional[str] = None


#: degradation-ladder floor: the unfused jnp mirror (DESIGN.md §9) — the
#: same program the shadow auditor uses as its reference, so a bucket at
#: the floor cannot, by construction, fail an audit.
_JNP_RUNG = 3


@dataclass
class _BucketHealth:
    """Per-bucket circuit-breaker ledger (DESIGN.md §9).

    ``level`` is how many rungs BELOW its tuned impl the bucket currently
    serves on (0 = healthy, serving the tuned winner). Trips — NaN-gate
    quarantines, trace/compile failures, shadow-audit mismatches — demote
    one rung at a time down the ladder ``fused_layer → pipeline →
    single-pass jnp → unfused jnp``; the bucket stays servable at every
    rung. After ``breaker_cooldown_s`` without a trip the breaker
    half-opens: it promotes one rung back up and marks the bucket
    ``probing``, which forces the next completions through the shadow
    auditor — a clean audit confirms the probe, a mismatch re-demotes and
    restarts the cooldown. ``probes`` is bounded by ``breaker_max_probes``
    so a permanently-broken impl cannot oscillate forever.
    """

    level: int = 0
    trips: int = 0
    probes: int = 0
    probing: bool = False
    last_trip_t: float = float("-inf")
    last_reason: Optional[str] = None


def _resolve(fut: Future, result=None, exc: Optional[BaseException] = None
             ) -> None:
    """Resolve a submission future, tolerating caller-side cancellation.

    Queued futures are CANCELLABLE until their batch resolves (they are
    never marked running earlier): if the caller cancelled, just drop the
    result instead of letting InvalidStateError kill a worker thread.
    """
    if not fut.set_running_or_notify_cancel():
        return
    if exc is not None:
        fut.set_exception(exc)
    else:
        fut.set_result(result)


class GraphStreamEngine:
    """Compile-once-per-bucket serving: scheduler -> executor-pool facade."""

    def __init__(self, cfg: GNNConfig, params,
                 dataflow: DataflowConfig = DEFAULT_DATAFLOW,
                 buckets: Tuple[int, ...] = (32, 64, 128, 256, 512, 1024),
                 *,
                 max_batch: int = 8,
                 max_wait_ms: float = 2.0,
                 max_nodes_per_batch: Optional[int] = None,
                 max_edges_per_batch: Optional[int] = None,
                 eager_flush: bool = True,
                 autotune: bool = False,
                 autotune_cache: Optional[str] = None,
                 max_autotune: int = 5,
                 max_pending: int = 4096,
                 queues: Optional[Sequence[QueueConfig]] = None,
                 preempt: bool = True,
                 preempt_chunk: int = 4,
                 preempt_horizon_ms: float = 20.0,
                 max_cached_programs: Optional[int] = 128,
                 drift_window: int = 32,
                 drift_device_factor: float = 3.0,
                 drift_fill_factor: float = 2.0,
                 drift_cooldown_s: float = 2.0,
                 max_retunes: int = 2,
                 devices: Optional[Sequence[Any]] = None,
                 max_retries: int = 1,
                 retry_backoff_ms: float = 1.0,
                 retry_backoff_max_ms: float = 50.0,
                 validate_outputs: bool = True,
                 inflight_timeout_s: Optional[float] = None,
                 respawn_executors: bool = False,
                 fault_injector: Optional[FaultInjector] = None,
                 validate_inputs: bool = True,
                 require_finite: bool = False,
                 audit_sample_rate: float = 0.0,
                 audit_rtol: float = 1e-3,
                 audit_atol: float = 1e-5,
                 audit_seed: int = 0,
                 breaker: bool = True,
                 breaker_cooldown_s: float = 1.0,
                 breaker_max_probes: int = 2,
                 wide: bool = False,
                 wide_k: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.dataflow = dataflow
        self.buckets = buckets
        self.model = make_gnn(cfg)
        self.stats = StreamStats()
        # passes-over-edges per compiled bucket (the paper's headline
        # dataflow property), recorded once at trace time per bucket
        self.edge_passes: Dict[BucketKey, int] = {}

        queue_cfgs = (tuple(queues) if queues is not None
                      else (QueueConfig(DEFAULT_QUEUE),))
        self._scheduler = BatchScheduler(
            queue_cfgs,
            default_max_batch=max_batch,
            default_max_wait_s=max_wait_ms * 1e-3,
            buckets=buckets,
            default_max_nodes=max_nodes_per_batch,
            default_max_edges=max_edges_per_batch,
            preempt_chunk=(int(preempt_chunk) if preempt else None),
            preempt_horizon_s=preempt_horizon_ms * 1e-3)
        self._eager_flush = eager_flush
        # admission backpressure is PER TENANT: a bulk queue pinned at its
        # cap must not block a latency queue's submissions
        self._queue_caps = {qc.name: (qc.max_pending
                                      if qc.max_pending is not None
                                      else max_pending)
                            for qc in queue_cfgs}
        self._pending_by_queue = {qc.name: 0 for qc in queue_cfgs}
        # what admission reads on every submit, read once here
        self._default_queue = queue_cfgs[0].name
        self._node_budget = max(buckets)
        self._needs_edge_feat = cfg.edge_feat_dim != 1
        # edge_feat_dim 1 means "model takes no edge features": any
        # provided width is legal there (it is ignored), so the width
        # check only binds when the model consumes edge features
        self._graph_dims = dict(
            node_feat_dim=cfg.node_feat_dim,
            edge_feat_dim=cfg.edge_feat_dim if self._needs_edge_feat else None,
            pos_dim=cfg.pos_dim)

        # failure-semantics knobs (DESIGN.md §8)
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        self._max_retries = int(max_retries)
        self._retry_backoff_s = max(0.0, retry_backoff_ms) * 1e-3
        self._retry_backoff_max_s = max(0.0, retry_backoff_max_ms) * 1e-3
        self._validate_outputs = bool(validate_outputs)
        self._inflight_timeout_s = inflight_timeout_s
        self._respawn = bool(respawn_executors)
        self._faults = fault_injector

        # defense-in-depth knobs + state (DESIGN.md §9)
        self._validate_inputs = bool(validate_inputs)
        self._require_finite = bool(require_finite)
        if not 0.0 <= audit_sample_rate <= 1.0:
            raise ValueError("audit_sample_rate must be in [0, 1]")
        self._audit_rate = float(audit_sample_rate)
        self._audit_rtol = float(audit_rtol)
        self._audit_atol = float(audit_atol)
        self._breaker = bool(breaker)
        self._breaker_cooldown_s = max(0.0, float(breaker_cooldown_s))
        self._breaker_max_probes = max(0, int(breaker_max_probes))
        self._bucket_health: Dict[BucketKey, _BucketHealth] = {}
        self._served_impl: Dict[BucketKey, str] = {}
        # shadow auditor: bounded handoff queue + its own rng (sampling
        # decisions happen under self._cv, so one engine-owned stream is
        # deterministic per submission order)
        self._audit_q: Optional[queue_lib.Queue] = (
            queue_lib.Queue(maxsize=32) if self._audit_rate > 0 else None)
        self._audit_thread: Optional[threading.Thread] = None
        self._audit_rng = np.random.default_rng(int(audit_seed))
        self._audit_ref = None         # lazily-jitted jnp mirror
        self._audits_enqueued = 0
        self._audits_done = 0
        # versioned params (hot reload): in-flight batches pin the version
        # their executor snapshot at dispatch; the auditor looks host
        # trees up by version so late audits of pre-swap batches compare
        # against the params that actually served them
        self._param_version = 0
        self._params_by_version: Dict[int, Any] = {0: params}
        self._update_lock = threading.Lock()
        self._canary_run = None        # lazily-jitted default-df program
        self._layouts: Dict[BucketKey, FlatLayout] = {}
        self._h2d_lock = threading.Lock()     # dispatch threads count puts

        # executor pool: one per device, params committed per device
        self._devices = (list(devices) if devices is not None
                         else list(jax.devices()))
        if not self._devices:
            raise ValueError("at least one device is required")
        self._executors = [
            self._make_executor(d, i, p)
            for i, (d, p) in enumerate(
                zip(self._devices, replicate_params(params, self._devices)))]
        # executor-death requeues are bounded separately from poison
        # retries: one hop per surviving executor plus slack covers any
        # cascade of deaths without looping forever when the pool is gone
        self._max_requeues = 2 * len(self._devices) + 2

        # wide placement (DESIGN.md §10): one oversized graph split across
        # a gang of K executors. State under self._cv except the program
        # cache (under _compile_lock like the narrow caches).
        self._wide_enabled = bool(wide)
        self._wide_k = (int(wide_k) if wide_k is not None
                        else len(self._devices))
        if self._wide_enabled:
            if self._wide_k < 2:
                raise ValueError("wide placement needs wide_k >= 2")
            if self._wide_k > len(self._devices):
                raise ValueError(
                    f"wide_k={self._wide_k} exceeds the pool size "
                    f"{len(self._devices)}")
        self._wide_queue: List[_WideRequest] = []
        self._wide_reserved: set = set()       # executor indices gang-held
        self._wide_running = 0
        self._wide_programs: Dict[Tuple[Any, ...], Any] = {}

        # autotune state; compiled programs live per executor (the
        # ``_compiled`` facade below merges them — its name is part of the
        # observable surface: tests assert compile-count stays bounded)
        self._compile_lock = threading.RLock()
        self._autotune = autotune
        self._autotune_cache = autotune_cache
        self._max_autotune = max(1, int(max_autotune))
        self._tuned: Dict[BucketKey, DataflowConfig] = {}
        self._tune_log: Dict[BucketKey, Dict[str, Any]] = {}
        self._load_autotune_cache()

        # drift detection + LRU program eviction (DESIGN.md §5): per-bucket
        # running stats under self._cv; eviction state under _compile_lock.
        if max_cached_programs is not None and max_cached_programs < 1:
            raise ValueError("max_cached_programs must be >= 1 or None")
        self._max_cached_programs = max_cached_programs
        self._drift_window = max(1, int(drift_window))
        self._drift_device_factor = float(drift_device_factor)
        self._drift_fill_factor = max(1.0, float(drift_fill_factor))
        self._drift_cooldown_s = max(0.0, float(drift_cooldown_s))
        self._max_retunes = max(0, int(max_retunes))
        self._bucket_load: Dict[BucketKey, _BucketLoad] = {}
        self._evict_log: Dict[BucketKey, int] = {}
        self._touch = itertools.count(1)   # engine-wide LRU touch sequence

        # async machinery (threads started lazily on first submit)
        self._cv = threading.Condition()
        self._pending = 0          # submitted graphs not yet completed
        self._drain_requested = False
        self._closed = False
        self._stopped = False
        self._placer: Optional[threading.Thread] = None

        # failure-semantics state, all under self._cv:
        self._req_ids = itertools.count()         # next request id
        self._requests: Dict[int, _Request] = {}  # outstanding futures
        self._retry_heap: List[Tuple[float, int, str, PackedBatch,
                                     Optional[int]]] = []
        self._retry_seq = 0
        self._dispatch_seq = 0
        self._inflight: Dict[int, _Inflight] = {}
        self._deadline_heap: List[Tuple[float, int]] = []
        self._deadlines_used = False
        self._supervised: set = set()             # id(ex) already handled
        self._watchdog: Optional[threading.Thread] = None
        self._watchdog_stop = threading.Event()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    @property
    def queue_names(self) -> Tuple[str, ...]:
        return self._scheduler.queue_names

    @property
    def num_devices(self) -> int:
        return len(self._executors)

    @property
    def _compiled(self) -> Dict[BucketKey, Any]:
        """Merged per-executor program caches (observable compile surface).

        A bucket appears once it is compiled on at least one executor; the
        per-device namespaces themselves live on the executors."""
        merged: Dict[BucketKey, Any] = {}
        for ex in self._executors:
            merged.update(ex.compiled)
        return merged

    def submit(self, node_feat: np.ndarray, senders: np.ndarray,
               receivers: np.ndarray, edge_feat: Optional[np.ndarray] = None,
               node_pos: Optional[np.ndarray] = None,
               record: bool = True, queue: Optional[str] = None,
               deadline: Optional[float] = None) -> Future:
        """Enqueue one arriving graph; the Future resolves to ITS prediction.

        Graph-level tasks resolve to a ``(out_dim,)`` vector; node-level
        tasks to the ``(n_nodes, out_dim)`` rows of this graph only. The
        future resolves the moment its batch completes on whichever device
        served it — results stream; ``drain`` is not a results barrier.
        ``queue`` names the tenant queue (see ``QueueConfig``); ``None``
        routes to the engine's default tenant — the FIRST configured
        queue — which also serves ``process``/``warmup`` traffic. A named
        queue must exist exactly (no silent remapping: a typo raises).
        Blocks (backpressure) while THIS tenant's ``max_pending`` graphs
        are outstanding — one queue at its cap never blocks another's
        admission. ``deadline`` is a per-request budget in seconds from
        enqueue: work whose deadline expires before it is dispatched is
        shed and its future fails with ``DeadlineExceeded`` — expired
        graphs never spend device time (DESIGN.md §8). The deadline clock
        starts at enqueue, BEFORE admission: a deadline'd request blocked
        at backpressure waits at most its remaining budget, then fails
        fast instead of burning the whole budget in the admission queue —
        an already-expired request is never admitted, let alone
        dispatched.
        """
        with spans.span(spans.SUBMIT) as span:
            return self._submit(span, node_feat, senders, receivers,
                                edge_feat, node_pos, record, queue, deadline)

    def _submit(self, span, node_feat, senders, receivers, edge_feat,
                node_pos, record, queue, deadline) -> Future:
        if edge_feat is None and self._needs_edge_feat:
            raise InvalidRequest("model expects edge features")
        if deadline is not None and deadline <= 0:
            raise InvalidRequest("deadline must be > 0 seconds")
        if self._closed:        # don't spin up worker threads just to reject
            raise EngineClosed("engine is closed")
        caps = self._queue_caps
        if queue is None:
            queue = self._default_queue
        elif queue not in caps:
            raise UnknownQueue(f"unknown queue '{queue}'; "
                               f"have {sorted(caps)}")
        req_id = next(self._req_ids)
        span.set_metadata(req=req_id)
        if self._faults is not None:
            self._faults.on_submit(req_id)       # may raise InjectedOOM
            # chaos site: a "buggy client" corrupts its own arrays BEFORE
            # admission validation — which must then reject them
            node_feat, senders, receivers, edge_feat = (
                self._faults.corrupt_input(req_id, node_feat, senders,
                                           receivers, edge_feat))
        node_feat = np.asarray(node_feat)
        senders = np.asarray(senders)
        receivers = np.asarray(receivers)
        if self._validate_inputs:
            # defense layer 1 (DESIGN.md §9): cheap vectorized admission
            # checks; a malformed graph fails HERE, typed and carrying its
            # request id, instead of poisoning a packed batch downstream
            with spans.span(spans.VALIDATE):
                reason = check_graph(
                    node_feat, senders, receivers, edge_feat, node_pos,
                    **self._graph_dims, require_finite=self._require_finite)
            if reason is not None:
                raise self._refused(InvalidGraph(reason,
                                                 request_ids=(req_id,)))
        n_nodes = node_feat.shape[0]
        n_edges = senders.shape[0]
        # the model's own per-graph limit (GPS's attention slot): checked
        # whatever validate_inputs says, as nothing downstream can serve it
        reason = check_graph_nodes(n_nodes, self.cfg.attention_slot)
        if reason is not None:
            raise self._refused(InvalidGraph(reason, request_ids=(req_id,)))
        # single-device budget gate (DESIGN.md §10): a graph no bucket can
        # hold is servable only by splitting it across a gang of executors
        wide_plan: Optional[WidePlan] = None
        if n_nodes > self._node_budget:
            reason = check_budget(n_nodes, n_edges,
                                  node_budget=self._node_budget,
                                  wide_enabled=self._wide_enabled)
            if not self._wide_enabled:
                raise self._refused(GraphTooLarge(reason,
                                                  request_ids=(req_id,)))
            try:
                wide_plan = plan_wide(senders, receivers, n_nodes,
                                      k=self._wide_k,
                                      node_budget=self._node_budget)
            except WidePlanError as exc:
                raise self._refused(GraphTooLarge(
                    f"graph does not fit a {self._wide_k}-shard wide "
                    f"split: {exc}", request_ids=(req_id,))) from exc
        t_arrival = time.perf_counter()
        deadline_t = None if deadline is None else t_arrival + deadline
        fut: Future = Future()
        req = _Request(future=fut, record=record, req_id=req_id, queue=queue,
                       deadline_t=deadline_t)
        item = (None if wide_plan is not None else
                PackItem(node_feat=node_feat, senders=senders,
                         receivers=receivers, edge_feat=edge_feat,
                         node_pos=node_pos, payload=req, t_arrival=t_arrival,
                         num_nodes=n_nodes, num_edges=n_edges))
        self._ensure_threads()
        cap = caps[queue]
        pending = self._pending_by_queue
        with self._cv:
            if pending[queue] >= cap and not self._closed:
                admitted = lambda: pending[queue] < cap or self._closed
                if deadline_t is None:
                    self._cv.wait_for(admitted)
                else:
                    # the admission-vs-deadline hole (DESIGN.md §8): the
                    # deadline clock started at t_arrival, so the wait is
                    # bounded by the REMAINING budget — wait_for re-arms
                    # across spurious wakeups until room or timeout
                    self._cv.wait_for(
                        admitted,
                        timeout=max(deadline_t - time.perf_counter(), 0.0))
            if self._closed:
                raise EngineClosed("engine is closed")
            if deadline_t is not None and (
                    pending[queue] >= cap
                    or time.perf_counter() >= deadline_t):
                # budget burned at backpressure (or expired the instant
                # room appeared): shed now — never admit, never dispatch
                self.stats.record_failure(queue=queue, shed=1, failed=1)
                expired = True
            else:
                expired = False
                self._pending += 1
                pending[queue] += 1
                self._requests[req_id] = req
                if deadline_t is not None:
                    self._deadlines_used = True
                    heapq.heappush(self._deadline_heap, (deadline_t, req_id))
                if wide_plan is not None:
                    self._wide_queue.append(_WideRequest(
                        req=req, plan=wide_plan,
                        node_feat=np.asarray(node_feat, np.float32),
                        edge_feat=(None if edge_feat is None else
                                   np.asarray(edge_feat, np.float32)),
                        node_pos=(None if node_pos is None else
                                  np.asarray(node_pos, np.float32)),
                        t_arrival=t_arrival))
                else:
                    self._scheduler.add(queue, item, now=t_arrival)
            self._cv.notify_all()
        if expired:
            _resolve(fut, exc=DeadlineExceeded(
                "deadline expired at admission backpressure",
                request_ids=(req_id,)))
        return fut

    def _refused(self, exc: Exception) -> Exception:
        """Count one graph refused at admission; returns ``exc`` to raise."""
        with self._cv:
            self.stats.invalid_rejects += 1
        return exc

    def process(self, node_feat: np.ndarray, senders: np.ndarray,
                receivers: np.ndarray, edge_feat: Optional[np.ndarray] = None,
                node_pos: Optional[np.ndarray] = None,
                record: bool = True) -> np.ndarray:
        """Synchronous batch-1 serving: submit one graph, wait for its result."""
        return self.submit(node_feat, senders, receivers, edge_feat, node_pos,
                           record=record).result()

    def drain(self, timeout: Optional[float] = None) -> None:
        """Flush all open batches and wait until every submission completes.

        Futures resolve incrementally as their batches complete — drain is
        a convenience barrier for callers that want the whole stream done,
        not a prerequisite for reading any individual result.

        With ``timeout``, drain is BOUNDED even if an executor wedges: on
        expiry every still-outstanding future fails with ``ExecutorDead``
        (no caller is ever stranded on ``.result()``), then
        ``TimeoutError`` is raised. Completions arriving after the
        timeout are ignored via the request registry.
        """
        with self._cv:
            if self._placer is None:            # nothing ever submitted
                return
            self._drain_requested = True
            self._cv.notify_all()
            done = self._cv.wait_for(lambda: self._pending == 0, timeout)
            self._drain_requested = False
            victims = ([] if done else self._abandon_outstanding_locked())
        if not done:
            exc = ExecutorDead(
                "drain timed out; outstanding work abandoned",
                request_ids=tuple(r.req_id for r in victims))
            for req in victims:
                _resolve(req.future, exc=exc)
            raise TimeoutError("drain timed out")

    def close(self, timeout: Optional[float] = None) -> None:
        """Drain, stop the worker threads, and reject further submissions.

        Idempotent, and safe after a worker crash (which marks the engine
        closed itself): each executor still gets its sentinel. With
        ``timeout``, each join/stop is bounded; work still outstanding
        after the budget fails with ``ExecutorDead`` instead of stranding
        its caller (wedged daemon threads are abandoned).
        """
        with self._cv:
            self._closed = True
            already_stopped = self._stopped
            self._stopped = True
            self._cv.notify_all()
        if self._placer is not None and not already_stopped:
            self._placer.join(timeout)
            for ex in self._executors:
                ex.stop(timeout=timeout)
            self._watchdog_stop.set()
            if self._audit_thread is not None:
                self._audit_q.put(None)        # sentinel: drain then exit
                self._audit_thread.join(timeout)
        with self._cv:
            victims = self._abandon_outstanding_locked()
        if victims:
            exc = ExecutorDead(
                "engine closed before completion",
                request_ids=tuple(r.req_id for r in victims))
            for req in victims:
                _resolve(req.future, exc=exc)

    def _abandon_outstanding_locked(self) -> List[_Request]:
        """Pop EVERY outstanding request (scheduler-held, retrying, and
        in-flight) so its future can be failed; late completions of
        abandoned work become registry misses and are dropped. Must be
        called under ``self._cv``; resolution happens outside it."""
        self._scheduler.flush_all()
        self._retry_heap.clear()
        self._inflight.clear()
        self._wide_queue.clear()
        victims = list(self._requests.values())
        self._requests.clear()
        for req in victims:
            self._pending -= 1
            if req.queue in self._pending_by_queue:
                self._pending_by_queue[req.queue] -= 1
        if victims:
            self.stats.record_failure(failed=len(victims))
        self._cv.notify_all()
        return victims

    def __enter__(self) -> "GraphStreamEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def warmup(self, node_feat, senders, receivers, edge_feat=None,
               node_pos=None) -> None:
        """Pre-compile the bucket of one representative arriving graph."""
        self.process(node_feat, senders, receivers, edge_feat, node_pos,
                     record=False)

    def warmup_all(self, pairs: Optional[List[Tuple[int, int]]] = None
                   ) -> List[BucketKey]:
        """Pre-compile (and, with autotune, tune) every configured bucket
        on EVERY executor.

        ``warmup`` only touches the arriving graph's bucket on one device,
        so the first graph landing in any other bucket — or placed on any
        other executor — still pays compile latency. This compiles the
        full (bucket x executor) table up front. ``pairs`` lists the
        (node_pad, edge_pad) combinations to prepare; the default pairs
        each node bucket with the next edge bucket up (``(b, 2b)``) — the
        shape a sparse graph stream (E ≈ 2N) lands in. Buckets are
        prepared for every distinct per-queue ``graph_pad``. Returns the
        bucket keys.
        """
        if pairs is None:
            pairs = [(b, pad_bucket(2 * b, self.buckets))
                     for b in self.buckets]
        keys = []
        for node_pad, edge_pad in pairs:
            for graph_pad in self._scheduler.graph_pads():
                key = (node_pad, edge_pad, graph_pad)
                flat = self._synthetic_batch(node_pad, edge_pad, graph_pad)
                for ex in self._executors:
                    ex.warm(key, jax.device_put(flat, ex.device))
                keys.append(key)
        return keys

    def autotune_report(self) -> Dict[str, Dict[str, Any]]:
        """Per-bucket chosen (num_banks, edge_tile, impl) + candidate
        timings (and, under ``failed``, why each candidate that could not
        run was dropped) + the device each bucket was tuned on, plus the
        bucket's observed-load envelope (EWMA fill / device time / arrival
        rate), drift re-tune count, and cold-program eviction count. Evicted
        buckets stay in the report — their tuning and history outlive the
        executable."""
        report: Dict[str, Dict[str, Any]] = {}
        with self._compile_lock:
            keys = (set(self._compiled) | set(self._tuned)
                    | set(self._tune_log) | set(self._bucket_load)
                    | set(self._evict_log) | set(self._bucket_health))
            for key in keys:
                df = self._tuned.get(key, self.dataflow)
                entry: Dict[str, Any] = {
                    "num_banks": df.num_banks,
                    "edge_tile": df.edge_tile,
                    "impl": df.impl,
                    "source": ("autotuned" if key in self._tune_log else
                               "cache" if key in self._tuned else "default"),
                }
                if key in self._tune_log:
                    entry.update(self._tune_log[key])
                load = self._bucket_load.get(key)
                if load is not None and load.batches:
                    entry["load"] = {
                        "batches": int(load.batches),
                        "graphs": int(load.graphs),
                        "ewma_fill": (None if load.ewma_fill is None
                                      else round(load.ewma_fill, 3)),
                        "ewma_device_us": (
                            None if load.ewma_device_s is None
                            else round(load.ewma_device_s * 1e6, 1)),
                        "arrival_hz": (
                            None if not load.ewma_gap_s
                            else round(1.0 / load.ewma_gap_s, 2)),
                        "retunes": int(load.retunes),
                        "last_retune_reason": load.last_reason,
                    }
                ev = self._evict_log.get(key)
                if ev:
                    entry["evictions"] = int(ev)
                health = self._bucket_health.get(key)
                if health is not None and (health.trips or health.probes):
                    entry["breaker"] = {
                        "level": int(health.level),
                        "trips": int(health.trips),
                        "probes": int(health.probes),
                        "probing": bool(health.probing),
                        "last_reason": health.last_reason,
                        "serving_impl": self._served_impl.get(key, df.impl),
                    }
                report["x".join(map(str, key))] = entry
        return report

    # ------------------------------------------------------------------
    # placer thread: weighted-fair drain -> least-backlog placement
    # ------------------------------------------------------------------

    def _ensure_threads(self) -> None:
        if self._placer is not None:
            return
        with self._cv:
            if self._placer is not None:
                return
            for ex in self._executors:
                ex.start()
            self._placer = threading.Thread(
                target=self._place_loop, name="flowgnn-placer", daemon=True)
            self._placer.start()
            if self._audit_q is not None and self._audit_thread is None:
                self._audit_thread = threading.Thread(
                    target=self._audit_loop, name="flowgnn-auditor",
                    daemon=True)
                self._audit_thread.start()
            if self._inflight_timeout_s is not None:
                self._watchdog = threading.Thread(
                    target=self._watchdog_loop, name="flowgnn-watchdog",
                    daemon=True)
                self._watchdog.start()

    def _place_loop(self) -> None:
        try:
            self._place_loop_inner()
        except BaseException as exc:   # never leave submitters hanging
            self._fail_scheduled(exc)
            raise

    def _place_loop_inner(self) -> None:
        while True:
            picked = None          # (queue_name, pb, exclude_index)
            to_fail: List[Tuple[_Request, BaseException]] = []
            # one span per pass of the placer, from its wake to the placed
            # batch's hand-off; a pass that places nothing ends at its wait
            place = spans.span(spans.PLACE).__enter__()
            with self._cv:
                while picked is None:
                    now = time.perf_counter()
                    self._scheduler.poll(now)
                    to_fail.extend(self._shed_scheduler_locked(now))
                    if to_fail:
                        break          # resolve outside the lock, re-enter
                    # wide gang scheduling (DESIGN.md §10): all-or-nothing
                    # reservation of K idle executors; on failure the wide
                    # request just stays queued (requeue semantics) while
                    # narrow traffic keeps flowing — and completions wake
                    # this loop, so a window is never missed
                    if self._wide_queue:
                        alive = sum(1 for ex in self._executors
                                    if not ex.dead)
                        if alive < self._wide_k and not self._respawn:
                            # the pool shrank below K and will not heal:
                            # waiting for a gang would strand the futures
                            to_fail.extend(self._fail_wide_queue_locked(
                                f"pool has {alive} live executors "
                                f"< wide_k={self._wide_k}"))
                            break
                        gang = self._try_reserve_gang_locked(now)
                        if gang is not None:
                            wreq = self._wide_queue.pop(0)
                            self._wide_running += 1
                            threading.Thread(
                                target=self._run_wide, args=(wreq, gang),
                                name="flowgnn-wide", daemon=True).start()
                            continue
                    has_cap = any(ex.has_capacity
                                  and ex.index not in self._wide_reserved
                                  for ex in self._executors)
                    # due retries jump the fairness queue: they are old
                    # work that has already been charged virtual time
                    if (has_cap and self._retry_heap
                            and self._retry_heap[0][0] <= now):
                        _, _, qn, pb, excl = heapq.heappop(self._retry_heap)
                        picked = (qn, pb, excl)
                        break
                    # pop from the scheduler only while some executor has
                    # pipeline room: excess backlog must queue HERE, where
                    # weighted fairness applies — not FIFO in an executor
                    # inbox where a late latency batch would sit behind
                    # the whole bulk backlog
                    # pipeline restraint (§5): while the preempt window is
                    # open, non-priority batches are claimed only when some
                    # executor is idle. Chunking alone is not enough — if
                    # chunks STACK in an executor's FIFO pipeline, the claim
                    # depth (PIPELINE_DEPTH x chunk time), not the chunk,
                    # bounds the next priority arrival's wait. Priority pops
                    # are never restrained, and a completion always wakes
                    # this loop, so restraint never deadlocks: when the last
                    # claimed batch finishes its executor goes idle.
                    restrained = (has_cap
                                  and self._scheduler.preempt_active(now)
                                  and not self._scheduler.priority_ready
                                  and not any(
                                      ex.idle for ex in self._executors
                                      if not ex.dead
                                      and ex.index not in
                                      self._wide_reserved))
                    if has_cap and not restrained:
                        nxt = self._scheduler.next_batch(now)
                        if nxt is not None:
                            picked = (nxt[0], nxt[1], None)
                            self.stats.preemptions = (
                                self._scheduler.preempt_splits)
                            break
                    if self._drain_requested or self._closed:
                        if self._scheduler.open_batches:
                            self._scheduler.poll(float("inf"))
                            continue
                        if (self._closed
                                and not self._scheduler.ready_batches
                                and not self._retry_heap):
                            place.__exit__(None, None, None)
                            return
                        # ready/retrying batches remain, no capacity (or a
                        # retry not yet due): wait below
                    elif (self._eager_flush and has_cap
                            and self._scheduler.open_batches
                            and any(ex.idle for ex in self._executors
                                    if ex.index not in
                                    self._wide_reserved)):
                        # an executor is idle: serving the oldest open batch
                        # NOW beats waiting out its deadline (adaptive
                        # batching: under load, batches fill while every
                        # device is busy)
                        nxt = self._scheduler.flush_oldest_open(now)
                        if nxt is not None:
                            picked = (nxt[0], nxt[1], None)
                            self.stats.preemptions = (
                                self._scheduler.preempt_splits)
                        break
                    wake = self._next_wake_locked(has_cap)
                    place.__exit__(None, None, None)
                    self._cv.wait(timeout=None if wake is None
                                  else max(wake - now, 0.0))
                    place = spans.span(spans.PLACE).__enter__()
                if picked is not None:
                    # last-moment shedding: expired members of the popped
                    # batch never reach a device
                    queue_name, pb, exclude = picked
                    pb, shed = self._shed_batch_locked(
                        pb, time.perf_counter())
                    to_fail.extend(shed)
                    picked = (None if pb is None
                              else (queue_name, pb, exclude))
            for req, exc in to_fail:
                _resolve(req.future, exc=exc)
            if picked is not None:
                placed = self._place(*picked)
                if placed is not None and place is not spans.OFF:
                    place.set_metadata(
                        batch=placed[0], dev=placed[1],
                        reqs=spans.id_list(it.payload.req_id
                                           for it in picked[1].items))
            place.__exit__(None, None, None)

    def _next_wake_locked(self, has_cap: bool) -> Optional[float]:
        """Earliest reason for the placer to wake: a packer flush
        deadline, a retry coming due (only useful with pipeline room —
        a completion notifies when capacity frees), or a request deadline
        to shed. Entries for requests already resolved or currently on a
        device are discarded lazily (a dispatched request can no longer
        be shed; if it requeues, pick-time shedding still covers it)."""
        cands = []
        d = self._scheduler.next_deadline()
        if d is not None:
            cands.append(d)
        if has_cap and self._retry_heap:
            cands.append(self._retry_heap[0][0])
        while self._deadline_heap:
            req = self._requests.get(self._deadline_heap[0][1])
            if req is None or req.dispatched:
                heapq.heappop(self._deadline_heap)
                continue
            cands.append(self._deadline_heap[0][0])
            break
        return min(cands) if cands else None

    def _place(self, queue_name: str, pb: PackedBatch,
               exclude: Optional[int] = None) -> Optional[Tuple[int, int]]:
        """Least-backlog placement across executors with pipeline room
        (ties: lowest index); dead executors are never chosen while an
        alive one exists, and a retry avoids the executor it failed on
        (``exclude``) when any alternative is alive. Returns the batch's
        dispatch id and its device's id, or ``None`` if it was not
        handed to an executor."""
        with self._cv:
            free = [ex for ex in self._executors
                    if ex.index not in self._wide_reserved]
            cands = ([ex for ex in free if ex.has_capacity]
                     or [ex for ex in free if not ex.dead])
            if exclude is not None:
                alt = [ex for ex in cands if ex.index != exclude]
                cands = alt or cands
            if not cands and any(not ex.dead for ex in self._executors):
                # every alive executor is gang-reserved: not a failure —
                # come back when the gang releases
                self._push_retry_locked(queue_name, pb, delay=0.001,
                                        exclude=exclude)
                return None
            if not cands:          # whole pool dead: nothing can run this
                reqs = self._take_requests_locked(pb)
                self.stats.record_failure(queue=queue_name, failed=len(reqs))
            else:
                ex = min(cands, key=lambda e: (e.backlog, e.index))
                dispatch_id = pb.dispatch_id = self._dispatch_seq
                self._dispatch_seq += 1
                self._inflight[pb.dispatch_id] = _Inflight(
                    queue=queue_name, batch=pb, ex=ex,
                    t_placed=time.perf_counter())
                for it in pb.items:
                    it.payload.dispatched = True
        if not cands:
            exc = ExecutorDead("no live executor to run batch",
                               request_ids=tuple(r.req_id for r in reqs))
            for req in reqs:
                _resolve(req.future, exc=exc)
            return None
        ex.submit(queue_name, pb)
        return dispatch_id, ex.device.id

    # ------------------------------------------------------------------
    # wide placement: gang scheduling + the gang runner (DESIGN.md §10)
    # ------------------------------------------------------------------

    def _try_reserve_gang_locked(self, now: float
                                 ) -> Optional[List[DeviceExecutor]]:
        """Atomically reserve K idle executors for a wide request, or
        ``None`` (request stays queued). Must be called under ``self._cv``.

        All-or-nothing: a partial hold would deadlock against narrow
        traffic (and against a second wide request), so nothing is
        reserved until K members are idle simultaneously. The priority
        preemption window is respected the same way pipeline restraint
        is — while a priority batch could claim an idle executor, the
        gang does not take it.
        """
        if (self._scheduler.preempt_active(now)
                and self._scheduler.priority_ready):
            return None
        avail = [ex for ex in self._executors
                 if not ex.dead and ex.idle
                 and ex.index not in self._wide_reserved]
        if len(avail) < self._wide_k:
            return None
        gang = avail[:self._wide_k]
        self._wide_reserved.update(ex.index for ex in gang)
        return gang

    def _fail_wide_queue_locked(self, reason: str
                                ) -> List[Tuple[_Request, BaseException]]:
        """Fail every queued wide request (under cv): the pool can no
        longer form a K-gang and will not heal (no respawn)."""
        out: List[Tuple[_Request, BaseException]] = []
        for wreq in self._wide_queue:
            req = self._requests.pop(wreq.req.req_id, None)
            if req is None:
                continue
            self._pending -= 1
            if req.queue in self._pending_by_queue:
                self._pending_by_queue[req.queue] -= 1
            self.stats.record_failure(queue=req.queue, failed=1)
            out.append((req, ExecutorDead(
                f"wide placement impossible: {reason}",
                request_ids=(req.req_id,))))
        self._wide_queue.clear()
        if out:
            self._cv.notify_all()
        return out

    def _ensure_wide_program(self, plan: WidePlan,
                             gang: List[DeviceExecutor], stacked):
        """The compiled SPMD wide program for (bucket geometry, gang).

        Keyed on the :class:`WideBucket` plus the gang's device ids —
        compile-once-per-bucket extended to gangs: every wide graph whose
        plan lands in the same padded geometry reuses the program on the
        same device set. The first build records trace-time edge passes
        under a ``('wide', ...)`` key next to the narrow buckets (the
        paper's one-pass property holds per shard per layer)."""
        bucket = plan.bucket
        key = (bucket, tuple(ex.device.id for ex in gang))
        fn = self._wide_programs.get(key)
        if fn is not None:
            return fn
        with self._compile_lock:
            fn = self._wide_programs.get(key)
            if fn is not None:
                return fn
            mesh = wide_mesh([ex.device for ex in gang])
            fn = build_wide_forward(self.cfg, bucket, mesh, self.dataflow)
            with count_edge_passes() as ps:
                jax.eval_shape(fn, self.params, stacked)
            self.edge_passes.setdefault(
                ("wide", bucket.k, bucket.n_pad, bucket.e_pad), ps.passes)
            self._wide_programs[key] = fn
            return fn

    def _run_wide(self, wreq: _WideRequest,
                  gang: List[DeviceExecutor]) -> None:
        """Run one wide request on its reserved gang (own thread), in one
        ``flowgnn.wide`` span."""
        with spans.span(spans.WIDE, req=wreq.req.req_id):
            self._run_gang(wreq, gang)

    def _run_gang(self, wreq: _WideRequest,
                  gang: List[DeviceExecutor]) -> None:
        """The body of ``_run_wide``.

        Fault semantics (DESIGN.md §10): a gang-member death before,
        during, or after the collective invalidates the WHOLE gang — a
        ring collective with a dead participant has no trustworthy
        result — so the request requeues intact (bounded by the requeue
        budget; the placer reforms a gang from survivors). A transient
        failure with the gang healthy retries like a narrow batch until
        ``max_retries``, then fails the future with ``BatchFailed``.
        Results pass the same non-finite validation gate as narrow
        traffic (``PoisonGraph``). Exactly-once resolution goes through
        the request registry like every other completion path.
        """
        req, plan = wreq.req, wreq.plan
        resolved: Optional[Tuple[Future, Any,
                                 Optional[BaseException]]] = None
        try:
            t_dispatch = time.perf_counter()
            with self._cv:
                if req.req_id not in self._requests:
                    return             # shed/abandoned while queued
                req.dispatched = True  # past the shedding window
            err: Optional[BaseException] = None
            out_np = None
            if not any(ex.dead for ex in gang):
                try:
                    stacked = stack_shard_arrays(
                        plan, wreq.node_feat, wreq.edge_feat,
                        wreq.node_pos)
                    fn = self._ensure_wide_program(plan, gang, stacked)
                    out_np = np.asarray(jax.block_until_ready(
                        fn(self.params, stacked)))
                except Exception as exc:
                    err = exc
            t_done = time.perf_counter()

            if any(ex.dead for ex in gang):
                # death path: requeue the whole gang's work on survivors
                with self._cv:
                    alive = sum(1 for ex in self._executors
                                if not ex.dead)
                    can_requeue = (not (self._stopped or self._closed)
                                   and (alive >= self._wide_k
                                        or self._respawn)
                                   and wreq.requeues < self._max_requeues
                                   and req.req_id in self._requests)
                    if can_requeue:
                        wreq.requeues += 1
                        req.dispatched = False     # sheddable again
                        self.stats.record_failure(queue=req.queue,
                                                  retries=1)
                        self._wide_queue.append(wreq)
                        self._cv.notify_all()
                        return
                    if self._requests.pop(req.req_id, None) is None:
                        return
                    self._pending -= 1
                    if req.queue in self._pending_by_queue:
                        self._pending_by_queue[req.queue] -= 1
                    self.stats.record_failure(queue=req.queue, failed=1)
                    self._cv.notify_all()
                failure: EngineError = ExecutorDead(
                    "gang member died and the wide graph could not be "
                    "re-placed", request_ids=(req.req_id,))
                failure.__cause__ = (err if isinstance(err, BaseException)
                                     else None)
                resolved = (req.future, None, failure)
                return

            if err is not None:
                # transient path: gang healthy, the program itself failed
                with self._cv:
                    can_retry = (not (self._stopped or self._closed)
                                 and wreq.attempts < self._max_retries
                                 and req.req_id in self._requests)
                    if can_retry:
                        # no backoff heap: gang reformation (waiting for
                        # K idle members again) naturally spaces retries
                        wreq.attempts += 1
                        req.dispatched = False
                        self.stats.record_failure(queue=req.queue,
                                                  retries=1)
                        self._wide_queue.append(wreq)
                        self._cv.notify_all()
                        return
                    if self._requests.pop(req.req_id, None) is None:
                        return
                    self._pending -= 1
                    if req.queue in self._pending_by_queue:
                        self._pending_by_queue[req.queue] -= 1
                    self.stats.record_failure(queue=req.queue, failed=1)
                    self._cv.notify_all()
                failure = BatchFailed(
                    f"wide graph failed after {wreq.attempts + 1} "
                    f"attempts: {err}", request_ids=(req.req_id,))
                failure.__cause__ = err
                resolved = (req.future, None, failure)
                return

            result = (out_np[0] if self.cfg.task == "graph"
                      else out_np[:plan.n_nodes])
            with self._cv:
                if self._requests.pop(req.req_id, None) is None:
                    return             # abandoned mid-run: drop result
                self._pending -= 1
                if req.queue in self._pending_by_queue:
                    self._pending_by_queue[req.queue] -= 1
                if (self._validate_outputs
                        and not bool(np.all(np.isfinite(result)))):
                    self.stats.record_failure(queue=req.queue,
                                              quarantined=1, failed=1)
                    resolved = (req.future, None, PoisonGraph(
                        "non-finite wide output quarantined by "
                        "validation gate", request_ids=(req.req_id,)))
                else:
                    if req.record:
                        self.stats.record_batch(
                            latencies=[t_done - wreq.t_arrival],
                            queue_waits=[t_dispatch - wreq.t_arrival],
                            device_s=t_done - t_dispatch, batch_size=1,
                            t_dispatch=t_dispatch, t_done=t_done,
                            queue=req.queue,
                            device=f"wide[{len(gang)}]")
                    resolved = (req.future, result, None)
                self._cv.notify_all()
        finally:
            with self._cv:
                self._wide_reserved.difference_update(
                    ex.index for ex in gang)
                self._wide_running -= 1
                self._cv.notify_all()
            if resolved is not None:
                _resolve(resolved[0], resolved[1], resolved[2])

    def _shed_scheduler_locked(self, now: float
                               ) -> List[Tuple[_Request, BaseException]]:
        """Shed expired graphs still held by the scheduler (under cv)."""
        if not self._deadlines_used:
            return []

        def expired(it: PackItem) -> bool:
            dt = it.payload.deadline_t
            return dt is not None and dt <= now

        out: List[Tuple[_Request, BaseException]] = []
        for queue_name, it in self._scheduler.shed(expired):
            req = self._requests.pop(it.payload.req_id, None)
            if req is None:
                continue
            self._pending -= 1
            if req.queue in self._pending_by_queue:
                self._pending_by_queue[req.queue] -= 1
            self.stats.record_failure(queue=req.queue, shed=1, failed=1)
            out.append((req, DeadlineExceeded(
                "deadline expired before dispatch",
                request_ids=(req.req_id,))))
        if self._wide_queue:
            # wide requests waiting on a gang window are sheddable too
            keep: List[_WideRequest] = []
            for wreq in self._wide_queue:
                dt = wreq.req.deadline_t
                if dt is None or dt > now:
                    keep.append(wreq)
                    continue
                req = self._requests.pop(wreq.req.req_id, None)
                if req is None:
                    continue
                self._pending -= 1
                if req.queue in self._pending_by_queue:
                    self._pending_by_queue[req.queue] -= 1
                self.stats.record_failure(queue=req.queue, shed=1,
                                          failed=1)
                out.append((req, DeadlineExceeded(
                    "deadline expired before a gang window opened",
                    request_ids=(req.req_id,))))
            self._wide_queue[:] = keep
        if out:
            self._cv.notify_all()
        return out

    def _shed_batch_locked(self, pb: PackedBatch, now: float
                           ) -> Tuple[Optional[PackedBatch],
                                      List[Tuple[_Request, BaseException]]]:
        """Shed expired members of a batch about to dispatch (under cv).

        Survivors keep the sealed bucket shapes (``subset``) so the
        compiled program — and result parity — are untouched. Returns
        ``(None, fails)`` when every member expired."""
        if not self._deadlines_used:
            return pb, []
        live: List[PackItem] = []
        fails: List[Tuple[_Request, BaseException]] = []
        for it in pb.items:
            req = it.payload
            if req.deadline_t is not None and req.deadline_t <= now:
                popped = self._requests.pop(req.req_id, None)
                if popped is None:
                    continue       # already resolved elsewhere
                self._pending -= 1
                if req.queue in self._pending_by_queue:
                    self._pending_by_queue[req.queue] -= 1
                self.stats.record_failure(queue=req.queue, shed=1, failed=1)
                fails.append((req, DeadlineExceeded(
                    "deadline expired before dispatch",
                    request_ids=(req.req_id,))))
            else:
                live.append(it)
        if not fails:
            return pb, []
        self._cv.notify_all()
        return (pb.subset(live) if live else None), fails

    def _take_requests_locked(self, pb: PackedBatch) -> List[_Request]:
        """Pop every still-outstanding request of ``pb`` (under cv)."""
        out: List[_Request] = []
        for it in pb.items:
            req = self._requests.pop(it.payload.req_id, None)
            if req is None:
                continue
            self._pending -= 1
            if req.queue in self._pending_by_queue:
                self._pending_by_queue[req.queue] -= 1
            out.append(req)
        if out:
            self._cv.notify_all()
        return out

    def _fail_scheduled(self, exc: BaseException) -> None:
        """Placer died: close the engine and fail everything not yet on an
        executor (in-flight batches still complete normally)."""
        with self._cv:
            self._closed = True
            stranded = self._scheduler.flush_all()
            stranded.extend((qn, pb)
                            for _, _, qn, pb, _ in self._retry_heap)
            self._retry_heap.clear()
            victims: List[_Request] = []
            for _, pb in stranded:
                victims.extend(self._take_requests_locked(pb))
            for wreq in self._wide_queue:
                req = self._requests.pop(wreq.req.req_id, None)
                if req is None:
                    continue
                self._pending -= 1
                if req.queue in self._pending_by_queue:
                    self._pending_by_queue[req.queue] -= 1
                victims.append(req)
            self._wide_queue.clear()
            if victims:
                self.stats.record_failure(failed=len(victims))
            self._cv.notify_all()
        for req in victims:
            _resolve(req.future, exc=exc)

    # ------------------------------------------------------------------
    # executor callbacks (dispatch threads / completer threads)
    # ------------------------------------------------------------------

    def _make_executor(self, device, index: int, params) -> DeviceExecutor:
        ex = DeviceExecutor(
            device=device, index=index, params=params,
            build_fn=lambda pb: self._to_device(pb, device),
            program_fn=self._ensure_program,
            unpack_fn=self._unpack,
            on_complete=self._handle_completion,
            on_fatal=self._handle_fatal,
            fault_hook=(self._faults.executor_hook
                        if self._faults is not None else None))
        # respawns after a hot reload must pin the CURRENT version, not 0
        ex.set_params(params, self._param_version)
        return ex

    def _layout(self, key: BucketKey) -> FlatLayout:
        layout = self._layouts.get(key)
        if layout is None:
            c = self.cfg
            layout = self._layouts.setdefault(key, FlatLayout(
                *key, c.node_feat_dim, c.edge_feat_dim, c.pos_dim))
        return layout

    def _build_batch(self, pb: PackedBatch) -> np.ndarray:
        """The batch packed into its bucket's flat host buffer."""
        return self._layout(pb.bucket).pack(pb.items)

    def _to_device(self, pb: PackedBatch, device) -> jax.Array:
        """The served program's input: one host→device transfer per
        batch, committed to the executor's own device."""
        words = self._build_batch(pb)
        flat = jax.device_put(words, device)
        with self._h2d_lock:
            self.stats.h2d_transfers += 1
            self.stats.h2d_bytes += words.nbytes
        return flat

    def _handle_completion(self, ex: DeviceExecutor,
                           done: CompletedBatch) -> None:
        pb = done.batch
        with spans.span(spans.RESOLVE, batch=pb.dispatch_id):
            with self._cv:
                if pb.dispatch_id is not None:
                    if self._inflight.pop(pb.dispatch_id, None) is None:
                        return  # superseded (watchdog/drain-timeout/close)
            if done.err is None:
                self._complete_ok(ex, done)
            else:
                self._complete_err(ex, done)

    def _complete_ok(self, ex: DeviceExecutor, done: CompletedBatch) -> None:
        pb = done.batch
        resolved = []          # (future, result, exc)
        tripped = False        # this batch tripped the NaN gate
        invalidate = False     # breaker moved a rung: drop compiled programs
        with self._cv:
            lat, qw = [], []
            for i, it in enumerate(pb.items):
                req = self._requests.pop(it.payload.req_id, None)
                if req is None:
                    continue   # resolved elsewhere (shed/abandoned)
                self._pending -= 1
                if req.queue in self._pending_by_queue:
                    self._pending_by_queue[req.queue] -= 1
                out = done.results[i]
                if (self._validate_outputs
                        and not bool(np.all(np.isfinite(out)))):
                    # the output-validation gate: a non-finite result is
                    # quarantined at the graph level, never returned
                    self.stats.record_failure(queue=req.queue,
                                              quarantined=1, failed=1)
                    resolved.append((req.future, None, PoisonGraph(
                        "non-finite output quarantined by validation gate",
                        request_ids=(req.req_id,), executor_index=ex.index)))
                    tripped = True
                    continue
                if req.record:
                    lat.append(done.t_ready - it.t_arrival)
                    qw.append(done.t_build_start - it.t_arrival)
                resolved.append((req.future, out, None))
            if lat:
                self.stats.record_batch(
                    latencies=lat, queue_waits=qw, device_s=done.device_s,
                    batch_size=len(lat), t_dispatch=done.t_dispatch,
                    t_done=done.t_ready, queue=done.queue, device=ex.label)
            now = done.t_ready
            h = self._bucket_health.get(pb.bucket)
            was_probing = h is not None and h.probing
            if tripped:
                # a NaN-producing impl and a NaN-producing graph look the
                # same from here; demote one rung either way — the jnp
                # floor is where "is it the graph?" is answered for sure
                invalidate = self._record_trip_locked(
                    pb.bucket, "nan_gate", now)
            else:
                if self._audit_q is not None:
                    # probing buckets are ALWAYS audited (the probe's
                    # verdict); healthy ones are sampled. The probing flag
                    # is read BEFORE any promotion below, so the batch
                    # that merely triggers a probe is not its verdict.
                    if (was_probing
                            or self._audit_rng.random() < self._audit_rate):
                        try:
                            self._audit_q.put_nowait(
                                (pb, list(done.results),
                                 done.params_version))
                            self._audits_enqueued += 1
                        except queue_lib.Full:
                            self.stats.audit_dropped += 1
                elif was_probing:
                    # no auditor: a clean completion is the best probe
                    # verdict available — confirm on it
                    h.probing = False
                invalidate = self._maybe_probe_locked(pb.bucket, now)
            retune_reason = self._observe_bucket_locked(pb, done)
            self._cv.notify_all()
        for fut, res, exc in resolved:
            _resolve(fut, res, exc)
        if invalidate:
            self._invalidate_programs(pb.bucket)
        if retune_reason is not None:
            self._trigger_retune(pb.bucket)

    def _complete_err(self, ex: DeviceExecutor, done: CompletedBatch) -> None:
        """Classify a failed batch: requeue (executor death), retry with
        backoff (transient), bisect (retries exhausted, >1 graph), or
        quarantine (single graph out of retries -> ``PoisonGraph``)."""
        pb, err = done.batch, done.err
        # a death-path failure (executor died / crash injected) is not
        # evidence against the batch contents: requeue on survivors
        is_death = (isinstance(err, ExecutorDead)
                    or not isinstance(err, Exception))
        resolved = []
        with self._cv:
            alive = any(not e.dead for e in self._executors)
            retryable = not (self._stopped or self._closed) and alive
            if is_death and retryable and pb.requeues < self._max_requeues:
                pb.requeues += 1
                self.stats.record_failure(queue=done.queue, retries=1)
                self._push_retry_locked(done.queue, pb, delay=0.0,
                                        exclude=ex.index)
                return
            if not is_death and retryable:
                if pb.attempts < self._max_retries:
                    pb.attempts += 1
                    self.stats.record_failure(queue=done.queue, retries=1)
                    self._push_retry_locked(
                        done.queue, pb, delay=self._backoff(pb.attempts),
                        exclude=ex.index)
                    return
                if pb.num_graphs > 1:
                    # bisection quarantine: both halves re-run (same
                    # bucket, no recompile); the poison graph is isolated
                    # in log2(batch) steps while every healthy graph's
                    # result stays bitwise identical to the fault-free run
                    left, right = pb.split()
                    self.stats.record_failure(queue=done.queue, retries=2)
                    delay = self._backoff(1)
                    self._push_retry_locked(done.queue, left, delay=delay,
                                            exclude=ex.index)
                    self._push_retry_locked(done.queue, right, delay=delay,
                                            exclude=ex.index)
                    return
            # terminal: fail the futures
            reqs = self._take_requests_locked(pb)
            if not reqs:
                return
            ids = tuple(r.req_id for r in reqs)
            if (not is_death and pb.num_graphs == 1
                    and pb.attempts >= self._max_retries):
                failure: EngineError = PoisonGraph(
                    f"graph failed after {pb.attempts + 1} attempts: {err}",
                    request_ids=ids, executor_index=ex.index)
                self.stats.record_failure(queue=done.queue, quarantined=1,
                                          failed=1)
            elif is_death:
                failure = ExecutorDead(
                    f"executor died and work could not be re-placed: {err}",
                    request_ids=ids, executor_index=ex.index)
                self.stats.record_failure(queue=done.queue, failed=len(reqs))
            else:
                failure = BatchFailed(
                    f"batch failed with retries exhausted: {err}",
                    request_ids=ids, executor_index=ex.index)
                self.stats.record_failure(queue=done.queue, failed=len(reqs))
            failure.__cause__ = (err if isinstance(err, BaseException)
                                 else None)
            resolved = [(r.future, failure) for r in reqs]
        for fut, exc in resolved:
            _resolve(fut, exc=exc)

    def _backoff(self, attempts: int) -> float:
        """Bounded exponential backoff for attempt N (1-based)."""
        return min(self._retry_backoff_s * (2.0 ** (attempts - 1)),
                   self._retry_backoff_max_s)

    def _push_retry_locked(self, queue: str, pb: PackedBatch, *,
                           delay: float, exclude: Optional[int]) -> None:
        pb.dispatch_id = None
        for it in pb.items:
            it.payload.dispatched = False    # sheddable again until placed
        heapq.heappush(self._retry_heap,
                       (time.perf_counter() + delay, self._retry_seq,
                        queue, pb, exclude))
        self._retry_seq += 1
        self._cv.notify_all()

    def _handle_fatal(self, ex: DeviceExecutor, exc: BaseException) -> None:
        # an executor loop died unexpectedly: supervision takes it out of
        # rotation (its queued batches were failed by the executor and
        # come back through _complete_err as requeues); the pool degrades
        # instead of the engine dying with it
        self._supervise(ex)

    def _supervise(self, ex: DeviceExecutor) -> None:
        """Take a dead executor out of rotation; optionally respawn it.

        Runs on the dying worker thread (via ``on_fatal``) or the
        watchdog. Idempotent per executor instance. With respawn enabled
        a fresh executor (new committed params replica, empty program
        cache) replaces it at the same pool slot; otherwise the pool
        stays degraded and survivors absorb the work.
        """
        with self._cv:
            if id(ex) in self._supervised:
                return
            self._supervised.add(id(ex))
            self.stats.executor_deaths += 1
            self.stats.pool_degraded = True
            do_respawn = self._respawn and not self._stopped
            self._cv.notify_all()
        if do_respawn:
            try:
                fresh = self._make_executor(
                    ex.device, ex.index,
                    replicate_params(self.params, [ex.device])[0])
                fresh.start()
            except Exception:
                fresh = None       # respawn failed: stay degraded
            if fresh is not None:
                with self._cv:
                    self._executors[ex.index] = fresh
                    self.stats.respawns += 1
                    if not any(e.dead for e in self._executors):
                        self.stats.pool_degraded = False
                    self._cv.notify_all()
                return
        with self._cv:
            if any(not e.dead for e in self._executors):
                self._cv.notify_all()
                return
            # whole pool dead: nothing can serve — close and fail
            # everything outstanding rather than strand submitters
            self._closed = True
            victims = self._abandon_outstanding_locked()
        exc = ExecutorDead("every executor died",
                           request_ids=tuple(r.req_id for r in victims))
        for req in victims:
            _resolve(req.future, exc=exc)

    # ------------------------------------------------------------------
    # in-flight watchdog
    # ------------------------------------------------------------------

    def _watchdog_loop(self) -> None:
        """Fail batches stuck inside an executor past the in-flight
        timeout: their executor is marked dead (its OTHER queued work
        requeues on survivors via the death path) and the stuck batch's
        futures fail with ``DeadlineExceeded`` — a wedged device never
        strands a caller. The stuck batch is popped from the in-flight
        registry first, so a late completion becomes a registry miss."""
        timeout = self._inflight_timeout_s
        interval = max(min(timeout / 4.0, 0.25), 1e-3)
        while not self._watchdog_stop.wait(interval):
            with self._cv:
                if self._stopped:
                    return
                now = time.perf_counter()
                stuck = [entry for entry in self._inflight.values()
                         if now - entry.t_placed > timeout]
                for entry in stuck:
                    self._inflight.pop(entry.batch.dispatch_id, None)
            for entry in stuck:
                entry.ex.mark_dead(ExecutorDead(
                    "executor exceeded the in-flight timeout",
                    executor_index=entry.ex.index))
                with self._cv:
                    reqs = self._take_requests_locked(entry.batch)
                    if reqs:
                        self.stats.record_failure(queue=entry.queue,
                                                  failed=len(reqs))
                exc = DeadlineExceeded(
                    f"batch stuck in flight > {timeout:.3f}s",
                    request_ids=tuple(r.req_id for r in reqs),
                    executor_index=entry.ex.index)
                for req in reqs:
                    _resolve(req.future, exc=exc)
                self._supervise(entry.ex)

    def _split_outputs(self, pb: PackedBatch, out_np: np.ndarray
                       ) -> List[np.ndarray]:
        """Per-graph views of the packed output (copied so buffers detach).
        Shared by the serving unpack path and the shadow auditor's
        reference re-execution, so both slice identically."""
        if self.cfg.task == "node":
            offs = pb.graph_offsets()
            return [np.array(out_np[offs[i]:offs[i + 1]])
                    for i in range(pb.num_graphs)]
        return [np.array(out_np[i]) for i in range(pb.num_graphs)]

    def _unpack(self, pb: PackedBatch, out_np: np.ndarray
                ) -> List[np.ndarray]:
        res = self._split_outputs(pb, out_np)
        if self._faults is not None:
            # chaos: scripted NaN corruption lands here, between device
            # readback and the engine's validation gate; a broken-impl
            # epsilon lands here too when this bucket served on it
            res = self._faults.corrupt_outputs(
                pb, res, impl=self._served_impl.get(pb.bucket))
        return res

    # ------------------------------------------------------------------
    # shadow auditor: sampled re-execution on the jnp mirror (§9)
    # ------------------------------------------------------------------

    def _audit_reference(self):
        """The lazily-jitted unfused jnp mirror — the ladder floor and
        the ground truth every audit and canary compares against."""
        fn = self._audit_ref
        if fn is None:
            apply, cfg = self.model.apply, self.cfg
            mirror = self.dataflow.replace(impl="unfused",
                                           single_pass=False)
            fn = jax.jit(lambda p, g: apply(p, g, cfg, mirror))
            self._audit_ref = fn
        return fn

    def _audit_loop(self) -> None:
        while True:
            entry = self._audit_q.get()
            if entry is None:
                return
            try:
                self._audit_one(*entry)
            except Exception:
                with self._cv:
                    self.stats.audit_dropped += 1
            finally:
                with self._cv:
                    self._audits_done += 1
                    self._cv.notify_all()

    def _audit_one(self, pb: PackedBatch, served: List[np.ndarray],
                   pver: int) -> None:
        """Re-execute one sampled batch on the jnp mirror (host-side,
        off the serving path) and compare what was SERVED — results after
        any fault corruption, exactly what callers saw — against it."""
        params = self._params_by_version.get(pver)
        if params is None:             # params retired mid-flight: skip
            with self._cv:
                self.stats.audit_dropped += 1
            return
        g = pb.build(pos_dim=self.cfg.pos_dim)
        out = np.asarray(self._audit_reference()(params, g))
        ref = self._split_outputs(pb, out)
        mismatch = False
        for i in range(pb.num_graphs):
            got = np.asarray(served[i])
            if not bool(np.all(np.isfinite(got))):
                continue               # the NaN gate owns non-finite rows
            if not np.allclose(got, ref[i], rtol=self._audit_rtol,
                               atol=self._audit_atol):
                mismatch = True
                break
        invalidate = False
        with self._cv:
            self.stats.audits += 1
            if mismatch:
                self.stats.audit_mismatches += 1
                invalidate = self._record_trip_locked(
                    pb.bucket, "audit_mismatch", time.perf_counter())
            else:
                h = self._bucket_health.get(pb.bucket)
                if h is not None and h.probing:
                    h.probing = False  # probe confirmed clean
            self._cv.notify_all()
        if invalidate:
            self._invalidate_programs(pb.bucket)

    def flush_audits(self, timeout: Optional[float] = None) -> bool:
        """Block until every audit enqueued so far has been judged (the
        deterministic handle chaos tests need — 'within one audit window'
        made waitable). Returns False on timeout."""
        with self._cv:
            return self._cv.wait_for(
                lambda: self._audits_done >= self._audits_enqueued, timeout)

    # ------------------------------------------------------------------
    # hot parameter reload: versioned replicas + canary + rollback (§9)
    # ------------------------------------------------------------------

    def update_params(self, new_params, *, canary: bool = True) -> int:
        """Install ``new_params`` across the pool with zero downtime.

        Serving never pauses: each executor snapshots its ``(params,
        version)`` pair at dispatch, so batches in flight finish on the
        version that dispatched them while new dispatches pick up the new
        one — no request is dropped, every future resolves exactly once.
        With ``canary=True`` (default) the staged replicas must first
        serve a probe batch with finite outputs matching the jnp mirror
        under the new params; any failure raises ``ParamUpdateFailed``
        and the previous version stays installed untouched (atomic
        rollback — the staged replicas are simply discarded). Returns
        the new version number.
        """
        if self._closed:
            raise EngineClosed("engine is closed")
        with self._update_lock:        # one update in flight at a time
            reason = params_compatible(self.params, new_params)
            if reason is not None:
                with self._cv:
                    self.stats.param_rollbacks += 1
                raise ParamUpdateFailed(reason)
            with self._cv:
                alive = [ex for ex in self._executors if not ex.dead]
            if not alive:
                with self._cv:
                    self.stats.param_rollbacks += 1
                raise ParamUpdateFailed("no live executor to stage onto")
            replicas = replicate_params(new_params,
                                        [ex.device for ex in alive])
            if canary:
                err = self._run_canary(new_params, alive, replicas)
                if err is not None:
                    with self._cv:
                        self.stats.param_rollbacks += 1
                    raise ParamUpdateFailed(
                        f"canary failed, previous params kept: {err}")
            with self._cv:
                self._param_version += 1
                version = self._param_version
                self.params = new_params
                self._params_by_version[version] = new_params
                while len(self._params_by_version) > 2:
                    # keep the previous version for in-flight pinning and
                    # late audits; anything older can no longer be live
                    del self._params_by_version[
                        min(self._params_by_version)]
                for ex, rep in zip(alive, replicas):
                    ex.set_params(rep, version)
                self.stats.param_updates += 1
                self._cv.notify_all()
            return version

    def _run_canary(self, new_params, alive, replicas) -> Optional[str]:
        """Why the staged params fail validation, or None. The probe
        batch runs per staged replica (on its executor's own device) and
        must be finite and allclose to the jnp mirror's answer under the
        SAME new params — a swap that would corrupt results is caught
        before any real traffic can see it."""
        pb = self._probe_batch()
        flat = self._build_batch(pb)
        try:
            ref = np.asarray(self._audit_reference()(
                new_params, pb.build(pos_dim=self.cfg.pos_dim)))
        except Exception as exc:
            return f"reference eval failed: {exc}"
        if not bool(np.all(np.isfinite(ref))):
            return "jnp-mirror outputs are non-finite under new params"
        run = self._canary_run
        if run is None:
            # default-dataflow probe program, compiled once per engine
            run = self._make_run(self.dataflow, pb.bucket)
            self._canary_run = run
        for ex, rep in zip(alive, replicas):
            try:
                out = np.asarray(jax.block_until_ready(
                    run(rep, jax.device_put(flat, ex.device))))
            except Exception as exc:
                return f"canary batch failed on {ex.label}: {exc}"
            if not bool(np.all(np.isfinite(out))):
                return f"canary outputs non-finite on {ex.label}"
            if not np.allclose(out, ref, rtol=self._audit_rtol,
                               atol=self._audit_atol):
                return f"canary diverges from jnp mirror on {ex.label}"
        return None

    def _probe_batch(self) -> PackedBatch:
        """A small deterministic ring graph with non-trivial features in
        the smallest bucket — rich enough that wrong params actually move
        its outputs (an all-zeros batch would pass any canary)."""
        rng = np.random.default_rng(0x9E3779B9)
        b0 = self.buckets[0]
        n = min(8, b0)
        nf = rng.standard_normal(
            (n, self.cfg.node_feat_dim)).astype(np.float32)
        snd = np.arange(n, dtype=np.int32)
        rcv = np.roll(snd, -1).astype(np.int32)
        ef = (rng.standard_normal(
            (n, self.cfg.edge_feat_dim)).astype(np.float32)
            if self.cfg.edge_feat_dim != 1 else None)
        return PackedBatch(
            items=[PackItem(node_feat=nf, senders=snd, receivers=rcv,
                            edge_feat=ef)],
            node_pad=b0, edge_pad=pad_bucket(2 * b0, self.buckets),
            graph_pad=1)

    # ------------------------------------------------------------------
    # drift detection -> bounded re-autotune (DESIGN.md §5)
    # ------------------------------------------------------------------

    def _observe_bucket_locked(self, pb: PackedBatch,
                               done: CompletedBatch) -> Optional[str]:
        """Fold one completed batch into its bucket's running stats (under
        ``self._cv``) and decide whether traffic has drifted out of the
        tuned envelope. Returns the drift reason when a re-autotune should
        fire (the trigger itself runs outside the cv), else ``None``.

        The retune budget is spent HERE, inside the lock, so concurrent
        completions of the same bucket can never double-trigger."""
        key = pb.bucket
        load = self._bucket_load.setdefault(key, _BucketLoad())
        a = 2.0 / (self._drift_window + 1.0)

        def ewma(old: Optional[float], new: float) -> float:
            return new if old is None else (1.0 - a) * old + a * new

        load.batches += 1
        load.graphs += pb.num_graphs
        load.batches_since_tune += 1
        fill = float(pb.num_graphs)
        load.ewma_fill = ewma(load.ewma_fill, fill)
        if done.device_s > 0:
            load.ewma_device_s = ewma(load.ewma_device_s, done.device_s)
        if load.last_seen_t is not None:
            load.ewma_gap_s = ewma(load.ewma_gap_s,
                                   done.t_ready - load.last_seen_t)
        load.last_seen_t = done.t_ready
        if load.tuned_fill is None:
            # first batch after (re)tuning anchors the envelope's mix
            load.tuned_fill = fill

        if not self._autotune or key not in self._tuned:
            return None            # nothing tuned: nothing to re-tune
        if (load.retunes >= self._max_retunes
                or load.batches_since_tune < self._drift_window
                or done.t_ready - load.last_tune_t < self._drift_cooldown_s):
            return None
        reason = None
        if (load.tuned_device_s is not None
                and load.ewma_device_s is not None
                and load.ewma_device_s
                > self._drift_device_factor * load.tuned_device_s):
            reason = "device_time"
        elif (load.tuned_fill is not None and load.ewma_fill is not None
              and not (load.tuned_fill / self._drift_fill_factor
                       <= load.ewma_fill
                       <= load.tuned_fill * self._drift_fill_factor)):
            reason = "batch_mix"
        if reason is None:
            return None
        load.retunes += 1
        load.last_tune_t = done.t_ready
        load.batches_since_tune = 0
        load.tuned_fill = None
        load.tuned_device_s = None
        load.last_reason = reason
        self.stats.retunes += 1
        return reason

    def _trigger_retune(self, key: BucketKey) -> None:
        """Invalidate a drifted bucket's tuned winner plus every
        executor's compiled program for it, so the next batch re-runs the
        autotune search against current traffic (``_ensure_program``'s
        ordinary miss path). The bucket is never left unservable: a
        dispatch that misses compiles on demand exactly like a first
        touch, and an in-flight dispatch that already fetched the old
        program finishes on it."""
        with self._compile_lock:
            self._tuned.pop(key, None)
            for ex in self._executors:
                ex.compiled.pop(key, None)
                ex.touched.pop(key, None)

    # ------------------------------------------------------------------
    # impl circuit breaker: degradation ladder + cooldown re-probe (§9)
    # ------------------------------------------------------------------

    @staticmethod
    def _impl_rung(df: DataflowConfig) -> int:
        """Position of a dataflow on the degradation ladder (0 = most
        fused / most lowering machinery in play; ``_JNP_RUNG`` = the
        plain unfused jnp mirror, the audit reference itself)."""
        if df.impl in ("fused_layer", "kernel"):
            return 0
        if df.impl in ("pipeline", "banked"):
            return 1
        if df.impl == "unfused" and not df.single_pass:
            return _JNP_RUNG
        return 2                       # single-pass jnp unit forms

    def _ladder_df(self, base: DataflowConfig, rung: int) -> DataflowConfig:
        """``base`` demoted to ``rung`` (clamped to the jnp floor); a rung
        at or above the base's own is the base unchanged — demotion only
        ever strips lowering machinery, never adds it."""
        rung = min(int(rung), _JNP_RUNG)
        if rung <= self._impl_rung(base):
            return base
        if rung == 1:
            return base.replace(impl="pipeline")
        if rung == 2:
            return base.replace(impl="fused", single_pass=True)
        return base.replace(impl="unfused", single_pass=False)

    def _effective_df(self, key: BucketKey, df: DataflowConfig
                      ) -> DataflowConfig:
        """The dataflow ``key`` actually serves on: its tuned/default
        winner demoted by the bucket's current breaker level."""
        h = self._bucket_health.get(key)
        if not self._breaker or h is None or h.level == 0:
            return df
        return self._ladder_df(df, self._impl_rung(df) + h.level)

    def _record_trip_locked(self, key: BucketKey, reason: str,
                            now: float) -> bool:
        """One breaker trip for ``key``; returns True when it demoted a
        rung (caller must then drop the bucket's compiled programs,
        OUTSIDE ``self._cv``). Callers hold ``self._cv`` or the compile
        lock; the ledger fields are GIL-atomic monitoring state, so the
        cross-lock races are the tolerable kind (same precedent as the
        autotune envelope writes)."""
        if not self._breaker:
            return False
        h = self._bucket_health.setdefault(key, _BucketHealth())
        h.trips += 1
        h.last_trip_t = now
        h.last_reason = reason
        h.probing = False              # a trip ends any open probe
        base = self._tuned.get(key, self.dataflow)
        if self._impl_rung(base) + h.level >= _JNP_RUNG:
            return False               # already serving the jnp floor
        h.level += 1
        self.stats.breaker_trips += 1
        return True

    def _maybe_probe_locked(self, key: BucketKey, now: float) -> bool:
        """Half-open the breaker after a quiet cooldown: promote one rung
        and mark the bucket probing (under ``self._cv``). Returns True
        when it promoted (caller drops the compiled programs so the next
        dispatch recompiles at the promoted rung)."""
        h = self._bucket_health.get(key)
        if (not self._breaker or h is None or h.level == 0 or h.probing
                or h.probes >= self._breaker_max_probes
                or now - h.last_trip_t < self._breaker_cooldown_s):
            return False
        h.level -= 1
        h.probes += 1
        h.probing = True
        h.last_trip_t = now            # re-arm the cooldown window
        self.stats.breaker_probes += 1
        return True

    def _invalidate_programs(self, key: BucketKey) -> None:
        """Drop every executor's compiled program for ``key`` so the next
        dispatch recompiles at the bucket's current breaker rung. Unlike
        ``_trigger_retune`` the tuned winner survives — the breaker moves
        along the ladder FROM it, and a healed bucket returns TO it."""
        with self._compile_lock:
            for ex in self._executors:
                ex.compiled.pop(key, None)
                ex.touched.pop(key, None)

    # ------------------------------------------------------------------
    # per-executor program cache + shared per-bucket autotuning
    # ------------------------------------------------------------------

    def _make_run(self, df: DataflowConfig, key: BucketKey):
        """The bucket's program: ``run(params, flat)`` on the batch's
        ``FlatLayout`` buffer, rebuilt into a ``GraphBatch`` in-program."""
        apply = self.model.apply
        cfg = self.cfg
        layout = self._layout(key)

        # a stable name: the program's modules read ``jit_flowgnn_forward``
        # on the device planes of a profiler trace
        def flowgnn_forward(params, flat):
            return apply(params, layout.unflatten(flat), cfg, df)

        return jax.jit(flowgnn_forward)

    def _ensure_program(self, ex: DeviceExecutor, key: BucketKey,
                        g: jax.Array):
        """The jitted program for ``key`` on executor ``ex``.

        The tuned dataflow is shared across the pool (first executor to
        hit a bucket tunes it on its own device — the pool is homogeneous,
        one entry per ``jax.devices()`` topology); the compiled program is
        per executor, so each device owns its namespace of executables.
        """
        # lock-free fast path: ex.compiled is written only under the
        # compile lock and only by this executor's bucket miss, so a hit
        # here never blocks behind another bucket's autotune search. The
        # touch write is a plain dict store (GIL-atomic) — LRU order is
        # approximate across racing dispatch threads, which is fine.
        run = ex.compiled.get(key)
        if run is not None:
            ex.touched[key] = next(self._touch)
            return run
        with spans.span(spans.COMPILE, bucket=spans.bucket_name(key)):
            return self._ensure_program_locked(ex, key, g)

    def _ensure_program_locked(self, ex: DeviceExecutor, key: BucketKey,
                               g: jax.Array):
        """``_ensure_program``'s miss path: tune, trace and install the
        bucket's program under the compile lock."""
        with self._compile_lock:
            run = ex.compiled.get(key)
            if run is not None:
                ex.touched[key] = next(self._touch)
                return run
            df = self._tuned.get(key)
            if df is None and self._autotune:
                df = self._run_autotune(ex, key, g)
            if df is None:
                df = self.dataflow
            # circuit breaker (§9): serve at the bucket's demoted rung,
            # and walk further down the ladder if the rung itself fails
            # to trace — the jnp floor always traces, so a bucket is
            # never left unservable by a broken lowering.
            while True:
                eff = self._effective_df(key, df)
                run = self._make_run(eff, key)
                try:
                    with count_edge_passes() as ps:
                        jax.eval_shape(run, ex.params, g)
                except Exception as exc:
                    if (not self._breaker
                            or self._impl_rung(eff) >= _JNP_RUNG):
                        raise
                    self._record_trip_locked(
                        key, f"trace_failure: {type(exc).__name__}",
                        time.perf_counter())
                    continue
                break
            self.edge_passes.setdefault(key, ps.passes)
            self._served_impl[key] = eff.impl
            ex.compiled[key] = run
            ex.touched[key] = next(self._touch)
            self._evict_cold_locked(ex, keep=key)
            return run

    def _evict_cold_locked(self, ex: DeviceExecutor, keep: BucketKey) -> None:
        """Bound ``ex``'s compiled-program namespace (under the compile
        lock): while over ``max_cached_programs``, drop the least-recently
        touched bucket — never the one just installed. Eviction only frees
        the executable; the bucket stays servable (next touch recompiles,
        reusing the still-cached tuned winner)."""
        cap = self._max_cached_programs
        if cap is None:
            return
        while len(ex.compiled) > cap:
            victim = min((k for k in ex.compiled if k != keep),
                         key=lambda k: ex.touched.get(k, 0), default=None)
            if victim is None:
                return
            ex.compiled.pop(victim, None)
            ex.touched.pop(victim, None)
            self._evict_log[victim] = self._evict_log.get(victim, 0) + 1
            self.stats.program_evictions += 1

    def _candidate_dataflows(self, key: BucketKey) -> List[DataflowConfig]:
        """Per-bucket DSE candidates (the paper's Fig. 10 design space:
        num_banks × edge_tile × impl).

        The cheap default set is 2-3 (num_banks, edge_tile) combos plus one
        candidate each for the fused edge pipeline (``impl='pipeline'``,
        DESIGN.md §6) and — on backends with the Pallas kernel path — the
        layer-fused one-launch step (``impl='fused_layer'``, §7); models
        without the fusable descriptions silently fall back, so both are
        always safe to time. Off-TPU ``fused_layer`` traces to exactly the
        pipeline mirror, so offering it would compile and time a bitwise
        duplicate; it joins the set only where it is a distinct program.
        Raising ``max_autotune`` expands toward the full grid
        (banks ∈ {1,2,4,8,16} × tiles ∈ {32,64,128,256} × impls), truncated
        to ``max_autotune`` candidates so warmup cost stays bounded.
        """
        from repro.core.message_passing import _pipeline_uses_kernel
        node_pad, edge_pad, _ = key

        def clamp(banks: int, tile: int) -> Tuple[int, int]:
            banks = max(1, min(banks, node_pad))
            while node_pad % banks:
                banks //= 2
            return banks, max(8, min(tile, edge_pad))

        extra_impls = ["pipeline"]
        if _pipeline_uses_kernel():
            extra_impls.append("fused_layer")
        impls = [self.dataflow.impl]
        for extra in extra_impls:
            if extra not in impls:
                impls.append(extra)

        pairs: List[Tuple[int, int]] = []
        for banks, tile in ((self.dataflow.num_banks, self.dataflow.edge_tile),
                            (1, 128), (8, 64)):
            bt = clamp(banks, tile)
            if bt not in pairs:
                pairs.append(bt)
        # impl diversity outranks tile diversity under truncation: the
        # staged default must survive into every bucket's timed set (the
        # PNA fused-pipeline regression showed a fused candidate can lose
        # to staged by 15%+, so fused vs staged stays a measured choice)
        base = self.dataflow.replace(num_banks=pairs[0][0],
                                     edge_tile=pairs[0][1])
        cands = [base]
        cands += [base.replace(impl=impl) for impl in impls[1:]]
        cands += [self.dataflow.replace(num_banks=b, edge_tile=t)
                  for b, t in pairs[1:3]]

        if self._max_autotune > len(cands):
            seen = {(c.num_banks, c.edge_tile, c.impl) for c in cands}
            for banks in (1, 2, 4, 8, 16):
                for tile in (32, 64, 128, 256):
                    b, t = clamp(banks, tile)
                    for impl in impls:
                        if (b, t, impl) not in seen:
                            seen.add((b, t, impl))
                            cands.append(self.dataflow.replace(
                                num_banks=b, edge_tile=t, impl=impl))
        return cands[:self._max_autotune]

    def _run_autotune(self, ex: DeviceExecutor, key: BucketKey,
                      g: jax.Array) -> DataflowConfig:
        """Time up to ``max_autotune`` (num_banks, edge_tile, impl) DSE
        candidates on the first batch of this bucket (on the executor that
        received it); cache and persist the winner for the whole pool."""
        timings: Dict[str, float] = {}
        failed: Dict[str, str] = {}
        best_df, best_t, best_name = None, float("inf"), None
        for df in self._candidate_dataflows(key):
            name = f"banks{df.num_banks}_tile{df.edge_tile}"
            if df.impl != self.dataflow.impl:
                name += f"_{df.impl}"
            run = self._make_run(df, key)
            try:
                jax.block_until_ready(run(ex.params, g))   # compile
                t = min(self._time_once(run, ex.params, g) for _ in range(3))
            except Exception as exc:
                # invalid for this shape, or refused by the device's
                # compiler: kept out of the race, but named in the report
                failed[name] = f"{type(exc).__name__}: {exc}"[:500]
                continue
            timings[name] = t * 1e6
            if t < best_t:
                best_df, best_t, best_name = df, t, name
        if best_df is None:                # every candidate failed: fall back
            best_df = self.dataflow
        self._tuned[key] = best_df
        # anchor the drift envelope (plain field writes; the cv-protected
        # observer tolerates them racing — they are monitoring state)
        load = self._bucket_load.setdefault(key, _BucketLoad())
        load.last_tune_t = time.perf_counter()
        load.batches_since_tune = 0
        load.tuned_fill = None             # next completion anchors the mix
        if np.isfinite(best_t):
            load.tuned_device_s = best_t
        log: Dict[str, Any] = {"candidates_us": timings,
                               "device": ex.label}
        if failed:
            log["failed"] = failed
        if best_name is not None:
            log["winner"] = best_name
        if np.isfinite(best_t):
            log["best_us"] = best_t * 1e6
        self._tune_log[key] = log
        self._save_autotune_cache()
        return best_df

    def _time_once(self, run, params, g: jax.Array) -> float:
        t0 = time.perf_counter()
        jax.block_until_ready(run(params, g))
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    # autotune cache persistence
    # ------------------------------------------------------------------

    # Bumped whenever the candidate set or the lowering behind an impl
    # name changes meaning (schema 2: one-launch attention/field forms —
    # GAT/DGN buckets tuned against the pre-flash candidate set must not
    # stay pinned to the old staged winners; schema 3: the fingerprint
    # gained a wide shard-count component, so schema-2 sections — keyed
    # without it — would alias a wide engine's narrow buckets onto a
    # non-wide engine's winners). A cache file whose "__schema__" does
    # not match is ignored on load and rebuilt on save.
    AUTOTUNE_CACHE_SCHEMA = 3

    def _cache_fingerprint(self) -> str:
        """Workload + topology identity for the autotune cache.

        Winners tuned for one model/dataflow must never be applied to
        another sharing the file — and winners tuned on one backend/device
        topology (CPU vs TPU generation, say) must not be silently reused
        on another, so the backend and device kind are part of the key.
        The wide shard count is part of the workload identity too: a
        wide-enabled engine's narrow buckets coexist with gang traffic
        (different cache pressure and arrival mix), so its winners get
        their own section (``@wide1`` = wide disabled).
        """
        c, d = self.cfg, self.dataflow
        topo = f"{jax.default_backend()}:{device_kind(self._devices[0])}"
        wide_k = self._wide_k if self._wide_enabled else 1
        return (f"{topo}/{c.model}-l{c.num_layers}-h{c.hidden_dim}-{c.task}-"
                f"{d.impl}{'-sp' if d.single_pass else ''}@wide{wide_k}")

    def _load_autotune_cache(self) -> None:
        path = self._autotune_cache
        if not path or not os.path.exists(path):
            return
        try:
            raw = json.loads(open(path).read())
        except (OSError, ValueError):
            return
        if not isinstance(raw, dict):
            return
        if raw.get("__schema__") != self.AUTOTUNE_CACHE_SCHEMA:
            return                 # stale (or pre-versioning) cache: re-tune
        section = raw.get(self._cache_fingerprint(), {})
        if not isinstance(section, dict):
            return
        for key_s, val in section.items():
            try:
                key = tuple(int(v) for v in key_s.split("x"))
                if len(key) != 3:
                    continue
                self._tuned[key] = self.dataflow.replace(
                    num_banks=int(val["num_banks"]),
                    edge_tile=int(val["edge_tile"]),
                    impl=str(val.get("impl", self.dataflow.impl)))
            except (KeyError, ValueError):
                continue
        self._tune_log.clear()      # cached winners are not re-timed

    def _save_autotune_cache(self) -> None:
        path = self._autotune_cache
        if not path:
            return
        existing: Dict[str, Any] = {}
        if os.path.exists(path):       # preserve other workloads' sections
            try:
                existing = json.loads(open(path).read())
                if not isinstance(existing, dict):
                    existing = {}
            except (OSError, ValueError):
                existing = {}
        if existing.get("__schema__") != self.AUTOTUNE_CACHE_SCHEMA:
            existing = {}              # drop every stale-schema section
        existing["__schema__"] = self.AUTOTUNE_CACHE_SCHEMA
        existing[self._cache_fingerprint()] = {
            "x".join(map(str, key)): {"num_banks": df.num_banks,
                                      "edge_tile": df.edge_tile,
                                      "impl": df.impl}
            for key, df in self._tuned.items()
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(existing, f, indent=2, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------

    def _synthetic_batch(self, node_pad: int, edge_pad: int,
                         graph_pad: int) -> np.ndarray:
        """Minimal real content in a bucket's flat host buffer (for
        warmup/compile)."""
        nf = np.zeros((2, self.cfg.node_feat_dim), np.float32)
        snd = np.array([0], np.int32)
        rcv = np.array([1], np.int32)
        ef = (np.zeros((1, self.cfg.edge_feat_dim), np.float32)
              if self.cfg.edge_feat_dim != 1 else None)
        return self._build_batch(PackedBatch(
            items=[PackItem(node_feat=nf, senders=snd, receivers=rcv,
                            edge_feat=ef)],
            node_pad=node_pad, edge_pad=edge_pad, graph_pad=graph_pad))
