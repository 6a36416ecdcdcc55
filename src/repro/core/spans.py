"""Named spans of the engine's host path, on the profiler's clock.

Each stage a request passes through in ``GraphStreamEngine`` opens one
``jax.profiler.TraceAnnotation``. The profiler writes them into the same
``.xplane.pb`` as the device planes, so a host stage and the chip's idle
time can be laid side by side on one clock. Capture a window with::

    with jax.profiler.trace(log_dir):
        ...   # submit and wait as usual

Every name starts with ``flowgnn.``. The spans of one batch carry its
dispatch id as ``batch``; ``flowgnn.submit`` carries the request id as
``req``, and ``flowgnn.place`` maps its batch to the request ids
(``reqs``, joined by ``;``) and the device id (``dev``) it was placed on,
so one request's spans can be joined across the client, placer, dispatch
and completer threads. A placer pass that places nothing records a
``flowgnn.place`` without ids.

Spans are wall-clock: a span includes any time its thread waits inside it,
for the interpreter lock too. While the profiler is off ``span`` returns a
shared no-op after one check of the profiler's flag, and formats nothing.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from jax.profiler import TraceAnnotation

SUBMIT = "flowgnn.submit"                 # client: the whole submit call
VALIDATE = "flowgnn.submit.validate"      # client: admission checks
PLACE = "flowgnn.place"                   # placer: poll, shed, place
BUILD = "flowgnn.build"                   # dispatch: pack, copy to device
LAUNCH = "flowgnn.launch"                 # dispatch: program call, enqueue
COMPILE = "flowgnn.compile"               # a trace or compile of a bucket
STAGE = "flowgnn.stage"                   # dispatch: double buffer full
DEVICE_WAIT = "flowgnn.device_wait"       # completer: wait for the chip
FETCH = "flowgnn.fetch"                   # completer: device -> host copy
UNPACK = "flowgnn.unpack"                 # completer: per-graph split
RESOLVE = "flowgnn.resolve"               # completer: stats and futures
WIDE = "flowgnn.wide"                     # wide runner: one gang run

enabled = TraceAnnotation.is_enabled


class _Off:
    """The span while the profiler is off: takes ids, does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def set_metadata(self, **ids) -> None:
        return None


OFF = _Off()


def _off(name: str, **ids) -> _Off:
    return OFF


def span(name: str, **ids):
    """A span ``name`` with ``ids`` as its stats while the profiler
    records; otherwise ``OFF``."""
    if not enabled():
        return OFF
    return TraceAnnotation(name, **ids)


def tracer():
    """``span`` for one pass of a loop that opens several spans: reads the
    profiler's flag once, and returns ``TraceAnnotation`` or a factory of
    ``OFF``."""
    return TraceAnnotation if enabled() else _off


def bucket_name(key: Tuple[int, ...]) -> str:
    """``(1024, 2048, 32)`` -> ``"1024x2048x32"``."""
    return "x".join(str(k) for k in key)


def id_list(ids: Iterable[int]) -> str:
    """Request ids as one stat value (a comma would split the stat)."""
    return ";".join(str(i) for i in ids)
