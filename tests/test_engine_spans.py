"""The engine's spans (``core/spans.py``): a few graphs served through
``GraphStreamEngine`` under ``jax.profiler`` on the CPU, the trace read
back from its ``.xplane.pb``.

Every stage a request passes through records one ``flowgnn.*`` span on
the thread that runs it, the spans of a batch share its dispatch id, a
bucket miss names itself, and nothing is recorded or built while the
profiler is off.
"""

from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import spans
from repro.core.engine import GraphStreamEngine
from repro.core.models import PAPER_GNN_CONFIGS, make_gnn
from repro.data.graphs import hep_like, molhiv_like

MARK = "test.main_thread"
PER_BATCH = (spans.PLACE, spans.BUILD, spans.LAUNCH, spans.STAGE,
             spans.DEVICE_WAIT, spans.FETCH, spans.UNPACK, spans.RESOLVE)
CLIENT = (spans.SUBMIT, spans.VALIDATE)
DISPATCH = (spans.BUILD, spans.LAUNCH, spans.STAGE)
COMPLETER = (spans.DEVICE_WAIT, spans.FETCH, spans.UNPACK, spans.RESOLVE)


def _engine(**kw):
    cfg = PAPER_GNN_CONFIGS["gin"]
    cfg = cfg.replace(num_layers=2, hidden_dim=16,
                      head_mlp=(8,) if cfg.head_mlp else ())
    params = make_gnn(cfg).init(jax.random.PRNGKey(0), cfg)
    return GraphStreamEngine(cfg, params, **kw)


def _args(g):
    return g.node_feat, g.senders, g.receivers, g.edge_feat, g.node_pos


def _read(log_dir):
    """Every ``flowgnn.*`` and marker event: (thread, name, start, end,
    stats), the thread being the event's line on the host plane."""
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(str(found[-1]))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for tid, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("flowgnn.") or ev.name == MARK:
                    out.append(((plane.name, tid), ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _reqs(value):
    return [int(v) for v in str(value).split(";")]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Molecules at batch 1 into a warmed bucket, then one HEP event
    into a bucket no batch has used (a miss), all inside one trace."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    mols = list(molhiv_like(seed=4, n_graphs=5))
    big = next(hep_like(seed=5, n_graphs=1))
    with _engine(max_batch=1, max_wait_ms=0.0) as eng:
        eng.warmup(*_args(mols[0]))
        jax.profiler.start_trace(log_dir)
        try:
            with jax.profiler.TraceAnnotation(MARK):
                pass
            futs = [eng.submit(*_args(g)) for g in mols[1:]]
            for f in futs:
                f.result(timeout=120)
            futs.append(eng.submit(*_args(big)))
            futs[-1].result(timeout=300)
        finally:
            jax.profiler.stop_trace()
        n_buckets = len(eng.autotune_report())
    return _read(log_dir), len(futs), n_buckets


def _by_name(events):
    out = defaultdict(list)
    for ev in events:
        out[ev[1]].append(ev)
    return out


def test_every_stage_records_a_span_on_its_thread(traced):
    events, n, _ = traced
    by = _by_name(events)
    main = by[MARK][0][0]
    for name in CLIENT + PER_BATCH + (spans.COMPILE,):
        assert by[name], f"no {name} span"
    assert len(by[spans.SUBMIT]) == n
    assert len(by[spans.VALIDATE]) == n
    assert {ev[0] for name in CLIENT for ev in by[name]} == {main}
    placer = {ev[0] for ev in by[spans.PLACE]}
    dispatch = {ev[0] for name in DISPATCH for ev in by[name]}
    completer = {ev[0] for name in COMPLETER for ev in by[name]}
    # one thread each, and four different threads
    assert len(placer) == len(dispatch) == len(completer) == 1
    assert len({main} | placer | dispatch | completer) == 4


def _inside(child, parents):
    return any(p[0] == child[0] and p[2] <= child[2] and child[3] <= p[3]
               for p in parents)


def test_children_nest_in_their_parents(traced):
    by = _by_name(traced[0])
    for v in by[spans.VALIDATE]:
        assert _inside(v, by[spans.SUBMIT])
    # the missed bucket traces and compiles inside its batch's launch
    assert any(_inside(c, by[spans.LAUNCH]) for c in by[spans.COMPILE])


def test_a_batch_shares_its_id_and_maps_its_requests(traced):
    events, n, _ = traced
    by = _by_name(events)
    placed = [ev for ev in by[spans.PLACE] if "batch" in ev[4]]
    assert len(placed) == n                 # batch 1: one batch each
    reqs = [r for ev in placed for r in _reqs(ev[4]["reqs"])]
    assert sorted(reqs) == sorted(ev[4]["req"] for ev in by[spans.SUBMIT])
    assert all(ev[4]["dev"] == jax.devices()[0].id for ev in placed)
    for ev in placed:
        bid = ev[4]["batch"]
        for name in PER_BATCH[1:]:
            same = [x for x in by[name] if x[4].get("batch") == bid]
            assert len(same) == 1, (name, bid)
            # each stage starts after the batch was picked
            assert same[0][2] >= ev[2]
    # a place span without ids is a pass that placed nothing
    assert all(not ev[4] for ev in by[spans.PLACE] if "batch" not in ev[4])


def test_a_bucket_miss_names_itself(traced):
    events, _, n_buckets = traced
    by = _by_name(events)
    buckets = {ev[4]["bucket"] for ev in by[spans.COMPILE]}
    # only the HEP event's bucket was new in the window
    assert len(buckets) == 1 and n_buckets == 2
    node_pad, edge_pad, graph_pad = (int(k) for k in
                                     buckets.pop().split("x"))
    assert node_pad >= 49 and graph_pad >= 1


def test_nothing_is_built_or_recorded_while_the_profiler_is_off(
        tmp_path, monkeypatch):
    class Refused:
        def __init__(self, *a, **kw):
            raise AssertionError("a span was built with the profiler off")

    monkeypatch.setattr(spans, "TraceAnnotation", Refused)
    assert not spans.enabled()
    mols = list(molhiv_like(seed=6, n_graphs=4))
    with _engine(max_batch=2, max_wait_ms=1.0) as eng:
        outs = [f.result(timeout=120)
                for f in [eng.submit(*_args(g)) for g in mols]]
        assert len(outs) == 4
        eng.drain(timeout=60)
    monkeypatch.undo()
    # a trace taken after that window holds none of its spans
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(MARK):
        pass
    jax.profiler.stop_trace()
    assert [ev[1] for ev in _read(str(tmp_path))] == [MARK]


def test_off_span_takes_ids_and_does_nothing():
    assert spans.span(spans.BUILD, batch=3) is spans.OFF
    with spans.tracer()(spans.LAUNCH, batch=3) as sp:
        sp.set_metadata(batch=4)
    assert sp is spans.OFF
    assert spans.bucket_name((64, 128, 1)) == "64x128x1"
    assert spans.id_list([3, 14, 15]) == "3;14;15"
    assert np.all([n.startswith("flowgnn.") for n in CLIENT + PER_BATCH])
