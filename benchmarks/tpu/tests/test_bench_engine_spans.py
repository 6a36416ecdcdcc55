"""The joins over the engine's spans (``engine_spans.py``) on hand-made
spans and busy intervals whose numbers are worked out by hand below, and
on a trace recorded on a TPU v5e (four gin.trigger requests).

Window [0, 1000) ns, one chip, busy [300, 340), [800, 830), [950, 960).
Threads: C client, P placer, D dispatch, K completer.

- request 1, batch 10: submit [100, 120) (validate [105, 115)), place
  [130, 150), build [160, 200), launch [200, 290), device wait [295, 345),
  fetch [345, 355), unpack [355, 360), resolve [360, 400);
- requests 2 and 3, batch 11: submits [600, 610) and [620, 640), place
  [650, 660), build [700, 720), launch [720, 760), stage [760, 790),
  device wait [780, 835), fetch [840, 845), unpack [845, 850), resolve
  [850, 900);
- a placer pass that placed nothing [500, 505); request 4, submitted
  [950, 955) and placed [960, 970) as batch 12, whose path the window
  does not hold.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import engine_spans as es  # noqa: E402

FIXTURE = BENCH / "fixtures" / "gin_trigger_spans_v5e.xplane.pb"
BEFORE = BENCH / "fixtures" / "gin_trigger_v5e.xplane.pb"
C, P, D, K = (("/host:CPU", i) for i in range(4))


def S(name, s, e, thread, **ids):
    return es.Span("flowgnn." + name, s, e, thread, ids)


SPANS = [
    S("submit", 100, 120, C, req=1), S("submit.validate", 105, 115, C),
    S("place", 130, 150, P, batch=10, reqs=1, dev=0),
    S("build", 160, 200, D, batch=10), S("launch", 200, 290, D, batch=10),
    S("device_wait", 295, 345, K, batch=10),
    S("fetch", 345, 355, K, batch=10), S("unpack", 355, 360, K, batch=10),
    S("resolve", 360, 400, K, batch=10),
    S("place", 500, 505, P),
    S("submit", 600, 610, C, req=2), S("submit", 620, 640, C, req=3),
    S("place", 650, 660, P, batch=11, reqs="2;3", dev=0),
    S("build", 700, 720, D, batch=11), S("launch", 720, 760, D, batch=11),
    S("stage", 760, 790, D, batch=11),
    S("device_wait", 780, 835, K, batch=11),
    S("fetch", 840, 845, K, batch=11), S("unpack", 845, 850, K, batch=11),
    S("resolve", 850, 900, K, batch=11),
    S("submit", 950, 955, C, req=4),
    S("place", 960, 970, P, batch=12, reqs=4, dev=0),
]
BUSY = [(300, 340), (800, 830), (950, 960)]


def spans(busy=BUSY):
    return es.EngineSpans(sorted(SPANS, key=lambda e: (e.start, e.end)),
                          {0: list(busy)}, (0, 1000))


def test_batches_and_requests_join_across_threads():
    sp = spans()
    by = es.batches(sp)
    assert sorted(by) == [10, 11, 12]
    assert by[11].reqs == (2, 3) and by[10].reqs == (1,)
    assert by[10].dev == 0
    assert by[11].stages[es.STAGE].thread == D
    reqs = es.requests(sp, by)
    # request 4's batch has no build, launch or completion in the window
    assert [r.id for r in reqs] == [1, 2, 3]
    assert [r.inflight for r in reqs] == [(100, 400), (600, 900),
                                          (620, 900)]
    # 120->130, 150->160, 290->295; 610->650, 660->700, stage end 790 is
    # after the device wait's start 780: 0; 640->650, 660->700, 0
    assert [r.handoffs_ns() for r in reqs] == [[10, 10, 5], [40, 40, 0],
                                               [10, 40, 0]]
    # request 1 is covered whole; 2 and 3 miss [835, 840)
    assert [r.coverage() for r in reqs] == pytest.approx(
        [1.0, 295 / 300, 275 / 280])


def test_idle_in_flight_split_by_running_span():
    sp = spans()
    by = es.batches(sp)
    idle, split = es.idle_in_flight(sp, es.requests(sp, by), by)
    # flying [100, 400) and [600, 900); idle there: [100, 300),
    # [340, 400), [600, 800), [830, 900)
    assert idle == 200 + 60 + 200 + 70
    assert split == {
        "flowgnn.submit": 5 + 5 + 10 + 20, "flowgnn.submit.validate": 10,
        "untraced host": 10 + 10 + 5 + 10 + 10 + 40 + 5,
        "flowgnn.place": 20 + 10, "flowgnn.build": 40 + 20,
        "flowgnn.launch": 90 + 40, "flowgnn.stage": 20,
        "flowgnn.device_wait": 5 + 5 + 20 + 5, "flowgnn.fetch": 10 + 5,
        "flowgnn.unpack": 5 + 5, "flowgnn.resolve": 40 + 50}
    assert sum(split.values()) == idle


def test_one_clock_and_host_work():
    sp = spans()
    # launch..device wait: [200, 345) and [720, 835) hold 40 + 30 of 80
    assert es.one_clock(sp, es.batches(sp)) == {0: pytest.approx(70 / 80)}
    assert es.work_ns(sp) == {
        "flowgnn.submit": 20 + 10 + 20 + 5,
        "flowgnn.place": 20 + 5 + 10 + 10, "flowgnn.build": 40 + 20,
        "flowgnn.launch": 90 + 40, "flowgnn.fetch": 10 + 5,
        "flowgnn.unpack": 5 + 5, "flowgnn.resolve": 40 + 50}


def test_clock_offset_finds_the_shift_that_lines_the_planes_up():
    # busy [300, 340) must land in [200, 345) and [800, 830) in
    # [720, 835): shifts from -80 to +5 do
    sp = spans([(300, 340), (800, 830)])
    got = es.clock_offset(sp, es.batches(sp), reach_ns=200, step_ns=5)[0]
    assert got["share_at_0"] == 1.0 and got["best_ns"] == 0
    assert got["range_ns"] == [-80, 5]
    # the device plane about 400 ns early: [-200, -160) lands in
    # [200, 345) from +400 to +505, [350, 380) in [720, 835) from +370 to
    # +455; the least shift that lines both up is the best
    sp = spans([(-200, -160), (350, 380)])
    got = es.clock_offset(sp, es.batches(sp), reach_ns=1000, step_ns=5)[0]
    assert got["share_at_0"] == 0.0 and got["share_at_best"] == 1.0
    assert got["range_ns"] == [400, 455] and got["best_ns"] == 400


def test_report_by_hand():
    r = es.report(spans(), graphs=3)
    assert r["batches"] == 3 and r["requests"] == 3
    assert r["build_ms"] == pytest.approx((40 + 20) / 2 * 1e-6)
    assert r["launch_ms"] == pytest.approx((90 + 40) / 2 * 1e-6)
    assert r["completion_ms"] == pytest.approx(
        ((10 + 5 + 40) + (5 + 5 + 50)) / 2 * 1e-6)
    assert r["handoff_ms"] == pytest.approx((25 + 80 + 50) / 3 * 1e-6)
    assert r["handoff_parts_ms"]["place_build"] == pytest.approx(
        (10 + 40 + 40) / 3 * 1e-6)
    assert r["median_coverage"] == pytest.approx(295 / 300)
    assert r["inflight_idle_ms"] == pytest.approx(530 / 3 * 1e-6)
    assert r["host_ms_per_graph"] == pytest.approx(405 / 3 * 1e-6)
    # without a count of graphs, the requests placed: 1 + 2 + 1
    assert es.report(spans())["host_ms_per_graph"] == pytest.approx(
        405 / 4 * 1e-6)


def test_fixture_recorded_on_the_chip():
    sp = es.read_spans(str(FIXTURE))
    # four requests at batch 1, 8 ms apart, in a 47.5 ms window, traced
    # with JAX's Python tracer on (the profiler's default)
    assert sp.window_ns == (46765391, 94309671)
    assert len(sp.events) == 48
    assert len({e.thread for e in sp.events}) == 4
    by = es.batches(sp)
    reqs = es.requests(sp, by)
    assert [(r.id, r.batch.id, r.batch.dev) for r in reqs] == \
        [(6, 6, 0), (7, 7, 0), (8, 8, 0), (9, 9, 0)]
    assert reqs[0].inflight == (50765891, 63699461)
    assert reqs[0].handoffs_ns() == [45340, 309220, 185600]
    assert all(r.coverage() > 0.98 for r in reqs)
    # the build (nine jnp.asarray transfers, every Python call traced) is
    # the longest stage
    assert [b.ms(es.BUILD) for b in by.values()] == pytest.approx(
        [9.11821, 10.09018, 9.10583, 7.55483])
    # as read, the device plane runs 1.2-1.9 ms ahead of the host's:
    # none of its busy time lies inside its launches until shifted
    assert es.one_clock(sp, by) == {0: 0.0}
    assert es.clock_offset(sp, by)[0]["range_ns"] == [1210000, 1910000]


def test_trace_without_engine_spans_reads_none():
    assert es.read_spans(str(BEFORE)) is None
