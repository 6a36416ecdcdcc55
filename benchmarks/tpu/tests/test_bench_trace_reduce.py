"""The reduction from a profiler trace to busy, idle and module time.

Hand-made intervals check the arithmetic; the fixture, a trace recorded on
a TPU v5e (three gin.trigger requests), checks the reading of a real file
against numbers worked out by hand from its events.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

FIXTURE = BENCH / "fixtures" / "gin_trigger_v5e.xplane.pb"


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 20)]) == \
        [(0, 4), (5, 12)]


def test_op_name():
    assert tr.op_name("%fusion.72 = f32[64,100]{1,0} fusion(f32[1] %p)") \
        == "fusion.72"
    assert tr.op_name("%while = (s32[], f32[64,100]) while(...)") == "while"


def test_gaps_and_clip():
    busy = [(2, 4), (6, 7)]
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([], 0, 10) == [(0, 10)]
    assert tr.clip([(0, 5), (8, 20), (30, 40)], 3, 10) == [(3, 5), (8, 10)]


def test_reduce_events_by_hand():
    chips = {
        0: tr.Chip(ops=[("a", 10, 30), ("b", 20, 40), ("a", 60, 70)],
                   modules=[(10, 40), (60, 70), (95, 120)]),
        1: tr.Chip(ops=[("b", 0, 50)], modules=[(0, 50)]),
    }
    host = [("bench.submit", 40, 55), ("TpuExecutable::Execute", 50, 60),
            ("bench.submit", 80, 99)]
    s = tr.reduce_events(chips, host, (0, 100))
    # chip 0: busy [10, 40) + [60, 70) = 40; gaps [0,10) [40,60) [70,100)
    # chip 1: busy [0, 50) = 50; gap [50, 100)
    assert s.busy_s == pytest.approx({0: 40e-9, 1: 50e-9})
    assert s.mean_busy_s == pytest.approx(45e-9)
    # modules clipped to the window: 30 + 10 + 5 on chip 0, 50 on chip 1
    assert s.module_s == pytest.approx({0: 45e-9, 1: 50e-9})
    assert s.launches == {0: 3, 1: 1}
    # op seconds over both chips: a 20 + 10, b 20 + 50
    assert [k for k, _ in s.device_ops] == ["b", "a"]
    assert [v for _, v in s.device_ops] == pytest.approx([70e-9, 30e-9])
    # a gap takes the name of the host event covering most of it, if that
    # covers half or more: [0,10) nothing -> untraced host 10; [40,60)
    # submit 15 of 20 (execute 10) -> submit 20; [70,100) submit 19 of 30
    # -> submit 30; chip 1 [50,100): submit 19 of 50 -> untraced host 50
    assert [k for k, _ in s.idle_gaps] == ["untraced host", "bench.submit"]
    assert [v for _, v in s.idle_gaps] == pytest.approx([60e-9, 50e-9])
    assert s.window_s == pytest.approx(100e-9)


def test_no_device_ops_gives_nothing():
    assert tr.reduce_events({0: tr.Chip()}, [], (0, 10)) is None
    assert tr.reduce_events({}, [], None) is None


def _sweep_union(intervals):
    """Covered length by an endpoint sweep: a second way to the union."""
    events = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    depth, last, covered = 0, None, 0
    for t, d in events:
        if depth > 0:
            covered += t - last
        depth += d
        last = t
    return covered


def test_fixture_recorded_on_the_chip():
    chips, host, window = tr.read_xplane(str(FIXTURE))
    # the benchmark's window span: start 48801099 ns, 23854339 ns long
    assert window == (48801099, 48801099 + 23854339)
    assert sorted(chips) == [0]
    chip = chips[0]
    # three forwards, jit__lambda, of 78897, 78353 and 78787 ns
    assert chip.modules == [(51929013, 51929013 + 78897),
                            (60056317, 60056317 + 78353),
                            (67521876, 67521876 + 78787)]
    assert len(chip.ops) == 510
    s = tr.reduce_events(chips, host, window)
    assert s.window_s == pytest.approx(23854339e-9)
    assert s.module_s[0] == pytest.approx((78897 + 78353 + 78787) * 1e-9)
    assert s.launches == {0: 3}
    assert s.busy_s[0] == pytest.approx(
        _sweep_union([(a, b) for _, a, b in chip.ops]) * 1e-9)
    assert s.busy_s[0] == pytest.approx(225221e-9)
    # every op lies inside a launch; busy is 95% of the launches' time
    assert s.busy_s[0] < s.module_s[0]
    assert s.device_ops[0] == ("while", pytest.approx(200525e-9))
    idle = dict(s.idle_gaps)
    assert sum(idle.values()) == pytest.approx(
        (23854339 - 225221) * 1e-9)
    assert idle["untraced host"] == pytest.approx(23628764e-9)
