"""The traffic mixes: seeded pools, fresh features, arrival schedules."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402

BIG_SEED = 2 ** 31 + 98765


def mix(name, **over):
    m = json.loads((BENCH / "mixes" / f"{name}.json").read_text())
    m["pool"] = 64
    m.update(over)
    return m


def trigger(seed, seconds=2.0, rate=300.0):
    return traffic.Traffic(mix("trigger", rate_hz=rate), seed, seconds)


def same_request(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", [0, BIG_SEED, 2 ** 40 + 3])
def test_same_seed_same_traffic(seed):
    a, b = trigger(seed), trigger(seed)
    assert np.array_equal(a.due_s, b.due_s)
    assert np.array_equal(a.order, b.order)
    for i in (0, 1, len(a) - 1):
        assert same_request(a.request(i), b.request(i))


def test_other_seed_other_requests_on_the_same_schedule():
    a, b = trigger(1), trigger(BIG_SEED)
    assert np.array_equal(a.due_s, b.due_s)
    assert not np.array_equal(a.order, b.order)
    assert not same_request(a.request(0), b.request(0))


@pytest.mark.parametrize("name", ["trigger", "screen"])
def test_no_two_requests_equal(name):
    tr = traffic.Traffic(mix(name, rate_hz=500.0), BIG_SEED, 2.0)
    n = min(len(tr), 3000)
    seen = set()
    for i in range(n):
        nf, snd, rcv, ef, _ = tr.request(i)
        seen.add((int(tr.order[i]), nf.tobytes()))
    assert len(seen) == n


@pytest.mark.parametrize("rows", [2 ** 11, traffic.FEATURE_ROWS])
def test_feature_rows_never_repeat_a_start(rows):
    # request i starts at row (i * stride) % rows: distinct for i < rows
    i = np.arange(rows, dtype=np.int64)
    assert len(np.unique((i * traffic.FEATURE_STRIDE) % rows)) == rows


def test_bank_holds_a_row_for_every_request():
    tr = trigger(5, seconds=3.0, rate=400.0)
    assert len(tr) <= tr.rows < 2 * len(tr)
    assert tr.bank.shape[0] >= tr.rows + max(s.n_nodes for s in tr.pool)


@pytest.mark.parametrize("rate", [50.0, 800.0])
def test_poisson_mean_rate(rate):
    duration = 4000.0 / rate
    t = traffic.poisson_arrivals(traffic.stream(BIG_SEED, "arrivals"), rate,
                                 duration)
    assert np.all(np.diff(t) > 0) and t[0] >= 0 and t[-1] < duration
    # 4000 expected arrivals: the count's standard deviation is 63
    assert abs(len(t) - 4000) < 4 * 63


def test_open_loop_schedule_covers_warm_up_and_window():
    tr = trigger(7, seconds=3.0, rate=400.0)
    assert tr.due_s[-1] < tr.warm_s + 3.0
    assert abs(len(tr) / (tr.warm_s + 3.0) - 400.0) < 4 * np.sqrt(2000) / 5


def test_pool_shapes():
    hep = traffic.Traffic(mix("trigger", rate_hz=10.0, pool=256), 3, 1.0)
    n = np.array([s.n_nodes for s in hep.pool])
    e = np.array([s.n_edges for s in hep.pool])
    assert np.array_equal(e, 16 * n)
    assert 45 < n.mean() < 53
    mol = traffic.Traffic(mix("screen", pool=256), 3, 1.0)
    n = np.array([s.n_nodes for s in mol.pool])
    e = np.array([s.n_edges for s in mol.pool])
    assert 23 < n.mean() < 28 and 50 < e.mean() < 62
    assert np.all(e >= 2 * (n - 1))


def test_batch_buckets():
    hep = traffic.Traffic(mix("trigger", rate_hz=10.0), 3, 1.0)
    buckets = (32, 64, 128, 256, 512, 1024)
    got = traffic.batch_buckets(1, None, None, hep.pool, buckets)
    want = sorted({(traffic.pad_pow2(s.n_nodes), traffic.pad_pow2(s.n_edges))
                   for s in hep.pool})
    assert got == want
    packed = traffic.batch_buckets(32, 1024, 2048, hep.pool, buckets)
    assert (1024, 2048) in packed and (32, 64) in packed
    assert all(n <= 1024 and e <= 2048 for n, e in packed)
