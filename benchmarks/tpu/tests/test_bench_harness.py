"""A benchmark run driven end to end on the CPU, at a small size.

The run skips only the harness's look for a chip: the engine, the window,
the sample and the reference are the same code as on the TPU. Sound runs
come out correct; the control and each fault the cells can have come out
not correct.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device"]
SMALL = {
    "gin.trigger": {"pool": 32, "rate_hz": 100.0, "sample": 16,
                    "warm_s": 0.5},
    "gin.screen": {"pool": 32, "outstanding_per_chip": 16, "sample": 16,
                   "warm_s": 0.5,
                   "queue": {"max_batch": 4, "max_nodes": 128,
                             "max_edges": 256}},
}
SMALL["gin.screen.pool4"] = SMALL["gin.screen"]


def small_cell(name):
    cell = harness.load_cell(name)
    cell.mix.update(SMALL[name])
    return cell


def run(name, seed=2 ** 31 + 11, trace=False):
    import jax
    return harness.run_cell(small_cell(name), seed=seed, seconds=1.0,
                            trace=trace, devices=jax.devices("cpu"),
                            t_start=time.perf_counter())


@pytest.fixture
def engine_cls():
    from repro.core.engine import GraphStreamEngine
    return GraphStreamEngine


@pytest.mark.parametrize("name", ["gin.trigger", "gin.screen"])
def test_sound_run_is_correct_and_its_line_has_the_keys(name):
    out = run(name)
    assert list(out) == KEYS + ["compared"]
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["compared"]) == {"failed_requests", "mean_rel_gap"}
    for c in out["compared"].values():
        assert set(c) == {"value", "limit"}
    assert "setup_s" in out["metrics"]
    assert out["device"]["platform"] == "cpu"
    json.dumps(out)


def test_traced_run_reports_the_host_metrics():
    # the CPU has no TPU plane: the device metrics find nothing to read
    out = run("gin.trigger", trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"submit_ms.trigger",
                                   "queue_wait_ms.trigger"}
    assert "breakdown" not in out


def test_answer_altered_where_produced_is_not_correct(monkeypatch,
                                                      engine_cls):
    split = engine_cls._split_outputs
    monkeypatch.setattr(engine_cls, "_split_outputs",
                        lambda self, pb, out: [-r for r in
                                               split(self, pb, out)])
    for name in ("gin.trigger", "gin.screen"):
        out = run(name)
        assert out["correct"] is False
        c = out["compared"]["mean_rel_gap"]
        assert c["value"] > c["limit"]


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch, engine_cls):
    build = engine_cls._build_batch

    def half(self, pb):
        return build(self, pb.subset(pb.items[:max(1, len(pb.items) // 2)]))
    monkeypatch.setattr(engine_cls, "_build_batch", half)
    out = run("gin.screen")
    assert out["correct"] is False
    c = out["compared"]["mean_rel_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [101, 102, 103])
def test_control_is_not_correct(seed):
    """The reference computed in bfloat16, in the program's place, reads
    above the limit; the program, on the CPU at float32, below."""
    import jax
    cell = small_cell("gin.trigger")
    cpu = jax.devices("cpu")[0]
    r, params, tr, sample, served = harness.measure(
        cell, seed=seed, seconds=1.0, trace=False, devices=[cpu],
        t_start=time.perf_counter(), log=lambda m: None)
    ref, mag = harness.reference_outputs(cell, params, tr, sample, cpu)
    low, _ = harness.reference_outputs(cell, params, tr, sample, cpu,
                                       control=True)
    limit = cell.limits["mean_rel_gap"]
    assert harness.mean_gap(low, ref, mag) > limit
    assert harness.mean_gap(np.stack(served), ref, mag) < limit


def test_result_line_of_a_traced_run_has_a_breakdown():
    import jax
    import trace_reduce
    cell = small_cell("gin.trigger")
    chips = {0: trace_reduce.Chip(ops=[("fusion", 0, 10), ("dot", 5, 30)],
                                  modules=[(0, 30)])}
    summary = trace_reduce.reduce_events(chips, [("bench.submit", 30, 90)],
                                         (0, 100))
    r = harness.Run(
        cell=cell, device_kind="TPU v5 lite", chips=1, seconds=1.0,
        setup_s=1.0, attempted=3, failed=0, latency_s=np.ones(3),
        lateness_s=np.zeros(3), completed=3,
        completed_nodes=np.full(3, 49), completed_edges=np.full(3, 784),
        submit_s=np.full(3, 1e-4), queue_wait_s=[1e-4] * 3,
        batch_sizes=[1] * 3, graphs_by_device={"tpu:0": 3},
        memory_peak_bytes=1, trace=summary,
        trace_nodes=np.full(2, 49), trace_edges=np.full(2, 784))
    out = harness.result(cell, r, jax.devices("cpu"), True, 1e-3,
                         lambda m: None)
    assert list(out) == KEYS + ["breakdown", "compared"]
    ops = out["breakdown"]["device_ops"]
    assert [k for k, _ in ops] == ["dot", "fusion"]
    assert [v for _, v in ops] == pytest.approx([25e-9, 10e-9])
    gaps = out["breakdown"]["idle_gaps"]
    assert [k for k, _ in gaps] == ["bench.submit"]
    assert [v for _, v in gaps] == pytest.approx([70e-9])
    assert out["device"]["busy_s"] == pytest.approx(30e-9)
    assert out["device"]["window_s"] == pytest.approx(100e-9)
    assert set(out["metrics"]) == {"submit_ms.trigger",
                                   "queue_wait_ms.trigger",
                                   "device_ms_per_request.trigger"}
    assert out["metrics"]["device_ms_per_request.trigger"]["value"] == \
        pytest.approx(30e-9 / 2 * 1e3)


def test_pool_of_four_serves_on_every_chip():
    """gin.screen.pool4 on four host devices: the served answers match the
    reference and the placement reads over all four."""
    code = (
        "import json, sys, time\n"
        f"sys.path.insert(0, {str(BENCH)!r})\n"
        f"sys.path.insert(0, {str(BENCH / 'tests')!r})\n"
        "import jax, test_bench_harness as t\n"
        "h = t.harness\n"
        "cell = t.small_cell('gin.screen.pool4')\n"
        "devs = jax.devices('cpu')\n"
        "run, params, tr, sample, served = h.measure(cell, seed=2 ** 31 + 5, "
        "seconds=1.0, trace=False, devices=devs, "
        "t_start=time.perf_counter(), log=lambda m: None)\n"
        "gap = h.compare(cell, params, tr, sample, served, devs[0])\n"
        "print(json.dumps({'chips': run.chips, 'failed': run.failed, "
        "'gap': gap, 'limit': cell.limits['mean_rel_gap'], "
        "'by_device': run.graphs_by_device, 'imbalance': h.metric_value("
        "run, {'name': 'placement_imbalance.pool4'})}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["chips"] == 4 and out["failed"] == 0
    assert out["gap"] <= out["limit"]
    assert len(out["by_device"]) == 4, out["by_device"]
    assert 1.0 <= out["imbalance"] < 2.0


def _cli(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/tpu/run.py", "--workload",
         "gin.trigger", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    r = _cli(ROOT)
    assert r.returncode == 2
    assert r.stdout == ""
    assert "no TPU found" in r.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout == ""
