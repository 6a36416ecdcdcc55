"""benchmarks/thread_cpu.py: per-thread CPU readings and their split by
the engine's thread roles (the chip run itself is not exercised here)."""

import threading
import time

import pytest

from benchmarks import thread_cpu


@pytest.mark.parametrize("key, role", [
    ("MainThread:101", "client"),
    ("flowgnn-dispatch-0:102", "dispatch"),
    ("flowgnn-complete-3:103", "completer"),
    ("flowgnn-placer:104", "placer"),
    ("thread-cpu-sampler:105", "other"),
    ("python3:106", "other"),
])
def test_role_of_each_thread_name(key, role):
    assert thread_cpu.role(key) == role


def test_thread_cpu_charges_a_busy_thread_to_its_role():
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(1000))

    t = threading.Thread(target=spin, name="flowgnn-dispatch-7")
    before = thread_cpu.thread_cpu()
    t.start()
    time.sleep(0.3)
    after = thread_cpu.thread_cpu()
    stop.set()
    t.join()
    keys = [k for k in after if k.startswith("flowgnn-dispatch-7:")]
    assert len(keys) == 1 and keys[0] not in before
    assert thread_cpu.role(keys[0]) == "dispatch"
    assert after[keys[0]] > 0.0
    assert any(thread_cpu.role(k) == "client" for k in after)


def test_sampler_takes_the_first_and_last_reading_inside_the_window():
    s = thread_cpu.Sampler()
    s.samples = [(t, {"MainThread:1": t / 10}) for t in (0.5, 1.5, 2.5, 3.5)]
    (ta, a), (tb, b) = s.inside(1.0, 3.0)
    assert (ta, tb) == (1.5, 2.5)
    assert b["MainThread:1"] - a["MainThread:1"] == pytest.approx(0.1)
    with pytest.raises(RuntimeError, match="fewer than two samples"):
        s.inside(1.0, 2.0)


def test_sampler_stops_promptly():
    s = thread_cpu.Sampler()
    s.start()
    time.sleep(0.05)
    t = time.perf_counter()
    s.stop_flag.set()
    s.join()
    assert time.perf_counter() - t < 0.5
    assert len(s.samples) >= 1
