"""FLOP and byte counts of the benchmark's models, and the peak table.

The counts are checked against hand counts on a 3-node, 4-edge graph at
the configurations' published widths.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from peaks import peak  # noqa: E402


def counts(name):
    return harness.load_module(BENCH / "counts" / f"{name}.py")


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_gin_counts_by_hand():
    c, cfg = counts("gin"), config("gin")
    # D = 100, F = 9, Fe = 3, 5 layers, head 100 -> 1; n = 3, e = 4
    # node encoder 2*3*9*100 + 3*100                      =     5700
    # per layer: bond 2*4*3*100 + 400 = 2800, message 400, sum 400,
    #   (1+eps)x + m 600, D->2D 120000 + 600, 2D->D 120000 + 300
    #   = 245100, times 5                                  =  1225500
    # mean pool 300, head 2*100 + 1 = 201
    assert c.flops(3, 4, cfg) == 5700 + 1225500 + 300 + 201
    # bytes: features 3*9*4 = 108; per layer (300 + 12 + 300)*4 + 8*4
    # = 2480, times 5 = 12400; answer 4
    assert c.bytes_moved(3, 4, cfg) == 108 + 12400 + 4


def test_pna_counts_by_hand():
    c, cfg = counts("pna"), config("pna")
    # D = 80, F = 9, Fe = 3, 4 layers, head 80 -> 40 -> 20 -> 1; n=3, e=4
    # node encoder 2*3*9*80 + 240                         =     4560
    # per layer: bond 1920 + 320, pre 2*4*160*80 + 320 = 102720,
    #   sum 320, squares 640, max/min 640, mean/var/std 1440,
    #   scalers 2880, post 2*3*1040*80 + 240 = 499440
    #   = 610320, times 4                                  =  2441280
    # mean pool 240, head 6440 + 1620 + 41 = 8101
    assert c.flops(3, 4, cfg) == 4560 + 2441280 + 240 + 8101
    # bytes: 108 + 4 * ((240 + 12 + 240)*4 + 32) + 4
    assert c.bytes_moved(3, 4, cfg) == 108 + 8000 + 4


@pytest.mark.parametrize("name", ["gin", "pna"])
def test_weight_bytes_match_the_weights(name):
    import jax
    cfg = config(name)
    ref = harness.load_module(BENCH / "refs" / f"{name}.py")
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert counts(name).weight_bytes(cfg) == 4 * n


@pytest.mark.parametrize("name", ["gin", "pna"])
def test_counts_take_arrays(name):
    c, cfg = counts(name), config(name)
    n = np.array([3.0, 25.0, 49.0])
    e = np.array([4.0, 56.0, 784.0])
    got = c.flops(n, e, cfg)
    want = [c.flops(int(a), int(b), cfg) for a, b in zip(n, e)]
    assert np.array_equal(got, want)


def test_peak_table():
    assert peak("TPU v5 lite") == {"flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="no published peak"):
        peak("TPU v99")
