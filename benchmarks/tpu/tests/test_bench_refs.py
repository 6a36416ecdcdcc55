"""The benchmark's references and weights against the program, on the CPU.

The references import nothing of the program; these tests tie them to it:
the weights have the layout the program serves, and the reference's answer
is the program's dense oracle's and its forward's, at float32.
"""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import traffic  # noqa: E402


def load(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg, harness.load_module(BENCH / "refs" / f"{name}.py")


@pytest.mark.parametrize("name", ["gin", "pna"])
def test_weights_have_the_programs_layout(name):
    import jax
    from repro.core.models import make_gnn
    cfg, ref = load(name)
    gcfg = harness.gnn_config(cfg)
    key = jax.random.PRNGKey(0)
    mine = jax.eval_shape(lambda k: ref.init(k, cfg), key)
    theirs = jax.eval_shape(lambda k: make_gnn(gcfg).init(k, gcfg), key)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)


@pytest.mark.parametrize("name,mix", [("gin", "trigger"), ("pna", "screen")])
def test_reference_is_the_programs_answer(name, mix):
    import jax
    import jax.numpy as jnp
    from repro.core.graph import build_graph_batch, pad_bucket
    from repro.core.models import make_gnn
    from repro.core.pyg_ref import DENSE_REFS
    cfg, ref = load(name)
    gcfg = harness.gnn_config(cfg)
    m = json.loads((BENCH / "mixes" / f"{mix}.json").read_text())
    m.update(pool=4, rate_hz=10.0)
    tr = traffic.Traffic(m, 5, 1.0)
    params = ref.init(jax.random.PRNGKey(3), cfg)
    model = make_gnn(gcfg)
    for i in range(3):
        nf, snd, rcv, ef, pos = tr.request(i)
        n, e = nf.shape[0], snd.shape[0]
        b = build_graph_batch(nf, snd, rcv, edge_feat=ef,
                              node_pad=pad_bucket(n), edge_pad=pad_bucket(e),
                              node_pos=pos)
        g = harness._padded(tr, [i], traffic.pad_pow2(n, 8),
                            traffic.pad_pow2(e, 8))
        g = {k: jnp.asarray(v[0]) for k, v in g.items()}
        with jax.default_matmul_precision("highest"):
            mine, mag = ref.forward(params, g, cfg)
            oracle = DENSE_REFS[name](params, b, gcfg)[0]
            served = model.apply(params, b, gcfg)[0]
        scale = float(mag[0])
        assert scale > 0
        assert abs(float(mine[0]) - float(oracle[0])) <= 1e-5 * scale
        assert abs(float(mine[0]) - float(served[0])) <= 1e-5 * scale
