"""The gps configuration's benchmark files against the program, on the CPU.

``refs/gps.py`` imports nothing of the program; these tests tie it to it:
its weights have the layout the program serves, and its answer is the
program's dense oracle's and its forward's at float32, at a small size.
The counts are checked by hand on a 3-node, 4-edge graph. The cell runs
end to end at its published widths on a small traffic: sound, it comes
out correct; its control and a negated answer come out not correct.
"""

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SMALL = {"num_layers": 2, "hidden_dim": 32, "heads": 4, "head_dim": 8,
         "ffn_dim": 64, "pe_steps": 4, "pe_dim": 8, "attention_slot": 16,
         "out_dim": 3}


def config(**kw):
    cfg = json.loads((BENCH / "configs" / "gps.json").read_text())
    cfg.update(kw)
    return cfg


def ref():
    return harness.load_module(BENCH / "refs" / "gps.py")


def counts():
    return harness.load_module(BENCH / "counts" / "gps.py")


def test_weights_have_the_programs_layout():
    import jax
    from repro.core.models import make_gnn
    cfg = config()
    gcfg = harness.gnn_config(cfg)
    key = jax.random.PRNGKey(0)
    mine = jax.eval_shape(lambda k: ref().init(k, cfg), key)
    theirs = jax.eval_shape(lambda k: make_gnn(gcfg).init(k, gcfg), key)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)


def test_weights_count_the_published_parameters():
    """9,744,496 published (GraphGPS, ogbg-molpcba); the gap is the OGB
    embedding tables (67,964) against the linear encoders (5,176)."""
    import jax
    cfg = config()
    shapes = jax.eval_shape(lambda k: ref().init(k, cfg),
                            jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    stored = sum(int(np.prod(a.shape)) for _, a in leaves)
    stats = sum(int(np.prod(a.shape)) for p, a in leaves
                if p[-1].key in ("mean", "var"))
    assert stored == cfg["parameters"]["stored_here"]
    assert stored - stats == cfg["parameters"]["trainable_here"]
    assert stored - stats + 67964 - 5176 == cfg["parameters"]["published"]
    assert abs(stored - stats - 9744496) / 9744496 < 0.007


def test_reference_is_the_programs_answer():
    import jax
    import jax.numpy as jnp
    from repro.core.graph import build_graph_batch
    from repro.core.models import make_gnn
    from repro.core.pyg_ref import DENSE_REFS
    from repro.data.graphs import sized_stream
    cfg = config(**SMALL)
    gcfg = harness.gnn_config(cfg)
    params = ref().init(jax.random.PRNGKey(3), cfg)
    model = make_gnn(gcfg)
    graphs = [g for g in sized_stream(seed=4, n_graphs=12, n_mean=10.0,
                                      n_std=3.0)
              if g.node_feat.shape[0] <= 16][:3]
    for gr in graphs:
        n, e = gr.node_feat.shape[0], gr.senders.shape[0]
        b = build_graph_batch(gr.node_feat, gr.senders, gr.receivers,
                              edge_feat=gr.edge_feat, node_pad=32,
                              edge_pad=64)
        g = {"node_feat": jnp.asarray(b.node_feat[:16]),
             "senders": b.senders, "receivers": b.receivers,
             "edge_feat": b.edge_feat, "edge_mask": b.edge_mask,
             "node_mask": b.node_mask[:16]}
        assert n <= 16 and e <= 64
        with jax.default_matmul_precision("highest"):
            mine, mag = ref().forward(params, g, cfg)
            oracle = DENSE_REFS["gps"](params, b, gcfg)[0]
            served = model.apply(params, b, gcfg)[0]
        # float32 in another order, answers O(10): a few 1e-6 of the
        # magnitude; 1e-5 of it as in the gin and pna references' test
        scale = np.asarray(mag)
        assert np.all(scale > 0)
        assert np.all(np.abs(mine - oracle) <= 1e-5 * scale)
        assert np.all(np.abs(mine - served) <= 1e-5 * scale)


def test_fitted_norms_fit_again_to_themselves():
    """Each BN's statistics are its input's under the BNs before it, in
    the forward's order: fitting the fitted weights again on the same
    molecules finds the same statistics."""
    import jax
    cfg = config(**SMALL)
    graphs = ref().molecules(jax.random.PRNGKey(5), 8, cfg)
    fitted = ref().fit_batch_norm(ref().init(jax.random.PRNGKey(3), cfg),
                                  graphs, cfg)
    again = ref().fit_batch_norm(fitted, graphs, cfg)
    for a, b in zip(jax.tree.leaves(fitted), jax.tree.leaves(again)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    var = np.concatenate([np.asarray(p[n]["var"]) for p in fitted["layers"]
                          for n in ref().LAYER_NORMS])
    assert np.all(var > 0)


def test_counts_by_hand():
    c = counts()
    cfg = config(num_layers=1, hidden_dim=8, heads=2, head_dim=4,
                 ffn_dim=16, node_feat_dim=2, edge_feat_dim=1, pe_steps=2,
                 pe_dim=2, out_dim=1)
    # D = 8, F = 16, h: 2 features, e: 1, 2 walk steps to 2, 2 heads;
    # n = 3, e = 4
    # node encoder 2*3*2*6 + 18 = 90, RWSE 2*2*27 = 108, its BN 12,
    #   pe map 24 + 6 = 30, bond encoder 64 + 32 = 96             =   336
    # local: A,B,D,E 4*(384 + 24) = 1632, C 512 + 32 = 544, e_hat 64,
    #   gate*Bx 32, two sums 64, x_hat 72, BN_x + x + BN_1 120,
    #   BN_e + e 96                                               =  2624
    # attention: QKV 1152 + 72, scale 24, scores 144, softmax 54,
    #   AV 144, out 384 + 24, + x and BN_2 72                     =  2070
    # ffn: 24 + (768 + 48) + (768 + 24) + 24 + 48                 =  1704
    # mean pool 24, head 2*8 + 1 = 17
    assert c.flops(3, 4, cfg) == 336 + 2624 + 2070 + 1704 + 24 + 17
    # bytes: features (6 + 4)*4 + indices 32; the layer 2*(24 + 32)*4
    #   + 32 = 480; answer 4
    assert c.bytes_moved(3, 4, cfg) == 72 + 480 + 4


def test_weight_bytes_match_the_weights():
    import jax
    cfg = config()
    shapes = jax.eval_shape(lambda k: ref().init(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert counts().weight_bytes(cfg) == 4 * n


def test_counts_take_arrays():
    c, cfg = counts(), config()
    n = np.array([3.0, 25.0, 49.0])
    e = np.array([4.0, 56.0, 110.0])
    want = [c.flops(int(a), int(b), cfg) for a, b in zip(n, e)]
    assert np.array_equal(c.flops(n, e, cfg), want)


def test_cell_reports_the_screen_metrics():
    cell = harness.load_cell("gps.screen", ROOT)
    assert cell.chips == 1 and cell.config["model"] == "gps"
    assert cell.mix["queue"]["max_batch"] == 32
    assert {m["name"] for m in cell.reported(trace=False)} == \
        {"graphs_per_s", "setup_s"}
    assert {m["name"] for m in cell.reported(trace=True)} == \
        {"batch_fill.screen", "device_idle_share.screen",
         "forward_roofline.screen", "mfu.screen"}
    assert cell.limits["mean_rel_gap"] > 0


@pytest.mark.parametrize("name", ["refs", "counts"])
def test_files_import_nothing_of_the_program(name):
    text = (BENCH / name / "gps.py").read_text()
    assert "repro" not in text


SMALL_SCREEN = {"pool": 32, "outstanding_per_chip": 16, "sample": 16,
                "warm_s": 0.5,
                "queue": {"max_batch": 4, "max_nodes": 128,
                          "max_edges": 256}}


def small_cell():
    cell = harness.load_cell("gps.screen", ROOT)
    cell.mix.update(SMALL_SCREEN)
    return cell


def test_sound_and_negated_runs_of_the_cell(monkeypatch):
    """Served at float32 on the CPU the cell is correct; the same run
    with every answer negated where the engine hands it out is not."""
    import jax
    from repro.core.engine import GraphStreamEngine
    out = harness.run_cell(small_cell(), seed=2 ** 31 + 11, seconds=1.0,
                           trace=False, devices=jax.devices("cpu"),
                           t_start=time.perf_counter())
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    split = GraphStreamEngine._split_outputs
    monkeypatch.setattr(GraphStreamEngine, "_split_outputs",
                        lambda self, pb, o: [-r for r in split(self, pb, o)])
    out = harness.run_cell(small_cell(), seed=2 ** 31 + 11, seconds=1.0,
                           trace=False, devices=jax.devices("cpu"),
                           t_start=time.perf_counter())
    c = out["compared"]["mean_rel_gap"]
    assert out["correct"] is False and c["value"] > c["limit"]


def test_control_is_not_correct():
    """The reference in bfloat16, in the program's place, reads above the
    cell's limit; the program, at float32 on the CPU, below."""
    import jax
    cell = small_cell()
    cpu = jax.devices("cpu")[0]
    _, params, tr, sample, served = harness.measure(
        cell, seed=2 ** 31 + 12, seconds=1.0, trace=False, devices=[cpu],
        t_start=time.perf_counter(), log=lambda m: None)
    ref_out, mag = harness.reference_outputs(cell, params, tr, sample, cpu)
    low, _ = harness.reference_outputs(cell, params, tr, sample, cpu,
                                       control=True)
    limit = cell.limits["mean_rel_gap"]
    assert harness.mean_gap(low, ref_out, mag) > limit
    assert harness.mean_gap(np.stack(served), ref_out, mag) < limit
