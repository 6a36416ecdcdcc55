"""The Pallas kernels compile for a TPU v5e (Mosaic), with no chip attached.

Every kernel form the six paper models use is lowered and compiled for a
described ``v5e:2x2`` chip at published widths (``PAPER_GNN_CONFIGS``), at
the smallest and the largest (node_pad, edge_pad) bucket pair the engine
warms, with the engine's edge_tile=128 and num_banks=4. Interpret mode
(every other kernel test) cannot see what Mosaic refuses: reshapes it
cannot lay out, or more VMEM than a kernel may use. Compiling runs
nothing, so results are covered by the interpret-mode tests.

The topology is described inside a module fixture — never at import — so
test collection is the same on every xdist worker, and only the worker
that runs this file loads the TPU compiler.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.models import PAPER_GNN_CONFIGS  # noqa: E402
from repro.kernels.layer_fused import layer_fused  # noqa: E402
from repro.kernels.mp_pipeline import mp_pipeline  # noqa: E402
from repro.kernels.mp_scatter import mp_scatter_multi  # noqa: E402
from repro.kernels.seg_softmax import seg_softmax  # noqa: E402

# smallest and largest engine bucket: (b, 2b) for b in (32, ..., 1024)
BUCKETS = [(32, 64), (1024, 2048)]
TILING = dict(edge_tile=128, num_banks=4, interpret=False)

GCN, GIN, GAT, PNA, DGN = (PAPER_GNN_CONFIGS[m]
                           for m in ("gcn", "gin", "gat", "pna", "dgn"))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _form(name, n, e, sharding):
    """(function, abstract arguments) of one kernel form on an (n, e)
    bucket, at the widths of the model that uses it."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    graph = (s((e,), jnp.int32), s((e,), jnp.int32), s((e,), jnp.bool_))
    if name == "mp_stats":              # every statistic, PNA's phi
        d = PNA.hidden_dim
        return (lambda x, snd, rcv, m, et, b: mp_pipeline(
            x, snd, rcv, m, n, stats=("sum", "sumsq", "count", "max", "min"),
            edge_term=et, bias=b, activation="relu", **TILING),
            (s((n, d)), *graph, s((e, d)), s((d,))))
    if name == "mp_attention":          # GAT's in-sweep online softmax
        d, h = GAT.heads * GAT.head_dim, GAT.heads
        return (lambda x, snd, rcv, m, a_s, a_d: mp_pipeline(
            x, snd, rcv, m, n, stats=("sum",), att_src=a_s, att_dst=a_d,
            **TILING),
            (s((n, d)), *graph, s((n, h)), s((n, h))))
    if name == "layer_fused_self":      # GIN: 1+eps self term, 2-layer MLP
        d = GIN.hidden_dim
        return (lambda x, snd, rcv, m, et, w1, b1, w2, b2, eps: layer_fused(
            x, snd, rcv, m, n, w1=w1, b1=b1, w2=w2, b2=b2, edge_term=et,
            phi_activation="relu", self_coeff=eps, **TILING),
            (s((n, d)), *graph, s((e, d)), s((d, 2 * d)), s((2 * d,)),
             s((2 * d, d)), s((d,)), s(())))
    if name == "layer_fused_self_raw":  # GCN layer 0: raw 9-wide features
        f, d = GCN.node_feat_dim, GCN.hidden_dim
        return (lambda x, snd, rcv, m, sw, w1, b1, sc: layer_fused(
            x, snd, rcv, m, n, w1=w1, b1=b1, src_weight=sw, self_coeff=sc,
            **TILING),
            (s((n, f)), *graph, s((e,)), s((f, d)), s((d,)), s((n,))))
    if name == "layer_fused_scalers":   # PNA: 4 statistics x 3 scalers
        d = PNA.hidden_dim
        return (lambda x, snd, rcv, m, y, et, pb, w1, b1, sc, deg:
                layer_fused(x, snd, rcv, m, n, w1=w1, b1=b1, node_input=y,
                            edge_term=et, phi_bias=pb, phi_activation="relu",
                            scalers=sc, degrees=deg, out_activation="relu",
                            **TILING),
                (s((n, d)), *graph, s((n, d)), s((e, d)), s((d,)),
                 s((13 * d, d)), s((d,)), s((n, 3)), s((n,))))
    if name == "layer_fused_field":     # DGN: stacked [x | x*w] lanes
        d = DGN.hidden_dim
        return (lambda x, snd, rcv, m, y, sw, w1, b1, ws, deg: layer_fused(
            x, snd, rcv, m, n, w1=w1, b1=b1, node_input=y, src_weight=sw,
            field_wsum=ws, degrees=deg, out_activation="relu", **TILING),
            (s((n, d)), *graph, s((n, 2 * d)), s((e, 2 * d)),
             s((3 * d, d)), s((d,)), s((n,)), s((n,))))
    if name == "mp_scatter_multi":      # PNA's messages, one stats sweep
        d = PNA.hidden_dim
        return (lambda msg, rcv, m: mp_scatter_multi(
            msg, rcv, m, n, stats=("sum", "sumsq", "count", "max", "min"),
            **TILING),
            (s((e, d)), graph[1], graph[2]))
    assert name == "seg_softmax"        # GAT's two-sweep softmax
    return (lambda lg, rcv, m: seg_softmax(lg, rcv, m, n, **TILING),
            (s((e, GAT.heads)), graph[1], graph[2]))


FORMS = ["mp_stats", "mp_attention", "layer_fused_self",
         "layer_fused_self_raw", "layer_fused_scalers", "layer_fused_field",
         "mp_scatter_multi", "seg_softmax"]


@pytest.mark.parametrize("bucket", BUCKETS, ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("form", FORMS)
def test_kernel_compiles_for_v5e(one_chip, form, bucket):
    fn, args = _form(form, *bucket, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
