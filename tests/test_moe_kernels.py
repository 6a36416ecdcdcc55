"""Banked MoE dispatch/combine kernels vs the jnp dispatch in nn/moe.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gather_rows import gather_rows, gather_rows_ref
from repro.kernels.moe_dispatch import moe_combine, moe_dispatch


@pytest.mark.parametrize("n,d,s,tile,banks", [
    (64, 32, 128, 32, 2),
    (128, 16, 256, 64, 4),
])
def test_gather_rows_sweep(n, d, s, tile, banks):
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, size=s).astype(np.int32))
    mask = jnp.asarray(rng.random(s) < 0.8)
    out = gather_rows(y, idx, mask, idx_tile=tile, num_banks=banks,
                      interpret=True)
    np.testing.assert_allclose(out, gather_rows_ref(y, idx, mask),
                               atol=1e-5, rtol=1e-5)


def test_moe_kernel_path_matches_jnp_dispatch():
    """Full kernel pipeline (dispatch -> expert FFN -> combine) equals the
    jnp sort-based dispatch for one bank-owned expert group."""
    rng = np.random.default_rng(1)
    t, d, e_loc, cap, k = 64, 16, 4, 32, 2
    x = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
    w_expert = jnp.asarray(
        rng.normal(size=(e_loc, d, d)).astype(np.float32) * 0.3)

    # synthetic routing: each token picks k distinct experts
    top_i = np.stack([rng.permutation(e_loc)[:k] for _ in range(t)])
    top_w = rng.random((t, k)).astype(np.float32)
    flat_e = top_i.reshape(-1)
    flat_t = np.repeat(np.arange(t, dtype=np.int32), k)
    flat_w = top_w.reshape(-1)
    order = np.argsort(flat_e, kind="stable")
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    starts = np.searchsorted(se, np.arange(e_loc), side="left")
    rank = np.arange(t * k) - starts[se]
    own = rank < cap
    slot = np.where(own, se * cap + rank, 0).astype(np.int32)

    # kernel path
    buf = moe_dispatch(x, jnp.asarray(st), jnp.asarray(slot),
                       jnp.asarray(own), e_loc * cap, edge_tile=32,
                       num_banks=2, interpret=True)
    y = jnp.einsum("ecd,edf->ecf", buf.reshape(e_loc, cap, d), w_expert)
    y = jnp.maximum(y, 0.0).reshape(e_loc * cap, d)
    out = moe_combine(y, jnp.asarray(st), jnp.asarray(slot),
                      jnp.asarray(own), jnp.asarray(sw), t, edge_tile=32,
                      num_banks=2, interpret=True)

    # jnp reference (same math, dense per token)
    ref = np.zeros((t, d), np.float32)
    for a in range(t * k):
        if not own[a]:
            continue
        token, expert, w = st[a], se[a], sw[a]
        ye = np.maximum(np.asarray(x)[token] @ np.asarray(w_expert)[expert],
                        0.0)
        ref[token] += w * ye
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_dispatch_is_permutation_invariant():
    """Routing entries in any order produce the same buffer (the zero-
    preprocessing property carried over to the MoE path)."""
    rng = np.random.default_rng(2)
    t, d, slots = 32, 8, 64
    x = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
    st = rng.integers(0, t, size=64).astype(np.int32)
    slot = rng.permutation(64).astype(np.int32)      # unique slots
    own = rng.random(64) < 0.8
    a = moe_dispatch(x, jnp.asarray(st), jnp.asarray(slot),
                     jnp.asarray(own), slots, edge_tile=32, num_banks=2,
                     interpret=True)
    perm = rng.permutation(64)
    b = moe_dispatch(x, jnp.asarray(st[perm]), jnp.asarray(slot[perm]),
                     jnp.asarray(own[perm]), slots, edge_tile=32,
                     num_banks=2, interpret=True)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
