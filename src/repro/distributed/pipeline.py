"""GPipe-style pipeline parallelism over the 'pod' axis.

At 2 pods the default deployment uses pod-as-DP (bubble overhead of a
2-stage pipeline exceeds the cross-pod gradient all-reduce for our sizes —
napkin math in EXPERIMENTS.md §Perf), but deeper multi-pod deployments want
PP, so the mechanism is a first-class feature:

  * the layer stack is split into ``n_stages`` contiguous chunks;
  * inside ``shard_map`` over the pipeline axis each device owns its
    stage's parameters only;
  * microbatches stream through: at step t, stage s processes microbatch
    (t - s) and passes activations to stage s+1 via ``ppermute`` — the
    classic fill/steady/drain schedule with (n_stages - 1) bubble slots.

This module implements the schedule for a simple homogeneous block stack
(demonstrated + tested on reduced configs; the full-size stacks reuse the
same stage_fn shape).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import compat_shard_map

Array = jax.Array


def ring_perm(size: int, *, steps: int = 1):
    """The ring permutation ``i -> (i + steps) % size`` as ppermute pairs."""
    return [(i, (i + steps) % size) for i in range(size)]


def ring_shift(x: Array, axis_name: str, *, steps: int = 1,
               size: int | None = None) -> Array:
    """Rotate ``x`` ``steps`` hops forward around the ring over ``axis_name``.

    The device at ring position i receives the value from position
    ``(i - steps) % size``. Used by the pipeline schedule (steps=1, the
    stage hand-off) and the wide-placement halo exchange (steps=s feeds the
    halo block for the peer s hops back). Must run inside ``shard_map``.
    """
    if size is None:
        size = jax.lax.axis_size(axis_name)
    return jax.lax.ppermute(x, axis_name, ring_perm(size, steps=steps))


def broadcast_from(x: Array, axis_name: str, src) -> Array:
    """Broadcast ``x`` from ring position ``src`` to every device.

    ``ppermute`` requires unique sources, so a one-to-all broadcast cannot
    be a permutation — the idiom is mask + psum: every device contributes
    zeros except ``src``, and the sum is the broadcast. Must run inside
    ``shard_map``; ``src`` may be traced (e.g. ``axis_size - 1``).
    """
    stage = jax.lax.axis_index(axis_name)
    return jax.lax.psum(jnp.where(stage == src, x, 0.0), axis_name)


def pipeline_apply(stage_fn: Callable[[Any, Array], Array],
                   stage_params: Any, x_microbatches: Array, *,
                   mesh, axis_name: str = "pod") -> Array:
    """Run microbatches through a pipeline over ``axis_name``.

    stage_fn(params_for_stage, x) -> x          (one stage's computation)
    stage_params: pytree whose leaves have leading dim n_stages
    x_microbatches: (n_micro, mb, ...) activations entering stage 0

    Returns (n_micro, mb, ...) outputs of the final stage.
    """
    n_stages = mesh.shape[axis_name]

    def local(params, xs):
        # params: this stage's slice; xs: all microbatches (only stage 0
        # consumes them; other stages ignore and take permuted inputs)
        params = jax.tree.map(lambda p: p[0], params)   # drop stage dim
        stage = jax.lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        total = n_micro + n_stages - 1

        def step(carry, t):
            acc, inflight = carry
            # stage 0 injects microbatch t (or zeros in the drain phase)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                 keepdims=False)
            x_in = jnp.where(stage == 0, fresh, inflight)
            y = stage_fn(params, x_in)
            # pass to the next stage
            inflight_next = ring_shift(y, axis_name, size=n_stages)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (stage == n_stages - 1)
            acc = jax.lax.cond(
                valid,
                lambda a: jax.lax.dynamic_update_index_in_dim(
                    a, y, jnp.clip(out_idx, 0, n_micro - 1), 0),
                lambda a: a, acc)
            return (acc, inflight_next), None

        acc0 = jnp.zeros_like(xs)
        inflight0 = jnp.zeros_like(
            jax.lax.dynamic_index_in_dim(xs, 0, 0, keepdims=False))
        (acc, _), _ = jax.lax.scan(step, (acc0, inflight0),
                                   jnp.arange(total))
        # broadcast final outputs from the last stage to all stages
        # (ppermute requires unique sources, so mask + psum)
        return broadcast_from(acc, axis_name, n_stages - 1)

    spec_params = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = compat_shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
    )
    return fn(stage_params, x_microbatches)
