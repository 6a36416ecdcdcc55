"""Logical-axis sharding rules (MaxText-style) + parameter definition infra.

Every tensor in the framework is annotated with *logical* axes
('batch', 'seq', 'embed', 'heads', 'ff', 'vocab', 'experts', ...). A
``ShardingRules`` table maps logical axes to mesh axes per deployment
(DP/FSDP/TP/EP are just different tables). ``ParamDef`` trees are the single
source of truth for parameter shapes + logical axes, which gives us:

  * ``init_params``      — real initialization (tests, examples, training),
  * ``abstract_params``  — ShapeDtypeStructs for the dry-run (no allocation),
  * ``param_shardings``  — NamedShardings for pjit in/out specs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

Array = jax.Array
MeshAxis = Union[None, str, Tuple[str, ...]]


# ---------------------------------------------------------------------------
# shard_map / mesh helpers
# ---------------------------------------------------------------------------

def compat_shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the manual
    collectives here (ppermute rings, mask+psum broadcasts) confuse the
    checker."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def compat_make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis of the Auto axis type."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


# ---------------------------------------------------------------------------
# logical -> physical rules
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardingRules:
    """Mapping from logical axis names to mesh axes (None = replicated)."""

    table: Mapping[str, MeshAxis]

    def axis(self, logical: Optional[str]) -> MeshAxis:
        if logical is None:
            return None
        return self.table.get(logical, None)

    def spec(self, *logical: Optional[str]) -> P:
        return P(*(self.axis(a) for a in logical))

    def sharding(self, mesh: Mesh, *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(*logical))


def make_rules(*, data_axes: Tuple[str, ...] = ("data",),
               model_axis: str = "model",
               fsdp: bool = False,
               expert_fsdp: bool = False,
               shard_seq_for_decode: bool = False,
               seq_parallel: bool = True) -> ShardingRules:
    """Build the standard rule tables used by the configs.

    fsdp: additionally shard the *largest* weight dim over the data axes
    (ZeRO-3 style); XLA inserts the per-layer all-gather / reduce-scatter.
    seq_parallel: shard the residual stream's seq dim over the model axis
    between blocks (sequence parallelism) — bounds remat-checkpoint memory.
    """
    data: MeshAxis = data_axes if len(data_axes) > 1 else data_axes[0]
    t = {
        # activations
        "batch": data,
        "seq": None,
        "seq_sp": model_axis if seq_parallel else None,  # residual stream
        "embed": None,             # residual stream feature dim
        "act_heads": model_axis,   # attention activations: heads sharded
        "act_ff": model_axis,
        "act_kv": None,
        "cache_seq": model_axis if shard_seq_for_decode else None,
        "cache_heads": None if shard_seq_for_decode else model_axis,
        # params
        "heads": model_axis,       # q-proj head dim
        "kv_heads": model_axis,    # kv-proj fused head*dim (divisible)
        "ff": model_axis,
        "vocab": model_axis,
        "embed_fsdp": data if fsdp else None,   # second weight dim under FSDP
        "experts": model_axis,
        "expert_ff": data if expert_fsdp else None,
        "layers": None,
        "ssm_heads": model_axis,
        "ssm_state": None,
        "lru_width": model_axis,
    }
    return ShardingRules(table=t)


def make_dp_only_rules(*, data_axes: Tuple[str, ...] = ("data",),
                       model_axis: str = "model") -> ShardingRules:
    """Pure data parallelism: batch sharded over EVERY mesh axis (model
    folded into batch), all parameters replicated. The right table for
    small models where tensor-parallel collectives dominate compute
    (EXPERIMENTS.md §Perf, qwen1.5-0.5b iteration 1)."""
    batch: MeshAxis = tuple(data_axes) + (model_axis,)
    t = {k: None for k in make_rules(data_axes=data_axes,
                                     model_axis=model_axis).table}
    t["batch"] = batch
    return ShardingRules(table=t)


def logical_constraint(x: Array, *logical: Optional[str],
                       rules: Optional[ShardingRules],
                       mesh: Optional[Mesh]) -> Array:
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    if mesh is None or rules is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, rules.spec(*logical)))


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]        # logical axes, len == len(shape)
    init: str = "normal"                   # normal | zeros | ones | constant
    scale: Optional[float] = None          # stddev for normal (default fan-in)
    constant: float = 0.0
    dtype: Any = jnp.bfloat16
    # optimizer-state axes when they should differ from the param's (ZeRO-1
    # style: e.g. a replicated embedding table with fully-sharded m/v)
    opt_axes: Optional[Tuple[Optional[str], ...]] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_one(key, d: ParamDef) -> Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "constant":
        return jnp.full(d.shape, d.constant, d.dtype)
    if d.scale is not None:
        scale = d.scale
    else:
        fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
        scale = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, d.shape, jnp.float32) * scale).astype(d.dtype)


def init_params(key, defs) -> Any:
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_one(k, d) for k, d in zip(keys, leaves)])


def abstract_params(defs) -> Any:
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs,
        is_leaf=lambda x: isinstance(x, ParamDef))


def param_specs(defs, rules: ShardingRules) -> Any:
    return jax.tree.map(
        lambda d: rules.spec(*d.axes), defs,
        is_leaf=lambda x: isinstance(x, ParamDef))


def param_shardings(defs, rules: ShardingRules, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda d: rules.sharding(mesh, *d.axes), defs,
        is_leaf=lambda x: isinstance(x, ParamDef))


def device_kind(device) -> str:
    """Canonical device-kind string for topology fingerprints (the
    serving autotune-cache namespace and BENCH_stream.json share it)."""
    return str(getattr(device, "device_kind", device.platform)).replace(
        " ", "_")


def executor_mesh(device) -> Mesh:
    """A single-device mesh for one serving executor (see core/executor.py)."""
    return Mesh(np.asarray([device], dtype=object), ("executor",))


def replicate_params(params, devices) -> list:
    """One committed, fully-replicated copy of ``params`` per executor device.

    The serving executor pool (core/executor.py) runs MPMD — each device
    executes *different* batches — so replication is per-device committed
    copies (a single-device ``Mesh`` + ``NamedSharding(P())`` each), not
    one mesh-spanning replicated array: a mesh-wide array would pin every
    jit call to the full mesh, while committed per-device copies let each
    executor's program run on its own device with host-resident inputs.
    Returns ``[params_on_dev for dev in devices]``.
    """
    copies = []
    for d in devices:
        sharding = NamedSharding(executor_mesh(d), P())
        copies.append(jax.tree.map(
            lambda x, s=sharding: jax.device_put(x, s), params))
    return copies


def params_compatible(old, new) -> Optional[str]:
    """Why ``new`` cannot replace ``old`` as a hot-reloaded params tree,
    or ``None`` when it can (same tree structure, leaf shapes, dtypes).

    The serving engine's ``update_params`` stages per-executor replicas
    of ``new`` via :func:`replicate_params`; every compiled per-bucket
    program was traced against ``old``'s avals, so a structure or shape
    mismatch would invalidate every executable mid-stream. Hot reload is
    therefore *same-architecture only* — anything else is a new engine.
    """
    s_old = jax.tree_util.tree_structure(old)
    s_new = jax.tree_util.tree_structure(new)
    if s_old != s_new:
        return (f"params tree structure changed: {s_new} != serving "
                f"{s_old}")
    for i, (a, b) in enumerate(zip(jax.tree.leaves(old),
                                   jax.tree.leaves(new))):
        a, b = jnp.asarray(a), jnp.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            return (f"params leaf {i} changed: {b.shape}/{b.dtype} != "
                    f"serving {a.shape}/{a.dtype}")
    return None


def param_count(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return int(sum(np.prod(d.shape) for d in leaves))


def param_bytes(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return int(sum(np.prod(d.shape) * jnp.dtype(d.dtype).itemsize
                   for d in leaves))
