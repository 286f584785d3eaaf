"""Logical-axis -> mesh PartitionSpec rules.

Every parameter in repro.nn carries a tuple of logical axis names. A rule
table maps logical names to mesh axes; ``spec_for`` resolves one axes tuple
into a PartitionSpec with two safety passes:

  * divisibility — a dim that does not divide the mesh-axis product falls
    back to replication (e.g. qwen2-moe's 60 experts on a 16-way model
    axis, smollm's 122753-vocab);
  * no-duplicates — a mesh axis may appear once per spec; the leftmost
    logical dim wins (e.g. MoE stacks ('expert','embed','mlp'): EP takes
    'model', the mlp dim stays unsharded).

This gives DP('data'[, 'pod']) x TP('model') with optional FSDP (weights'
'embed' dim over 'data') and EP ('expert' over 'model') per arch config.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig
from ..nn import core


def batch_sharding(mesh_sig: tuple, batch: int) -> NamedSharding:
    """Batch-axis NamedSharding from a ``DittoPlan.mesh_sig()``.

    Built over an :class:`AbstractMesh`, so it works at trace time with no
    concrete devices — this is how a plan's mesh signature enters the
    traced jaxpr (``repro.core.ditto.dit_runner`` stamps it as a
    ``sharding_constraint``; the trace-identity audit reads it back
    abstractly on a single-device host). A batch the submesh width does
    not divide falls back to replication — same mesh, still mesh-signed,
    just an unsplit layout (mirrors ``spec_for``'s divisibility pass).
    """
    ndev, axis = mesh_sig
    amesh = AbstractMesh((int(ndev),), (str(axis),))
    spec = P(axis) if batch % int(ndev) == 0 else P()
    return NamedSharding(amesh, spec)


def constrain_batch(x: jax.Array, mesh_sig: tuple | None) -> jax.Array:
    """``with_sharding_constraint`` over :func:`batch_sharding` (no-op for
    ``mesh_sig=None`` — unsharded plans keep an untouched jaxpr)."""
    if mesh_sig is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, batch_sharding(mesh_sig, x.shape[0]))


def make_rules(arch: ArchConfig, *, multi_pod: bool = False) -> dict[str, Any]:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    return {
        "batch": batch_axes,
        "vocab": ("model",),
        "heads": ("model",),
        "kv": ("model",),
        "mlp": ("model",),
        "expert": ("model",),
        "moe_ff": ("data",),  # EP ff-over-data scheme (arctic §Perf C)
        "embed": ("data",) if arch.fsdp else None,
        "embed2": None,
        "layer": None,
        "super": None,
        "seq": None,  # flipped to ('model',) by the SP hillclimb configs
    }


def spec_for(axes: tuple, shape: tuple, rules: dict, mesh: Mesh) -> P:
    used: set[str] = set()
    out = []
    # axes tag may be shorter than rank when a stacked dim was added without
    # retagging; left-pad with None (stack dims lead).
    if len(axes) < len(shape):
        axes = (None,) * (len(shape) - len(axes)) + tuple(axes)
    for dim, name in zip(shape, axes):
        rule = rules.get(name) if name else None
        if not rule:
            out.append(None)
            continue
        want = tuple(a for a in rule if a in mesh.axis_names and a not in used)
        size = math.prod(mesh.shape[a] for a in want) if want else 1
        if want and dim % size == 0:
            out.append(want[0] if len(want) == 1 else want)
            used.update(want)
        else:
            out.append(None)
    return P(*out)


def param_shardings(axes_tree, shape_tree, rules: dict, mesh: Mesh):
    """NamedSharding tree matching a (split) param tree."""
    return jax.tree.map(
        lambda axes, sds: NamedSharding(mesh, spec_for(axes, sds.shape, rules, mesh)),
        axes_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def make_shard_fn(rules: dict, mesh: Mesh | None):
    """fn(array, logical_axes) applying a sharding constraint inside jit."""
    if mesh is None:
        return lambda a, axes: a

    def shard(a, axes):
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, spec_for(axes, a.shape, rules, mesh))
        )

    return shard


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
