"""NamedSharding helpers for pytrees.

Replaces the reference's explicit tensor shipping (state_dict pickles over
MPI/gRPC, SURVEY.md §2.1) with sharding annotations: XLA inserts the
collectives; we only declare layouts.

This module is the single spec layer shared by the data-parallel trainer
(Megatron path rules, :func:`transformer_param_specs`) and the federated
simulator's 2-D ``client`` × ``model`` mesh (shape-driven inference,
:func:`auto_partition_specs`) — Cheetah-style training and federated rounds
place model state through the same helpers.
"""

from __future__ import annotations

import warnings
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_along(mesh: Mesh, axis_name: str, dim: int = 0) -> NamedSharding:
    """Sharding that splits array dimension ``dim`` across mesh axis ``axis_name``.

    Validates against the mesh up front: an unknown axis name or a negative
    ``dim`` would otherwise produce a ``PartitionSpec`` that only fails (with
    an opaque GSPMD error, or silently out-of-range) once an array is placed.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(
            f"shard_along: mesh has no axis {axis_name!r} "
            f"(mesh axes: {tuple(mesh.axis_names)})")
    if not isinstance(dim, int) or dim < 0:
        raise ValueError(
            f"shard_along: dim must be a non-negative int (array dimension "
            f"to split), got {dim!r}")
    spec = [None] * (dim + 1)
    spec[dim] = axis_name
    return NamedSharding(mesh, P(*spec))


def shard_leading_axis(tree: Any, mesh: Mesh, axis_name: str) -> Any:
    """Place every leaf with its leading dim split across ``axis_name``.

    Used for stacked per-client state (leading client axis) — the TPU
    equivalent of the reference scattering client subsets to MPI workers
    (``nccl/base_framework/Server.py:109-122`` client_schedule + broadcast).
    """
    sharding = shard_along(mesh, axis_name, dim=0)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def replicate_tree(tree: Any, mesh: Optional[Mesh] = None) -> Any:
    """Replicate every leaf on all mesh devices (server/global state)."""
    if mesh is None:
        from .mesh import get_default_mesh

        mesh = get_default_mesh()
    sharding = replicated(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def _leaf_path_str(path) -> str:
    return jax.tree_util.keystr(path)


def auto_partition_specs(
    tree: Any,
    axis_name: str,
    axis_size: int,
    *,
    overrides: Optional[dict] = None,
    warn: bool = True,
) -> Any:
    """Shape-driven per-leaf ``PartitionSpec`` inference for one mesh axis.

    Largest-divisible-dim rule: each leaf shards the largest dimension whose
    extent is divisible by ``axis_size`` (ties broken toward the lowest dim
    index, so the rule is deterministic for equal extents). Leaves with no
    such dimension — or scalars — fall back to replicated (``P()``); all
    fallback paths are collected into ONE ``UserWarning`` rather than a
    per-leaf storm.

    ``overrides`` maps a path substring (matched against
    ``jax.tree_util.keystr`` of the leaf path; patterns tried in sorted order,
    first match wins) to either a dim index to shard or ``None`` to pin the
    leaf replicated. An override naming an out-of-range or indivisible dim
    raises — a silent bad layout would surface as a GSPMD error far from the
    config knob that caused it.

    Leaf order is the pytree's own deterministic flatten order; two calls on
    the same structure always yield identical spec trees (graftcheck's
    determinism fixture pins this).
    """
    if axis_size < 1:
        raise ValueError(f"auto_partition_specs: axis_size must be >= 1, "
                         f"got {axis_size}")
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    sorted_pats = sorted(overrides) if overrides else ()
    specs = []
    fallbacks = []
    for path, leaf in flat:
        pstr = _leaf_path_str(path)
        # .shape first: leaves may be ShapeDtypeStructs or tracers (the
        # simulator infers update-stack specs at trace time)
        shape = (tuple(leaf.shape) if hasattr(leaf, "shape")
                 else tuple(np.shape(leaf)))
        spec = None
        for pat in sorted_pats:
            if pat in pstr:
                dim = overrides[pat]
                if dim is None:
                    spec = P()
                    break
                if not isinstance(dim, int) or dim < 0 or dim >= len(shape):
                    raise ValueError(
                        f"auto_partition_specs: override {pat!r} names dim "
                        f"{dim!r} but leaf {pstr} has shape {shape}")
                if shape[dim] % axis_size != 0:
                    raise ValueError(
                        f"auto_partition_specs: override {pat!r} shards dim "
                        f"{dim} of leaf {pstr} (shape {shape}) but "
                        f"{shape[dim]} is not divisible by axis size "
                        f"{axis_size}")
                spec = P(*([None] * dim + [axis_name]))
                break
        if spec is None:
            cands = [d for d, s in enumerate(shape)
                     if s >= axis_size and s % axis_size == 0]
            if cands and axis_size > 1:
                best = max(cands, key=lambda d: (shape[d], -d))
                spec = P(*([None] * best + [axis_name]))
            else:
                spec = P()
                if axis_size > 1:
                    fallbacks.append(pstr or "<root>")
        specs.append(spec)
    if fallbacks and warn:
        warnings.warn(
            f"auto_partition_specs: {len(fallbacks)} leaf(s) have no "
            f"dimension divisible by {axis_name!r} axis size {axis_size}; "
            f"replicated fallback for: {', '.join(fallbacks)}",
            UserWarning, stacklevel=2)
    return jax.tree_util.tree_unflatten(treedef, specs)


def tree_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    """Map a tree of ``PartitionSpec``s to ``NamedSharding``s on ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def prepend_axis(spec_tree: Any, axis_name: Optional[str]) -> Any:
    """Prefix every spec with a leading mesh axis (stacked per-client rows:
    dim 0 is the cohort axis, trailing dims keep the model layout)."""
    return jax.tree.map(
        lambda s: P(axis_name, *s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def replicated_specs(tree: Any) -> Any:
    """Every leaf replicated: the layout of a model that trains under data
    parallelism alone (the ``layer_types`` decoder's tree, whose routed
    experts and grouped KV heads have no model-axis rule yet)."""
    return jax.tree.map(lambda _: P(), tree)


def transformer_param_specs(params: Any) -> Any:
    """Megatron-style TP layout by parameter path.

    qkv / mlp-in kernels: column-sharded (output dim over ``model``);
    proj / mlp-out: row-sharded (input dim); head: vocab-sharded output;
    embeddings, norms, biases: replicated.
    """
    from .mesh import AXIS_MODEL

    def spec_for(path, leaf) -> P:
        names = [str(getattr(p, "key", p)) for p in path]
        joined = "/".join(names)
        if leaf.ndim < 2:
            return P()
        if "qkv" in joined and names[-1] == "kernel":
            return P(None, AXIS_MODEL)
        if "proj" in joined and names[-1] == "kernel":
            return P(AXIS_MODEL, None)
        if "MLPBlock" in joined and "Dense_0" in joined and names[-1] == "kernel":
            return P(None, AXIS_MODEL)
        if "MLPBlock" in joined and "Dense_1" in joined and names[-1] == "kernel":
            return P(AXIS_MODEL, None)
        if "head" in joined and names[-1] == "kernel":
            return P(None, AXIS_MODEL)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)
