"""Parameter declaration: one structure drives init, sharding, and shapes.

A model declares its parameters as a pytree of :class:`PSpec` (shape +
logical axes + initializer).  From that single tree we derive:

* ``init_params``      — materialized arrays (PRNG-split deterministically)
* ``abstract_params``  — ShapeDtypeStructs (dry-run: no allocation)
* ``partition_specs``  — jax.sharding.PartitionSpec tree via logical rules
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis per dim (None = never sharded)
    init: str = "normal"                 # normal | zeros | ones | scaled | lecun
    scale: float = 1.0
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pspec(x: Any) -> bool:
    return isinstance(x, PSpec)


def _init_leaf(key: jax.Array, p: PSpec) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, p.dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, p.dtype)
    if p.init == "normal":
        return (p.scale * jax.random.normal(key, p.shape)).astype(p.dtype)
    if p.init in ("scaled", "lecun"):
        # a stacked leaf (scanned layers, experts) holds one matrix per entry:
        # its fan-in is that matrix's, not the stack's length
        dims = [d for d, a in zip(p.shape, p.axes) if a not in ("layers", "expert")]
        fan_in = dims[0] if len(dims) >= 2 else max(np.prod(dims), 1)
        std = p.scale / np.sqrt(fan_in)
        return (std * jax.random.normal(key, p.shape)).astype(p.dtype)
    raise ValueError(p.init)


def init_params(rng: jax.Array, tree: Any) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_pspec)
    keys = jax.random.split(rng, len(leaves))
    out = [_init_leaf(k, p) for k, p in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), tree, is_leaf=is_pspec
    )


class Rules:
    """logical axis -> mesh axes, with divisibility-aware fallback.

    ``rules`` maps a logical axis name to a mesh axis (or tuple of axes).
    When a parameter dimension is not divisible by the mesh axes' total
    size, the dimension falls back to replication (recorded in
    ``fallbacks`` so EXPERIMENTS can report them).
    """

    def __init__(self, rules: Dict[str, Any], mesh_axis_sizes: Dict[str, int]):
        self.rules = dict(rules)
        self.sizes = dict(mesh_axis_sizes)
        self.fallbacks: Dict[Tuple[str, int], str] = {}

    def mesh_axes_for(self, logical: Optional[str], dim: int) -> Optional[Any]:
        if logical is None:
            return None
        ax = self.rules.get(logical)
        if ax is None:
            return None
        axes = ax if isinstance(ax, tuple) else (ax,)
        total = 1
        for a in axes:
            total *= self.sizes.get(a, 1)
        if total <= 1:
            return None
        if dim % total != 0:
            self.fallbacks[(logical, dim)] = f"{dim} % {total} != 0"
            return None
        return ax

    def pspec(self, p: PSpec) -> P:
        return P(*[self.mesh_axes_for(a, d) for a, d in zip(p.axes, p.shape)])

    def act(self, *logical: Optional[str]) -> P:
        """PartitionSpec for an activation with the given logical axes.
        (No divisibility check: activation dims are chosen shardable.)"""
        out = []
        for l in logical:
            out.append(self.rules.get(l) if l is not None else None)
        return P(*out)


def partition_specs(tree: Any, rules: Rules) -> Any:
    return jax.tree_util.tree_map(lambda p: rules.pspec(p), tree, is_leaf=is_pspec)


def count_params(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=is_pspec)
    total = 0
    for l in leaves:
        shape = l.shape if hasattr(l, "shape") else ()
        n = 1
        for d in shape:
            n *= d
        total += n
    return total
