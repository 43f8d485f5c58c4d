"""Seeded random weights, drawn from any architecture's weight table
(``shapes(c)`` of its module under ``bench/reference/``: ``{path: (shape,
std)}`` in the program's parameter layout).

The benchmark makes them, not the program, so that the reference can make
the same ones again from the seed without taking anything the program made.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Table = Dict[str, Tuple[Tuple[int, ...], float]]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, 64 bits and more included."""
    words = np.random.SeedSequence([int(seed), 0]).generate_state(2)
    key = jax.random.PRNGKey(0)
    for w in words:
        key = jax.random.fold_in(key, int(w))
    return key


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {"rest": []}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return tree


def make(table: Table, key: jax.Array, dtype=jnp.float32) -> Dict[str, Any]:
    """The whole tree from ``seed_key(seed)``: the ``i``-th path in sorted
    order drawn from ``fold_in(key, i)``.  Call under ``jax.jit``, the key an
    argument and not a constant, so that it is drawn on the device by one
    program that every seed shares."""
    flat = {}
    for i, (path, (shape, std)) in enumerate(sorted(table.items())):
        flat[path] = (std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return _nest(flat)
