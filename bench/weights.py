"""Seeded random weights for a dense transformer, in the parameter layout
the program serves (``models/transformer.py``: one stack of
layers under ``groups/b0``).

The benchmark makes them, not the program, so that the reference can make
the same ones again from the seed without taking anything the program made.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, 64 bits and more included."""
    words = np.random.SeedSequence([int(seed), 0]).generate_state(2)
    key = jax.random.PRNGKey(0)
    for w in words:
        key = jax.random.fold_in(key, int(w))
    return key


def shapes(c: Dict) -> Dict[str, Any]:
    """{path: (shape, std)} of every leaf."""
    d, L, H, K, hd, f, V = (c[k] for k in
                            ("d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab_size"))
    out = {
        # a tied embedding is also the head, so it is drawn at the head's scale
        "embed/tok": ((V, d), d ** -0.5 if c["weight_tying"] else 1.0),
        "groups/b0/attn/wq": ((L, d, H, hd), d ** -0.5),
        "groups/b0/attn/wk": ((L, d, K, hd), d ** -0.5),
        "groups/b0/attn/wv": ((L, d, K, hd), d ** -0.5),
        "groups/b0/attn/wo": ((L, H, hd, d), (H * hd) ** -0.5),
        "groups/b0/mlp/w_gate": ((L, d, f), d ** -0.5),
        "groups/b0/mlp/w_up": ((L, d, f), d ** -0.5),
        "groups/b0/mlp/w_down": ((L, f, d), f ** -0.5),
    }
    if not c["weight_tying"]:
        out["lm_head"] = ((d, V), d ** -0.5)
    return out


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {"rest": []}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return tree


def make(c: Dict, key: jax.Array, dtype=jnp.float32) -> Dict[str, Any]:
    """The whole tree from ``seed_key(seed)``; call under ``jax.jit``, the
    key an argument and not a constant, so that it is drawn on the device by
    one program that every seed shares."""
    flat = {}
    for i, (path, (shape, std)) in enumerate(sorted(shapes(c).items())):
        flat[path] = (std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return _nest(flat)

