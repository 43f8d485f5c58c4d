"""Pipeline parallelism: GPipe-style fill-drain schedule with shard_map +
collective_permute over a mesh axis.

``pipeline_apply`` runs ``stage_fn`` as an S-stage pipeline over
microbatches.  Stage parameters are stacked on a leading axis sharded over
the pipeline mesh axis; activations flow stage->stage via ppermute.
Differentiable (ppermute transposes to the reverse permute), so the same
schedule trains — the multi-pod mesh's "pod" axis can act as a 2-stage
pipeline (see tests/test_pipeline.py and EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    mesh: Mesh,
    axis: str,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,            # pytree, leaves stacked (n_stages, ...)
    x_mb: jax.Array,              # (n_micro, mb, ...) microbatched input
) -> jax.Array:
    """Returns (n_micro, mb, ...) outputs of the final stage."""
    n_stages = mesh.shape[axis]

    def per_device(params, x):
        # params leaves arrive as (1, ...) shards of the stacked stage dim
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        n_micro = x.shape[0]
        T = n_micro + n_stages - 1

        buf = jnp.zeros_like(x[0])                    # inter-stage recv buffer
        out = jnp.zeros_like(x)

        def tick(carry, t):
            buf, out = carry
            feed = x[jnp.minimum(t, n_micro - 1)]
            inp = jnp.where(stage == 0, feed, buf)
            y = stage_fn(params, inp)
            # shift activations down the pipe: stage i -> i+1
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            # last stage emits microbatch t-(S-1)
            emit_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_emit = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(out, emit_idx, 0, keepdims=False)
            upd = jnp.where(is_emit, y, cur)
            out = jax.lax.dynamic_update_index_in_dim(out, upd, emit_idx, 0)
            return (nxt, out), None

        (buf, out), _ = jax.lax.scan(tick, (buf, out), jnp.arange(T))
        # replicate the final-stage outputs to all stages (masked psum)
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)), axis
        )
        return out

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        P(),
    )
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )
    return fn(stage_params, x_mb)
