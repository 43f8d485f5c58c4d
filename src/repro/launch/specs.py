"""Abstract inputs + shardings for every (arch × shape × mesh) cell.

``input_specs()`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, no device allocation) for every model input of a cell, and the
matching NamedSharding trees used as jit in_shardings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs import ArchSpec, ShapeSpec, SHAPES, get_arch
from ..models.config import ModelConfig
from ..models.params import Rules, abstract_params, partition_specs
from ..models.sharding import make_rules
from ..models.transformer import cache_specs, model_pspecs
from ..training.train_step import abstract_train_state

# ---------------------------------------------------------------------------
# batch specs
# ---------------------------------------------------------------------------


def batch_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    B, S = shape.global_batch, shape.seq
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    out: Dict[str, jax.ShapeDtypeStruct] = {
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)
    }
    if cfg.frontend != "none":
        # modality frontend stub: precomputed frame/patch embeddings
        out["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    else:
        out["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return out


def batch_shardings(
    cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, rules: Rules
) -> Dict[str, NamedSharding]:
    B = shape.global_batch
    dp = rules.mesh_axes_for("batch", B)  # falls back to None if indivisible
    ns = lambda spec: NamedSharding(mesh, spec)
    out: Dict[str, NamedSharding] = {}
    for k, v in batch_specs(cfg, shape).items():
        if v.ndim == 2:
            out[k] = ns(P(dp, None))
        else:
            out[k] = ns(P(dp, None, None))
    return out


# ---------------------------------------------------------------------------
# cache shardings (mirrors transformer.cache_specs structure)
# ---------------------------------------------------------------------------


def _entry_pspec(entry: Dict[str, Any], rules: Rules, stacked: bool) -> Dict[str, P]:
    """PartitionSpec dict for one cache entry (kv / latent / mamba / rglru)."""
    pre = (None,) if stacked else ()
    out: Dict[str, P] = {}
    for key, arr in entry.items():
        dims = arr.shape[1:] if stacked else arr.shape
        if key in ("k", "v", "k_scale", "v_scale"):
            s, kheads, b, hd = dims            # layers.kv_cache_pspec's order
            out[key] = P(
                *pre,
                rules.mesh_axes_for("cache_seq", s),
                rules.mesh_axes_for("kv_heads", kheads),
                rules.mesh_axes_for("batch", b),
                None,
            )
        elif key == "c_kv":                    # mla.mla_cache_pspec's orders
            b, s, _ = dims
            out[key] = P(*pre, rules.mesh_axes_for("batch", b),
                         rules.mesh_axes_for("cache_seq", s), None)
        elif key == "k_pe":
            b, _, s = dims
            out[key] = P(*pre, rules.mesh_axes_for("batch", b), None,
                         rules.mesh_axes_for("cache_seq", s))
        elif key == "conv":
            b, w, inner = dims
            out[key] = P(*pre, rules.mesh_axes_for("batch", b), None,
                         rules.mesh_axes_for("inner", inner))
        elif key == "ssm":
            b, inner, st = dims
            out[key] = P(*pre, rules.mesh_axes_for("batch", b),
                         rules.mesh_axes_for("inner", inner), None)
        elif key == "h":
            b, w = dims
            out[key] = P(*pre, rules.mesh_axes_for("batch", b),
                         rules.mesh_axes_for("lru", w))
        else:
            out[key] = P(*pre, *([None] * len(dims)))
    return out


def cache_shardings(
    cfg: ModelConfig, batch: int, max_seq: int, mesh: Mesh, rules: Rules
) -> Dict[str, Any]:
    specs = cache_specs(cfg, batch, max_seq)
    ns = lambda spec: NamedSharding(mesh, spec)
    out: Dict[str, Any] = {"rest": []}
    for stack in ("lead", "groups"):
        if stack in specs:
            out[stack] = {
                name: jax.tree_util.tree_map(
                    ns, _entry_pspec(entry, rules, stacked=True),
                    is_leaf=lambda x: isinstance(x, P),
                )
                for name, entry in specs[stack].items()
            }
    for entry in specs["rest"]:
        out["rest"].append(
            jax.tree_util.tree_map(
                ns, _entry_pspec(entry, rules, stacked=False),
                is_leaf=lambda x: isinstance(x, P),
            )
        )
    return out


# ---------------------------------------------------------------------------
# full cell assembly
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    arch: ArchSpec
    cfg: ModelConfig
    shape: ShapeSpec
    mesh: Mesh
    rules: Rules
    abstract_args: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    donate: Tuple[int, ...]
    kind: str
    microbatches: int = 1
    out_shardings: Any = None


def _effective_microbatches(requested: int, global_batch: int, dp_total: int) -> int:
    """Largest mb <= requested with (global_batch/mb) divisible by the DP
    degree — a smaller per-microbatch batch would replicate instead of
    shard (sub-DP microbatches blow up memory, not shrink it)."""
    cap = max(global_batch // max(dp_total, 1), 1)
    mb = min(requested, cap)
    while mb > 1 and (global_batch % mb or (global_batch // mb) % dp_total):
        mb -= 1
    return max(mb, 1)


def build_cell(
    arch_name: str,
    shape_name: str,
    mesh: Mesh,
    fsdp: bool = True,
    zero1: bool = False,
    parallel_mode: str = "tp",
    cfg_overrides: Optional[Dict[str, Any]] = None,
) -> Cell:
    """``zero1=True``: ZeRO-1 — parameters replicated over the data axis
    (bf16 storage recommended) while optimizer moments + master stay
    FSDP-sharded; gradients reduce-scatter into the optimizer shards and
    fresh params all-gather ONCE per step instead of per microbatch.

    ``parallel_mode="fsdp_all"``: no TP; batch + params shard over the full
    (data, model) grid (per-token TP all-reduces disappear)."""
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    cfg = arch.config_for(shape_name)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    # KV-cache sequence sharding (SP for serving):
    #  * long-context batch=1 decode: shard seq over "data" (batch unusable)
    #  * KV heads not divisible by the model axis: shard seq over "model"
    #    (otherwise the replicated-head cache blows past per-chip HBM)
    shard_cache_seq = None
    if shape.kind == "decode":
        model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        # serving avoids ZeRO-3 when the TP-sharded params fit replicated
        # over data: per-token param re-gathers dominate the decode step
        # otherwise (measured 158x collective-term reduction; §Perf).  Very
        # large models (chameleon/llama4) keep FSDP for memory.
        if cfg.n_params * 2 / model_size <= 4e9:
            fsdp = False
        if shape.global_batch == 1:
            shard_cache_seq = "data"
        elif cfg.n_kv_heads % model_size != 0 and cfg.uses_attention:
            shard_cache_seq = "model"
    rules = make_rules(mesh, fsdp=fsdp, shard_cache_seq=shard_cache_seq,
                       parallel_mode=parallel_mode)
    ns = lambda spec: NamedSharding(mesh, spec)

    pspecs = model_pspecs(cfg)
    params_abs = abstract_params(pspecs)
    if zero1:
        rules_params = make_rules(mesh, fsdp=False, shard_cache_seq=shard_cache_seq,
                                  parallel_mode=parallel_mode)
        rules_opt = rules  # keep FSDP sharding for the optimizer states
        params_shard = jax.tree_util.tree_map(
            ns, partition_specs(pspecs, rules_params), is_leaf=lambda x: isinstance(x, P)
        )
        opt_param_shard = jax.tree_util.tree_map(
            ns, partition_specs(pspecs, rules_opt), is_leaf=lambda x: isinstance(x, P)
        )
        rules = rules_params   # activations follow the replicated-param rules
    else:
        params_shard = jax.tree_util.tree_map(
            ns, partition_specs(pspecs, rules), is_leaf=lambda x: isinstance(x, P)
        )
        opt_param_shard = params_shard

    dp_axes = rules.rules.get("batch")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if dp_axes is None:
        dp_total = 1
    elif isinstance(dp_axes, tuple):
        dp_total = 1
        for a in dp_axes:
            dp_total *= sizes.get(a, 1)
    else:
        dp_total = sizes.get(dp_axes, 1)

    if shape.kind == "train":
        state_abs = abstract_train_state(params_abs)
        opt_shard = {"m": opt_param_shard, "v": opt_param_shard}
        if "master" in state_abs["opt"]:
            opt_shard["master"] = opt_param_shard
        state_shard = {
            "params": params_shard,
            "opt": opt_shard,
            "step": ns(P()),
        }
        batch_abs = batch_specs(cfg, shape)
        batch_shard = batch_shardings(cfg, shape, mesh, rules)
        mb = _effective_microbatches(
            arch.microbatches.get(shape.name, 1), shape.global_batch, dp_total
        )
        # pin the output state to the input shardings: without this XLA may
        # materialize replicated gradients (all-reduce + slice) instead of
        # reduce-scattering into the FSDP shards
        metric_shard = {
            k: ns(P()) for k in ("loss", "ce", "moe_aux", "z", "grad_norm", "lr")
        }
        return Cell(arch, cfg, shape, mesh, rules,
                    (state_abs, batch_abs), (state_shard, batch_shard), (0,), "train",
                    microbatches=mb, out_shardings=(state_shard, metric_shard))

    if shape.kind == "prefill":
        # prefill caches of archs with non-shardable KV heads shard the
        # sequence dim over "model" (same rule as decode) via out_shardings
        model_size = sizes.get("model", 1)
        if (cfg.n_kv_heads % model_size != 0 and cfg.uses_attention
                and parallel_mode == "tp"):
            rules = make_rules(mesh, fsdp=fsdp, shard_cache_seq="model")
        batch_abs = batch_specs(cfg, shape)
        batch_shard = batch_shardings(cfg, shape, mesh, rules)
        B = shape.global_batch
        out_shard = (
            ns(P(rules.mesh_axes_for("batch", B), rules.mesh_axes_for("vocab", cfg.vocab_size))),
            cache_shardings(cfg, B, shape.seq, mesh, rules),
        )
        return Cell(arch, cfg, shape, mesh, rules,
                    (params_abs, batch_abs), (params_shard, batch_shard), (), "prefill",
                    out_shardings=out_shard)

    # decode
    B, S = shape.global_batch, shape.seq
    tokens_abs = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    cache_abs = cache_specs(cfg, B, S)
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    tok_shard = {"tokens": ns(P(rules.mesh_axes_for("batch", B), None))}
    cshard = cache_shardings(cfg, B, S, mesh, rules)
    return Cell(
        arch, cfg, shape, mesh, rules,
        (params_abs, tokens_abs, cache_abs, pos_abs),
        (params_shard, tok_shard, cshard, ns(P())),
        (2,),
        "decode",
    )
