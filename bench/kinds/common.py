"""Pieces the drivers share: the program's model configuration as the
configuration file states it, and the device readings."""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List

# configuration-file key -> ModelConfig field, for the keys every
# architecture's file may have; an architecture's module adds its own as
# ``FIELDS``
_FIELDS = {"d_model": "d_model", "n_layers": "n_layers", "n_heads": "n_heads",
           "n_kv_heads": "n_kv_heads", "head_dim": "head_dim", "d_ff": "d_ff",
           "vocab_size": "vocab_size", "rope_theta": "rope_theta", "mlp_act": "mlp_act",
           "weight_tying": "tie_embeddings", "param_dtype": "param_dtype",
           "compute_dtype": "dtype", "kv_cache_dtype": "kv_cache_dtype"}
_MISSING = object()


def model_config(c: Dict[str, Any]):
    """The registry's config for ``c["arch"]`` with the file's values set
    (through ``_FIELDS`` and the architecture module's ``FIELDS``) and the
    file's optional ``program`` section of further ``ModelConfig`` fields
    set as they stand; checked to compute what the file says and what the
    architecture's reference computes (its ``COMPUTES``)."""
    from repro.configs import get_arch

    from bench import harness

    arch = harness.arch(c)
    fields = {**_FIELDS, **getattr(arch, "FIELDS", {})}
    over = {f: c[k] for k, f in fields.items() if k in c}
    program = c.get("program", {})
    both = sorted(set(program) & set(over))
    if both:
        raise ValueError(f"{c['name']}: `program` sets {both}, which the file states under keys of its own")
    cfg = dataclasses.replace(get_arch(c["arch"]).config, **over, **program)
    if cfg.nonparametric_ln != (c["norm"] == "layernorm_nonparametric"):
        raise ValueError(f"{c['name']}: the program's norm is not the file's {c['norm']!r}")
    wrong = [f"{f} {getattr(cfg, f, 'missing')!r} (the reference computes {sorted(map(repr, ok))})"
             for f, ok in arch.COMPUTES.items() if getattr(cfg, f, _MISSING) not in ok]
    if wrong:
        raise ValueError(f"{c['name']}: the program computes what {c['reference']!r} does not: "
                         + "; ".join(wrong))
    return cfg


def check_layout(cfg, params) -> None:
    """The benchmark's weights have exactly the program's parameter layout."""
    import jax

    from repro.models.params import abstract_params
    from repro.models.transformer import model_pspecs

    want = abstract_params(model_pspecs(cfg))
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(params):
        raise ValueError(f"weights layout {jax.tree_util.tree_structure(params)} "
                         f"is not the program's {jax.tree_util.tree_structure(want)}")
    for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(params)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {b.shape} {b.dtype}, program wants {a.shape} {a.dtype}")


def memory_peak(devices: List[Any]) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def now() -> float:
    return time.perf_counter()

