"""Model assembly: pattern-cyclic blocks, scan-over-layer-groups, embeddings,
LM head; forward (train/prefill) and decode paths with caches.

Layers are grouped by the config's block pattern period and stacked so a
single ``lax.scan`` executes all full groups (HLO size O(pattern period),
not O(depth)); remainder layers run unscanned.  Leading layers that keep a
dense MLP (``first_dense_layers``, before a MoE model's expert layers) are
a stack of their own, ``lead``, scanned the same way before the groups.
Caches mirror the same grouping: prefill's scan emits each stack's caches
as stacked ys, and the decode scan carries them whole beside the
activations, so each layer writes its new token in place and reads its
cache where it lies.  Prefill applies the head at the last position only.

Every path names its parts with ``jax.named_scope`` (``embed``, ``layers``,
``head``; ``norm``, ``attn``, ``kv_write`` and ``mlp`` in ``layers.py``,
``mla.py`` and ``moe.py``), so each compiled instruction's ``op_name``
metadata says which part it runs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (
    apply_norm,
    attention_pspecs,
    decode_attention,
    init_kv_cache,
    kv_cache_pspec,
    mlp,
    mlp_pspecs,
    multihead_attention,
    norm_pspec,
)
from .mla import mla_attention, mla_cache_pspec, mla_decode, mla_pspecs
from .moe import moe_block, moe_pspecs
from .params import PSpec, is_pspec
from .rglru import rglru_block, rglru_decode, rglru_pspecs, rglru_state_specs
from .sharding import constrain
from .ssm import mamba_block, mamba_decode, mamba_pspecs, mamba_state_specs

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------


def block_pspecs(cfg: ModelConfig, kind: str, moe: bool = True) -> Params:
    """One block's parameters; with ``moe`` false a MoE model's block keeps
    the dense MLP (its leading layers)."""
    p: Params = {}
    n1 = norm_pspec(cfg)
    if n1 is not None:
        p["norm1"] = n1
    if kind in ("attn", "attn_local"):
        p["attn"] = attention_pspecs(cfg)
    elif kind == "mla":
        p["attn"] = mla_pspecs(cfg)
    elif kind == "rglru":
        p["rglru"] = rglru_pspecs(cfg)
    elif kind == "mamba":
        p["mamba"] = mamba_pspecs(cfg)
        return p  # mamba blocks have no separate MLP
    n2 = norm_pspec(cfg)
    if n2 is not None:
        p["norm2"] = n2
    if cfg.is_moe and moe:
        p["moe"] = moe_pspecs(cfg)
    else:
        p["mlp"] = mlp_pspecs(cfg)
    return p


def _stack(tree: Any, n: int) -> Any:
    def f(p: PSpec) -> PSpec:
        return PSpec((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale, p.dtype)

    return jax.tree_util.tree_map(f, tree, is_leaf=is_pspec)


def model_pspecs(cfg: ModelConfig) -> Params:
    p: Params = {
        "embed": {"tok": PSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="lecun")}
    }
    return _with_param_dtype(_model_pspecs_body(cfg, p), cfg)


def _with_param_dtype(tree: Params, cfg: ModelConfig) -> Params:
    """Store parameters in cfg.param_dtype (bf16 halves FSDP gathers and
    gradient buffers; the optimizer then keeps an f32 master copy)."""
    if cfg.param_dtype == "float32":
        return tree
    dt = jnp.dtype(cfg.param_dtype)

    def f(p: PSpec) -> PSpec:
        return PSpec(p.shape, p.axes, p.init, p.scale, dt)

    return jax.tree_util.tree_map(f, tree, is_leaf=is_pspec)


def _model_pspecs_body(cfg: ModelConfig, p: Params) -> Params:
    G, P_, F = cfg.n_groups, cfg.pattern_period, cfg.first_dense_layers
    if F:
        p["lead"] = {"b0": _stack(block_pspecs(cfg, cfg.block_pattern[0], moe=False), F)}
    if cfg.scan_layers and G > 0:
        p["groups"] = {
            f"b{i}": _stack(block_pspecs(cfg, kind), G)
            for i, kind in enumerate(cfg.block_pattern)
        }
        rest_kinds = [cfg.block_kind(F + G * P_ + j) for j in range(cfg.n_rest_layers)]
    else:
        rest_kinds = [cfg.block_kind(i) for i in range(F, cfg.n_layers)]
    p["rest"] = [block_pspecs(cfg, k) for k in rest_kinds]
    fn = norm_pspec(cfg)
    if fn is not None:
        p["final_norm"] = fn
    if not cfg.tie_embeddings:
        p["lm_head"] = PSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"), init="lecun")
    return p


def _stacks(cfg: ModelConfig, params: Params) -> List[Tuple[str, Tuple[str, ...]]]:
    """The scanned stacks present, in layer order, each with its pattern."""
    out = []
    if "lead" in params:
        out.append(("lead", cfg.block_pattern[:1]))
    if cfg.scan_layers and cfg.n_groups > 0 and "groups" in params:
        out.append(("groups", cfg.block_pattern))
    return out


def _rest_start(cfg: ModelConfig, params: Params) -> int:
    """Index of the first unscanned layer."""
    groups = cfg.scan_layers and "groups" in params
    return cfg.first_dense_layers + (cfg.n_groups * cfg.pattern_period if groups else 0)


# ---------------------------------------------------------------------------
# Blocks (train / prefill)
# ---------------------------------------------------------------------------


def apply_block(
    cfg: ModelConfig, kind: str, p: Params, x: jax.Array, positions: jax.Array,
    train: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, moe_aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg, x, p.get("norm1"))
    if kind in ("attn", "attn_local"):
        x = x + multihead_attention(cfg, p["attn"], h, positions, local=(kind == "attn_local"))
    elif kind == "mla":
        x = x + mla_attention(cfg, p["attn"], h, positions)
    elif kind == "rglru":
        x = x + rglru_block(cfg, p["rglru"], h)
    elif kind == "mamba":
        return x + mamba_block(cfg, p["mamba"], h), aux
    x = constrain(x, "batch", None, None)
    h2 = apply_norm(cfg, x, p.get("norm2"), "float32" if "moe" in p else None)
    if "moe" in p:
        y, aux = moe_block(cfg, p["moe"], h2, train=train)
    else:
        y = mlp(cfg, p["mlp"], h2)
    x = x + y
    return constrain(x, "batch", None, None), aux


def _group_body(cfg: ModelConfig, carry, group_params, positions, pattern, train=False):
    x, aux = carry
    for i, kind in enumerate(pattern):
        x, a = apply_block(cfg, kind, group_params[f"b{i}"], x, positions, train=train)
        aux = aux + a
    return (x, aux)


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


@jax.named_scope("embed")
def _embed(cfg: ModelConfig, params: Params, tokens, embeds) -> jax.Array:
    """Input activations (B, S, d) in the compute dtype."""
    if embeds is not None:
        return embeds.astype(cfg.dtype)
    return params["embed"]["tok"][tokens].astype(cfg.dtype)


@jax.named_scope("head")
def _head(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """Final norm and LM head: logits (B, S, V)."""
    x = apply_norm(cfg, x, params.get("final_norm"))
    head = params["embed"]["tok"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
    if cfg.logits_f32:
        logits = logits.astype(jnp.float32)
    return constrain(logits, "batch", None, "act_vocab")


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: Optional[jax.Array] = None,       # (B, S) int32
    embeds: Optional[jax.Array] = None,       # (B, S, d) modality-frontend stub
    positions: Optional[jax.Array] = None,    # (S,)
    train: bool = False,                      # capacity-drop MoE tokens (train only)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B, S, V), moe_aux)."""
    x = _embed(cfg, params, tokens, embeds)
    S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S)
    x = constrain(x, "batch", None, None)

    with jax.named_scope("layers"):
        aux = jnp.zeros((), jnp.float32)
        for name, pattern in _stacks(cfg, params):
            body = _remat(cfg, functools.partial(_group_body, cfg, positions=positions,
                                                 pattern=pattern, train=train))

            def scan_fn(carry, gp, body=body):
                return body(carry, gp), None

            (x, aux), _ = jax.lax.scan(scan_fn, (x, aux), params[name])
        # remainder layers (and the no-scan path) run unrolled:
        rest_start = _rest_start(cfg, params)
        for j, p_rest in enumerate(params["rest"]):
            kind = cfg.block_kind(rest_start + j)
            x, a = apply_block(cfg, kind, p_rest, x, positions, train=train)
            aux = aux + a

    return _head(cfg, params, x), aux


# ---------------------------------------------------------------------------
# Prefill: forward + populated decode caches
# ---------------------------------------------------------------------------


def apply_block_prefill(
    cfg: ModelConfig,
    kind: str,
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    max_seq: int,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    h = apply_norm(cfg, x, p.get("norm1"))
    if kind in ("attn", "attn_local"):
        y, cache = multihead_attention(
            cfg, p["attn"], h, positions, local=(kind == "attn_local"),
            cache_max_seq=max_seq,
        )
        x = x + y
    elif kind == "mla":
        y, cache = mla_attention(cfg, p["attn"], h, positions, cache_max_seq=max_seq)
        x = x + y
    elif kind == "rglru":
        from .rglru import rglru_block as _rg

        y, cache = _rg(cfg, p["rglru"], h, return_state=True)
        x = x + y
    elif kind == "mamba":
        from .ssm import mamba_block as _mb

        y, cache = _mb(cfg, p["mamba"], h, return_state=True)
        return x + y, cache
    h2 = apply_norm(cfg, x, p.get("norm2"), "float32" if "moe" in p else None)
    if "moe" in p:
        y, _ = moe_block(cfg, p["moe"], h2)
    else:
        y = mlp(cfg, p["mlp"], h2)
    return x + y, cache


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    max_seq: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Full-sequence forward that also builds the decode cache.
    Returns (logits (B, 1, V) at the last position, cache sized for
    ``max_seq`` (default S))."""
    x = _embed(cfg, params, tokens, embeds)
    S = x.shape[1]
    max_seq = max_seq or S
    positions = jnp.arange(S)
    x = constrain(x, "batch", None, None)

    with jax.named_scope("layers"):
        cache: Dict[str, Any] = {"rest": []}
        for name, pattern in _stacks(cfg, params):

            def scan_fn(xc, gp, pattern=pattern):
                entries = {}
                for i, kind in enumerate(pattern):
                    xc, c = apply_block_prefill(cfg, kind, gp[f"b{i}"], xc, positions, max_seq)
                    entries[f"b{i}"] = c
                return xc, entries

            x, cache[name] = jax.lax.scan(scan_fn, x, params[name])
        rest_start = _rest_start(cfg, params)
        for j, p_rest in enumerate(params["rest"]):
            kind = cfg.block_kind(rest_start + j)
            x, c = apply_block_prefill(cfg, kind, p_rest, x, positions, max_seq)
            cache["rest"].append(c)

    return _head(cfg, params, x[:, -1:]), cache


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def _cache_entry_spec(cfg: ModelConfig, kind: str, batch: int, max_seq: int):
    if kind == "attn":
        return kv_cache_pspec(cfg, batch, local=False, max_seq=max_seq)
    if kind == "attn_local":
        return kv_cache_pspec(cfg, batch, local=True, max_seq=max_seq)
    if kind == "mla":
        return mla_cache_pspec(cfg, batch, max_seq)
    if kind == "rglru":
        return rglru_state_specs(cfg, batch)
    if kind == "mamba":
        return mamba_state_specs(cfg, batch)
    raise ValueError(kind)


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    """Abstract cache pytree mirroring the grouped layer structure."""
    out: Dict[str, Any] = {}
    G, F = cfg.n_groups, cfg.first_dense_layers

    def stack_specs(tree, n):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree
        )

    if F:
        out["lead"] = {"b0": stack_specs(_cache_entry_spec(cfg, cfg.block_pattern[0], batch, max_seq), F)}
    if cfg.scan_layers and G > 0:
        out["groups"] = {
            f"b{i}": stack_specs(_cache_entry_spec(cfg, kind, batch, max_seq), G)
            for i, kind in enumerate(cfg.block_pattern)
        }
        rest_kinds = [cfg.block_kind(F + G * cfg.pattern_period + j) for j in range(cfg.n_rest_layers)]
    else:
        rest_kinds = [cfg.block_kind(i) for i in range(F, cfg.n_layers)]
    out["rest"] = [_cache_entry_spec(cfg, k, batch, max_seq) for k in rest_kinds]
    return out


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_specs(cfg, batch, max_seq)
    )


def _layer_state(state: Dict[str, jax.Array], layer: Optional[jax.Array]) -> Dict[str, jax.Array]:
    """A recurrent layer's own state: the entry at ``layer`` of a stacked one."""
    if layer is None:
        return state
    return {k: jax.lax.dynamic_index_in_dim(a, layer, 0, False) for k, a in state.items()}


def _put_layer_state(
    state: Dict[str, jax.Array], new: Dict[str, jax.Array], layer: Optional[jax.Array]
) -> Dict[str, jax.Array]:
    """``state`` with ``new`` as its entry at ``layer`` (``new`` itself unstacked)."""
    if layer is None:
        return new
    return {k: jax.lax.dynamic_update_index_in_dim(a, new[k].astype(a.dtype), layer, 0)
            for k, a in state.items()}


def apply_block_decode(
    cfg: ModelConfig,
    kind: str,
    p: Params,
    x: jax.Array,              # (B, 1, d)
    cache: Dict[str, jax.Array],
    pos: jax.Array,            # scalar
    layer: Optional[jax.Array] = None,   # index into a stacked (scan-carried) cache
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    h = apply_norm(cfg, x, p.get("norm1"))
    if kind in ("attn", "attn_local"):
        y, cache = decode_attention(
            cfg, p["attn"], h, cache, pos, local=(kind == "attn_local"), layer=layer
        )
        x = x + y
    elif kind == "mla":
        y, cache = mla_decode(cfg, p["attn"], h, cache, pos, layer=layer)
        x = x + y
    elif kind == "rglru":
        y, new = rglru_decode(cfg, p["rglru"], h, _layer_state(cache, layer))
        cache = _put_layer_state(cache, new, layer)
        x = x + y
    elif kind == "mamba":
        y, new = mamba_decode(cfg, p["mamba"], h, _layer_state(cache, layer))
        return x + y, _put_layer_state(cache, new, layer)
    h2 = apply_norm(cfg, x, p.get("norm2"), "float32" if "moe" in p else None)
    if "moe" in p:
        y, _ = moe_block(cfg, p["moe"], h2)
    else:
        y = mlp(cfg, p["mlp"], h2)
    return x + y, cache


def decode_step(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                 # (B, 1) int32
    cache: Dict[str, Any],
    pos: jax.Array,                    # scalar int32
    embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One token for every sequence in the batch.  Returns (logits, cache).

    The layer scan carries the groups' stacked cache with the activations
    and steps over (group params, group index): each layer updates its own
    entry of the carried buffers, so a donated cache is updated in place."""
    x = _embed(cfg, params, tokens, embeds)
    x = constrain(x, "batch", None, None)

    with jax.named_scope("layers"):
        new_cache: Dict[str, Any] = {"rest": []}
        for name, pattern in _stacks(cfg, params):

            def scan_fn(carry, xs, pattern=pattern):
                xc, gc = carry
                gp, layer = xs
                gc = dict(gc)
                for i, kind in enumerate(pattern):
                    xc, gc[f"b{i}"] = apply_block_decode(
                        cfg, kind, gp[f"b{i}"], xc, gc[f"b{i}"], pos, layer
                    )
                return (xc, gc), None

            n = jax.tree_util.tree_leaves(params[name])[0].shape[0]
            (x, new_cache[name]), _ = jax.lax.scan(
                scan_fn, (x, cache[name]), (params[name], jnp.arange(n))
            )
        rest_start = _rest_start(cfg, params)
        for j, p_rest in enumerate(params["rest"]):
            kind = cfg.block_kind(rest_start + j)
            x, c = apply_block_decode(cfg, kind, p_rest, x, cache["rest"][j], pos)
            new_cache["rest"].append(c)

    return _head(cfg, params, x), new_cache
