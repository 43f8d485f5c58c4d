"""Mixture-of-Experts block: token-choice top-k routing over all
``n_experts``, computed for the experts this layer holds, plus any shared
experts that every token passes through.

Routing (``route``): softmax or sigmoid scores; with ``router_bias`` a
learned bias is added to the scores to choose the top_k experts and never
to their gates (DeepSeek-V3's ``noaux_tc``); the chosen scores are
renormalised to sum to 1 and scaled by ``routed_scale``.

The held share: the layer holds experts ``[expert_offset, expert_offset +
n_held_experts)`` of the router's ``n_experts`` and gives only their part of
the result, what one rank of expert parallelism computes for the tokens
routed to its experts, without the exchange.  With every expert held this
is the whole layer.

Inference is dropless and linear in tokens: each held expert runs on every
token of a group of ``moe_group`` tokens, weighted by a gate that is 0
where the token did not choose it.  Training keeps GShard/Switch-style
per-group capacity with einsum dispatch (the expert dimension shards over
"model"/EP and XLA inserts the all-to-alls).

The whole layer runs under the ``mlp`` scope, its parts under ``router``,
``experts`` and ``shared``.

granite-moe-1b: 32 experts, top-8, expert d_ff 512.
llama4-scout:   16 experts, top-1, expert d_ff 8192 + always-on shared expert.
moonlight:      64 experts, top-6 by sigmoid with a selection bias, expert
                d_ff 1408 + two shared (one MLP of width 2816).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import mlp, mlp_pspecs
from .params import PSpec

Params = Dict[str, Any]


def moe_pspecs(cfg: ModelConfig) -> Params:
    d, E, n, f = cfg.d_model, cfg.n_experts, cfg.n_held_experts, cfg.expert_d_ff
    p: Params = {
        "router": PSpec((d, E), ("embed", None), init="lecun"),
        "w_gate": PSpec((n, d, f), ("expert", "embed", None), init="lecun"),
        "w_up": PSpec((n, d, f), ("expert", "embed", None), init="lecun"),
        "w_down": PSpec((n, f, d), ("expert", None, "embed"), init="lecun"),
    }
    if cfg.router_bias:
        p["router_bias"] = PSpec((E,), (None,), init="zeros")
    if cfg.shared_expert_d_ff:
        p["shared"] = mlp_pspecs(cfg, d_ff=cfg.shared_expert_d_ff)
    return p


def _capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


@jax.named_scope("router")
def route(cfg: ModelConfig, p: Params, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (..., d) -> gates (..., k) float32, expert ids (..., k) in
    [0, n_experts), and every expert's score (..., E).  The router's product
    runs in float32 (HIGHEST: not rounded to bfloat16 on the TPU), as the
    published models compute their gates."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1) if cfg.router_score == "softmax" else jax.nn.sigmoid(logits)
    choice = scores + p["router_bias"].astype(jnp.float32) if cfg.router_bias else scores
    _, idx = jax.lax.top_k(choice, cfg.top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:
        gates = gates * cfg.routed_scale
    return gates, idx, scores


def _held(cfg: ModelConfig, idx: jax.Array) -> jax.Array:
    """One-hot (..., k, n_held) of the chosen experts that are held here;
    all zeros for a choice held elsewhere."""
    return jax.nn.one_hot(idx - cfg.expert_offset, cfg.n_held_experts, dtype=jnp.float32)


def _act(cfg: ModelConfig):
    return jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu


@jax.named_scope("experts")
def held_experts(cfg: ModelConfig, p: Params, x: jax.Array, gates: jax.Array) -> jax.Array:
    """x (T, d), gates (T, n_held) float32 -> Σ_e gate_e · expert_e(x),
    every held expert on every token."""
    dt = x.dtype
    h = jnp.einsum("td,edf->tef", x, p["w_gate"].astype(dt))
    if cfg.mlp_act in ("swiglu", "geglu"):
        h = _act(cfg)(h) * jnp.einsum("td,edf->tef", x, p["w_up"].astype(dt))
    else:
        h = jax.nn.gelu(h)
    h = (h.astype(jnp.float32) * gates[..., None]).astype(dt)
    return jnp.einsum("tef,efd->td", h, p["w_down"].astype(dt))


def _dropless(cfg: ModelConfig, p: Params, x: jax.Array, gates: jax.Array,
              idx: jax.Array) -> jax.Array:
    """Inference: the held experts' part for every token (T, d), in groups
    of ``moe_group`` tokens so that no intermediate grows past a group's."""
    T, d = x.shape
    g = (gates[..., None] * _held(cfg, idx)).sum(axis=1)       # (T, n_held), exact in float32
    Tg = cfg.moe_group
    if T <= Tg or T % Tg:
        return held_experts(cfg, p, x, g)
    y = jax.lax.map(lambda a: held_experts(cfg, p, *a),
                    (x.reshape(T // Tg, Tg, d), g.reshape(T // Tg, Tg, -1)))
    return y.reshape(T, d)


def _capacity_dispatch(cfg: ModelConfig, p: Params, x: jax.Array, gate_vals: jax.Array,
                       gate_idx: jax.Array, group: int) -> jax.Array:
    """Training: per-group capacity (groups of ``group`` tokens of x (T, d)),
    tokens over capacity dropped."""
    T, d = x.shape
    dt = x.dtype
    n, k = cfg.n_held_experts, cfg.top_k
    G = T // group
    xg = x.reshape(G, group, d)
    gate_vals = gate_vals.reshape(G, group, k)
    held = _held(cfg, gate_idx.reshape(G, group, k))        # (G, Tg, k, n)

    C = _capacity(cfg, group)
    counts = jnp.zeros((G, n), jnp.float32)
    dispatch = jnp.zeros((G, group, n, C), dtype=dt)
    combine = jnp.zeros((G, group, n, C), dtype=jnp.float32)
    for j in range(k):
        m = held[..., j, :]                                             # (G,Tg,n)
        pos = jnp.cumsum(m, axis=1) - m + counts[:, None, :]            # slot index
        keep = (pos < C) * m                                            # (G,Tg,n)
        counts = counts + keep.sum(axis=1)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
        dd = keep[..., None] * pos_oh                                   # (G,Tg,n,C)
        dispatch = dispatch + dd.astype(dt)
        combine = combine + gate_vals[..., j, None, None] * dd

    with jax.named_scope("experts"):
        expert_in = jnp.einsum("gtec,gtd->gecd", dispatch, xg)          # (G,n,C,d)
        h = jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"].astype(dt))
        if cfg.mlp_act in ("swiglu", "geglu"):
            h = _act(cfg)(h) * jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"].astype(dt))
        else:
            h = jax.nn.gelu(h)
        expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt))
        y = jnp.einsum("gtec,gecd->gtd", combine.astype(dt), expert_out)
    return y.reshape(T, d)


@jax.named_scope("mlp")
def moe_block(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                      # (B, S, d)
    train: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss).

    Capacity-based token dropping only applies when ``train=True``: drops
    depend on the other tokens in the group, so a capacity-bound forward()
    diverges from incremental decode (which sees one token per call and can
    never overflow).  Inference is dropless (``_dropless``).  ``x`` may come
    in float32 (the block's norm gives it so): the router scores it as it
    is, the experts compute in the compute dtype.
    """
    B, S, d = x.shape
    gates, idx, scores = route(cfg, p, x.reshape(B * S, d))
    x = x.astype(cfg.dtype)
    xt = x.reshape(B * S, d)
    if train:
        y = _capacity_dispatch(cfg, p, xt, gates, idx, min(cfg.moe_group, S))
    else:
        y = _dropless(cfg, p, xt, gates, idx)
    y = y.reshape(B, S, d)
    if cfg.shared_expert_d_ff:
        with jax.named_scope("shared"):
            y = y + mlp(cfg, p["shared"], x)

    # Switch-style load-balance aux loss: E * sum_e f_e * P_e
    f_e = jnp.mean(jax.nn.one_hot(idx[:, 0], cfg.n_experts, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(scores, axis=0)
    return y, cfg.n_experts * jnp.sum(f_e * p_e)
