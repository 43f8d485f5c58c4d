"""A DeepSeek-V3-style decoder (Moonlight-16B-A3B), as the benchmark knows
it: the module that a configuration file names under ``reference``
(``harness.arch``).  From the published description (``config.json`` of
moonshotai/Moonlight-16B-A3B; DeepSeek-V2, arXiv:2405.04434, for MLA;
DeepSeek-V3, arXiv:2412.19437, for the routing) and the configuration file
alone; it imports nothing of the program.  The file keeps the catalog's key
names (``FIELDS`` maps them onto ``ModelConfig``).

The model: pre-norm blocks, each norm an RMSNorm.  Attention is multi-head
latent attention without query compression: per token a query ``h W_q``
of ``qk_nope_head_dim + qk_rope_head_dim`` a head, a latent ``c_kv =
RMSNorm(h W_dkv)`` of ``kv_lora_rank`` and one rotary key part ``k_pe =
RoPE(h W_kr)`` that all heads share; keys ``[c_kv W_uk, k_pe]``, values
``c_kv W_uv``; causal softmax at scale ``(qk_nope + qk_rope)^-0.5``; RoPE on
the rotary parts only.  The first ``first_k_dense_replace`` layers end in a
SwiGLU MLP of ``intermediate_size``; every later layer in experts: sigmoid
scores over all routed experts, the top ``num_experts_per_tok`` chosen by
score plus a per-expert bias (``noaux_tc``, one group), their scores (not
the bias) renormalised to sum to 1 and scaled by ``routed_scaling_factor``
as gates; each expert a SwiGLU of ``moe_intermediate_size``; plus
``n_shared_experts`` shared experts, one SwiGLU of that many times the
width, on every token.  A final RMSNorm and an untied head.

Here, as in the program, the layer holds a share of the experts: the
configuration's ``n_routed_experts`` of the router's ``router_experts``,
from ``first_held_expert`` on; the router scores and chooses over all of
them, and only the held experts' terms enter the result.  The vocabulary
is the configuration's slice, ``vocab_size`` rows.

Departures from the published model, none of which changes the
computation's form:
- RoPE rotates the two halves of the 64 rotary dimensions against each
  other; the published checkpoint stores them interleaved and its code
  de-interleaves them first, which under random weights is a fixed
  permutation of the columns of ``W_q``'s rotary part and of ``W_kr``;
- the published fused ``kv_a_proj_with_mqa`` is ``[W_dkv, W_kr]`` and
  ``kv_b_proj`` is ``[W_uk, W_uv]`` per head, held here as separate
  matrices;
- every RMSNorm weight is ``1 + scale``;
- the latent's RMSNorm uses eps 1e-6, the published code's default for
  ``kv_a_layernorm`` (assumed: it is given no other), the others
  ``rms_norm_eps``;
- gates are renormalised by ``max(sum, 1e-9)`` where the published code
  adds 1e-20.

float32, every matrix product at ``Precision.HIGHEST``; not absorbed, no
cache, no batching, no kernels.  ``quant="fp8"`` is the control, as in
``dense_transformer``: both operands of every matrix product rounded to
scaled float8 e4m3.

``route_margins`` says where the choice of held experts is a near-tie.

The least work: ``forward_flops`` and ``decode_least`` for the readers of
the whole step; ``mla_least`` and ``moe_least`` for those of the attention
and of the expert layers.
"""
from __future__ import annotations

from typing import Dict, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from bench.costs import BYTES
from bench.reference import dense_transformer as dense

mm = dense.mm

# the file's catalog keys -> ModelConfig fields
FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "first_dense_layers",
    "moe_intermediate_size": "expert_d_ff",
    "num_experts_per_tok": "top_k",
    "scoring_func": "router_score",
    "routed_scaling_factor": "routed_scale",
    "router_experts": "n_experts",
    "n_routed_experts": "experts_held",
    "first_held_expert": "expert_offset",
}

# ModelConfig attribute -> the values this reference computes
COMPUTES = {
    "block_pattern": {("mla",)},         # latent attention in every layer
    "is_moe": {True},
    "moe_every": {1},                    # experts in every layer after the dense ones
    "router_score": {"sigmoid"},
    "router_bias": {True},               # noaux_tc: a bias for choosing only
    "qk_norm": {False},
    "nonparametric_ln": {False},         # RMSNorm with a scale
    "mlp_act": {"swiglu"},
    "tie_embeddings": {False},
    "kv_cache_dtype": {"bfloat16", "float32"},
}

# catalog keys whose published values are the only ones computed here
_AS_PUBLISHED = {"hidden_act": "silu", "attention_bias": False, "q_lora_rank": None,
                 "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc", "moe_layer_freq": 1,
                 "norm_topk_prob": True, "num_nextn_predict_layers": 0}
KV_NORM_EPS = 1e-6


def _check(c: Dict) -> None:
    wrong = {k: c.get(k) for k, v in _AS_PUBLISHED.items() if c.get(k, v) != v}
    if wrong:
        raise ValueError(f"{c['name']}: the reference computes {_AS_PUBLISHED}, not {wrong}")


def _sizes(c: Dict):
    return (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"])


def _lead_and_moe_layers(c: Dict):
    F = c["first_k_dense_replace"]
    return F, c["num_hidden_layers"] - F


def shapes(c: Dict) -> Dict:
    """{path: (shape, std)} of every leaf, in the program's layout: the
    dense layers stacked under ``lead/b0``, the expert layers under
    ``groups/b0``."""
    _check(c)
    d, H, r, n, e, v = _sizes(c)
    F, G = _lead_and_moe_layers(c)
    E, held, f = c["router_experts"], c["n_routed_experts"], c["moe_intermediate_size"]
    fs, ff, V = c["n_shared_experts"] * f, c["intermediate_size"], c["vocab_size"]

    def attn(L, pre):
        return {
            f"{pre}/norm1": ((L, d), 0.1),
            f"{pre}/attn/wq": ((L, d, H, n + e), d ** -0.5),
            f"{pre}/attn/w_dkv": ((L, d, r), d ** -0.5),
            f"{pre}/attn/w_kr": ((L, d, e), d ** -0.5),
            f"{pre}/attn/kv_norm": ((L, r), 0.1),
            f"{pre}/attn/w_uk": ((L, r, H, n), r ** -0.5),
            f"{pre}/attn/w_uv": ((L, r, H, v), r ** -0.5),
            f"{pre}/attn/wo": ((L, H, v, d), (H * v) ** -0.5),
            f"{pre}/norm2": ((L, d), 0.1),
        }

    def swiglu(L, pre, width):
        return {f"{pre}/w_gate": ((L, d, width), d ** -0.5), f"{pre}/w_up": ((L, d, width), d ** -0.5),
                f"{pre}/w_down": ((L, width, d), width ** -0.5)}

    out = {"embed/tok": ((V, d), 1.0), "final_norm": ((d,), 0.1), "lm_head": ((d, V), d ** -0.5)}
    if F:
        out.update(attn(F, "lead/b0"))
        out.update(swiglu(F, "lead/b0/mlp", ff))
    out.update(attn(G, "groups/b0"))
    out.update({
        "groups/b0/moe/router": ((G, d, E), d ** -0.5),
        "groups/b0/moe/router_bias": ((G, E), 0.1),
        "groups/b0/moe/w_gate": ((G, held, d, f), d ** -0.5),
        "groups/b0/moe/w_up": ((G, held, d, f), d ** -0.5),
        "groups/b0/moe/w_down": ((G, held, f, d), f ** -0.5),
    })
    out.update(swiglu(G, "groups/b0/moe/shared", fs))
    return out


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + scale)


def rope(c: Dict, x: jax.Array, pos: jax.Array) -> jax.Array:
    """RoPE over the whole last axis of x (S, ..., e), halves rotated."""
    half = x.shape[-1] // 2
    inv = 1.0 / (c["rope_theta"] ** (np.arange(half, dtype=np.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla(c: Dict, p: Dict, h: jax.Array, quant) -> jax.Array:
    """h: (S, d) of one sequence; causal latent attention, keys and values
    expanded from the latent."""
    S = h.shape[0]
    _, H, _, n, e, _ = _sizes(c)
    pos = jnp.arange(S)
    q = mm("sd,dnh->snh", h, p["wq"], quant)
    q = jnp.concatenate([q[..., :n], rope(c, q[..., n:], pos)], axis=-1)
    ckv = rmsnorm(mm("sd,dr->sr", h, p["w_dkv"], quant), p["kv_norm"], KV_NORM_EPS)
    kpe = rope(c, mm("sd,de->se", h, p["w_kr"], quant), pos)
    k = jnp.concatenate([mm("sr,rnh->snh", ckv, p["w_uk"], quant),
                         jnp.broadcast_to(kpe[:, None], (S, H, e))], axis=-1)
    v = mm("sr,rnh->snh", ckv, p["w_uv"], quant)
    s = mm("qnh,tnh->nqt", q, k, quant) * (n + e) ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = mm("nqt,tnh->qnh", jax.nn.softmax(s, axis=-1), v, quant)
    return mm("snh,nhd->sd", o, p["wo"], quant)


def gates(c: Dict, p: Dict, h: jax.Array, quant=None) -> jax.Array:
    """(S, router_experts): each token's gate on every expert, 0 where it
    did not choose it.  The bias chooses; the scores weigh."""
    scores = jax.nn.sigmoid(mm("sd,de->se", h, p["router"], quant))
    _, idx = jax.lax.top_k(scores + p["router_bias"], c["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top * c["routed_scaling_factor"])


def margins(c: Dict, p: Dict, h: jax.Array) -> jax.Array:
    """(S,): per token, the least change of one expert's selection value
    (score plus bias) that changes whether a held expert is chosen: a
    chosen held expert's lead over the first expert left out, or an
    unchosen one's distance below the last expert chosen."""
    k, o = c["num_experts_per_tok"], c.get("first_held_expert", 0)
    sel = jax.nn.sigmoid(mm("sd,de->se", h, p["router"], None)) + p["router_bias"]
    top, _ = jax.lax.top_k(sel, k + 1)
    last, first_out = top[:, k - 1:k], top[:, k:k + 1]
    held = sel[:, o:o + c["n_routed_experts"]]
    return jnp.where(held >= last, held - first_out, last - held).min(-1)


def swiglu(p: Dict, h: jax.Array, quant) -> jax.Array:
    return dense.mlp(p, h, quant)


def moe(c: Dict, p: Dict, h: jax.Array, quant) -> jax.Array:
    """h: (S, d); the held experts' terms, every held expert on every token
    weighted by its gate, and the shared experts."""
    o = c.get("first_held_expert", 0)
    g = gates(c, p, h, quant)[:, o:o + c["n_routed_experts"]]
    a = jax.nn.silu(mm("sd,edf->esf", h, p["w_gate"], quant)) * mm("sd,edf->esf", h, p["w_up"], quant)
    y = mm("se,esd->sd", g, mm("esf,efd->esd", a, p["w_down"], quant), quant)
    return y + swiglu(p["shared"], h, quant)


def _forward(c: Dict, w: Dict, tokens: jax.Array, quant):
    """The logits (S, V) and each position's smallest routing margin over
    the expert layers (S,)."""
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = w["embed"]["tok"][tokens].astype(jnp.float32)

        def block(ffn):
            def layer(x, p):
                x = x + mla(c, p["attn"], rmsnorm(x, p["norm1"], eps), quant)
                y, m = ffn(p, rmsnorm(x, p["norm2"], eps))
                return x + y, m
            return layer

        if "lead" in w:
            x, _ = jax.lax.scan(block(lambda p, h: (swiglu(p["mlp"], h, quant), None)), x, w["lead"]["b0"])
        x, m = jax.lax.scan(block(lambda p, h: (moe(c, p["moe"], h, quant), margins(c, p["moe"], h))),
                            x, w["groups"]["b0"])
        return mm("sd,dv->sv", rmsnorm(x, w["final_norm"], eps), w["lm_head"], quant), m.min(0)


def logits(c: Dict, w: Dict, tokens: jax.Array, quant=None) -> jax.Array:
    """(S, V) float32 logits of one sequence of ids (S,)."""
    return _forward(c, w, tokens, quant)[0]


def route_margins(c: Dict, w: Dict, tokens: jax.Array) -> jax.Array:
    """(S,) float32: per position, the smallest of ``margins`` over the
    expert layers of the float32 forward.  Where it is near 0 the held
    experts' choice is a near-tie, which a computation below float32 may
    settle the other way."""
    return _forward(c, w, tokens, None)[1]


# -- least FLOPs and bytes ----------------------------------------------------
#
# A row's routed work on the held experts is counted at its expectation under
# uniform routing: a token chooses each expert with probability k/E, so it
# uses k·held/E held experts, and ``rows`` tokens touch held·(1 − (1 − k/E)^rows)
# of them.  Attention is counted in the form with the fewer FLOPs: expanded
# (2·H·(nope + rope) for QK and 2·H·v for PV a key) over a whole sequence,
# absorbed (2·H·(rank + rope) and 2·H·rank a key) in a decode step, which
# reads the latent cache and cannot expand it for less.


def mla_params(c: Dict) -> int:
    """Matrix parameters of one latent attention."""
    d, H, r, n, e, v = _sizes(c)
    return d * H * (n + e) + d * (r + e) + r * H * (n + v) + H * v * d


def _expert(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _share(c: Dict) -> float:
    """Held experts a token uses, in expectation."""
    return c["num_experts_per_tok"] * c["n_routed_experts"] / c["router_experts"]


def touched(c: Dict, rows: int) -> float:
    """Held experts that ``rows`` tokens touch, in expectation."""
    return c["n_routed_experts"] * (1.0 - (1.0 - c["num_experts_per_tok"] / c["router_experts"]) ** rows)


def _ffn_token_params(c: Dict) -> float:
    """Matrix parameters of the dense and expert layers that one token uses."""
    F, G = _lead_and_moe_layers(c)
    d = c["hidden_size"]
    moe = d * c["router_experts"] + c["n_shared_experts"] * _expert(c) + _share(c) * _expert(c)
    return F * 3 * d * c["intermediate_size"] + G * moe


def _ffn_bytes(c: Dict, rows: int, dtype: str) -> float:
    """Weights of the dense and expert layers that a step of ``rows`` tokens reads."""
    F, G = _lead_and_moe_layers(c)
    d = c["hidden_size"]
    moe = (d * c["router_experts"] + c["router_experts"] + c["n_shared_experts"] * _expert(c)
           + touched(c, rows) * _expert(c))
    return (F * 3 * d * c["intermediate_size"] + G * moe) * BYTES[dtype]


def _latent(c: Dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def _absorbed_flops(c: Dict, keys: float) -> float:
    _, H, r, _, e, _ = _sizes(c)
    return c["num_hidden_layers"] * (2.0 * H * (r + e) + 2.0 * H * r) * keys


def forward_flops(c: Dict, tokens: int, contexts_sum: float, head_tokens: int) -> float:
    """A forward pass over ``tokens`` positions whose causal contexts sum to
    ``contexts_sum``, with the head at ``head_tokens`` of them."""
    _, H, _, n, e, v = _sizes(c)
    L = c["num_hidden_layers"]
    return (2.0 * (L * mla_params(c) + _ffn_token_params(c)) * tokens
            + L * (2.0 * H * (n + e) + 2.0 * H * v) * contexts_sum
            + 2.0 * c["hidden_size"] * c["vocab_size"] * head_tokens)


def mla_least(c: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    """Least FLOPs and bytes of one decode step's latent attention, all
    layers, for the live rows at their contexts: the projections and the
    absorbed attention; the weights in the compute dtype, each row's live
    latent cache and the new entries."""
    ctx = list(contexts)
    L, rows = c["num_hidden_layers"], len(ctx)
    flops = 2.0 * L * mla_params(c) * rows + _absorbed_flops(c, float(sum(ctx)))
    cache = L * _latent(c) * BYTES[c["kv_cache_dtype"]] * float(sum(ctx) + rows)
    return {"flops": flops, "bytes": L * mla_params(c) * BYTES[c["compute_dtype"]] + cache}


def moe_least(c: Dict, rows: int) -> Dict[str, float]:
    """Least FLOPs and bytes of one decode step's dense and expert layers
    for ``rows`` live rows: the leading dense MLP, the routers, the shared
    experts and the held experts the rows touch, in the compute dtype."""
    return {"flops": 2.0 * _ffn_token_params(c) * rows,
            "bytes": _ffn_bytes(c, rows, c["compute_dtype"])}


def decode_least(c: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    """Least FLOPs and HBM bytes of one whole decode step for the live rows:
    weights as stored (as ``dense_transformer`` counts them), the live
    latent cache, and the head."""
    ctx = list(contexts)
    L, rows = c["num_hidden_layers"], len(ctx)
    head = c["hidden_size"] * c["vocab_size"]
    flops = (2.0 * (L * mla_params(c) + _ffn_token_params(c) + head) * rows
             + _absorbed_flops(c, float(sum(ctx))))
    weights = (L * mla_params(c) + head) * BYTES[c["param_dtype"]] + _ffn_bytes(c, rows, c["param_dtype"])
    cache = L * _latent(c) * BYTES[c["kv_cache_dtype"]] * float(sum(ctx))
    return {"flops": flops, "bytes": weights + cache}
