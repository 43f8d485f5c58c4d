"""A decoder-only transformer with grouped-query attention and a dropless
top-k softmax mixture of experts in every layer (the program's
granite-moe-1b-a400m), as the benchmark knows an architecture: a test
fixture, which ``test_discovery.py`` copies into ``bench/reference/`` of a
copy of the benchmark to show that such an architecture is added as new
files only.  It imports nothing of the program.

Pre-norm blocks, each norm an RMSNorm with a learned ``1 + scale``: causal
attention with rotary positions (the dense reference's, which repeats each
key and value head over its group of query heads), then the experts.  A
router's softmax over all experts keeps the ``top_k`` largest, renormalised
to sum to 1; each kept expert is a SwiGLU MLP.  No capacity and no dropped
token, as inference runs.  float32, every matrix product at
``Precision.HIGHEST``; ``quant="fp8"`` is the control, as in the dense
reference.
"""
from __future__ import annotations

from typing import Dict, Iterable

import jax
import jax.numpy as jnp

from bench.costs import BYTES
from bench.reference import dense_transformer as dense

# the file's own keys -> ModelConfig fields
FIELDS = {"n_experts": "n_experts", "top_k": "top_k", "expert_d_ff": "expert_d_ff"}

# ModelConfig attribute -> the values this reference computes
COMPUTES = {
    "block_pattern": {("attn",)},
    "is_moe": {True},
    "moe_every": {1},                    # experts in every layer
    "shared_expert_d_ff": {0},           # and no shared expert
    "qk_norm": {False},
    "nonparametric_ln": {False},         # RMSNorm with a scale
    "mlp_act": {"swiglu"},
    "tie_embeddings": {False},
}


def shapes(c: Dict) -> Dict:
    """{path: (shape, std)} of every leaf."""
    d, L, H, K, hd, V = (c[k] for k in ("d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim", "vocab_size"))
    E, f = c["n_experts"], c["expert_d_ff"]
    return {
        "embed/tok": ((V, d), 1.0),
        "groups/b0/norm1": ((L, d), 0.1),
        "groups/b0/attn/wq": ((L, d, H, hd), d ** -0.5),
        "groups/b0/attn/wk": ((L, d, K, hd), d ** -0.5),
        "groups/b0/attn/wv": ((L, d, K, hd), d ** -0.5),
        "groups/b0/attn/wo": ((L, H, hd, d), (H * hd) ** -0.5),
        "groups/b0/norm2": ((L, d), 0.1),
        "groups/b0/moe/router": ((L, d, E), d ** -0.5),
        "groups/b0/moe/w_gate": ((L, E, d, f), d ** -0.5),
        "groups/b0/moe/w_up": ((L, E, d, f), d ** -0.5),
        "groups/b0/moe/w_down": ((L, E, f, d), f ** -0.5),
        "final_norm": ((d,), 0.1),
        "lm_head": ((d, V), d ** -0.5),
    }


def rmsnorm(c: Dict, x: jax.Array, scale: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + c["norm_eps"]) * (1.0 + scale)


def moe(c: Dict, p: Dict, h: jax.Array, quant) -> jax.Array:
    """h: (S, d); every expert runs on every token, weighted by a gate that
    is 0 where the token did not choose it."""
    probs = jax.nn.softmax(dense.mm("sd,de->se", h, p["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, c["top_k"])
    rows = jnp.arange(h.shape[0])[:, None]
    gate = jnp.zeros_like(probs).at[rows, idx].set(top / top.sum(-1, keepdims=True))
    a = jax.nn.silu(dense.mm("sd,edf->esf", h, p["w_gate"], quant)) * dense.mm("sd,edf->esf", h, p["w_up"], quant)
    return dense.mm("se,esd->sd", gate, dense.mm("esf,efd->esd", a, p["w_down"], quant), quant)


def logits(c: Dict, w: Dict, tokens: jax.Array, quant=None) -> jax.Array:
    """(S, V) float32 logits of one sequence of ids (S,)."""
    with jax.default_matmul_precision("highest"):
        x = w["embed"]["tok"][tokens].astype(jnp.float32)

        def layer(x, p):
            x = x + dense.attention(c, p["attn"], rmsnorm(c, x, p["norm1"]), quant, 512)
            x = x + moe(c, p["moe"], rmsnorm(c, x, p["norm2"]), quant)
            return x, None

        x, _ = jax.lax.scan(layer, x, w["groups"]["b0"])
        return dense.mm("sd,dv->sv", rmsnorm(c, x, w["final_norm"]), w["lm_head"], quant)


# -- least FLOPs and bytes: attention, the router and the chosen experts ------


def layer_params(c: Dict) -> int:
    """Matrix parameters of one block that one token uses."""
    d, H, K, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return d * H * hd + 2 * d * K * hd + H * hd * d + d * c["n_experts"] + c["top_k"] * 3 * d * c["expert_d_ff"]


def forward_flops(c: Dict, tokens: int, contexts_sum: float, head_tokens: int) -> float:
    return (2.0 * c["n_layers"] * layer_params(c) * tokens
            + 4.0 * c["n_layers"] * c["n_heads"] * c["head_dim"] * contexts_sum
            + 2.0 * c["d_model"] * c["vocab_size"] * head_tokens)


def decode_least(c: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    """At least ``top_k`` experts of every layer are read, however the rows
    route."""
    ctx = list(contexts)
    weights = c["n_layers"] * layer_params(c) + c["d_model"] * c["vocab_size"]
    kv = 2.0 * c["n_layers"] * c["n_kv_heads"] * c["head_dim"] * BYTES[c["kv_cache_dtype"]]
    return {"flops": forward_flops(c, len(ctx), float(sum(ctx)), len(ctx)),
            "bytes": weights * BYTES[c["param_dtype"]] + kv * sum(ctx)}
