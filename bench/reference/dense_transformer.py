"""A dense decoder-only transformer, as the benchmark knows it: the module
that a configuration file names under ``reference`` (``harness.arch``).  It
holds the four things the benchmark needs of an architecture, from its
published description and the configuration file alone, and imports
nothing of the program:

- ``COMPUTES``: what the reference computes, in the program's
  ``ModelConfig`` terms; ``kinds/common.model_config`` refuses a program
  configuration that computes anything else (an architecture whose file
  has keys of its own that set ``ModelConfig`` fields also maps them, as
  ``FIELDS``; this one needs none);
- ``shapes(c)``: the weight table ``{path: (shape, std)}`` in the program's
  parameter layout (``models/transformer.py``: one stack of layers under
  ``groups/b0``), which ``weights.make`` draws from the seed;
- ``logits(c, w, tokens, quant=None)``: the plain reference, pre-norm blocks
  of causal multi-head attention with rotary positions and a SwiGLU MLP, a
  final norm and an output head (the embedding, transposed, where the
  configuration ties them).  Straightforward ``jax.numpy`` in float32 with
  every matrix product at ``Precision.HIGHEST``; no cache, no batching of
  requests, no kernels.  ``quant="fp8"`` gives the control: the same
  computation with both operands of every matrix product rounded to float8
  e4m3, each under a scale of its own, as float8 serving recipes take them;
- ``forward_flops`` and ``decode_least``: the least FLOPs and bytes of its
  work (``bench/costs.py`` says how they are counted).
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.costs import BYTES

HIGHEST = jax.lax.Precision.HIGHEST

# ModelConfig attribute -> the values this reference computes
COMPUTES = {
    "block_pattern": {("attn",)},        # global causal attention in every layer
    "is_moe": {False},                   # one dense MLP per layer
    "qk_norm": {False},
    "nonparametric_ln": {True},          # LayerNorm without scale or bias
    "mlp_act": {"swiglu"},
    "tie_embeddings": {True, False},     # the head is whichever the weight table holds
}


def shapes(c: Dict) -> Dict:
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


def _fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under a per-tensor scale that maps its
    absolute maximum to the format's largest value."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def mm(eq: str, a: jax.Array, b: jax.Array, quant: Optional[str]) -> jax.Array:
    """A matrix product at HIGHEST precision; under ``quant="fp8"`` its
    operands are first rounded to scaled e4m3."""
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def norm(c: Dict, x: jax.Array) -> jax.Array:
    if c["norm"] != "layernorm_nonparametric":
        raise ValueError(f"unknown norm {c['norm']!r}")
    mu = x.mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(((x - mu) ** 2).mean(-1, keepdims=True) + c["norm_eps"])


def rope(c: Dict, x: jax.Array, pos: jax.Array) -> jax.Array:
    """Rotary embedding over the first ``rotary_fraction`` of each head,
    rotating its two halves against each other (GPT-NeoX layout)."""
    hd = x.shape[-1]
    r = int(hd * c["rotary_fraction"])
    half = r // 2
    inv = 1.0 / (c["rope_theta"] ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(c: Dict, p: Dict, h: jax.Array, quant, q_block: int) -> jax.Array:
    """h: (S, d) of one sequence; causal softmax attention over all of it."""
    S = h.shape[0]
    pos = jnp.arange(S)
    q = rope(c, mm("sd,dnh->snh", h, p["wq"], quant), pos) * c["head_dim"] ** -0.5
    k = rope(c, mm("sd,dnh->snh", h, p["wk"], quant), pos)
    v = mm("sd,dnh->snh", h, p["wv"], quant)
    g = c["n_heads"] // c["n_kv_heads"]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)

    def block(args):
        qb, qpos = args
        s = mm("qnh,tnh->nqt", qb, k, quant)
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        return mm("nqt,tnh->qnh", jax.nn.softmax(s, axis=-1), v, quant)

    nb = max(1, S // q_block) if S % q_block == 0 else 1
    qs, ps = q.reshape(nb, S // nb, *q.shape[1:]), pos.reshape(nb, S // nb)
    o = jax.lax.map(block, (qs, ps)).reshape(q.shape)
    return mm("snh,nhd->sd", o, p["wo"], quant)


def mlp(p: Dict, h: jax.Array, quant) -> jax.Array:
    a = jax.nn.silu(mm("sd,df->sf", h, p["w_gate"], quant)) * mm("sd,df->sf", h, p["w_up"], quant)
    return mm("sf,fd->sd", a, p["w_down"], quant)


def hidden(c: Dict, w: Dict, tokens: jax.Array, quant=None, q_block: int = 512) -> jax.Array:
    """Final normed hidden states (S, d) of one sequence of ids (S,)."""
    x = w["embed"]["tok"][tokens].astype(jnp.float32)
    layers = w["groups"]["b0"]

    def layer(x, p):
        x = x + attention(c, p["attn"], norm(c, x), quant, q_block)
        x = x + mlp(p["mlp"], norm(c, x), quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, layers)
    return norm(c, x)


def head(w: Dict) -> jax.Array:
    return w["lm_head"] if "lm_head" in w else w["embed"]["tok"].T


def logits(c: Dict, w: Dict, tokens: jax.Array, quant=None) -> jax.Array:
    """(S, V) float32 logits of one sequence."""
    with jax.default_matmul_precision("highest"):
        return mm("sd,dv->sv", hidden(c, w, tokens, quant), head(w), quant)



# -- least FLOPs and bytes ----------------------------------------------------


def layer_params(c: Dict) -> int:
    """Matrix parameters of one block (attention and MLP)."""
    d, H, K, hd, f = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_ff"]
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp = (3 if c["mlp_act"] in ("swiglu", "geglu") else 2) * d * f
    return attn + mlp


def body_params(c: Dict) -> int:
    return c["n_layers"] * layer_params(c)


def head_params(c: Dict) -> int:
    return c["d_model"] * c["vocab_size"]


def attn_flops(c: Dict, contexts: Iterable[int]) -> float:
    """QK and PV of one token per entry, each attending to that many keys."""
    return 4.0 * c["n_layers"] * c["n_heads"] * c["head_dim"] * float(sum(contexts))


def forward_flops(c: Dict, tokens: int, contexts_sum: float, head_tokens: int) -> float:
    """A forward pass over ``tokens`` positions whose causal contexts sum to
    ``contexts_sum``, with the head applied at ``head_tokens`` of them: the
    projections and MLP of every layer, causal attention over the live
    context only (QK and PV, each 2*head_dim per key), and the head.  The
    embedding is a gather and costs no FLOPs."""
    return (2.0 * body_params(c) * tokens
            + 4.0 * c["n_layers"] * c["n_heads"] * c["head_dim"] * contexts_sum
            + 2.0 * head_params(c) * head_tokens)


def param_bytes(c: Dict) -> float:
    """Bytes of the weights a decode step has to read as stored: every
    matrix of the blocks and the head (a tied head is the embedding, read
    whole; the rows the step gathers from it are counted there)."""
    return float(body_params(c) + head_params(c)) * BYTES[c["param_dtype"]]


def kv_bytes_per_token(c: Dict) -> float:
    return 2.0 * c["n_layers"] * c["n_kv_heads"] * c["head_dim"] * BYTES[c.get("kv_cache_dtype", "bfloat16")]


def decode_least(c: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    """Least FLOPs and HBM bytes of one decode step for the live rows, each
    attending to the given context (its prompt and the tokens so far)."""
    ctx = list(contexts)
    rows = len(ctx)
    flops = 2.0 * (body_params(c) + head_params(c)) * rows + attn_flops(c, ctx)
    bytes_ = param_bytes(c) + kv_bytes_per_token(c) * float(sum(ctx))
    return {"flops": flops, "bytes": bytes_}
