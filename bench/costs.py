"""Operations and bytes a dense transformer's work needs, from the shapes in
its configuration file alone.

FLOPs count 2 per multiply-add of the matrix products the model defines:
the projections and MLP of every layer, the output head, and causal
attention over the live context only (QK and PV, each 2*head_dim per key).
The embedding is a gather, so it costs no FLOPs and only its used rows'
bytes.  Recomputation and padding are never counted: these are the least
that the work needs, so a share of a peak built on them cannot pass 100%.
"""
from __future__ import annotations

from typing import Dict, Iterable

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


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
    ``contexts_sum``, with the head applied at ``head_tokens`` of them."""
    return (2.0 * body_params(c) * tokens
            + 4.0 * c["n_layers"] * c["n_heads"] * c["head_dim"] * contexts_sum
            + 2.0 * head_params(c) * head_tokens)


def causal_sum(start: int, n: int) -> float:
    """Sum of the contexts of positions start .. start+n-1 (each sees itself
    and everything before it)."""
    return n * start + n * (n + 1) / 2.0


def param_bytes(c: Dict) -> float:
    """Bytes of the weights a decode step has to read as stored: every
    matrix of the blocks and the head (a tied head is the embedding, read
    whole; the rows the step gathers from it are counted there)."""
    return float(body_params(c) + head_params(c)) * _BYTES[c["param_dtype"]]


def kv_bytes_per_token(c: Dict) -> float:
    return 2.0 * c["n_layers"] * c["n_kv_heads"] * c["head_dim"] * _BYTES[c.get("kv_cache_dtype", "bfloat16")]


def decode_least(c: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    """Least FLOPs and HBM bytes of one decode step for the live rows, each
    attending to the given context (its prompt and the tokens so far)."""
    ctx = list(contexts)
    rows = len(ctx)
    flops = 2.0 * (body_params(c) + head_params(c)) * rows + attn_flops(c, ctx)
    bytes_ = param_bytes(c) + kv_bytes_per_token(c) * float(sum(ctx))
    return {"flops": flops, "bytes": bytes_}
