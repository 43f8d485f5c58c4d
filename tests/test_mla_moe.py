"""Moonlight-16B-A3B's mechanisms in the program (latent attention with an
absorbed decode step, sigmoid-routed experts with a selection-only bias, the
held share of the experts, shared experts, a leading dense layer) against
the benchmark's plain reference ``bench/reference/mla_moe.py``, on seeded
random weights at the configuration's rehearsal size on the CPU."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, weights  # noqa: E402
from bench.kinds import common  # noqa: E402
from repro.models import moe as program_moe  # noqa: E402
from repro.models.transformer import decode_step, prefill  # noqa: E402

CONFIG = json.load(open(os.path.join(ROOT, "bench", "configs", "moonlight-16b-a3b.json")))
C = {**CONFIG, **CONFIG["rehearsal"]}          # the rehearsal size
REF = harness.arch(C)


def _weights(c, seed):
    return jax.jit(lambda k: weights.make(REF.shapes(c), k))(weights.seed_key(seed))


def _float32(c):
    return dataclasses.replace(common.model_config(c), dtype="float32", kv_cache_dtype="float32")


@pytest.mark.parametrize("seed", [1, 2000000011])
def test_prefill_then_decode_matches_the_reference(seed):
    """Prefill of 12 tokens, then 8 decode steps through the latent cache
    (the absorbed step and its kernel), against the reference's forward over
    all 20 tokens (expanded, not absorbed).  Both in float32, so they agree
    to float32 rounding; 1e-4 leaves room for the different order of the
    sums (absorbed or not, the program's groups of tokens)."""
    cfg = _float32(C)
    w = _weights(C, seed)
    toks = jax.random.randint(jax.random.PRNGKey(seed % 2**31), (20,), 1, C["vocab_size"])
    want = np.asarray(jax.jit(lambda w, t: REF.logits(C, w, t))(w, toks))
    lg, cache = jax.jit(lambda w, t: prefill(cfg, w, t, max_seq=24))(w, toks[None, :12])
    got = [np.asarray(lg[0, 0])]
    step = jax.jit(lambda w, t, c, p: decode_step(cfg, w, t, c, p))
    for i in range(12, 19):
        lg, cache = step(w, toks[None, i:i + 1], cache, jnp.int32(i))
        got.append(np.asarray(lg[0, 0]))
    np.testing.assert_allclose(np.stack(got), want[11:19], rtol=0, atol=1e-4)
    assert np.abs(want).max() > 1.0            # the logits are not all small


def _router(c, seed, bias):
    d, E = c["hidden_size"], c["router_experts"]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = {"router": jax.random.normal(k1, (d, E)) * d ** -0.5, "router_bias": bias(k2, E)}
    return p, jax.random.normal(k3, (64, d))


def test_the_selection_bias_chooses_but_never_weighs():
    """With a bias, other experts are chosen than by the scores alone; each
    chosen expert's gate is its score (never score + bias) over the chosen
    scores' sum, times the scale, as the reference has it.  A bias that
    makes expert 0 win everywhere puts it among every token's choice with
    its own score's gate."""
    cfg = _float32(C)
    p, x = _router(C, 0, lambda k, E: 0.3 * jax.random.normal(k, (E,)))
    gates, idx, scores = program_moe.route(cfg, p, x)
    _, unbiased = jax.lax.top_k(scores, cfg.top_k)
    assert (np.sort(np.asarray(idx), -1) != np.sort(np.asarray(unbiased), -1)).any(axis=-1).sum() >= 8
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(gates), chosen / chosen.sum(-1, keepdims=True) * 2.446, rtol=1e-6)
    dense = np.zeros(scores.shape, np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(gates), -1)
    np.testing.assert_allclose(dense, np.asarray(REF.gates(C, p, x)), rtol=1e-5, atol=1e-7)

    p0, _ = _router(C, 0, lambda k, E: jnp.zeros(E).at[0].set(10.0))
    gates0, idx0, scores0 = program_moe.route(cfg, p0, x)
    assert (np.asarray(idx0) == 0).any(axis=-1).all()
    at0 = np.asarray(gates0)[np.asarray(idx0) == 0]
    chosen0 = np.take_along_axis(np.asarray(scores0), np.asarray(idx0), -1)
    np.testing.assert_allclose(at0, np.asarray(scores0)[:, 0] / chosen0.sum(-1) * 2.446, rtol=1e-6)


def test_route_margins_are_the_least_change_that_reroutes_a_held_expert():
    """The reference's routing margin of a token: moving one held expert's
    selection value by a little less than the margin towards the boundary
    changes no token's held choice; by a little more, for some held expert,
    it changes that token's."""
    p, x = _router(C, 5, lambda k, E: 0.1 * jax.random.normal(k, (E,)))
    x = x[:6]
    margin = np.asarray(REF.margins(C, p, x))
    o, held = C.get("first_held_expert", 0), C["n_routed_experts"]
    chosen = lambda p: np.asarray(REF.gates(C, p, x))[:, o:o + held] > 0
    before = chosen(p)
    for t in range(x.shape[0]):
        rerouted = False
        for j in range(held):
            toward = -1.0 if before[t, j] else 1.0
            for f, flips in ((0.99, False), (1.01, True)):
                q = dict(p, router_bias=p["router_bias"].at[o + j].add(toward * f * margin[t]))
                changed = (chosen(q)[t] != before[t]).any()
                if not flips:
                    assert not changed, (t, j)
                rerouted |= flips and changed
        assert rerouted, t


def test_the_shares_add_up_to_the_uncut_layer():
    """A small expert layer of 16 experts, top 3, divided over 8 shares of 2
    experts (what eight chips of expert parallelism hold): the shares'
    results, with the shared experts that every share computes counted
    once, add up to the uncut layer, and the uncut layer is the
    reference's."""
    c = dict(C, router_experts=16, n_routed_experts=16)
    cfg = _float32(c)
    w = _weights(c, 5)
    p = jax.tree_util.tree_map(lambda a: a[0], w["groups"]["b0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, c["hidden_size"]))
    whole, _ = program_moe.moe_block(cfg, p, x)
    parts = []
    for s in range(8):
        held = {k: (v[2 * s:2 * s + 2] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p.items()}
        y, _ = program_moe.moe_block(dataclasses.replace(cfg, experts_held=2, expert_offset=2 * s), held, x)
        parts.append(y)
    shared = program_moe.mlp(cfg, p["shared"], x)
    np.testing.assert_allclose(sum(parts) - 7 * shared, whole, rtol=0, atol=1e-4)
    with jax.default_matmul_precision("highest"):
        want = REF.moe(c, p, x.reshape(-1, c["hidden_size"]), None)
    np.testing.assert_allclose(np.asarray(whole).reshape(want.shape), want, rtol=0, atol=1e-4)
    # each share alone is not the layer: the others' experts are missing
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-2


@pytest.mark.parametrize("size", ["rehearsal", "published widths"])
def test_check_layout_passes(size):
    """The weight table of the reference module is the program's parameter
    layout: at the rehearsal size with weights made, at the configuration's
    own size with their shapes only."""
    c = C if size == "rehearsal" else CONFIG
    cfg = common.model_config(c)
    if size == "rehearsal":
        params = _weights(c, 7)
    else:
        params = jax.eval_shape(lambda k: weights.make(REF.shapes(c), k), weights.seed_key(7))
        assert (cfg.n_experts, cfg.n_held_experts, cfg.top_k, cfg.first_dense_layers) == (64, 8, 6, 1)
        assert (cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.shared_expert_d_ff) == (512, 64, 2816)
    common.check_layout(cfg, params)
    with pytest.raises(ValueError):
        common.check_layout(dataclasses.replace(cfg, experts_held=cfg.n_held_experts // 2), params)


def test_the_cell_rehearses_correct():
    """``bench/run.py --rehearse`` of the cell: the same code as a chip run,
    at the rehearsal size on the CPU, comes out correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join("bench", "run.py"), "--workload", "moonlight-p1k-sat",
                        "--seed", "3000000019", "--seconds", "2", "--trace", "0", "--rehearse"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, p.stderr[-3000:]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compilations inside the window: 0" in p.stderr
