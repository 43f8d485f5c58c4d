"""Serving engine behaviour."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import forward, init_params, model_pspecs
from repro.models.transformer import decode_step, prefill
from repro.serving import Request, ServingEngine

# long-running engine/decode loops: excluded from the tier-1 profile
slow = pytest.mark.slow

# float32 throughout: the greedy tests compare argmax between the engine's
# incremental decode and a full-sequence forward(), and in bf16 the reduced
# 512-vocab config hits exact logit ties whose winner flips with summation
# order (same reason test_arch_decode_matches_forward pins float32)
CFG = dataclasses.replace(
    get_arch("olmo-1b").config.reduced(n_layers=2),
    dtype="float32", kv_cache_dtype="float32", logits_f32=True,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), model_pspecs(CFG))


@slow
def test_greedy_serving_matches_manual_decode(params):
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CFG.vocab_size, size=8).astype(np.int32)
    engine = ServingEngine(CFG, params, batch_size=1, max_seq=32)
    [req] = engine.serve([Request(prompt=prompt, max_new_tokens=6)])
    assert req.output is not None and len(req.output) == 6

    # manual greedy rollout with plain forward() must agree
    toks = list(prompt)
    for _ in range(6):
        lg, _ = jax.jit(lambda p, t: forward(CFG, p, t))(
            params, jnp.asarray([toks], jnp.int32)
        )
        toks.append(int(jnp.argmax(lg[0, -1])))
    np.testing.assert_array_equal(req.output, np.asarray(toks[len(prompt):], np.int32))


@slow
def test_batched_waves_and_stats(params):
    rng = np.random.default_rng(1)
    reqs = [
        Request(prompt=rng.integers(0, CFG.vocab_size, size=8).astype(np.int32),
                max_new_tokens=4, temperature=0.8 if i % 2 else 0.0)
        for i in range(6)
    ]
    engine = ServingEngine(CFG, params, batch_size=4, max_seq=16)
    engine.serve(reqs)
    assert engine.stats.waves == 2
    assert engine.stats.requests == 6
    assert all(r.output is not None and len(r.output) == 4 for r in reqs)
    s = engine.stats
    assert (s.prompt_tokens, s.pad_tokens, s.decode_steps, s.decode_rows_live) == (48, 16, 6, 18)
    assert all(r.first_token_s <= r.done_s for r in reqs)

    # one wave of known shape: prompts (4, 8, 8) left-padded to 8 in a batch
    # of 4, outputs (2, 4, 3): three decode steps, six of their rows live
    known = [Request(prompt=rng.integers(0, CFG.vocab_size, size=P).astype(np.int32),
                     max_new_tokens=T) for P, T in ((4, 2), (8, 4), (8, 3))]
    engine = ServingEngine(CFG, params, batch_size=4, max_seq=16)
    engine.serve(known)
    s = engine.stats
    assert (s.waves, s.requests, s.prompt_tokens, s.pad_tokens) == (1, 3, 20, 12)
    assert (s.decode_steps, s.decode_rows_live) == (3, 6)
    assert [len(r.output) for r in known] == [2, 4, 3]
    assert all(r.first_token_s <= r.done_s for r in known)


@slow
def test_eos_stops_generation(params):
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, CFG.vocab_size, size=4).astype(np.int32)
    engine = ServingEngine(CFG, params, batch_size=1, max_seq=64)
    # discover the greedy first token, then use it as "EOS"
    [probe] = engine.serve([Request(prompt=prompt.copy(), max_new_tokens=3)])
    eos = int(probe.output[0])
    [req] = engine.serve([Request(prompt=prompt.copy(), max_new_tokens=32, eos_id=eos)])
    assert len(req.output) <= 2


def _fetch_first(params, waves, batch_size, max_seq, rng_seed):
    """A serving loop that fetches each step's tokens before it dispatches
    the next, splits the sampling key on the host and makes each step's
    position there: (each wave's outputs, each wave's decode steps)."""
    pre = jax.jit(lambda p, t: prefill(CFG, p, tokens=t, max_seq=max_seq))

    @jax.jit
    def step(p, toks, cache, pos, key, temps):
        logits, cache = decode_step(CFG, p, toks, cache, pos)
        logits = logits[:, 0, :]
        sampled = jax.random.categorical(key, logits / jnp.maximum(temps, 1e-6)[:, None])
        return jnp.where(temps > 0, sampled, jnp.argmax(logits, axis=-1)).astype(jnp.int32)[:, None], cache

    key = jax.random.PRNGKey(rng_seed)
    outs, steps = [], []
    for wave in waves:
        S = max(len(r.prompt) for r in wave)
        prompts = np.zeros((batch_size, S), np.int32)
        for i, r in enumerate(wave):
            prompts[i, S - len(r.prompt):] = r.prompt
        logits, cache = pre(params, jnp.asarray(prompts))
        temps = jnp.asarray([r.temperature for r in wave] + [0.0] * (batch_size - len(wave)), jnp.float32)
        toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out = [[int(t)] for t in np.asarray(toks)[: len(wave), 0]]
        live = lambda i: ((wave[i].eos_id is None or out[i][-1] != wave[i].eos_id)
                          and len(out[i]) < wave[i].max_new_tokens)
        n = 0
        while any(live(i) for i in range(len(wave))):
            key, sub = jax.random.split(key)
            toks, cache = step(params, toks, cache, jnp.int32(S + n), sub, temps)
            host = np.asarray(toks)[:, 0]
            n += 1
            for i in [i for i in range(len(wave)) if live(i)]:
                out[i].append(int(host[i]))
        outs.append([np.asarray(o, np.int32) for o in out])
        steps.append(n)
    return outs, steps


def _wave(shapes, seed):
    """Requests of (prompt length, max_new_tokens, temperature, eos_id)."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, CFG.vocab_size, size=P).astype(np.int32),
                    max_new_tokens=T, temperature=t, eos_id=e) for P, T, t, e in shapes]


def _serve(params, waves, batch_size, max_seq, rng_seed):
    engine = ServingEngine(CFG, params, batch_size=batch_size, max_seq=max_seq, rng_seed=rng_seed)
    steps = []
    for wave in waves:
        before = engine.stats.decode_steps
        engine.serve(wave)
        steps.append(engine.stats.decode_steps - before)
    return engine, steps


def test_dispatch_ahead_matches_a_fetch_first_loop(params):
    """Two waves of unequal lengths, greedy and sampled rows, and one row
    whose eos_id fires on its 8th token, outlasting the rest: the engine
    gives, token for token, what a fetch-first loop gives under the same
    seed, in as many decode executions, dispatching ahead every step a
    row with no eos_id needs."""
    B, MAX_SEQ, SEED = 4, 32, 5
    shapes = [(8, 3, 0.0, None), (6, 5, 0.9, None), (8, 12, 0.0, None), (7, 4, 1.3, None)]
    [probe], _ = _fetch_first(params, [_wave(shapes, 0)], B, MAX_SEQ, SEED)
    k = next(k for k in range(7, 12) if probe[2][k] not in probe[2][:k])
    eos_shapes = shapes[:2] + [(8, 12, 0.0, int(probe[2][k])), shapes[3]]

    waves = [_wave(shapes, 0), _wave(eos_shapes, 0), _wave(shapes[1:], 1)]
    want, want_steps = _fetch_first(params, waves, B, MAX_SEQ, SEED)
    engine, steps = _serve(params, waves, B, MAX_SEQ, SEED)
    for wave, outs in zip(waves, want):
        for r, o in zip(wave, outs):
            np.testing.assert_array_equal(r.output, o)
            assert r.first_token_s <= r.done_s
    assert [len(r.output) for r in waves[1]] == [3, 5, k + 1, 4]
    assert steps == want_steps == [12 - 1, k, 12 - 1]
    # the eos row's steps past the other rows' longest (5 - 1) are fetched first
    assert engine.stats.decode_ahead == 11 + 4 + 11


@pytest.mark.parametrize("shapes,ahead", [
    ([(8, 6, 0.0, None), (5, 3, 0.7, None)], 6 - 1),
    ([(8, 6, 0.7, 1)], 0),
])
def test_decode_ahead_counts_the_steps_dispatched_ahead(params, shapes, ahead):
    """Every decode step of a wave with no eos_id is dispatched ahead of the
    tokens it follows; none of a one-row wave whose eos_id can fire is.
    Either way the wave runs the fetch-first loop's decode steps."""
    _, want = _fetch_first(params, [_wave(shapes, 2)], 2, 16, 0)
    engine, steps = _serve(params, [_wave(shapes, 2)], 2, 16, 0)
    assert engine.stats.decode_steps == steps[0] == want[0]
    assert engine.stats.decode_ahead == ahead
    if ahead:
        assert engine.stats.decode_steps == ahead
