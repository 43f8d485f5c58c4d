"""Serving engine behaviour."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# long-running engine/decode loops: excluded from the tier-1 profile
pytestmark = pytest.mark.slow

from repro.configs import get_arch
from repro.models import forward, init_params, model_pspecs
from repro.serving import Request, ServingEngine

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


def test_eos_stops_generation(params):
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, CFG.vocab_size, size=4).astype(np.int32)
    engine = ServingEngine(CFG, params, batch_size=1, max_seq=64)
    # discover the greedy first token, then use it as "EOS"
    [probe] = engine.serve([Request(prompt=prompt.copy(), max_new_tokens=3)])
    eos = int(probe.output[0])
    [req] = engine.serve([Request(prompt=prompt.copy(), max_new_tokens=32, eos_id=eos)])
    assert len(req.output) <= 2
