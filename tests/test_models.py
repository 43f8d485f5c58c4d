"""Per-arch smoke tests (reduced configs) + decode/prefill equivalence,
and the decode step's in-place cache write."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# minutes of jit compiles across every arch: excluded from the tier-1
# profile (pyproject addopts -m "not slow"); run with pytest -m ""
slow = pytest.mark.slow

from repro.configs import ARCHS, get_arch
from repro.models import (
    ModelConfig,
    forward,
    init_cache,
    init_params,
    model_pspecs,
)
from repro.models.transformer import decode_step, prefill
from repro.training import AdamWConfig, TrainConfig, init_train_state, make_train_step


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@slow
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_smoke_forward_and_train_step(arch, rng):
    """Reduced same-family config: one forward + one train step on CPU,
    asserting output shapes and no NaNs (assignment requirement)."""
    cfg = get_arch(arch).config.reduced()
    params = init_params(rng, model_pspecs(cfg))
    B, S = 2, 4 * cfg.window if cfg.window < 16 else 64
    S = min(S, 64)
    batch = {"labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    if cfg.frontend != "none":
        batch["embeds"] = 0.02 * jax.random.normal(rng, (B, S, cfg.d_model))
    else:
        batch["tokens"] = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)

    logits, aux = jax.jit(
        lambda p, b: forward(cfg, p, tokens=b.get("tokens"), embeds=b.get("embeds"))
    )(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: NaNs in logits"

    tc = TrainConfig(adamw=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10))
    state = init_train_state(cfg, params)
    step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0,))
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(state["step"]) == 1


@slow
@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma3-27b", "recurrentgemma-2b",
                                  "falcon-mamba-7b", "granite-moe-1b-a400m",
                                  "moonlight-16b-a3b"])
def test_arch_decode_matches_forward(arch, rng):
    cfg = dataclasses.replace(
        get_arch(arch).config.reduced(),
        dtype="float32", kv_cache_dtype="float32", logits_f32=True,
    )
    params = init_params(rng, model_pspecs(cfg))
    B, S = 2, 16
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    full, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, toks)
    cache = init_cache(cfg, B, S)
    dec = jax.jit(lambda p, t, c, pos: decode_step(cfg, p, t, c, pos))
    for i in range(S):
        lg, cache = dec(params, toks[:, i : i + 1], cache, jnp.int32(i))
        err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, i])))
        assert err < 5e-3, f"{arch} step {i}: {err}"


@slow
@pytest.mark.parametrize("arch", ["gemma3-27b", "recurrentgemma-2b", "falcon-mamba-7b",
                                  "moonlight-16b-a3b"])
def test_arch_prefill_then_decode(arch, rng):
    cfg = dataclasses.replace(
        get_arch(arch).config.reduced(),
        dtype="float32", kv_cache_dtype="float32", logits_f32=True,
    )
    params = init_params(rng, model_pspecs(cfg))
    B, S, P = 2, 16, 12
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    full, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, toks)
    lg_pf, cache = jax.jit(lambda p, t: prefill(cfg, p, t, max_seq=S))(params, toks[:, :P])
    assert float(jnp.max(jnp.abs(lg_pf - full[:, P - 1 : P]))) < 5e-3    # the head at the last position
    dec = jax.jit(lambda p, t, c, pos: decode_step(cfg, p, t, c, pos))
    for i in range(P, S):
        lg, cache = dec(params, toks[:, i : i + 1], cache, jnp.int32(i))
        assert float(jnp.max(jnp.abs(lg[:, 0] - full[:, i]))) < 5e-3


@slow
def test_int8_kv_cache_close_to_bf16(rng):
    cfg = get_arch("qwen3-8b").config.reduced()
    cfg_f = dataclasses.replace(cfg, dtype="float32", kv_cache_dtype="float32")
    cfg_q = dataclasses.replace(cfg, dtype="float32", kv_cache_dtype="int8")
    params = init_params(rng, model_pspecs(cfg_f))
    B, S = 2, 16
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    caches = {k: init_cache(c, B, S) for k, c in [("f", cfg_f), ("q", cfg_q)]}
    outs = {}
    for key, c in [("f", cfg_f), ("q", cfg_q)]:
        dec = jax.jit(lambda p, t, ca, pos, c=c: decode_step(c, p, t, ca, pos))
        cache = caches[key]
        for i in range(8):
            lg, cache = dec(params, toks[:, i : i + 1], cache, jnp.int32(i))
        outs[key] = lg
    # int8 cache tracks the exact cache closely (top-1 agreement)
    assert jnp.argmax(outs["f"][:, 0], -1).tolist() == jnp.argmax(outs["q"][:, 0], -1).tolist()


@slow
def test_param_count_estimates_match_declared_tree():
    """cfg.n_params (analytic, used for MODEL_FLOPS) ~ actual tree size."""
    from repro.models.params import count_params

    for arch in ARCHS:
        cfg = get_arch(arch).config
        declared = count_params(model_pspecs(cfg))
        analytic = cfg.n_params
        ratio = declared / analytic
        assert 0.9 < ratio < 1.12, f"{arch}: declared={declared:.3e} analytic={analytic:.3e}"


def test_prefill_head_is_forwards_last_position(rng):
    """olmo-1b's prefill applies the head at the last position only: its
    logits (B, 1, V) are forward's logits there."""
    cfg = dataclasses.replace(get_arch("olmo-1b").config.reduced(), dtype="float32",
                              kv_cache_dtype="float32")
    params = jax.jit(lambda k: init_params(k, model_pspecs(cfg)))(rng)
    toks = jax.random.randint(rng, (2, 24), 0, cfg.vocab_size)
    full, _ = jax.jit(lambda p, t: forward(cfg, p, t))(params, toks)
    last, _ = jax.jit(lambda p, t: prefill(cfg, p, t, max_seq=32))(params, toks)
    assert last.shape == (2, 1, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1:]), rtol=0, atol=1e-5)


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unscanned"])
@pytest.mark.parametrize("kind", ["attn", "attn_local"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_decode_writes_only_its_slot(kv_dtype, kind, scan):
    """One decode step at ``pos`` leaves every cache entry outside slot
    ``pos`` (``pos % W`` in a local window's ring) of every layer
    bit-identical, and puts the step's K/V into that slot: the values a
    prefill over the same tokens writes there."""
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=128, block_pattern=(kind,),
                      window=8, scan_layers=scan, dtype="float32", kv_cache_dtype=kv_dtype)
    params = jax.jit(lambda k: init_params(k, model_pspecs(cfg)))(jax.random.PRNGKey(0))
    B, S, pos = 2, 16, 11
    rs = np.random.default_rng(0)
    toks = jnp.asarray(rs.integers(0, cfg.vocab_size, (B, pos + 1)), jnp.int32)
    want = jax.jit(lambda t: prefill(cfg, params, t, max_seq=S)[1])(toks)
    W = jax.tree_util.tree_leaves(want)[0].shape[-4]       # (S, K, B, hd) last
    assert W == (8 if kind == "attn_local" else S)
    slot = pos % W
    # the step reads the prefill's entries of the tokens before ``pos``;
    # its own slot and the slots it masks hold noise
    read = np.arange(W) != slot if kind == "attn_local" else np.arange(W) < pos

    def fill(a):
        if a.dtype == jnp.int8:
            noise = rs.integers(-127, 128, a.shape)
        else:
            noise = rs.uniform(0.01, 1.0, a.shape)
        return jnp.asarray(np.where(read.reshape(W, 1, 1, 1), a, noise), a.dtype)

    before = jax.tree_util.tree_map(fill, want)
    after = jax.jit(lambda c: decode_step(cfg, params, toks[:, pos:], c, jnp.int32(pos))[1])(before)

    keep = np.arange(W) != slot
    for b, a in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after)):
        t = b.ndim - 4
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(np.compress(keep, a, t), np.compress(keep, b, t))
        assert not np.array_equal(np.take(a, slot, t), np.take(b, slot, t))

    def at_slot(entry, name):
        a = np.asarray(entry[name], np.float32)
        if kv_dtype == "int8":
            a = a * np.asarray(entry[name + "_scale"])
        return np.take(a, slot, a.ndim - 4)

    entries = lambda c: list(c.get("groups", {}).values()) + c["rest"]
    for a, w in zip(entries(after), entries(want)):
        for name in ("k", "v"):
            ref = at_slot(w, name)
            np.testing.assert_allclose(at_slot(a, name), ref, rtol=0,
                                       atol=0.03 * np.abs(ref).max())
