"""The TPU compiler's verdict on the main path's kernels at real widths.

Each test compiles for one chip of a described (not attached) v5e:2x2 and
asserts that the compiled program holds the Pallas kernel as a
``tpu_custom_call``, so a kernel the chip's compiler refuses (tiling, VMEM)
fails here and not on the chip.  Nothing runs: the tests pass shapes.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time, and every
test worker imports every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention_fwd

    q = jax.ShapeDtypeStruct((1, 16, 2048, 128), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(flash_attention_fwd, q, q, q)


def test_decode_attention_compiles(one_chip):
    from repro.kernels.decode_attention import decode_attention_fwd

    q = jax.ShapeDtypeStruct((8, 16, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 16, 2048, 128), jnp.bfloat16, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(decode_attention_fwd, q, kv, kv, n)


def test_rmsnorm_compiles(one_chip):
    from repro.kernels.rmsnorm import rmsnorm_fwd

    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(rmsnorm_fwd, x, s)


def test_olmo_pallas_prefill_compiles(one_chip, monkeypatch):
    """olmo-1b at its published config, prefill of 1024 tokens with the
    flash-attention kernel, as chip_smoke.py runs it."""
    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.models import abstract_params, model_pspecs
    from repro.models.transformer import prefill

    # this process's backend is the CPU, where the kernels interpret
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_arch("olmo-1b").config, attention_impl="pallas")
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract_params(model_pspecs(cfg)),
    )
    toks = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda p, t: prefill(cfg, p, tokens=t, max_seq=1056)[0][:, -1], params, toks
    )
    assert "tpu_custom_call" in text
