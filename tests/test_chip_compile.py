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
import math
import os
import re

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


def _computations(text):
    """{name: [instruction lines]} of an optimized HLO module's text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def test_olmo_decode_updates_cache_in_place(one_chip):
    """The engine's decode program for olmo-1b at batch 16 and cache 1280,
    its cache donated as the engine donates it: no instruction outside a
    fusion makes a value with a layer's or the whole stack's K/V element
    count, save the in-place ``dynamic-update-slice`` under ``kv_write``;
    and every cache leaf is aliased from input to output, so each step
    updates the same buffer."""
    from repro.configs import get_arch
    from repro.models import abstract_params, model_pspecs
    from repro.models.transformer import cache_specs
    from repro.serving import ServingEngine

    cfg = get_arch("olmo-1b").config
    B, S = 16, 1280
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    cache = jax.tree_util.tree_map(on_chip, cache_specs(cfg, B, S))
    args = (jax.tree_util.tree_map(on_chip, abstract_params(model_pspecs(cfg))),
            on_chip(jax.ShapeDtypeStruct((B, 1), jnp.int32)),
            cache,
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32)),
            on_chip(jax.ShapeDtypeStruct((B,), jnp.float32)))
    engine = ServingEngine(cfg, None, batch_size=B, max_seq=S)
    text = engine._decode.lower(*args).compile().as_text()

    leaves = jax.tree_util.tree_leaves(cache)
    want = (cfg.n_layers, S, cfg.n_kv_heads, B, cfg.resolved_head_dim)     # K and V
    assert [a.shape for a in leaves] == [want] * 2
    sizes = {math.prod(leaves[0].shape), math.prod(leaves[0].shape[1:])}
    comps = _computations(text)
    fused = {c for lines in comps.values() for line in lines if " fusion(" in line
             for c in re.findall(r"calls=%([\w.\-]+)", line)}

    def in_place_write(line):
        called = re.search(r"calls=%([\w.\-]+)", line)
        root = line if called is None else next(
            l for l in comps[called.group(1)] if l.lstrip().startswith("ROOT"))
        return " dynamic-update-slice(" in root and "/kv_write/" in root

    inst = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
    moved, writes = [], 0
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = inst.match(line)
            if not m or math.prod(int(d) for d in m.group(2).split(",") if d) not in sizes:
                continue
            if m.group(3) in ("parameter", "get-tuple-element", "bitcast"):
                continue
            if m.group(3) in ("fusion", "dynamic-update-slice") and in_place_write(line):
                writes += 1
                continue
            moved.append(f"{m.group(1)} {m.group(3)}[{m.group(2)}]")
    assert not moved, moved
    assert writes == 2                                   # K and V

    cache_params = {int(n) for n in re.findall(
        r"parameter\((\d+)\).*op_name=\"cache\[", text)}
    aliased = {int(n) for n in re.findall(r"\{[\d,]*\}: \((\d+), \{\}", text)}
    assert len(cache_params) == len(leaves)
    assert cache_params <= aliased, (cache_params, aliased)
