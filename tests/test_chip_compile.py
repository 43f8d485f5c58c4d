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
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


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


def _decode_text(one_chip, cfg, B, S):
    """The engine's decode program for ``cfg`` at batch ``B`` and cache
    ``S``, its cache donated as the engine donates it: (compiled text, the
    abstract cache)."""
    from repro.models import abstract_params, model_pspecs
    from repro.models.transformer import cache_specs
    from repro.serving import ServingEngine

    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    cache = jax.tree_util.tree_map(on_chip, cache_specs(cfg, B, S))
    args = (jax.tree_util.tree_map(on_chip, abstract_params(model_pspecs(cfg))),
            on_chip(jax.ShapeDtypeStruct((B, 1), jnp.int32)),
            cache,
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32)),
            on_chip(jax.ShapeDtypeStruct((B,), jnp.float32)))
    engine = ServingEngine(cfg, None, batch_size=B, max_seq=S)
    return engine._decode.lower(*args).compile().as_text(), cache


def _assert_cache_updated_in_place(text, leaves, writes_wanted, reads_into_vmem=False):
    """No instruction outside a fusion makes a value with a layer's or a
    whole stack's element count of any cache leaf, save the in-place
    ``dynamic-update-slice``s under ``kv_write`` (and, with
    ``reads_into_vmem``, the compiler's prefetches from HBM into on-chip
    memory: a ``copy-start`` whose destination alone is in memory space
    1); every cache leaf is aliased from input to output."""
    sizes = {math.prod(s) for a in leaves for s in (a.shape, a.shape[1:])}
    comps = _computations(text)
    fused = {c for lines in comps.values() for line in lines if " fusion(" in line
             for c in re.findall(r"calls=%([\w.\-]+)", line)}

    def in_place_write(line):
        called = re.search(r"calls=%([\w.\-]+)", line)
        root = line if called is None else next(
            l for l in comps[called.group(1)] if l.lstrip().startswith("ROOT"))
        return " dynamic-update-slice(" in root and "/kv_write/" in root

    inst = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
    # copy-start's tuple: (destination, source, context)
    prefetches = {m.group(1) for lines in comps.values() for line in lines
                  for m in [re.match(r"\s*%([\w.\-]+) = \((\S+), (\S+), \S+\) copy-start\(", line)]
                  if m and "S(1)" in m.group(2) and "S(1)" not in m.group(3)}
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
            if (reads_into_vmem and m.group(3) == "copy-done"
                    and re.search(r"copy-done\(%([\w.\-]+)\)", line).group(1) in prefetches):
                continue                       # a read into on-chip memory: no copy in HBM
            if m.group(3) in ("fusion", "dynamic-update-slice") and in_place_write(line):
                writes += 1
                continue
            moved.append(f"{m.group(1)} {m.group(3)}[{m.group(2)}]")
    assert not moved, moved
    assert writes == writes_wanted

    cache_params = {int(n) for n in re.findall(
        r"parameter\((\d+)\).*op_name=\"cache\[", text)}
    aliased = {int(n) for n in re.findall(r"\{[\d,]*\}: \((\d+), \{\}", text)}
    assert len(cache_params) == len(leaves)
    assert cache_params <= aliased, (cache_params, aliased)


def test_olmo_decode_updates_cache_in_place(one_chip):
    """The engine's decode program for olmo-1b at batch 16 and cache 1280,
    its cache donated as the engine donates it: no instruction outside a
    fusion makes a value with a layer's or the whole stack's K/V element
    count, save the in-place ``dynamic-update-slice`` under ``kv_write``;
    and every cache leaf is aliased from input to output, so each step
    updates the same buffer."""
    from repro.configs import get_arch

    cfg = get_arch("olmo-1b").config
    B, S = 16, 1280
    text, cache = _decode_text(one_chip, cfg, B, S)
    leaves = jax.tree_util.tree_leaves(cache)
    want = (cfg.n_layers, S, cfg.n_kv_heads, B, cfg.resolved_head_dim)     # K and V
    assert [a.shape for a in leaves] == [want] * 2
    _assert_cache_updated_in_place(text, leaves, 2)                      # K and V


def test_moonlight_decode_updates_latent_cache_in_place(one_chip, monkeypatch):
    """The same for Moonlight-16B-A3B's benchmark configuration (the dense
    layer and 8 expert layers, 8 of 64 experts held, vocabulary 20480) at
    batch 64 and cache 1152: the latent cache (``c_kv`` 512 and ``k_pe``
    64 a token) of the leading dense layer's stack and of the expert layers'
    stack is written in place, one write a leaf, and never copied; the
    absorbed attention is the Pallas kernel, which reads it where it lies."""
    import json

    from bench.kinds import common
    from repro.kernels import ops

    # this process's backend is the CPU, where the kernels interpret
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    c = json.load(open(os.path.join(ROOT, "bench", "configs", "moonlight-16b-a3b.json")))
    cfg = common.model_config(c)
    B, S = 64, 1152
    text, cache = _decode_text(one_chip, cfg, B, S)
    leaves = jax.tree_util.tree_leaves(cache)
    assert [a.shape for a in leaves] == [(8, B, S, 512), (8, B, 64, S),     # groups
                                         (1, B, S, 512), (1, B, 64, S)]     # lead
    assert "tpu_custom_call" in text
    # the compiler prefetches the dense layer's k_pe (one layer, 9.4 MB)
    # into on-chip memory for the kernel, which reads all of it anyway
    _assert_cache_updated_in_place(text, leaves, 4, reads_into_vmem=True)


def test_mla_decode_kernel_compiles(one_chip):
    """The absorbed MLA decode kernel at Moonlight's widths: 16 heads,
    latent 512 + 64, a stack of 8 layers' caches of 1152 slots, batch 64."""
    from repro.kernels.mla_decode import mla_decode_fwd

    on_chip = lambda *s: jax.ShapeDtypeStruct(s[:-1], s[-1], sharding=one_chip)
    text = _compiled_text(
        lambda ql, qr, c, r, l, p: mla_decode_fwd(ql, qr, c, r, l, p, scale=192 ** -0.5),
        on_chip(64, 16, 512, jnp.bfloat16), on_chip(64, 16, 64, jnp.bfloat16),
        on_chip(8, 64, 1152, 512, jnp.bfloat16), on_chip(8, 64, 64, 1152, jnp.bfloat16),
        on_chip(jnp.int32), on_chip(jnp.int32))
    assert "tpu_custom_call" in text
