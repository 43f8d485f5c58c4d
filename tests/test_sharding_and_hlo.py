"""Sharding rules, cell assembly, HLO stats parsing, and the cost-
extrapolation methodology validated against a fully-unrolled compile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.params import PSpec, Rules
from repro.xla.hlo_stats import collective_stats, parse_shape_bytes


def test_rules_divisibility_fallback():
    r = Rules({"vocab": "model", "embed": "data"}, {"data": 16, "model": 16})
    assert r.pspec(PSpec((512, 128), ("vocab", "embed")))[0] == "model"
    # 49155 % 16 != 0 -> replicate + record
    spec = r.pspec(PSpec((49155, 128), ("vocab", "embed")))
    assert spec[0] is None
    assert ("vocab", 49155) in r.fallbacks


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert parse_shape_bytes("bf16[8]") == 16
    assert parse_shape_bytes("(f32[2,2], s8[4])") == 16 + 4
    assert parse_shape_bytes("pred[7]") == 7


def test_collective_stats_parsing():
    hlo = """
  %all-gather.1 = f32[512,2048]{0,1} all-gather(%p), channel_id=1, replica_groups=[16,16]<=[256], dimensions={1}
  %all-reduce.2 = bf16[1024]{0} all-reduce(%q), replica_groups=[8,32]<=[256], to_apply=%add
  %ar-done = bf16[4]{0} all-reduce-done(%h)
  %cp = f32[64]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %unrelated = f32[2]{0} add(%a, %b)
"""
    s = collective_stats(hlo)
    ag = s["per_kind"]["all-gather"]
    assert ag["count"] == 1 and ag["bytes"] == 512 * 2048 * 4 // 16
    ar = s["per_kind"]["all-reduce"]
    assert ar["count"] == 1 and ar["bytes"] == 1024 * 2
    cp = s["per_kind"]["collective-permute"]
    assert cp["count"] == 1 and cp["bytes"] == 64 * 4
    # wire model: AR rings move 2(N-1)/N * B
    assert ar["wire_bytes"] == int(2 * 1024 * 2 * 31 / 32)


def test_build_cell_shardings_match_abstract_shapes(subproc):
    out = subproc(
        """
import jax
from repro.launch.mesh import make_mesh
from repro.launch.specs import build_cell
mesh = make_mesh((2, 2), ('data', 'model'))
for shape in ('train_4k', 'prefill_32k', 'decode_32k'):
    cell = build_cell('olmo-1b', shape, mesh)
    flat_a = jax.tree_util.tree_leaves(cell.abstract_args)
    flat_s = jax.tree_util.tree_leaves(cell.in_shardings,
              is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding))
    assert len(flat_a) == len(flat_s), (shape, len(flat_a), len(flat_s))
    for a, s in zip(flat_a, flat_s):
        assert isinstance(s, jax.sharding.NamedSharding), (shape, s)
        s.shard_shape(a.shape)   # raises if incompatible
print('CELLS_OK')
""",
        devices=4,
    )
    assert "CELLS_OK" in out


def test_cost_extrapolation_methodology(subproc):
    """Depth-1P/2P extrapolated FLOPs must match a fully-unrolled compile of
    a deeper model (the §Roofline methodology's correctness check)."""
    out = subproc(
        """
import jax, jax.numpy as jnp, dataclasses
from repro.models import ModelConfig, model_pspecs, abstract_params, forward
from repro.xla.hlo_stats import cost_summary

def flops_at_depth(L):
    cfg = ModelConfig(name='t', family='dense', n_layers=L, d_model=128, n_heads=4,
                      n_kv_heads=2, d_ff=256, vocab_size=512, head_dim=32,
                      remat='none', scan_layers=False, unroll_inner=True,
                      attn_block_q=64)
    params = abstract_params(model_pspecs(cfg))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    c = jax.jit(lambda p, t: forward(cfg, p, t)[0]).lower(params, toks).compile()
    return cost_summary(c)['flops']

c1, c2, c6 = flops_at_depth(1), flops_at_depth(2), flops_at_depth(6)
per = c2 - c1
outside = c1 - per
pred6 = outside + 6 * per
rel = abs(pred6 - c6) / c6
assert rel < 0.02, (pred6, c6, rel)
print('EXTRAP_OK', rel)
""",
        devices=0,
    )
    assert "EXTRAP_OK" in out
