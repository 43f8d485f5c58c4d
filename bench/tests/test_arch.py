"""An architecture's knowledge lives in its module under ``bench/reference/``,
which the configuration names (``harness.arch``).  Moving the dense
transformer's weight table, declaration and costs there changed nothing
that olmo-1b runs: the readings below were recorded from the code before
the move."""
import dataclasses
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench import harness, weights
from bench.kinds import common

from conftest import ROOT

OLMO = json.load(open(os.path.join(ROOT, "bench", "configs", "olmo-1b.json")))
GRANITE = json.load(open(os.path.join(ROOT, "bench", "tests", "data", "granite", "granite-moe-1b-a400m.json")))

D, F = 2048 ** -0.5, 8192 ** -0.5
OLMO_TABLE = {
    "embed/tok": ((50304, 2048), D),
    "groups/b0/attn/wq": ((16, 2048, 16, 128), D),
    "groups/b0/attn/wk": ((16, 2048, 16, 128), D),
    "groups/b0/attn/wv": ((16, 2048, 16, 128), D),
    "groups/b0/attn/wo": ((16, 16, 128, 2048), D),
    "groups/b0/mlp/w_gate": ((16, 2048, 8192), D),
    "groups/b0/mlp/w_up": ((16, 2048, 8192), D),
    "groups/b0/mlp/w_down": ((16, 8192, 2048), F),
}
# sha256 over (path, bytes) of every leaf, in the tree's order, at the
# rehearsal sizes
OLMO_SUMS = {7: "d0d08419f8dee5f99c8bf677479a5bf6c2e5fc682337470d946e7d17840a9a3a",
             3000000019: "be38844417a50b6802f78bce461f5b85de071302f3e879d74dcf848aaee94eab"}


def _rehearsal(c):
    c = dict(c)
    c.update(c.pop("rehearsal"))
    return c


def test_olmo_weight_table_is_unchanged():
    table = harness.arch(OLMO).shapes(OLMO)
    assert {k: (tuple(s), std) for k, (s, std) in table.items()} == OLMO_TABLE


@pytest.mark.parametrize("seed", sorted(OLMO_SUMS))
def test_olmo_weights_are_unchanged_bit_for_bit(seed):
    c = _rehearsal(OLMO)
    table = harness.arch(c).shapes(c)
    w = jax.jit(lambda k: weights.make(table, k))(weights.seed_key(seed))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == OLMO_SUMS[seed]


def test_olmo_model_config_is_unchanged():
    """The generic build equals the dense-only one it replaced: the
    registry's config with the file's keys set through the same map."""
    from repro.configs import get_arch

    parent = dataclasses.replace(get_arch("olmo-1b").config,
                                 **{f: OLMO[k] for k, f in common._FIELDS.items() if k in OLMO})
    assert common.model_config(OLMO) == parent
    assert common.model_config(_rehearsal(OLMO)).d_model == 128


def test_the_program_section_is_applied_as_it_stands():
    cfg = common.model_config(dict(OLMO, program={"attn_block_q": 256, "scan_chunk": 64}))
    assert cfg == dataclasses.replace(common.model_config(OLMO), attn_block_q=256, scan_chunk=64)
    with pytest.raises(ValueError, match="d_model"):
        common.model_config(dict(OLMO, program={"d_model": 64}))    # the file states it


def test_an_architectures_own_keys_reach_the_program():
    cfg = common.model_config(_rehearsal(dict(GRANITE, reference="../tests/data/granite/moe_transformer")))
    assert (cfg.n_experts, cfg.top_k, cfg.expert_d_ff, cfg.attn_block_q) == (8, 2, 64, 256)
    assert (cfg.d_model, cfg.n_kv_heads, cfg.tie_embeddings) == (128, 2, False)


@pytest.mark.parametrize("c, why", [
    (dict(GRANITE, reference="dense_transformer"), "is_moe True"),
    (dict(GRANITE, reference="dense_transformer"), "nonparametric_ln False"),
    (dict(OLMO, program={"qk_norm": True}), "qk_norm True"),
    (dict(OLMO, program={"block_pattern": ("attn", "attn_local")}), "block_pattern"),
])
def test_what_the_reference_does_not_compute_is_refused(c, why):
    with pytest.raises(ValueError, match=why):
        common.model_config(c)


def test_a_configuration_without_its_module_is_refused():
    with pytest.raises(harness.Refused):
        harness.arch(dict(OLMO, reference="no_such_architecture"))
