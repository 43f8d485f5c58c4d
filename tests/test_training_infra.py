"""Trainer loop, checkpointing (atomic/keep-k/async/resume), elastic
restart across device counts, compression, data determinism."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

# trainer loops, checkpoint round-trips and multi-device subprocesses:
# excluded from the tier-1 profile (pyproject addopts -m "not slow")
pytestmark = pytest.mark.slow

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.distributed.compression import (
    dequantize_int8,
    ef_compress,
    quantize_int8,
)
from repro.models import ModelConfig
from repro.training import AdamWConfig, TrainConfig
from repro.training.trainer import Trainer, TrainerConfig

TINY = ModelConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=128, vocab_size=512, head_dim=16, remat="none",
)


def _trainer(tmp, steps=6, ckpt_every=0, **kw):
    return Trainer(
        TINY,
        TrainConfig(adamw=AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=steps)),
        TrainerConfig(total_steps=steps, ckpt_every=ckpt_every,
                      ckpt_dir=str(tmp), log_every=0, **kw),
    )


def test_loss_decreases(tmp_path):
    tr = _trainer(tmp_path, steps=10)
    tr.run()
    losses = [m["loss"] for m in tr.metrics_log]
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


def test_checkpoint_save_restore_resume(tmp_path):
    tr = _trainer(tmp_path / "ck", steps=6, ckpt_every=2, ckpt_async=False)
    state = tr.run()
    mgr = tr.ckpt
    assert mgr.all_steps() == [2, 4, 6]

    # restore equals live state at the final step
    restored, _ = mgr.restore(state)
    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # resume: a fresh trainer continues from step 6 (runs 6..9)
    tr2 = _trainer(tmp_path / "ck", steps=10, ckpt_every=2, ckpt_async=False)
    tr2.run()
    assert len(tr2.metrics_log) == 4


def test_checkpoint_keep_k_and_atomicity(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 3))}}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.all_steps() == [3, 4]
    assert not glob.glob(os.path.join(str(tmp_path), ".tmp_ckpt_*"))


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    tree = {"w": jnp.full((16,), 3.0)}
    mgr.save_async(1, tree)
    mgr.wait()
    got, _ = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))


def test_preemption_checkpoint(tmp_path):
    flag = str(tmp_path / "preempt")
    tr = _trainer(tmp_path / "ck", steps=50, ckpt_every=5, preemption_file=flag)
    tr.hooks.append(lambda step, m: open(flag, "w").close() if step == 7 else None)
    tr.run()
    assert len(tr.metrics_log) <= 9
    assert tr.ckpt.latest_step() == 8  # preemption checkpoint at break step


def test_elastic_restart_reshards_across_device_counts(subproc):
    """Save on a (4,2) mesh, restore onto (2,1) — values identical."""
    out = subproc(
        """
import os, jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh
from repro.models import ModelConfig, init_params, model_pspecs, make_rules, partition_specs
from jax.sharding import NamedSharding, PartitionSpec as P
import tempfile

cfg = ModelConfig(name='t', family='dense', n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16, remat='none')
pspecs = model_pspecs(cfg)
params = init_params(jax.random.PRNGKey(0), pspecs)
d = tempfile.mkdtemp()

mesh_a = make_mesh((4, 2), ('data', 'model'))
rules_a = make_rules(mesh_a)
sh_a = jax.tree_util.tree_map(lambda s: NamedSharding(mesh_a, s),
                              partition_specs(pspecs, rules_a),
                              is_leaf=lambda x: isinstance(x, P))
params_a = jax.device_put(params, sh_a)
mgr = CheckpointManager(d)
mgr.save(1, params_a)

mesh_b = make_mesh((2, 1), ('data', 'model'))
rules_b = make_rules(mesh_b)
sh_b = jax.tree_util.tree_map(lambda s: NamedSharding(mesh_b, s),
                              partition_specs(pspecs, rules_b),
                              is_leaf=lambda x: isinstance(x, P))
restored, _ = mgr.restore(params, shardings=sh_b)
for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('ELASTIC_OK', len(jax.tree_util.tree_leaves(restored)))
""",
        devices=8,
    )
    assert "ELASTIC_OK" in out


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------


@given(st.integers(min_value=1, max_value=2000))
@settings(max_examples=30, deadline=None)
def test_quantize_roundtrip_bounded_error(n):
    x = np.random.default_rng(n).standard_normal(n).astype(np.float32)
    q, s = quantize_int8(jnp.asarray(x))
    back = np.asarray(dequantize_int8(q, s, x.shape))
    blockmax = np.abs(x).max() if n else 0
    assert np.abs(back - x).max() <= blockmax / 127.0 + 1e-6


def test_error_feedback_converges():
    """SGD on a quadratic with int8-EF gradients reaches the optimum."""
    w = jnp.full((256,), 5.0)
    err = jnp.zeros_like(w)
    for _ in range(60):
        g = 2 * w                                # grad of ||w||^2
        q, s, err = ef_compress(g, err)
        g_hat = dequantize_int8(q, s, g.shape)
        w = w - 0.05 * g_hat
    assert float(jnp.abs(w).max()) < 1e-2


def test_compressed_psum_matches_exact(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ('d',))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 128))
f = jax.shard_map(lambda x: compressed_psum(x[0], 'd'), mesh=mesh,
              in_specs=P('d'), out_specs=P(), check_vma=False)
approx = f(x)
exact = x.sum(0)
rel = float(jnp.max(jnp.abs(approx - exact)) / (jnp.max(jnp.abs(exact)) + 1e-9))
assert rel < 0.05, rel
print('PSUM_OK', rel)
""",
        devices=4,
    )
    assert "PSUM_OK" in out


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_determinism_and_sharding():
    cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=8, seed=3)
    a = SyntheticLM(cfg, host_index=0, host_count=2)
    b = SyntheticLM(cfg, host_index=0, host_count=2)
    c = SyntheticLM(cfg, host_index=1, host_count=2)
    np.testing.assert_array_equal(a.batch_at(5)["tokens"], b.batch_at(5)["tokens"])
    assert not np.array_equal(a.batch_at(5)["tokens"], c.batch_at(5)["tokens"])
    t = a.batch_at(0)["tokens"]
    assert t.min() >= 0 and t.max() < 1000
    # labels are next-token shifted
    full_a = a.batch_at(7)
    assert full_a["tokens"].shape == full_a["labels"].shape


def test_pipeline_parallel_matches_sequential(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp
from repro.distributed.pipeline import pipeline_apply
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ('stage',))
Ws = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 16))
out = pipeline_apply(mesh, 'stage', lambda W, h: jnp.tanh(h @ W), Ws, x)
ref = x
for i in range(4): ref = jnp.tanh(ref @ Ws[i])
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-6, err
g = jax.grad(lambda Ws: pipeline_apply(mesh, 'stage', lambda W,h: jnp.tanh(h @ W), Ws, x).sum())(Ws)
gr = jax.grad(lambda Ws: _ref(Ws))(Ws) if False else None
print('PP_OK', err)
""",
        devices=4,
    )
    assert "PP_OK" in out
