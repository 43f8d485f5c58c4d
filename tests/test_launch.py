"""The launch path on CPU: meshes with Auto axes, sharded training against
one device, the launchers, the compile-cache helper, kernel interpret mode,
and ``chip_smoke.py`` as a rehearsal and as a refusal off the chip."""
import json
import os

import jax
import pytest

from conftest import REPO

CHIP_SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_chip_smoke(args):
    """Snippet running chip_smoke.py in-process; prints its exit code."""
    return f"""
import runpy, sys
sys.argv = [{CHIP_SMOKE!r}] + {list(args)!r}
try:
    runpy.run_path({CHIP_SMOKE!r}, run_name='__main__')
except SystemExit as e:
    print('EXIT', e.code)
"""


def test_mesh_training_matches_one_device(subproc):
    """Two steps of reduced olmo-1b on a (data=2, model=2) mesh give the
    losses of the same steps on one device (float32: same math, another
    summation order)."""
    out = subproc(
        """
import dataclasses
from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.training import AdamWConfig, TrainConfig
from repro.training.trainer import Trainer, TrainerConfig
cfg = dataclasses.replace(get_arch('olmo-1b').config.reduced(), dtype='float32')
tc = TrainConfig(adamw=AdamWConfig(lr=1e-3, total_steps=3, warmup_steps=1))
def losses(mesh):
    tr = Trainer(cfg, tc, TrainerConfig(total_steps=3, log_every=0), mesh=mesh)
    tr.run()
    return [m['loss'] for m in tr.metrics_log]
one = losses(None)
mesh = make_mesh((2, 2), ('data', 'model'))
from jax.sharding import AxisType
assert mesh.axis_types == (AxisType.Auto, AxisType.Auto), mesh.axis_types
sharded = losses(mesh)
assert len(one) == 3 and one[2] < one[0] - 1e-2, one
diff = max(abs(a - b) for a, b in zip(one, sharded))
assert diff < 1e-4, (one, sharded)
print('MESH_OK', diff)
""",
        devices=4,
    )
    assert "MESH_OK" in out


def test_train_launcher_on_mesh(subproc, tmp_path):
    """``launch/train.py --devices 4 --mesh 2x2`` trains, and --devices adds
    its flag to XLA_FLAGS instead of replacing them."""
    out = subproc(
        """
import os, sys
sys.argv = ['train', '--arch', 'olmo-1b', '--smoke', '--steps', '2',
            '--devices', '4', '--mesh', '2x2']
from repro.launch.train import main
main()
import jax
print('FLAGS', os.environ['XLA_FLAGS'])
print('DEVICES', len(jax.devices()))
""",
        env={"XLA_FLAGS": "--xla_cpu_enable_fast_math=false",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert "done at step 2" in out
    assert ("FLAGS --xla_cpu_enable_fast_math=false "
            "--xla_force_host_platform_device_count=4") in out
    assert "DEVICES 4" in out


def test_serve_launcher_smoke(subproc, tmp_path):
    out = subproc(
        """
import sys
sys.argv = ['serve', '--arch', 'olmo-1b', '--smoke', '--requests', '4',
            '--batch', '2', '--prompt-len', '16', '--max-new', '4']
from repro.launch.serve import main
main()
""",
        env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert "served 4 requests in 2 waves on cpu" in out


def test_cache_shardings_follow_the_cache_order(subproc):
    """Each K/V leaf (and int8 scale) of the decode cache, stacked in the
    layer scan or not, is sharded as (cache_seq, kv_heads, batch, None)
    after its layer axis: the order of ``layers.kv_cache_pspec``."""
    out = subproc(
        """
import dataclasses
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.launch.specs import cache_shardings
from repro.models.sharding import make_rules
mesh = make_mesh((2, 2, 2), ('data', 'model', 'seq'))
rules = make_rules(mesh, fsdp=False, shard_cache_seq='seq')
base = dataclasses.replace(get_arch('gemma3-27b').config.reduced(), kv_cache_dtype='int8')
n = 0
for scan in (True, False):
    cfg = dataclasses.replace(base, scan_layers=scan)
    sh = cache_shardings(cfg, 4, 128, mesh, rules)
    for path, s in jax.tree_util.tree_leaves_with_path(sh):
        stacked = path[0].key == 'groups'
        want = P(*([None] * stacked), 'seq', 'model', 'data', None)
        assert s.spec == want, (jax.tree_util.keystr(path), s.spec)
        n += 1
print('SPECS_OK', n)
""",
        devices=8,
    )
    assert "SPECS_OK 72" in out


def test_latent_cache_shardings_follow_the_cache_order(subproc):
    """Each latent-cache leaf of an MLA model (the leading dense layer's
    stack and the expert layers' stack) is sharded after its layer axis in
    the order of ``mla.mla_cache_pspec``: ``c_kv`` (batch, cache_seq, None),
    ``k_pe`` (batch, None, cache_seq)."""
    out = subproc(
        """
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.launch.specs import cache_shardings
from repro.models.sharding import make_rules
mesh = make_mesh((2, 2, 2), ('data', 'model', 'seq'))
rules = make_rules(mesh, fsdp=False, shard_cache_seq='seq')
cfg = get_arch('moonlight-16b-a3b').config.reduced(n_layers=3)
sh = cache_shardings(cfg, 4, 128, mesh, rules)
stacks = []
want = {'c_kv': P(None, 'data', 'seq', None), 'k_pe': P(None, 'data', None, 'seq')}
for path, s in jax.tree_util.tree_leaves_with_path(sh):
    assert s.spec == want[path[-1].key], (jax.tree_util.keystr(path), s.spec)
    stacks.append(path[0].key)
print('SPECS_OK', sorted(stacks))
""",
        devices=8,
    )
    assert "SPECS_OK ['groups', 'groups', 'lead', 'lead']" in out


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/cache-from-env"])
def test_compile_cache_dir(subproc, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the fixed
    ``<checkout>/.jax_cache``."""
    out = subproc(
        f"""
import os
env_dir = {env_dir!r}
if env_dir is None:
    os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)
else:
    os.environ['JAX_COMPILATION_CACHE_DIR'] = env_dir
import jax
from repro.launch.compile_cache import use_compile_cache
print('USED', use_compile_cache())
print('CONFIG', jax.config.jax_compilation_cache_dir)
"""
    )
    want = env_dir or os.path.join(REPO, ".jax_cache")
    assert f"USED {want}\n" in out
    assert f"CONFIG {want}\n" in out


def test_compile_cache_keeps_each_programs_scopes(subproc, tmp_path):
    """A program that differs from a cached one only in its named scopes is
    compiled anew, so its profile names its own scopes; the same program
    from another directory is found again."""
    script = """
import sys
import jax, jax.numpy as jnp, numpy as np
from repro.launch.compile_cache import use_compile_cache
use_compile_cache()
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
hits = []
jax.monitoring.register_event_listener(
    lambda name, **_: hits.append(name) if name == '/jax/compilation_cache/cache_hits' else None)
def f(x):
    if sys.argv[1] == 'scoped':
        with jax.named_scope('mlp'):
            return jnp.tanh(x @ x)
    return jnp.tanh(x @ x)
text = jax.jit(f).lower(np.ones((8, 8), np.float32)).compile().as_text()
print('OP', [l.split('op_name=')[1].split()[0] for l in text.splitlines() if ' tanh(' in l][0])
print('HITS', len(hits))
"""
    env = {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    outs = {}
    for where, kind in (("a", "plain"), ("a", "scoped"), ("b", "scoped")):
        d = tmp_path / where
        d.mkdir(exist_ok=True)
        (d / "f.py").write_text(script)
        outs[where, kind] = subproc(
            f"import sys, runpy; sys.argv = ['f.py', {kind!r}]; runpy.run_path({str(d / 'f.py')!r}, run_name='__main__')",
            env=env)
    assert 'OP "jit(f)/tanh"' in outs["a", "plain"]
    assert 'OP "jit(f)/mlp/tanh"' in outs["a", "scoped"]
    assert "HITS 0" in outs["a", "scoped"]
    assert 'OP "jit(f)/mlp/tanh"' in outs["b", "scoped"]
    assert "HITS 1" in outs["b", "scoped"]


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


def test_chip_smoke_refuses_cpu(subproc):
    """Without --tiny the script stops at the device check on the CPU."""
    out = subproc(_run_chip_smoke([]), env={"JAX_PLATFORMS": "cpu"})
    assert "EXIT chip_smoke: JAX found no TPU" in out
    assert '"ok": true' not in out
    assert "compile cache" not in out      # stopped before anything else


@pytest.mark.parametrize("four", [False, True], ids=["one-chip", "four-chips"])
def test_chip_smoke_tiny_rehearsal(subproc, tmp_path, four):
    args = ["--tiny"] + (["--four-chips"] if four else [])
    out = subproc(
        _run_chip_smoke(args),
        devices=4 if four else 0,
        env={"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert "EXIT" not in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4 if four else 1}}
