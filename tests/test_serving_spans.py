"""The serving engine's profiler spans and the model's named scopes, read
back the way the benchmark reads them (``bench/trace_reduce.py``,
``bench/program_trace.py``), on the CPU."""
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import init_params, model_pspecs
from repro.serving import Request, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program_trace, trace_reduce  # noqa: E402

CFG = get_arch("olmo-1b").config.reduced(n_layers=2)
B, MAX_SEQ = 2, 16


@pytest.fixture(scope="module")
def engine():
    params = init_params(jax.random.PRNGKey(0), model_pspecs(CFG))
    return ServingEngine(CFG, params, batch_size=B, max_seq=MAX_SEQ)


def _requests(shapes):
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, CFG.vocab_size, size=P).astype(np.int32),
                    max_new_tokens=T, temperature=0.7 * (i % 2)) for i, (P, T) in enumerate(shapes)]


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    """A two-wave serve under a CPU profiler trace."""
    engine.serve(_requests([(8, 3)]))                  # compile outside the trace
    before = engine.stats.decode_steps
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    reqs = engine.serve(_requests([(8, 4), (6, 2), (8, 3)]))
    jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return path, reqs, engine.stats.decode_steps - before


def test_engine_spans_nest_in_their_wave(traced):
    path, reqs, steps = traced
    t = trace_reduce.load(path, program_trace.ENGINE_SPANS)
    names = [n for _, _, n in t.spans]
    assert set(names) == set(program_trace.ENGINE_SPANS)
    waves = [(s, e) for s, e, n in t.spans if n == "engine.wave"]
    assert len(waves) == 2
    for s, e, n in t.spans:
        assert sum(a <= s and e <= b for a, b in waves) == 1, n
    assert names.count("engine.prefill") == 2
    assert names.count("engine.dispatch") == steps == 3 + 2
    assert names.count("engine.token_sync") == names.count("engine.bookkeeping") == steps
    assert all(r.first_token_s <= r.done_s for r in reqs)



def test_next_step_is_dispatched_before_this_steps_tokens_are_read(traced):
    """In each wave the ``engine.dispatch`` of step k+1 starts before the
    ``engine.token_sync`` of step k ends: the device holds the next step
    while the host waits for this one's tokens."""
    path, _, _ = traced
    t = trace_reduce.load(path, program_trace.ENGINE_SPANS)
    for a, b, _ in [w for w in t.spans if w[2] == "engine.wave"]:
        inside = lambda name: [(s, e) for s, e, n in t.spans if n == name and a <= s and e <= b]
        dispatch, sync = inside("engine.dispatch"), inside("engine.token_sync")
        assert len(dispatch) == len(sync) >= 2
        assert all(d[0] < s[1] for d, s in zip(dispatch[1:], sync))

def _decode_args(engine):
    _, cache = jax.eval_shape(engine._prefill, engine.params, jax.ShapeDtypeStruct((B, 8), jnp.int32))
    return (engine.params, jax.ShapeDtypeStruct((B, 1), jnp.int32), cache,
            jax.ShapeDtypeStruct((), jnp.int32), jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct((B,), jnp.float32))


def _prefill_args(engine):
    return engine.params, jax.ShapeDtypeStruct((B, 8), jnp.int32)


@pytest.mark.parametrize("program,args,want", [
    ("_decode", _decode_args, {"embed", "layers", "norm", "attn", "kv_write", "mlp", "head", "sample"}),
    ("_prefill", _prefill_args, {"embed", "layers", "norm", "attn", "kv_write", "mlp", "head"}),
])
def test_compiled_programs_name_every_scope(engine, program, args, want):
    fn = getattr(engine, program)
    scopes = program_trace.scopes_from_text(fn.lower(*args(engine)).compile().as_text())
    assert want <= set(scopes.values()) <= want | {program_trace.UNSCOPED}


def test_trace_holds_the_decode_programs_scope_map(engine, traced):
    """The profiler's copy of the decode program names every instruction of
    the compiled program, each with the scope its compiled text gives."""
    path, _, _ = traced
    text = engine._decode.lower(*_decode_args(engine)).compile().as_text()
    want = program_trace.scopes_from_text(text)
    found = [program_trace.scopes_from_proto(p) for name, p in program_trace.hlo_protos(path).items()
             if name.startswith("jit__decode(")]
    assert want in found
    assert program_trace.program_scopes(path, "jit__decode", want) == want


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_decode)/layers/while/body/closed_call/attn/kv_write/dynamic_update_slice", "kv_write"),
    ("jit(_decode)/layers/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(_decode)/layers/while/body/dynamic_slice", "layers"),
    ("jit(_decode)/sample/jit(_gumbel)/jit(_uniform)/slice", "sample"),
    ("jit(_decode)/head/norm/reduce_sum", "norm"),
    ("jit(_decode)/while/body/closed_call/mlp", program_trace.UNSCOPED),   # a primitive, not a scope
    ("", program_trace.UNSCOPED),
])
def test_innermost_scope(op_name, scope):
    assert program_trace.innermost(op_name) == scope
