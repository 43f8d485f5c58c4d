import json
import os
import shutil
import tempfile

import pytest

from bench import harness, peaks
from bench import program_trace as pt
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "metrics")
OLD = ("engine.decode_row_use", "engine.host_gap_share", "serve.wave_mfu", "decode.step_ms",
       "decode.hbm_roofline")
NEW = ("engine.step_idle_ms", "decode.plumbing_ms", "decode.attn_ms", "decode.mlp_ms")


def _path(name):
    path = os.path.join(DATA, name)
    if not os.path.exists(path):
        pytest.fail(f"missing recorded trace {path}")
    return path


def _meta(name):
    """What ``bench/kinds/serve.py`` would have handed the readers of a
    recorded trace: its record and configuration, where kept beside it."""
    p = os.path.join(DATA, name.replace(".xplane.pb", ".json"))
    if not os.path.exists(p):
        return {}, {}
    with open(p) as f:
        m = json.load(f)
    return m["record"], m["c"]


def _read(names, trace, record, c):
    lo, hi = trace.window()
    r = harness.Reading(trace, lo, hi, record, c, peaks.peak_for("TPU v5 lite"))
    return {n: harness.load_module(os.path.join(METRICS, n + ".py")).read(r) for n in names}


@pytest.fixture
def as_harness(tmp_path, monkeypatch):
    """Put a recorded trace where the harness writes a run's trace, and
    return the harness's reduction of it."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))

    def put(name):
        d = tmp_path / "bench_trace_recorded" / "plugins" / "profile"
        d.mkdir(parents=True)
        shutil.copy(_path(name), d)
        return tr.load(str(d / name), harness.ANNOTATIONS)

    return put


def test_scope_time_splits_by_innermost_scope_and_skips_containers():
    ops = [tr.Op(0.0, 1.0, "fusion.0", "fusion"),                 # before the execution
           tr.Op(2.0, 10.0, "while.1", "while"),
           tr.Op(2.0, 3.0, "fusion.1", "fusion"), tr.Op(3.0, 5.0, "copy.2", "copy"),
           tr.Op(5.0, 6.5, "fusion.3", "fusion"), tr.Op(7.0, 8.0, "fusion.4", "fusion"),
           tr.Op(8.0, 9.0, "fusion.9", "fusion"),
           tr.Op(11.0, 12.0, "fusion.1", "fusion")]              # another program's
    dev = tr.Device(0, ops, [(2.0, 10.0, "jit__decode"), (11.0, 12.0, "jit__other")])
    scopes = {"fusion.1": "attn", "copy.2": pt.UNSCOPED, "fusion.3": "mlp", "fusion.4": "attn",
              "while.1": "layers"}
    got = pt.scope_time(dev, "jit__decode", 0.0, 20.0, scopes)
    assert got == {"attn": 2.0, pt.UNSCOPED: 2.0, "mlp": 1.5, pt.MISSING: 1.0}
    assert pt.scope_time(dev, "jit__decode", 2.5, 20.0, scopes) == {}     # it starts before lo


@pytest.mark.parametrize("name", ["serve_tiny.xplane.pb", "serve_spans_tiny.xplane.pb"])
def test_engine_spans_change_no_existing_reading(name):
    """Reduced with the engine's spans beside the harness's annotations, a
    trace gives the harness's window and its five readers the same values."""
    record, c = _meta(name)
    old = tr.load(_path(name), harness.ANNOTATIONS)
    ext = tr.load(_path(name), harness.ANNOTATIONS + pt.ENGINE_SPANS)
    assert old.window() == ext.window()
    assert _read(OLD, old, record, c) == _read(OLD, ext, record, c)


def test_a_program_without_marks_gives_nothing_to_read(as_harness):
    """The recorded trace of a program with no engine spans and no scopes."""
    t = as_harness("serve_tiny.xplane.pb")
    assert _read(NEW, t, {}, {}) == {n: None for n in NEW}


def test_the_profilers_hlo_names_every_executed_instruction():
    path = _path("serve_tiny.xplane.pb")
    t = tr.load(path, harness.ANNOTATIONS)
    lo, hi = t.window()
    ran = {o.name for o in pt.executed_ops(t.devices[0], pt.DECODE, lo, hi)}
    scopes = pt.program_scopes(path, pt.DECODE, ran)
    assert ran and ran <= scopes.keys()


def test_readers_of_a_recorded_chip_trace_with_spans(as_harness):
    name = "serve_spans_tiny.xplane.pb"
    record, c = _meta(name)
    t = as_harness(name)
    got = _read(NEW, t, record, c)
    assert all(v is not None and v > 0 for v in got.values()), got

    lo, hi = t.window()
    dev = t.devices[0]
    p = pt.of(harness.Reading(t, lo, hi, record, c, None))
    with open(os.path.join(DATA, "serve_spans_tiny.json")) as f:
        assert p.decode_scopes == json.load(f)["decode_scopes"]     # the trace's HLO is the compiled text's
    by = pt.scope_time(dev, pt.DECODE, lo, hi, p.decode_scopes)
    assert pt.MISSING not in by
    n = len(tr.executions(dev, pt.DECODE, lo, hi))
    assert got["decode.plumbing_ms"] + got["decode.attn_ms"] + got["decode.mlp_ms"] + 1e3 / n * sum(
        v for k, v in by.items() if k not in ("attn", "kv_write", "mlp") + pt.PLUMBING) \
        == pytest.approx(1e3 / n * sum(by.values()))
    busy = tr.total(tr.union((o.start, o.end) for o in pt.executed_ops(dev, pt.DECODE, lo, hi)))
    assert sum(by.values()) == pytest.approx(busy, rel=0.01)      # no operation counted twice

    waves = p.spans(("engine.wave",))
    assert len(waves) == len(record["waves"])
    for s, e, _ in p.trace.spans:
        assert s < e
    for s, e, n_ in p.trace.spans:
        if n_ in pt.ENGINE_SPANS:
            assert any(a <= s and e <= b for a, b in waves), n_
    assert len([1 for _, _, n_ in p.trace.spans if n_ == "engine.dispatch"]) == n
