import os

import pytest

from bench import harness
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_parse_op_reads_name_and_opcode():
    assert tr.parse_op("%fusion.12 = bf16[1024]{0:T(1024)} fusion(bf16[1024,1024]{1,0} %x), kind=kOutput") \
        == ("fusion.12", "fusion")
    assert tr.parse_op("%copy-start = (bf16[8]{0}, bf16[8]{0:S(1)}, u32[]{:S(2)}) copy-start(bf16[8]{0} %x)") \
        == ("copy-start", "copy-start")
    assert tr.Op(0, 1, *tr.parse_op("%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b")).container


def test_a_loop_counts_through_its_body():
    dev = tr.Device(0, [tr.Op(0.0, 10.0, "while.1", "while"), tr.Op(1.0, 2.0, "fusion.1", "fusion"),
                        tr.Op(7.0, 8.0, "fusion.2", "fusion")], [(0.0, 10.0, "jit__decode")])
    t = tr.Trace({0: dev}, [(0.0, 10.0, "serve_wave")])
    assert tr.top_ops(t, 0, 10) == [["jit__decode/fusion.1", 1.0], ["jit__decode/fusion.2", 1.0]]
    assert tr.total(tr.busy(dev, 0, 10)) == 10.0


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (5, 6), (9, 12)]) == [(0, 1), (2, 5), (6, 9)]
    assert tr.intersect_total([(0, 10)], [(1, 2), (5, 7)]) == 3
    assert tr.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def _load(name):
    path = os.path.join(DATA, name)
    if not os.path.exists(path):
        pytest.fail(f"missing recorded trace {path}")
    return tr.load(path, harness.ANNOTATIONS)


def test_serving_trace_recorded_on_the_chip():
    t = _load("serve_tiny.xplane.pb")
    assert list(t.devices) == [0]
    lo, hi = t.window()
    dev = t.devices[0]
    decode = tr.executions(dev, "jit__decode", lo, hi)
    assert decode and all(e > s for s, e in decode)
    busy = tr.total(tr.busy(dev, lo, hi))
    assert 0 < busy <= hi - lo
    assert {n for _, _, n in t.spans} >= {"serve_wave"}
    idle = sum(v for _, v in tr.idle_by_host(t, lo, hi, n=100))
    assert abs(idle - ((hi - lo) - busy)) < 1e-6
    ops = tr.top_ops(t, lo, hi)
    assert 0 < len(ops) <= 10 and ops[0][1] >= ops[-1][1]
