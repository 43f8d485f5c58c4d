"""The dense and expert layers' share of their roofline in the decode
program: for every decode step of the traced waves, the least time of the
leading dense MLP, the routers, the shared experts and the held experts the
step's live rows touch (the larger of least FLOPs over peak FLOP/s and
least bytes, those weights in the compute dtype, over peak bandwidth),
summed and divided by the device time of the same executions under the
scope ``mlp`` (``models/moe.py`` ``moe_block``, ``models/layers.py``
``mlp``).  The held experts a step touches are not counted on the device
(that would add a fetch to the engine's per-step loop): they are taken at
their expectation under uniform routing from the step's live rows,
held·(1 − (1 − k/E)^rows), which is 8·(1 − (58/64)^rows) for Moonlight's
share.  The least work is ``moe_least`` of the configuration's architecture
module; an architecture without it reads nothing."""
import os

from bench import harness
from bench import program_trace as pt
from bench import trace_reduce as tr

_steps = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                          "decode.hbm_roofline.py")).step_contexts


def read(r):
    least_of = getattr(harness.arch(r.c), "moe_least", None)
    if least_of is None or r.peak is None or not r.record.get("waves"):
        return None
    ms = pt.decode_ms(r)
    dev = r.trace.devices[min(r.trace.devices)]
    n = len(tr.executions(dev, pt.DECODE, r.lo, r.hi))
    ctxs = [ctx for w in r.record["waves"] for ctx in _steps(w["prompt"], w["out"])]
    if ms is None or not n or n != len(ctxs):
        return None
    spent = 1e-3 * n * ms.get("mlp", 0.0)
    least = 0.0
    for ctx in ctxs:
        need = least_of(r.c, len(ctx))
        least += max(need["flops"] / r.peak.flops_bf16, need["bytes"] / r.peak.hbm_bytes_per_s)
    return 100.0 * least / spent if spent > 0 else None
