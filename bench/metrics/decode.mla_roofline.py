"""Latent attention's share of its roofline in the decode program: for
every decode step of the traced waves, the least time of its absorbed
attention (all layers; the larger of least FLOPs over peak FLOP/s and least
bytes, the attention's weights in the compute dtype plus each live row's
live latent cache, over peak bandwidth), summed and divided by the device
time of the same executions under the scopes ``attn`` and ``kv_write``
(``models/mla.py`` ``mla_decode``).  The least work is ``mla_least`` of the
configuration's architecture module; an architecture without it reads
nothing."""
import os

from bench import harness
from bench import program_trace as pt
from bench import trace_reduce as tr

_steps = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                          "decode.hbm_roofline.py")).step_contexts


def read(r):
    least_of = getattr(harness.arch(r.c), "mla_least", None)
    if least_of is None or r.peak is None or not r.record.get("waves"):
        return None
    ms = pt.decode_ms(r)
    dev = r.trace.devices[min(r.trace.devices)]
    n = len(tr.executions(dev, pt.DECODE, r.lo, r.hi))
    ctxs = [ctx for w in r.record["waves"] for ctx in _steps(w["prompt"], w["out"])]
    if ms is None or not n or n != len(ctxs):
        return None
    spent = 1e-3 * n * (ms.get("attn", 0.0) + ms.get("kv_write", 0.0))
    least = 0.0
    for ctx in ctxs:
        need = least_of(r.c, ctx)
        least += max(need["flops"] / r.peak.flops_bf16, need["bytes"] / r.peak.hbm_bytes_per_s)
    return 100.0 * least / spent if spent > 0 else None
