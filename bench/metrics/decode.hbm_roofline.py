"""The decode program's share of its roofline: for every decode step of the
traced waves, the least time the chip could take for it, the larger of its
least FLOPs over peak FLOP/s and its least HBM bytes (weights as stored,
plus each live row's live KV) over peak bandwidth, summed and divided by
the device time of the same executions; the least FLOPs and bytes are
``decode_least`` of the configuration's architecture module.  The program
stands for its kernels until they carry names of their own."""
from bench import harness
from bench import trace_reduce as tr

DECODE = "jit__decode"


def step_contexts(prompts, outs):
    """Live rows' contexts at each decode step of one wave."""
    steps = max(outs) - 1
    return [[P + s for P, o in zip(prompts, outs) if o > s] for s in range(1, steps + 1)]


def read(r):
    if r.peak is None or not r.record.get("waves"):
        return None
    dev = r.trace.devices[min(r.trace.devices)]
    execs = tr.executions(dev, DECODE, r.lo, r.hi)
    ctxs = [ctx for w in r.record["waves"] for ctx in step_contexts(w["prompt"], w["out"])]
    if not execs or len(execs) != len(ctxs):
        return None
    decode_least = harness.arch(r.c).decode_least
    least = 0.0
    for ctx in ctxs:
        need = decode_least(r.c, ctx)
        least += max(need["flops"] / r.peak.flops_bf16, need["bytes"] / r.peak.hbm_bytes_per_s)
    return 100.0 * least / sum(e - s for s, e in execs)
