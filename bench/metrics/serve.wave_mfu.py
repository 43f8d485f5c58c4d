"""Model FLOPs of the traced waves' useful work, over (the ``serve_wave``
spans' time x the chip's peak): each request's own prompt (not its
padding) and its output tokens, each attending to its live context only
(``forward_flops`` of the configuration's architecture module).  The whole
serve step's share of the chip's peak."""
from bench import costs, harness
from bench import trace_reduce as tr


def wave_flops(c, prompts, outs):
    forward_flops = harness.arch(c).forward_flops
    total = 0.0
    for P, o in zip(prompts, outs):
        total += forward_flops(c, P, costs.causal_sum(0, P), 1)
        total += forward_flops(c, o - 1, costs.causal_sum(P, o - 1), o - 1)
    return total


def read(r):
    if r.peak is None or not r.record.get("waves"):
        return None
    span = tr.total(tr.union((s, e) for s, e, n in r.trace.spans if n == "serve_wave"))
    if span <= 0:
        return None
    flops = sum(wave_flops(r.c, w["prompt"], w["out"]) for w in r.record["waves"])
    return 100.0 * flops / (span * r.peak.flops_bf16)
