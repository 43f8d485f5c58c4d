"""Device-idle milliseconds per decode step spent in the serving engine's
host loop: idle device time inside the engine's per-step spans
(``engine.dispatch``: the RNG split, the position scalar and the decode
call; ``engine.token_sync``: the blocking read of the step's tokens;
``engine.bookkeeping``: the per-row Python loop), over the executions of
the decode program in the traced sub-window."""
from bench import program_trace as pt
from bench import trace_reduce as tr


def read(r):
    p = pt.of(r)
    if p is None:
        return None
    steps = tr.clip(p.spans(pt.STEP_SPANS), r.lo, r.hi)
    dev = r.trace.devices[min(r.trace.devices)]
    n = len(tr.executions(dev, pt.DECODE, r.lo, r.hi))
    if not steps or not n:
        return None
    idle = tr.total(steps) - tr.intersect_total(steps, tr.busy(dev, r.lo, r.hi))
    return 1e3 * idle / n
