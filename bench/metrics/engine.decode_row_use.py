"""Share of the decode program's rows that delivered a token a request
asked for: output tokens produced by decode steps in the traced waves, over
(executions of the decode program in the device trace x batch size).  The
rest is the waste of lockstep waves: empty slots and rows that finished."""
from bench import trace_reduce as tr

DECODE = "jit__decode"


def read(r):
    dev = r.trace.devices[min(r.trace.devices)]
    execs = tr.executions(dev, DECODE, r.lo, r.hi)
    if not execs or not r.record.get("waves"):
        return None
    delivered = sum(o - 1 for w in r.record["waves"] for o in w["out"])
    return 100.0 * delivered / (len(execs) * r.record["batch_size"])
