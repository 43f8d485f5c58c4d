"""Mean device time of one execution of the decode program
(``models/transformer.py`` ``decode_step`` under the engine's jit), from the
``XLA Modules`` line of the device trace."""
from bench import trace_reduce as tr

DECODE = "jit__decode"


def read(r):
    dev = r.trace.devices[min(r.trace.devices)]
    execs = tr.executions(dev, DECODE, r.lo, r.hi)
    if not execs:
        return None
    return 1e3 * sum(e - s for s, e in execs) / len(execs)
