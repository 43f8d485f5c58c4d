"""Device milliseconds per execution of the decode program in operations
under no model scope but the layer scan's own (``layers``) or under none:
the scan's slicing of per-layer weights and cache and its stacking of the
new cache, and the copies and casts XLA adds without a scope.  Scopes are
read from the program's HLO in the trace (``bench/program_trace.py``)."""
from bench import program_trace as pt


def read(r):
    ms = pt.decode_ms(r)
    return None if ms is None else sum(ms.get(k, 0.0) for k in pt.PLUMBING)
