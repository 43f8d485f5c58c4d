"""Share of the serving engine's wave time in which no operation runs on
the device: the host's own work inside ``ServingEngine.serve`` (padding,
sampling hand-off, the per-token sync, bookkeeping), over the
``serve_wave`` spans of the traced sub-window."""
from bench import trace_reduce as tr


def read(r):
    waves = tr.union((s, e) for s, e, n in r.trace.spans if n == "serve_wave")
    span = tr.total(waves)
    if span <= 0:
        return None
    dev = r.trace.devices[min(r.trace.devices)]
    busy = tr.intersect_total(waves, tr.busy(dev, r.lo, r.hi))
    return 100.0 * (span - busy) / span
