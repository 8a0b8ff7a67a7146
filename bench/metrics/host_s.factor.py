"""Host seconds per factor: the length of each ``bench.factor`` span less
the device-busy time inside it, averaged over the factors traced.  It is
the host staging of ``OOCSolver.factor``: tiling, widening, casting and
the host<->device copies."""
from bench import tracing


def read(ctx):
    tr = ctx["trace"]
    spans = tr.span_list("bench.factor")
    if not spans:
        return None
    host = [(e - s) - tracing.busy_ns(tr, s, e) for s, e in spans]
    return sum(host) / len(host) / 1e9
