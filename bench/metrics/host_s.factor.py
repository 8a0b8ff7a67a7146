"""Host seconds per factor: the length of each ``bench.factor`` span less
the time inside it in which at least one chip used was busy, averaged
over the factors traced.  It is the host staging of
``OOCSolver.factor``: tiling, widening, casting and the host<->device
copies."""
from bench import tracing


def read(ctx):
    tr = ctx["trace"]
    spans = tr.span_list("bench.factor")
    if not spans:
        return None
    host = [(e - s) - tracing.any_busy_ns(tr, s, e, ctx["chips"])
            for s, e in spans]
    return sum(host) / len(host) / 1e9
