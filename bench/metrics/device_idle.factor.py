"""Device idle share of the traced window of a factor loop, in percent:
1 - (union of the device's op intervals) / (window length), averaged
over the chips used."""
from bench import tracing


def read(ctx):
    tr = ctx["trace"]
    if not tr.span_list("bench.factor"):
        return None
    lo, hi = tr.window()
    return 100.0 * (1.0 - tracing.busy_ns(tr, lo, hi) / (hi - lo))
