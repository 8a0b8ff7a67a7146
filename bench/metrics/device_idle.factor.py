"""Device idle share of the traced window of a factor loop, in percent:
1 - (union of the device's op intervals) / (window length), averaged
over the chips used; a used chip that ran no op counts as idle."""
from bench import tracing


def read(ctx):
    tr = ctx["trace"]
    if not tr.span_list("bench.factor"):
        return None
    lo, hi = tr.window()
    return 100.0 * (1.0 - tracing.busy_ns(tr, lo, hi, ctx["chips"])
                    / (hi - lo))
