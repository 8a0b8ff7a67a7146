"""Share of the roofline reached by the device work of a factor, in
percent: the least time of the tiled algorithm on one chip
(``bench.work.least_seconds``, from ``nt``, ``tb`` and the class map
alone) times the factors traced, over the chip-seconds spent inside the
``bench.factor`` spans (the busy time summed over the chips used).  On
``ndev`` chips the least chip-time of the work is the same as on one,
so the share means the same on one chip and on four."""
from bench import tracing, work


def read(ctx):
    tr = ctx["trace"]
    spans = tr.span_list("bench.factor")
    busy = sum(tracing.chip_busy_ns(tr, s, e, ctx["chips"])
               for s, e in spans) / 1e9
    if not spans or busy <= 0.0:
        return None
    cfg = ctx["config"]
    nt = cfg["n"] // cfg["tb"]
    least = work.least_seconds(nt, cfg["tb"], ctx["classes"],
                               ctx["device_kind"])
    return 100.0 * least * len(spans) / busy
