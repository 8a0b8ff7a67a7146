"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Two kinds of interval come out of a trace:

* device ops: the events of the ``XLA Ops`` line of each ``/device:``
  plane.  A trace recorded on the CPU has no such plane; only where the
  caller asks for it (``host_ops=True``, in the tests) do the events that
  carry an ``hlo_op`` stat on the host threads stand in for them, each
  under ``/cpu:<device ordinal>``.  A trace with no device ops otherwise
  raises;
* spans: the benchmark's own ``jax.profiler.TraceAnnotation`` events,
  named ``bench.<what>``, on the host plane.

JAX's profiler puts the host and device events on one clock, so a span's
interval can be intersected with the device ops directly.

A run uses the chips of ordinals ``0 .. chips - 1``.  The busy times and
the op totals are taken over those chips alone: a used chip whose plane
has no op line counts as idle, and a plane of any other device is left
out.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
_ORDINAL = re.compile(r":(\d+)$")


@dataclasses.dataclass
class Trace:
    ops: dict        # device name -> sorted [(start_ns, end_ns, op name)]
    spans: list      # sorted [(start_ns, end_ns, span name)]

    def span_list(self, name: str) -> list:
        return [(s, e) for s, e, n in self.spans if n == name]

    def window(self) -> tuple[float, float]:
        w = self.span_list(SPAN_PREFIX + "window")
        if len(w) != 1:
            raise ValueError(f"expected one {SPAN_PREFIX}window span, "
                             f"found {len(w)}")
        return w[0]


def latest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, host_ops: bool = False) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict = defaultdict(list)
    spans = []
    on_host: dict = defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] += [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, end, e.name))
                        continue
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        dev = f"/cpu:{stats.get('device_ordinal', 0)}"
                        on_host[dev].append((e.start_ns, end, e.name))
    if not ops:
        if not host_ops:
            raise ValueError(f"{path}: no XLA Ops line on any /device: "
                             f"plane")
        ops = on_host
    return Trace({d: sorted(v) for d, v in ops.items()}, sorted(spans))


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]`` covering the given intervals."""
    out: list = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def covered(merged, lo, hi) -> float:
    """Nanoseconds of ``[lo, hi]`` covered by the merged intervals."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def chip_ops(ops: dict, chips: int) -> list:
    """The op lists of ``ops`` (device -> sorted events) of the chips a run
    used, by ordinal ``0 .. chips - 1`` (``/device:TPU:<ordinal>``,
    ``/cpu:<ordinal>``): an empty list for a chip with no op line; the
    planes of other devices are left out."""
    by = defaultdict(list)
    for dev, v in ops.items():
        m = _ORDINAL.search(dev)
        if m:
            by[int(m.group(1))] += v
    return [sorted(by[i], key=lambda ev: ev[:2]) for i in range(chips)]


def chip_busy_ns(trace: Trace, lo: float, hi: float, chips: int) -> float:
    """Device-busy nanoseconds in ``[lo, hi]`` summed over the chips used:
    the chip-time spent."""
    return sum(covered(union(v), lo, hi)
               for v in chip_ops(trace.ops, chips))


def busy_ns(trace: Trace, lo: float, hi: float, chips: int) -> float:
    """Device-busy nanoseconds in ``[lo, hi]``, averaged over the chips
    used."""
    return chip_busy_ns(trace, lo, hi, chips) / chips


def any_busy_ns(trace: Trace, lo: float, hi: float, chips: int) -> float:
    """Nanoseconds of ``[lo, hi]`` in which at least one chip used was
    busy."""
    ops = chip_ops(trace.ops, chips)
    return covered(union(iv for v in ops for iv in v), lo, hi)


def top_ops(trace: Trace, lo: float, hi: float, chips: int,
            k: int = 10) -> list:
    """``[[op name, seconds]]`` of the ``k`` ops that took most device time
    in ``[lo, hi]``, summed over their runs and averaged over the chips
    used."""
    tot: dict = defaultdict(float)
    for v in chip_ops(trace.ops, chips):
        for s, e, name in v:
            if e > lo and s < hi:
                tot[name] += min(e, hi) - max(s, lo)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / chips / 1e9] for name, ns in best]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """``[[label, seconds]]`` of the ``k`` longest stretches of ``[lo, hi]``
    in which no device ran an op.  A gap is cut at the edges of the
    benchmark's call spans and labelled by where the host was: inside a
    ``bench.<call>`` span, before its first device op
    (``<call>.before_first_op``), between two (``<call>.between_ops``),
    after its last (``<call>.after_last_op``), or in a span with none
    (``<call>.no_device_op``); outside every call span,
    ``window.between_calls``."""
    busy = union(iv for v in trace.ops.values() for iv in v)
    calls = [(s, e, n) for s, e, n in trace.spans
             if n != SPAN_PREFIX + "window" and e > lo and s < hi]
    # the call spans and the stretches between them partition [lo, hi]
    pieces = []
    t = lo
    for s, e, n in calls:
        s, e = max(s, lo), min(e, hi)
        if s > t:
            pieces.append((t, s, None))
        pieces.append((s, e, n[len(SPAN_PREFIX):]))
        t = max(t, e)
    if t < hi:
        pieces.append((t, hi, None))
    gaps = []
    for s, e, call in pieces:
        inside = clip(busy, s, e)
        free = []
        t = s
        for bs, be in inside:
            if bs > t:
                free.append((t, bs))
            t = max(t, be)
        if t < e:
            free.append((t, e))
        for fs, fe in free:
            if call is None:
                label = "window.between_calls"
            elif not inside:
                label = f"{call}.no_device_op"
            elif fe <= inside[0][0]:
                label = f"{call}.before_first_op"
            elif fs >= inside[-1][1]:
                label = f"{call}.after_last_op"
            else:
                label = f"{call}.between_ops"
            gaps.append((fe - fs, label))
    gaps.sort(key=lambda g: -g[0])
    return [[label, ns / 1e9] for ns, label in gaps[:k]]
