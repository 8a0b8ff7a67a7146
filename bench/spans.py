"""The program's own spans and scopes in a traced run.

``bench/tracing.py`` keeps the benchmark's ``bench.`` spans and the
device ops by HLO name.  This module reads the same ``.xplane.pb`` for
what the program under test puts there:

* host spans named ``repro.<what>``, each with its args (``bytes``,
  ``call``): ``OOCSolver.factor`` and its phases;
* the device ops of each ``/device:`` plane, each with the innermost
  executor scope (``<kind>.<class>``, ``round.<class>``,
  ``column.<k>``) of the op that made it, or None for an op made outside
  every scope (a copy that XLA inserted, say).

The scope of a device op is the innermost scope in its ``/``-separated op
name (``jit(traced)/store.bf16/scatter:``).  The op's event does not
carry that name.  A TPU trace keeps it in the ``tf_op`` stat of the
event's metadata on the ``/device:`` plane; a CPU trace only in the HLO
module in the ``/host:metadata`` plane, whose instructions' metadata
hold it.  Both are read from the raw protobuf, which ``ProfileData``
does not show.  A trace of a program without spans or scopes reads as
empty: the metrics that read it then report nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
TRACES = ROOT / ".bench_cache" / "trace"
PREFIX = "repro."
_HLO_NAME = re.compile(r"%?([\w.\-]+)")


def parse_scope(path: str):
    """The program's own ``repro.obs.timed.parse_scope``; None where the
    program has none, as before its scopes existed."""
    try:
        from repro.obs.timed import parse_scope as parse
    except ImportError:
        return None
    return parse(path)


@dataclasses.dataclass
class Spans:
    spans: list     # sorted [(start_ns, end_ns, name, {arg: value})]
    ops: dict       # device -> sorted [(start_ns, end_ns, hlo name, scope)]
    scoped: bool    # some device op was found inside an executor scope

    def named(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> list:
        """``[(start, end, args)]`` of the spans ``name`` inside
        ``[lo, hi]``."""
        return [(s, e, a) for s, e, n, a in self.spans
                if n == name and s >= lo and e <= hi]


# -- the protobuf wire format, for the parts ProfileData does not show ----

def _varint(buf, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def fields(buf):
    """``(field number, value)`` of each field of one message: a varint as
    an int, a length-delimited field as a memoryview."""
    buf = memoryview(buf)
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wire == 1:
            val, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            val, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ids(v) -> list:
    """A repeated int64 field: one varint, or a packed run of them."""
    if isinstance(v, int):
        return [v]
    out, pos = [], 0
    while pos < len(v):
        x, pos = _varint(v, pos)
        out.append(x)
    return out


def _instruction(blob) -> dict:
    inst = {"name": "", "opcode": "", "op_name": "", "id": None,
            "operands": [], "calls": []}
    for k, w in fields(blob):
        if k == 1:                      # HloInstructionProto.name
            inst["name"] = _text(w)
        elif k == 2:                    # .opcode
            inst["opcode"] = _text(w)
        elif k == 7:                    # .metadata
            for m, x in fields(w):
                if m == 2:              # OpMetadata.op_name
                    inst["op_name"] = _text(x)
        elif k == 35:                   # .id
            inst["id"] = w
        elif k == 36:                   # .operand_ids
            inst["operands"] += _ids(w)
        elif k == 38:                   # .called_computation_ids
            inst["calls"] += _ids(w)
    return inst


def hlo_op_names(hlo_proto) -> dict:
    """``{instruction name: op name}`` of a serialized ``xla.HloProto``, a
    fusion's instructions included.

    XLA makes some instructions with no op name: where several fusions
    each read a convert of one slice of the same array, it converts the
    whole array once and leaves a slice in each fusion.  Such an
    instruction takes the op name of the instructions of its own opcode
    inside the fusions that use it, where all of those lie in one
    executor scope; else it stays without one."""
    comps = {}
    for f, mod in fields(hlo_proto):
        if f != 1:                      # HloProto.hlo_module
            continue
        for g, v in fields(mod):
            if g == 3:                  # HloModuleProto.computations
                cid, insts = None, []
                for h, w in fields(v):
                    if h == 2:          # HloComputationProto.instructions
                        insts.append(_instruction(w))
                    elif h == 5:        # .id
                        cid = w
                comps[cid] = insts
    names = {i["name"]: i["op_name"] for insts in comps.values()
             for i in insts if i["op_name"]}
    for insts in comps.values():
        users = defaultdict(list)
        for i in insts:
            for o in i["operands"]:
                users[o].append(i)
        for i in insts:
            if i["op_name"] or i["id"] not in users:
                continue
            found = {j["op_name"] for u in users[i["id"]]
                     for c in u["calls"] for j in comps.get(c, ())
                     if j["opcode"] == i["opcode"] and j["op_name"]}
            scopes = {parse_scope(n) for n in found}
            if len(scopes) == 1 and None not in scopes:
                names[i["name"]] = min(found)
    return names


def _map_value(entry):
    """The value of one entry of a protobuf map field."""
    for k, v in fields(entry):
        if k == 2:
            return v
    return b""


def _stat_values(meta, stat_names: dict) -> dict:
    """``{stat name: value}`` of an ``XEventMetadata``: a number, a
    string, or the bytes of a serialized message."""
    out = {}
    for k, w in fields(meta):
        if k != 5:                      # XEventMetadata.stats
            continue
        st = dict(fields(w))
        name = stat_names.get(st.pop(1, None))
        if 7 in st:                     # XStat.ref_value
            out[name] = stat_names.get(st[7], "")
        elif 5 in st:                   # .str_value
            out[name] = _text(st[5])
        elif st:                        # a number, or .bytes_value
            out[name] = next(iter(st.values()))
    return out


def trace_op_names(path: str) -> tuple:
    """``(by_event, by_program)`` from the metadata of the ``.xplane.pb``
    at ``path``: ``{plane: {event name: (op name, program id)}}`` from the
    ``tf_op`` and ``program_id`` stats of each ``/device:`` plane's event
    metadata, and ``{program id: {instruction name: op name}}`` from the
    HLO protos in the ``/host:metadata`` plane, one per program."""
    raw = Path(path).read_bytes()
    by_event: dict = {}
    by_program: dict = {}
    for f, plane in fields(raw):
        if f != 1:                      # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for g, v in fields(plane):
            if g == 2:                  # XPlane.name
                name = _text(v)
            elif g == 4:                # .event_metadata (map entry)
                metas.append(_map_value(v))
            elif g == 5:                # .stat_metadata (map entry)
                sm = dict(fields(_map_value(v)))
                stat_names[sm.get(1)] = _text(sm.get(2, b""))
        if name.startswith("/device:"):
            ops = by_event.setdefault(name, {})
            for meta in metas:
                st = _stat_values(meta, stat_names)
                event = _text(dict(fields(meta)).get(2, b""))
                ops[event] = (st.get("tf_op", ""), st.get("program_id"))
        elif name == "/host:metadata":
            for meta in metas:
                proto = _stat_values(meta, stat_names).get("Hlo Proto")
                if not isinstance(proto, memoryview):
                    continue
                try:
                    names = hlo_op_names(proto)
                except (IndexError, ValueError):
                    continue            # not an HloProto
                by_program[dict(fields(meta)).get(1)] = names
    return by_event, by_program


def hlo_name(event_name: str) -> str:
    """The instruction name of a device op event: ``fusion.12`` of
    ``%fusion.12 = f32[...] fusion(...)`` or of ``fusion.12``."""
    m = _HLO_NAME.match(event_name.strip())
    return m.group(1) if m else event_name


def latest_xplane(log_dir=None) -> str | None:
    log_dir = TRACES if log_dir is None else log_dir
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Spans:
    """The ``repro.`` spans and the scoped device ops of one trace.  In a
    trace with no ``/device:`` plane (one recorded on the CPU) the host
    events with an ``hlo_op`` stat stand in for device ops."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans = []
    ops: dict = defaultdict(list)     # device -> [(s, e, name, stats)]
    on_host: dict = defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] += [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(PREFIX):
                        spans.append((e.start_ns, end, e.name,
                                      dict(e.stats)))
                    else:
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            dev = f"/cpu:{st.get('device_ordinal', 0)}"
                            on_host[dev].append((e.start_ns, end, e.name,
                                                 st))
    if not ops:
        ops = on_host
    by_event, by_program = trace_op_names(path)
    scoped_ops: dict = {}
    found = False
    for dev, evs in ops.items():
        named = by_event.get(dev, {})
        out = []
        for s, e, name, st in evs:
            op_name, program = named.get(name, ("", None))
            hlo = by_program.get(st.get("program_id", program), {})
            scope = (parse_scope(op_name) or parse_scope(
                hlo.get(st.get("hlo_op") or hlo_name(name), "")))
            found |= scope is not None
            out.append((s, e, name, scope))
        scoped_ops[dev] = sorted(out)
    spans.sort(key=lambda t: (t[0], -t[1]))
    return Spans(spans, scoped_ops, found)


_LOADED: dict = {}


def current() -> Spans | None:
    """The newest trace under ``.bench_cache/trace`` (the traced run's
    own: ``run.py`` clears the cell's directory first), parsed once per
    process; None where there is no trace."""
    path = latest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path)
    return _LOADED[key]


# -- what the per-layer metrics read ---------------------------------------

FACTOR = PREFIX + "factor"


def factors(ctx: dict):
    """``(spans, [(start, end, args)])``: the program's spans and its
    ``repro.factor`` spans inside the traced window; None where the
    trace has none."""
    sp = current()
    if sp is None:
        return None
    lo, hi = ctx["trace"].window()
    calls = sp.named(FACTOR, lo, hi)
    return (sp, calls) if calls else None


def phase_s(ctx: dict, phases) -> float | None:
    """Seconds per factor in the ``repro.factor.<phase>`` spans of
    ``phases``, summed within each factor and averaged over the
    factors."""
    got = factors(ctx)
    if got is None:
        return None
    sp, calls = got
    total, seen = 0, False
    for s, e, _ in calls:
        for ph in phases:
            for a, b, _args in sp.named(f"{FACTOR}.{ph}", s, e):
                total += b - a
                seen = True
    return total / len(calls) / 1e9 if seen else None


def link_gbs(ctx: dict, phase: str) -> float | None:
    """GB/s of the ``repro.factor.<phase>`` spans inside the factors:
    their ``bytes`` over their seconds, both summed."""
    got = factors(ctx)
    if got is None:
        return None
    sp, calls = got
    nbytes = ns = 0
    for s, e, _ in calls:
        for a, b, args in sp.named(f"{FACTOR}.{phase}", s, e):
            nbytes += args.get("bytes", 0)
            ns += b - a
    return nbytes / ns if ns > 0 and nbytes > 0 else None


def scope_busy_s(ctx: dict, kind: str) -> float | None:
    """Device-busy seconds per factor of the ops whose innermost scope
    is ``<kind>.*``, averaged over the chips used; None where no op of
    the trace carries a scope."""
    from bench import tracing
    got = factors(ctx)
    if got is None or not got[0].scoped:
        return None
    sp, calls = got
    busy = 0.0
    for ops in tracing.chip_ops(sp.ops, ctx["chips"]):
        mine = tracing.union((s, e) for s, e, _, scope in ops
                             if scope and scope.startswith(kind + "."))
        busy += sum(tracing.covered(mine, s, e) for s, e, _ in calls)
    return busy / ctx["chips"] / len(calls) / 1e9


def scope_breakdown(sp: Spans, lo: float, hi: float, chips: int) -> dict:
    """Device-busy seconds in ``[lo, hi]`` by innermost scope, an op with
    none under ``unscoped:<hlo name>``, averaged over the chips used.  An
    op overlapping another is cut to the stretch no earlier op covered,
    so the parts add up to the device-busy time."""
    from bench import tracing
    out: dict = defaultdict(float)
    for ops in tracing.chip_ops(sp.ops, chips):
        t = lo
        for s, e, name, scope in ops:
            s, e = max(s, t), min(e, hi)
            if e <= s:
                continue
            out[scope or f"unscoped:{hlo_name(name)}"] += (e - s) / 1e9
            t = e
    return {k: v / chips for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


# -- a report of one traced run, for PERF.md -------------------------------

def report(path: str, chips: int, host_ops: bool = False) -> dict:
    """Each ``bench.factor`` span of the trace at ``path``, of a run on
    ``chips`` chips: its seconds, the ``repro.factor`` phases in it with
    their seconds and bytes, the share of the span they cover, its
    device-busy seconds and the sum of their split by scope (both
    averaged over the chips); and that split averaged over the
    factors."""
    from bench import tracing
    sp, tr = load(path), tracing.load(path, host_ops=host_ops)
    factors, by_scope = [], defaultdict(float)
    for lo, hi in tr.span_list(tracing.SPAN_PREFIX + "factor"):
        phases = [(n[len(FACTOR) + 1:], (e - s) / 1e9, a.get("bytes"))
                  for s, e, n, a in sp.spans
                  if n.startswith(FACTOR + ".") and lo <= s and e <= hi]
        parts = scope_breakdown(sp, lo, hi, chips)
        for k, v in parts.items():
            by_scope[k] += v
        factors.append({
            "s": (hi - lo) / 1e9, "phases": phases,
            "covered": sum(p[1] for p in phases) / ((hi - lo) / 1e9),
            "busy_s": tracing.busy_ns(tr, lo, hi, chips) / 1e9,
            "by_scope_sum_s": sum(parts.values())})
    n = max(1, len(factors))
    return {"factors": factors,
            "by_scope_s": {k: v / n for k, v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])}}


if __name__ == "__main__":
    # PYTHONPATH=src python -m bench.spans [XPLANE [CHIPS]]: the newest
    # trace under .bench_cache/trace, of a run on one chip, by default
    import json
    import sys
    print(json.dumps(report(sys.argv[1] if len(sys.argv) > 1
                            else latest_xplane(),
                            int(sys.argv[2]) if len(sys.argv) > 2 else 1),
                     indent=1))
