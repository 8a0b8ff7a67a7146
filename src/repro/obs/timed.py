"""Spans and scopes on the timed path, for ``jax.profiler``.

Unlike :class:`~repro.obs.trace.TraceRecorder`, which replays a schedule
op by op with a fence after each, nothing here changes what runs:

* :func:`span` opens a ``jax.profiler.TraceAnnotation`` around a host
  phase.  Given ``nbytes`` it carries them as the span's ``bytes`` arg
  and, once the phase has run, adds the same number to the
  :data:`~repro.obs.metrics.REGISTRY` counter ``<name>_bytes``: the
  trace and the operator's counter come from one call site and one
  value.  With no profiler running, a span costs a few microseconds.
* :func:`scope` names the ``jax.named_scope`` of a tile op inside the
  executor: ``<kind>.<tag>``, as in ``gemm.f32``, ``round.bf16`` or
  ``column.3``.  Scopes are trace-time metadata: the compiled ops and
  their results are the same with or without them.
"""
from __future__ import annotations

import contextlib
import re

from .metrics import REGISTRY

#: the span of one ``OOCSolver.factor`` call; its phases are
#: ``repro.factor.<phase>``
FACTOR = "repro.factor"

#: the first word of every executor scope: the tile ops, the rounding of
#: a tile through its class, and a fused column step
SCOPE_KINDS = ("potrf", "trsm", "syrk", "gemm", "load", "store", "round",
               "column")
_SCOPE = re.compile(rf"^({'|'.join(SCOPE_KINDS)})\.([A-Za-z0-9_]+)$")


@contextlib.contextmanager
def span(name: str, nbytes: int | None = None, **args):
    """A profiler span ``name`` with ``args``; with ``nbytes`` also the
    arg ``bytes`` and, after the body, the counter ``<name>_bytes``."""
    from jax.profiler import TraceAnnotation
    if nbytes is not None:
        args["bytes"] = int(nbytes)
    with TraceAnnotation(name, **args):
        yield
    if nbytes is not None:
        REGISTRY.inc(f"{name}_bytes", int(nbytes))


def scope(kind: str, tag) -> str:
    """The named scope of ``kind`` (one of :data:`SCOPE_KINDS`) for a
    class name or a column index ``tag``."""
    name = f"{kind}.{tag}"
    if not _SCOPE.match(name):
        raise ValueError(f"not an executor scope: {name!r}")
    return name


def parse_scope(path: str) -> str | None:
    """The innermost executor scope in a ``/``-separated op name such as
    ``jit(f)/jit(main)/store.bf16/round.bf16/convert_element_type``, or
    None where the op was made outside every scope."""
    for part in reversed(path.split("/")):
        if _SCOPE.match(part):
            return part
    return None
