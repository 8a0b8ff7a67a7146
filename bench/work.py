"""The least time of a tiled Cholesky factor on a chip, from its shape
alone.

The tasks are those of the tiled algorithm, whatever schedule, policy or
kernel runs them: ``nt`` POTRF, ``nt(nt-1)/2`` TRSM and SYRK, and
``nt(nt-1)(nt-2)/6`` GEMM.  Each task is counted at the class of the
tile it writes; its bytes are its operand tiles read and its output tile
read and written, each at its own class's width.  Nothing here reads a
schedule, an HLO module or a kernel, so no change to those can move the
numerator of a roofline share.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"

# element width in bytes, and which published peak the class runs at
_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1,
          "f8e4m3s": 1}
_RATE = {"f64": "float16+", "f32": "float16+", "f16": "float16+",
         "bf16": "float16+", "f8e4m3": "8bit", "f8e4m3s": "8bit"}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def tasks(nt: int):
    """``(kind, written tile, read tiles)`` of every task of the tiled
    factor, in right-looking order."""
    for k in range(nt):
        yield "potrf", (k, k), ()
        for i in range(k + 1, nt):
            yield "trsm", (i, k), ((k, k),)
        for i in range(k + 1, nt):
            yield "syrk", (i, i), ((i, k),)
            for j in range(k + 1, i):
                yield "gemm", (i, j), ((i, k), (j, k))


_FLOPS = {"potrf": 1 / 3, "trsm": 1.0, "syrk": 1.0, "gemm": 2.0}


def task_work(nt: int, tb: int, classes):
    """``(class, flops, bytes)`` of every task; ``classes`` is the
    ``nt x nt`` map of the class names of the tiles as they are run."""
    classes = np.asarray(classes, dtype=object)
    if classes.shape != (nt, nt):
        raise ValueError(f"class map of shape {classes.shape}, expected "
                         f"({nt}, {nt})")
    for kind, (i, j), reads in tasks(nt):
        cls = classes[i, j]
        nbytes = tb * tb * (2 * _BYTES[cls]
                            + sum(_BYTES[classes[r]] for r in reads))
        yield cls, _FLOPS[kind] * tb ** 3, nbytes


def class_work(nt: int, tb: int, classes) -> dict:
    """``{class: {"flops": f, "bytes": b}}`` of one factor."""
    out: dict = {}
    for cls, flops, nbytes in task_work(nt, tb, classes):
        w = out.setdefault(cls, {"flops": 0.0, "bytes": 0.0})
        w["flops"] += flops
        w["bytes"] += nbytes
    return out


def least_seconds(nt: int, tb: int, classes, device_kind: str) -> float:
    """Sum over the tasks of max(flops / peak of the class, bytes / HBM
    bandwidth) on ``device_kind``."""
    p = peaks(device_kind)
    return sum(max(flops / p["ops_per_s"][_RATE[cls]],
                   nbytes / p["hbm_bytes_per_s"])
               for cls, flops, nbytes in task_work(nt, tb, classes))
