"""The work counter: n^3/3 flops for any plan, one count for every
schedule, and a peak table that knows its devices."""
import collections
import json

import numpy as np
import pytest

import repro
from bench import cell as cells
from bench import work
from repro.core.precision import uniform_plan
from repro.core.schedule import OpKind


@pytest.mark.parametrize("nt,tb", [(1, 64), (2, 128), (5, 32), (12, 2048)])
def test_uniform_plan_counts_n_cubed_over_three(nt, tb):
    w = work.class_work(nt, tb, [["f32"] * nt] * nt)
    assert list(w) == ["f32"]
    assert w["f32"]["flops"] == pytest.approx((nt * tb) ** 3 / 3, rel=1e-12)


def test_bytes_by_hand_at_two_tiles():
    # potrf(0,0) 2 tiles, trsm(1,0) 3, syrk(1,1) 3, potrf(1,1) 2: 10 tiles
    # of f32, of which the trsm reads the f64 diagonal tile at 8 bytes
    tb = 16
    classes = [["f64", "f64"], ["f32", "f64"]]
    w = work.class_work(2, tb, classes)
    assert w["f32"]["bytes"] == tb * tb * (2 * 4 + 8)          # trsm
    assert w["f64"]["bytes"] == tb * tb * (2 * 8 + 2 * 8 + (2 * 8 + 4))
    assert w["f32"]["flops"] == tb ** 3
    assert w["f64"]["flops"] == pytest.approx(tb ** 3 * (2 / 3 + 1))


def _compute_tasks(sched):
    kinds = {OpKind.POTRF: "potrf", OpKind.TRSM: "trsm",
             OpKind.SYRK: "syrk", OpKind.GEMM: "gemm"}
    return collections.Counter(kinds[o.kind] for o in sched.ops
                               if o.kind in kinds)


@pytest.mark.parametrize("policy", ["v1", "v2", "v3", "v4"])
@pytest.mark.parametrize("fuse", [False, True])
def test_count_is_the_same_for_every_schedule(policy, fuse):
    nt, tb = 6, 32
    classes = [["f32"] * nt] * nt
    counted = collections.Counter(k for k, _, _ in work.tasks(nt))
    cfg = repro.CholeskyConfig(tb=tb, policy=policy, backend="jax",
                               fuse_columns=fuse,
                               plan=uniform_plan(nt, "f32"))
    sched = repro.plan(nt * tb, cfg).single_schedule()
    assert _compute_tasks(sched) == counted
    assert work.class_work(nt, tb, classes)["f32"]["flops"] == \
        pytest.approx((nt * tb) ** 3 / 3, rel=1e-12)


def test_peaks_name_their_source_and_refuse_unknown_devices():
    assert json.loads(work.PEAKS.read_text())["source"]
    p = work.peaks("TPU v5 lite")
    assert p["ops_per_s"]["float16+"] == 197e12
    assert p["ops_per_s"]["8bit"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_a_class_map_of_the_wrong_shape_is_refused():
    cfg = cells.load("matern24k-mxp.factor")["config"]
    assert np.array(cells.class_map(cfg)).shape == (12, 12)
    cfg["precision"]["classes"] = cfg["precision"]["classes"][:-1]
    with pytest.raises(ValueError, match="12 x 12"):
        cells.class_map(cfg)
    with pytest.raises(ValueError):
        work.least_seconds(12, 2048, [["f32"] * 11] * 12, "TPU v5 lite")


def test_the_committed_map_matches_its_histogram():
    p = cells.load("matern24k-mxp.factor")["config"]["precision"]
    hist = collections.Counter(p["classes"][i][j]
                               for i in range(12) for j in range(i + 1))
    assert dict(hist) == {k: v for k, v in p["histogram_lower"].items() if v}
