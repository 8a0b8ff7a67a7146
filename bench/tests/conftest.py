import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def small_cell(config_name: str, n: int, tb: int) -> dict:
    """The cell of ``config_name`` cut to ``n x n`` with ``tb`` tiles, its
    class map (if any) recomputed for that size, and the limit of the
    full-size cell."""
    from bench import cell as cells, mxp_map
    cell = cells.load(f"{config_name}.factor")
    cfg = cell["config"]
    cfg["n"], cfg["tb"] = n, tb
    if cfg["precision"]["kind"] == "map":
        cfg["precision"]["classes"] = mxp_map.class_map(cfg)["classes"]
    return cell


@pytest.fixture
def cell_of():
    return small_cell
