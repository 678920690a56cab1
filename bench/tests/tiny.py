"""Tiny versions of the benchmark's cells, small enough for the CPU."""
import copy
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402


NAMES = [w["name"] for w in manifest.load_manifest()["workloads"]]


def tiny_cell(name: str):
    """The cell ``name`` at widths a CPU test can hold: two stages of 8
    and 16 channels on 8x8 images, FC 256 -> 20 -> 10, r=8, n_stat=16,
    batch 4; every factor mode of the full cell still occurs (Brand,
    low-rank from M, dense), and each step kind of the cell runs among
    the checked steps."""
    cell = copy.deepcopy(manifest.load_cell(name))
    cell.sizes.update(stages=[8, 16], fc_hidden=20, img=8)
    cell.job["data"].update(batch=4, pool=8)
    cell.job["optimizer"].update(r=8, n_stat=16, max_dense_dim=64)
    return cell
