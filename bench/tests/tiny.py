"""Tiny versions of the benchmark's cells, small enough for the CPU."""
import json
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
    """The cell ``name`` at its configuration's tiny size (``tiny`` in
    ``bench/configs/<c>.py``)."""
    cell = manifest.load_cell(name)
    return manifest.config_module(cell.config).tiny(cell)


TOY = "toy.stacked"
TOY_DIR = pathlib.Path(__file__).resolve().parent / "toy"


def toy_cell(monkeypatch):
    """The toy stacked cell of ``toy/``, which no ``BENCHMARK.json``
    names: its configuration, reference and batch kind (all named
    ``toy``) are handed to the harness through the manifest's lookups,
    for the length of the test."""
    spec = json.loads((TOY_DIR / "cell.json").read_text())
    for lookup, stem in (("config_module", "config"),
                         ("reference_module", "reference"),
                         ("batches_module", "batches")):
        mod = manifest.load_module(TOY_DIR / f"{stem}.py", f"bench_toy_{stem}")
        real = getattr(manifest, lookup)
        monkeypatch.setattr(manifest, lookup,
                            lambda name, real=real, mod=mod:
                            mod if name == "toy" else real(name))
    e2e = [m for m in manifest.load_manifest()["end_to_end"]
           if "workloads" not in m]
    return manifest.Cell(name=TOY, config="toy", traffic="toy", chips=1,
                         sizes=spec["sizes"], job=spec["job"],
                         limits=spec["limits"], end_to_end=e2e, per_layer=[])


def cell(name: str, monkeypatch):
    """``tiny_cell(name)``, or the toy stacked cell."""
    return toy_cell(monkeypatch) if name == TOY else tiny_cell(name)
