"""Finds every file of a cell by the names in ``BENCHMARK.json``.

A configuration ``<c>`` is ``bench/configs/<c>.json`` (its sizes, as
run) with ``bench/configs/<c>.py`` (what builds the program from them)
and ``bench/reference/<c>.py`` (its plain float32 reference).  A traffic
mix ``<t>`` is ``bench/traffic/<t>.json``.  A per-layer metric ``<m>``
is ``bench/metrics/<m>.py``.  A cell ``<w>`` keeps the limits of its
correctness check in ``bench/limits/<w>.json``.  Adding any of these
takes new files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: pathlib.Path, name: str):
    """Import a file by path under a name of its own (file names carry
    '-' and '.', so they cannot be imported by the usual statement)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _safe(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def config_module(config: str):
    return load_module(BENCH / "configs" / f"{config}.py",
                       f"bench_config_{_safe(config)}")


def reference_module(config: str):
    return load_module(BENCH / "reference" / f"{config}.py",
                       f"bench_reference_{_safe(config)}")


def reference_train():
    """The plain optimizers every training cell's reference runs."""
    return load_module(BENCH / "reference" / "train.py",
                       "bench_reference_train")


def metric_module(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py",
                       f"bench_metric_{_safe(metric)}")


@dataclasses.dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    sizes: dict                 # the configuration file, as run
    job: dict                   # the traffic file
    limits: dict                # the correctness limits of this cell
    end_to_end: List[dict]      # metrics this cell reports with --trace 0
    per_layer: List[dict]       # metrics this cell reports with --trace 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    man = load_manifest(root)
    cells: Dict[str, dict] = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    cfg = configs[w["config"]]
    e2e = [m for m in man["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"]
                 if _applies(m, name) and m["moves"] in moved]
    return Cell(
        name=name, config=w["config"], traffic=w["traffic"],
        chips=int(w["chips"]),
        sizes=json.loads((root / cfg["file"]).read_text()),
        job=json.loads((root / "bench" / "traffic"
                        / f"{w['traffic']}.json").read_text()),
        limits=json.loads((root / "bench" / "limits"
                           / f"{name}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer)
