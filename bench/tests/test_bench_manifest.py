"""The manifest loader finds every configuration, cell, traffic file,
limit file and metric reader by its name, and BENCHMARK.json keeps to
the shapes the harness reads."""
import json
import re

import pytest

import compare
import manifest

MAN = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["bench"]
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads(w):
    cell = manifest.load_cell(w["name"])
    assert cell.config == w["config"] and cell.chips == w["chips"]
    assert manifest.config_module(cell.config).build_model
    assert manifest.reference_module(cell.config).loss_and_stats
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert cell.limits and set(cell.limits) <= set(compare.NUMBERS)
    assert all(v > 0 for v in cell.limits.values())


@pytest.mark.parametrize("m", MAN["per_layer"], ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    assert callable(manifest.metric_module(m["name"]).value)
    moved = {e["name"] for e in MAN["end_to_end"]}
    assert m["moves"] in moved


def test_names_and_files():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    for c in MAN["configs"]:
        sizes = json.loads((manifest.ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in sizes and key in sizes["published"]
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
