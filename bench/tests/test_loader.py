"""A configuration, a cell, a loop, a query family and a per-layer metric
added as new files are found by name, and no file of the benchmark
changes."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from bench import loader

BENCH = Path(__file__).resolve().parents[1]


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                     ).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    before = digest(BENCH)
    bench = tmp_path / "bench"
    for kind in ("configs", "workloads", "loops", "queries", "layer_metrics"):
        shutil.copytree(BENCH / kind, bench / kind)
    (bench / "configs" / "my-deployment.json").write_text(json.dumps(
        {"name": "my-deployment", "n_events": 7}))
    (bench / "workloads" / "my-cell.json").write_text(json.dumps(
        {"name": "my-cell", "config": "my-deployment", "family": "my_family",
         "chips": 1, "loop": {"kind": "my_loop"}}))
    (bench / "loops" / "my_loop.py").write_text(
        "def widths(loop, max_batch):\n    return [3]\n")
    (bench / "queries" / "my_family.py").write_text(
        "def expression(p):\n    return 'e_total > 1'\n")
    (bench / "layer_metrics" / "my_metric.py").write_text(
        "def read(run):\n    return 42.0\n")

    cell = loader.workload("my-cell", bench)
    assert loader.config(cell["config"], bench)["n_events"] == 7
    assert loader.family(cell["family"], bench).expression({}) \
        == "e_total > 1"
    assert loader.loop(cell["loop"]["kind"], bench).widths({}, 64) == [3]
    assert loader.layer_metric("my_metric", bench).read(None) == 42.0
    # what was there before is still found, unchanged
    assert loader.workload("paper-pt-cuts", bench)["config"] == "geps-paper"
    assert digest(BENCH) == before


def test_unknown_and_bad_names_are_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        loader.workload("no-such-cell")
    with pytest.raises(ValueError):
        loader.config("../etc/passwd")


def test_every_cell_of_the_benchmark_is_on_disk():
    bench = loader.benchmark()
    for cell in bench["workloads"]:
        wl = loader.workload(cell["name"])
        assert wl["config"] == cell["config"]
        assert wl["chips"] == cell["chips"]
        loader.config(wl["config"])
        loader.family(wl["family"])
        assert hasattr(loader.loop(wl["loop"]["kind"]), "run")
        for m in loader.cell_metrics(bench, cell["name"], "per_layer"):
            assert hasattr(loader.layer_metric(m["name"]), "read")
        for m in loader.cell_metrics(bench, cell["name"], "end_to_end"):
            from bench import measure
            assert m["name"] in measure.END_TO_END
