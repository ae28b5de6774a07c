"""Finds a cell's parts by name, so that a later change adds a
configuration, a traffic mix, a query family or a per-layer metric by
adding a file and edits none:

- ``bench/workloads/<cell>.json``: the cell (configuration, chips, loop,
  tenants, query family, limits of the comparison);
- ``bench/configs/<config>.json``: the deployment (schema widths, brick
  size, calibration, event count, data distributions, and the
  ``backend_kwargs`` the service's backend is built with, passed through
  unchanged);
- ``bench/loops/<kind>.py``: how a cell sends its queries (``widths``,
  the window sizes to warm up, and ``run``, which drives one window);
- ``bench/queries/<family>.py``: a query family (``draw``,
  ``expression``, ``scalar_columns``, ``param_arrays``, ``NEVER`` and its
  plain ``reference``);
- ``bench/layer_metrics/<metric>.py``: a per-layer metric (``read(run)``,
  returning a number or None when the run has nothing to read).

A directory given in place of ``bench/`` (the benchmark's own tests keep
tiny cells in one) is searched first, then ``bench/`` itself.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _path(bench_dir: Path, kind: str, name: str, suffix: str) -> Path:
    """``<bench_dir>/<kind>/<name><suffix>``, else the benchmark's own."""
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    for root in dict.fromkeys((Path(bench_dir), BENCH_DIR)):
        path = root / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind} entry named {name!r} under "
                            f"{bench_dir}")


def _json(bench_dir, kind, name) -> dict:
    with open(_path(bench_dir, kind, name, ".json")) as f:
        return json.load(f)


def _module(bench_dir, kind, name):
    path = _path(bench_dir, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(bench_dir, "workloads", name)


def config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(bench_dir, "configs", name)


def family(name: str, bench_dir: Path = BENCH_DIR):
    return _module(bench_dir, "queries", name)


def loop(name: str, bench_dir: Path = BENCH_DIR):
    return _module(bench_dir, "loops", name)


def layer_metric(name: str, bench_dir: Path = BENCH_DIR):
    return _module(bench_dir, "layer_metrics", name)


def benchmark(bench_dir: Path = BENCH_DIR) -> dict:
    """``BENCHMARK.json`` at the root beside the benchmark's directory."""
    with open(Path(bench_dir).parent / "BENCHMARK.json") as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that ``cell``
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]
