"""Find everything a cell needs by name.

``BENCHMARK.json`` names a cell's configuration and traffic mix; each lives
in a file of its own, found by that name:

* ``bench/configs/<config>.json``  sizes, precision and grid of a
  configuration; its ``env`` names ``bench/envs/<env>.py`` (the system's
  own environment, built as a user builds it) and
  ``bench/reference/<env>.py`` (the plain reference), its
  ``feature_kind`` names ``bench/work/<feature_kind>.py``;
* ``bench/traffic/<traffic>.json`` channel, seeds per call and mesh;
* ``bench/limits/<cell>.json``     the limit of each number ``correct``
  compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric.

A new configuration, mix, cell or metric is a new file plus an entry in
``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` under a module name of its own."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_config(bench: str, name: str) -> dict:
    cfg = load_json(os.path.join(bench, "configs", f"{name}.json"))
    if cfg["w0"] == "zeros":
        cfg["w0"] = [0.0] * cfg["features"]
    return cfg


def resolve(root: str, cell_name: str, bench: str = BENCH) -> dict:
    """Everything cell ``cell_name`` of ``<root>/BENCHMARK.json`` needs.

    ``bench`` is the directory holding the files found by name (this one,
    unless a test builds its own).  Raises ``KeyError`` for an unknown cell.
    ``limits`` is None where the cell has no limits file yet.
    """
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[cell_name]
    cfg = load_config(bench, cell["config"])
    applies = lambda m: cell_name in m.get("workloads", [cell_name])  # noqa
    limits = os.path.join(bench, "limits", f"{cell_name}.json")
    return {
        "cell": cell,
        "config": cfg,
        "traffic": load_json(os.path.join(bench, "traffic",
                                          f"{cell['traffic']}.json")),
        # absent while a new cell's readings are taken (bench/control.py)
        "limits": (load_json(limits)["limits"] if os.path.exists(limits)
                   else None),
        "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
        "per_layer": [m for m in manifest["per_layer"] if applies(m)],
        "program_env": os.path.join(bench, "envs", f"{cfg['env']}.py"),
        "reference_env": os.path.join(bench, "reference", f"{cfg['env']}.py"),
        "work": os.path.join(bench, "work", f"{cfg['feature_kind']}.py"),
        "metrics_dir": os.path.join(bench, "metrics"),
        "bench": bench,
    }


def peaks(bench: str, device_kind: str) -> dict:
    """The chip's peaks; a kind that the table lacks is an error."""
    table = load_json(os.path.join(bench, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def read_metric(metrics_dir: str, name: str, ctx: dict):
    """The value of per-layer metric ``name``, or None where it found
    nothing to read."""
    module = load_module(os.path.join(metrics_dir, f"{name}.py"),
                         f"bench_metric_{name.replace('.', '_')}")
    return module.read(ctx)
