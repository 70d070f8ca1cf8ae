"""Finds a cell's parts by name: the manifest drives the harness.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
each per-layer metric.  Their files sit under ``chipbench/``:

- a configuration: ``configs/<name>.json``, with its ``generator``, a
  module ``generators/<generator>.py``;
- a traffic mix: ``traffic/<name>.json``;
- a metric reader: ``metrics/<name>.py``.  A quantity that moves
  different end-to-end metrics in different cells is split by a suffix
  (``walk_roofline.walks``, ``walk_roofline.saturated``); such a name
  is read by ``metrics/<quantity>.py`` unless a file of its own full
  name is there.

Adding a cell, a mix or a metric adds files and manifest entries and
edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class ManifestError(RuntimeError):
    """The manifest or a file it names is missing or inconsistent."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise ManifestError(f"missing file {path}") from e


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise ManifestError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_config(root: str, name: str) -> dict:
    return _read_json(os.path.join(root, "chipbench", "configs", f"{name}.json"))


def load_traffic(root: str, name: str) -> dict:
    return _read_json(os.path.join(root, "chipbench", "traffic", f"{name}.json"))


def load_generator(root: str, kind: str):
    return _load_module(
        os.path.join(root, "chipbench", "generators", f"{kind}.py"),
        f"chipbench_generator_{kind}",
    )


def load_metric(root: str, name: str):
    """The reader module of a metric, by its full name or else by the
    quantity before its first ``.``; it exposes ``read(ctx)``, which
    returns a number, or None where the run gives nothing to read."""
    base = os.path.join(root, "chipbench", "metrics")
    path = os.path.join(base, f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(base, f"{name.split('.')[0]}.py")
    return _load_module(
        path, "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
    )


def cell_plan(bench: dict, workload: str, trace: bool) -> dict:
    """Everything one run of ``workload`` needs from the manifest.

    ``metrics`` lists the end-to-end metrics the cell reports (untraced
    run) or its per-layer metrics (traced run), as manifest entries.
    """
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise ManifestError(
            f"unknown workload {workload!r}; the manifest has {sorted(cells)}"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise ManifestError(f"{workload}: unknown config {cell['config']!r}")
    e2e = [
        m for m in bench["end_to_end"]
        if workload in m.get("workloads", [workload])
    ]
    reported = {m["name"] for m in e2e}
    layer = [
        m for m in bench["per_layer"]
        if workload in m.get("workloads", [workload])
        and m["moves"] in reported
    ]
    return {
        "cell": cell,
        "config_entry": configs[cell["config"]],
        "end_to_end": e2e,
        "per_layer": layer,
        "metrics": layer if trace else e2e,
    }
