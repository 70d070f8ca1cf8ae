"""BENCHMARK.json against the benchmark's contract, and the harness
finding a cell's parts by name."""
import json
import os
import re
import shutil

import pytest

from chipbench import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n\r]{1,200}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_benchmark(ROOT)


def test_top_level_keys_command_and_paths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32
    for word in bench["command"]:
        assert LINE.match(word) and not word.startswith("/") and ".." not in word
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_run_seconds_fits_a_full_check(bench):
    r = bench["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert 1200 + (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 <= 43200


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_names_and_units(bench, section):
    entries = bench[section]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert LINE.match(e[k]), (e["name"], k)
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["name"]
            assert e["better"] in ("lower", "higher")
        if section == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace")
            assert 0 < e["bound"] <= 0.25
        if section == "per_layer":
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        if section == "configs":
            assert len(e["reduced"]) <= 16
            assert all(NAME.match(k) for k in e["reduced"])
            assert e["file"] == f"chipbench/configs/{e['name']}.json"
        if section == "workloads":
            assert e["chips"] in (1, 4)
            assert NAME.match(e["config"]) and NAME.match(e["traffic"])


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for cell in bench["workloads"]:
        e2e = manifest.cell_plan(bench, cell["name"], False)["metrics"]
        layer = manifest.cell_plan(bench, cell["name"], True)["metrics"]
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert layer


def test_per_layer_workloads_report_what_they_move(bench):
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = {e["name"] for e in manifest.cell_plan(bench, cell, False)["metrics"]}
            assert m["moves"] in reported, (m["name"], cell)


def test_configs_are_used_and_reduced_keys_exist(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        cfg = manifest.load_config(ROOT, c["name"])
        assert cfg["name"] == c["name"]
        for k in c["reduced"]:
            assert k in cfg["graph"], k
        assert set(cfg["correct_limits"]) >= {"walk_err", "walks_checked"}


def test_every_part_is_found_by_name(bench):
    for w in bench["workloads"]:
        cfg = manifest.load_config(ROOT, w["config"])
        manifest.load_generator(ROOT, cfg["generator"])
        manifest.load_traffic(ROOT, w["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(manifest.load_metric(ROOT, m["name"]).read)


def test_dummy_parts_dropped_in_are_found(tmp_path):
    root = tmp_path
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench")
    bench = manifest.load_benchmark(ROOT)
    bench["configs"].append({"name": "dummy-cfg", "source": "a test",
                             "file": "chipbench/configs/dummy-cfg.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy-cfg",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "dummy_metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "a test", "moves": "setup_s",
                               "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench/configs/dummy-cfg.json").write_text(
        json.dumps({"name": "dummy-cfg", "generator": "dummy_gen", "graph": {}}))
    (root / "chipbench/traffic/dummy-mix.json").write_text(
        json.dumps({"walk_clients": 3}))
    (root / "chipbench/metrics/dummy_metric.py").write_text(
        "def read(ctx):\n    return 41 + ctx\n")
    (root / "chipbench/generators/dummy_gen.py").write_text(
        "def generate(cfg, seed):\n    return seed, {}\n")
    loaded = manifest.load_benchmark(str(root))
    plan = manifest.cell_plan(loaded, "dummy.cell", True)
    assert "dummy_metric" in [m["name"] for m in plan["metrics"]]
    assert manifest.load_config(str(root), "dummy-cfg")["generator"] == "dummy_gen"
    assert manifest.load_traffic(str(root), "dummy-mix")["walk_clients"] == 3
    assert manifest.load_metric(str(root), "dummy_metric").read(1) == 42
    assert manifest.load_generator(str(root), "dummy_gen").generate({}, 7) == (7, {})
    with pytest.raises(manifest.ManifestError):
        manifest.cell_plan(loaded, "no.such.cell", False)


def test_a_suffixed_metric_is_read_by_its_quantity(tmp_path):
    """``walk_roofline.walks`` is read by ``metrics/walk_roofline.py``
    unless a file of the full name is there."""
    (tmp_path / "chipbench/metrics").mkdir(parents=True)
    (tmp_path / "chipbench/metrics/q.py").write_text(
        "def read(ctx):\n    return 1\n")
    assert manifest.load_metric(str(tmp_path), "q.a").read(None) == 1
    (tmp_path / "chipbench/metrics/q.b.py").write_text(
        "def read(ctx):\n    return 2\n")
    assert manifest.load_metric(str(tmp_path), "q.b").read(None) == 2
    with pytest.raises(manifest.ManifestError):
        manifest.load_metric(str(tmp_path), "r.a")
