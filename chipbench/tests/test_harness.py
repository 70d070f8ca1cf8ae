"""The harness end to end at a tiny size on the CPU: the look for a chip
is skipped, everything after it runs.  A sound run is correct; a run
with the served path broken underneath is not."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from chipbench import control, manifest, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["g500-22.saturated", "osm-road.walks"]
GRAPH = {"scale": 8, "vertices": 3000, "shortcuts": 200}
#: the mixes at a tiny size; the road cell keeps its updates
MIX = {"g500-22.saturated": {"walk_rate": 40.0, "drain_s": 30.0},
       "osm-road.walks": {"walk_rate": 40.0, "ingest_rate": 4.0,
                          "drain_s": 30.0}}
SEED = 2**31 + 99


#: a cell defined in the repo's files but not yet in its manifest
PROPOSED = {"name": "g500-22.saturated", "config": "graph500-22",
            "traffic": "saturated", "chips": 1, "why": "a test"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark whose manifest lists every cell of
    ``CELLS``, with the ``.saturated`` metrics of the proposed cell."""
    tmp = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp / "chipbench")
    bench = manifest.load_benchmark(ROOT)
    if PROPOSED["name"] not in {w["name"] for w in bench["workloads"]}:
        bench["configs"].append({"name": "graph500-22", "source": "a test",
                                 "file": "chipbench/configs/graph500-22.json",
                                 "reduced": [], "why": "a test"})
        bench["workloads"].append(PROPOSED)
        bench["end_to_end"].append(
            {"name": "walks_per_s", "unit": "walks/s", "better": "higher",
             "bound": 0.25, "source": "host_clock",
             "workloads": [PROPOSED["name"]]})
        for m in [m for m in bench["per_layer"] if m["name"].endswith(".walks")]:
            name = m["name"].replace(".walks", ".saturated")
            bench["per_layer"].append(dict(m, name=name, moves="walks_per_s",
                                           workloads=[PROPOSED["name"]]))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp)


def _tiny(cell):
    return {"graph": GRAPH, "mix": MIX[cell]}


def _run(root, cell, **kw):
    return run.run_cell(root, cell, SEED, 1.5, False, overrides=_tiny(cell),
                        **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct_and_reports_the_cells_metrics(cell, root):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] > 0
    bench = manifest.load_benchmark(root)
    want = {m["name"] for m in manifest.cell_plan(bench, cell, False)["metrics"]}
    assert set(out["metrics"]) == want
    # on a loaded CPU an update may not be acked inside the short window
    assert all(m["value"] >= 0 for m in out["metrics"].values()), out["metrics"]
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["checks"]["walks_checked"]["value"] >= 1
    json.dumps(out)


def _break_walk_answer(monkeypatch):
    from repro.core import walk_image

    orig = walk_image.WalkImage.walk

    def walk(self, *a, **kw):
        out = orig(self, *a, **kw)
        return jnp.asarray(out) * 1.001

    monkeypatch.setattr(walk_image.WalkImage, "walk", walk)


def _drop_every_other_update(monkeypatch):
    from repro.runtime import durable

    orig = durable.DurableGraph._rep_apply
    calls = []

    def rep_apply(self, plan):
        calls.append(1)
        return orig(self, plan) if len(calls) % 2 else 0

    monkeypatch.setattr(durable.DurableGraph, "_rep_apply", rep_apply)


def _skip_wal_writes(monkeypatch):
    from repro.runtime import durable

    monkeypatch.setattr(durable.UpdateJournal, "_write_flush",
                        lambda self, buf: None)


@pytest.mark.parametrize("cell,fault,check", [
    ("osm-road.walks", _break_walk_answer, "walk_err"),
    ("osm-road.walks", _drop_every_other_update, "edges_wrong"),
    ("osm-road.walks", _skip_wal_writes, "wal_wrong"),
    ("g500-22.saturated", _break_walk_answer, "walk_err"),
])
def test_a_broken_served_path_is_not_correct(cell, fault, check, monkeypatch,
                                             root):
    fault(monkeypatch)
    out = _run(root, cell)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_lower_precision_control_fails_the_walk_limit(cell, root):
    r = control.control_reading(root, cell, 5, overrides=_tiny(cell))
    assert not r["correct"]
    c = r["checks"]["walk_err"]
    assert c["value"] > c["limit"], r["checks"]
    assert r["checks"]["walks_checked"]["value"] >= 1


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", "osm-road.walks", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "osm-road.walks",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout == ""
