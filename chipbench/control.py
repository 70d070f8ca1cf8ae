"""The lower-precision control of a cell's comparison.

    python -m chipbench.control --workload <cell> --seeds 1,2,3

For each seed it makes the cell's graph and the walks of a run's window
as a run does.  The plain reference then stands in for the served
stack: it answers those walks with every step stored in bfloat16, the
precision below the float32 the configuration states (a tempting way to
halve the bytes of the ``[B, V]`` visit rows).  The answers go through
the run's own comparison (``check.compare``), which has to find the
control not correct.  It prints, per seed, ``correct`` and each number
compared beside its limit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_reading(root: str, workload: str, seed: int,
                    overrides: dict | None = None) -> dict:
    import ml_dtypes

    from chipbench import check, manifest, reference
    from chipbench.traffic import Traffic, WalkRecord

    overrides = overrides or {}
    bench = manifest.load_benchmark(root)
    plan = manifest.cell_plan(bench, workload, False)
    cfg = manifest.load_config(root, plan["cell"]["config"])
    cfg["graph"].update(overrides.get("graph", {}))
    mix = manifest.load_traffic(root, plan["cell"]["traffic"])
    mix.update(overrides.get("mix", {}))
    gen = manifest.load_generator(root, cfg["generator"])
    base, extras = gen.generate(cfg, seed)
    traffic = Traffic(mix, cfg, base, gen, extras, seed,
                      submit_walk=None, make_plan=None, submit_update=None)
    reqs = traffic.plan(float(bench["run_seconds"]))
    walks = [p for _off, kind, p in reqs if kind == "walk"]
    walks = walks[:int(mix.get("check_max", len(walks)))]
    state = reference.EdgeState(base.offsets, base.dst, base.wgt, base.n)
    steps = int(mix["walk"]["steps"])
    low, _ = reference.Walker(state).walk(
        state.delta(), [w[0] for w in walks], steps,
        weights_list=[w[1] for w in walks], round_to=ml_dtypes.bfloat16)
    traffic.log.walks = [
        WalkRecord(seeds, weights, due=0.0, keep=True, done=0.0,
                   status="served", generation=0, visits=low.column(j))
        for j, (seeds, weights, _keep) in enumerate(walks)
    ]
    got_keys, got_wgt = state.edges()
    result = check.compare(base, traffic, got_keys, got_wgt, ([], 0), steps,
                           cfg["correct_limits"])
    return {"seed": seed, "walks": len(walks), "correct": result["correct"],
            "checks": result["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    for s in args.seeds.split(","):
        print(json.dumps(control_reading(ROOT, args.workload, int(s))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
