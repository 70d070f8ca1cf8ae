"""Find the load a cell's served stack sustains: a sweep of fixed rates.

    python -m chipbench.sweep --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.2:0.05,0.4:0.1,0.8:0.2

One set-up, then one open-loop window per ``walk_rate:ingest_rate``
pair, each after the queues have emptied.  Prints one JSON line per
window: the offered and completed rates and the latency quantiles from
when each request was due.  The cells' fixed rates in ``traffic/`` were
chosen from such a sweep; the benchmark's own runs never search.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quantiles(lat):
    import numpy as np

    if not lat:
        return None
    return [float(np.percentile(lat, q)) * 1e3 for q in (50, 95)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from chipbench import run
    from chipbench.traffic import Traffic

    run.use_compile_cache(ROOT)
    s = run.build(ROOT, args.workload, args.seed)
    old = s.traffic
    for i, pair in enumerate(args.rates.split(",")):
        walk_rate, ingest_rate = (float(x) for x in pair.split(":"))
        mix = dict(s.mix, walk_rate=walk_rate, ingest_rate=ingest_rate)
        t = Traffic(mix, s.cfg, s.base, s.gen, old.extras, args.seed + i + 1,
                    old.submit_walk_fn, old.make_plan_fn, old.submit_update_fn)
        start, end = t.run(args.seconds, t.plan(args.seconds), 0.0)
        st = s.server.stats()
        walks = [w for w in t.log.walks if w.status == "served"]
        upds = [u for u in t.log.updates if u.status == "served"]
        print(json.dumps({
            "walk_rate": walk_rate, "ingest_rate": ingest_rate,
            "walks_per_s": sum(w.done <= end for w in walks) / args.seconds,
            "acked_edges_per_s": sum(u.n_ops for u in upds if u.done <= end)
            / args.seconds,
            "walk_p50_p95_ms": _quantiles([w.done - w.due for w in walks]),
            "ack_p50_p95_ms": _quantiles([u.done - u.due for u in upds]),
            "queued_at_end": [st["queue_depth"], st["update_depth"]],
        }), flush=True)
        while True:  # let the queues empty before the next rate
            st = s.server.stats()
            if not (st["queue_depth"] or st["update_depth"]):
                break
            time.sleep(0.5)
        time.sleep(2 * (1.0 / max(walk_rate, 1e-3)))
    s.server.stop(drain=True, timeout=600.0)
    s.dg.close()
    shutil.rmtree(s.state_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
