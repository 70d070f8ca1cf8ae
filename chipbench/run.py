"""Run one cell of the chip benchmark once.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
Set-up makes the graph on the device from the seed, builds the served
stack (``DiGraph`` -> ``DurableGraph`` with its WAL fsynced ->
``WalkServer``), warms every walk batch shape and the first update
shapes, and draws the window's requests from the seed.  The window then
offers the mix's open-loop load for ``--seconds``; every request is
timed on the client's side.  After the window the answers are compared
with the plain reference (``chipbench/reference.py``): the checked walks
at the generation each names, the final edge set, and the write-ahead
log.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared, beside its
limit.  The checks are also the last lines on standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run prints no
result and exits non-zero.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the executables JAX compiles or loads from its cache."""

    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.count += 1


def use_compile_cache(root: str) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def program_edges(rep):
    """The (keys, weights) of every live edge in a ``DiGraph``'s arena,
    sorted by key, read from its device buffers and block table."""
    import numpy as np

    from chipbench import reference

    n = rep.n_max_vertex() + 1
    deg = np.asarray(rep.degrees[:n], np.int64)
    starts = np.asarray(rep.starts[:n], np.int64).clip(0)
    first = np.cumsum(deg) - deg
    gidx = np.repeat(starts - first, deg) + np.arange(int(deg.sum()))
    dst = np.asarray(rep.dst)[gidx]
    wgt = np.asarray(rep.wgt)[gidx]
    keys = reference.keys_of(np.repeat(np.arange(n), deg), dst)
    if keys.shape[0] > 1 and not bool(np.all(keys[1:] > keys[:-1])):
        order = np.argsort(keys, kind="stable")
        keys, wgt = keys[order], wgt[order]
    return keys, wgt


def build(root: str, workload: str, seed: int, *, trace: bool = False,
          overrides: dict | None = None):
    """Set-up: the graph, the served stack, the warm-up and the traffic.

    ``overrides`` replaces parts of the configuration and the mix (the
    tests run tiny graphs on the CPU with it).
    """
    import jax
    import numpy as np

    from chipbench.traffic import Traffic
    from repro.core import DiGraph, edgebatch, updates
    from repro.core import csr as csr_mod
    from repro.runtime import durable
    from repro.runtime import serve as serve_mod

    overrides = overrides or {}
    plan = manifest.cell_plan(manifest.load_benchmark(root), workload, trace)
    cell = plan["cell"]
    cfg = manifest.load_config(root, cell["config"])
    for part in ("graph", "serve"):
        cfg[part].update(overrides.get(part, {}))
    mix = manifest.load_traffic(root, cell["traffic"])
    mix.update(overrides.get("mix", {}))
    gen = manifest.load_generator(root, cfg["generator"])
    s = SimpleNamespace(root=root, workload=workload, seed=seed, plan=plan,
                        cfg=cfg, mix=mix, gen=gen)
    s.compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(s.compiles)
    s.devices = jax.devices()[: int(cell["chips"])]
    dev = s.devices[0]
    log(f"{workload}: device {dev.platform} {dev.device_kind} x{len(s.devices)}")

    s.base, extras = gen.generate(cfg, seed)
    log(f"graph |V| {s.base.n} |E| {s.base.m} at {time.monotonic() - T0:.2f}s")
    rep = DiGraph.from_csr(
        csr_mod.CSR(s.base.offsets, s.base.dst, s.base.wgt, s.base.n, s.base.m)
    )
    rep.block_on()
    log(f"DiGraph (cap_e {rep.cap_e}) at {time.monotonic() - T0:.2f}s")
    s.state_dir = os.path.join(root, ".chipbench_state", workload)
    shutil.rmtree(s.state_dir, ignore_errors=True)
    s.wal_dir = os.path.join(s.state_dir, "wal")
    dur = cfg["durability"]
    s.dg = durable.DurableGraph(
        rep, s.wal_dir, os.path.join(s.state_dir, "ckpt"),
        fsync=bool(dur["wal_fsync"]),
        checkpoint_every=int(dur["checkpoint_every"]),
        segment_bytes=int(dur["wal_segment_bytes"]),
    )
    log(f"DurableGraph (step-0 checkpoint) at {time.monotonic() - T0:.2f}s")
    srv = cfg["serve"]
    s.batch_max = int(srv["batch_max"])
    s.server = serve_mod.WalkServer(
        s.dg, batch_max=s.batch_max, max_queue=int(srv["max_queue"]),
        seal_group_max=int(srv["seal_group_max"]),
    ).start()
    steps = int(mix["walk"]["steps"])
    img = s.server.generation.image
    b = 4
    while True:  # every batch shape the dispatcher pads to
        np.asarray(img.walk(steps, visits0=np.zeros((b, img.nv), np.float32)))
        if b >= s.batch_max:
            break
        b *= 2
    del img

    def make_plan(ins, dels):
        return updates.plan_update(
            inserts=edgebatch.from_arrays(*ins) if ins[0].shape[0] else None,
            deletes=edgebatch.from_arrays(*dels) if dels[0].shape[0] else None,
        )

    s.traffic = Traffic(
        mix, cfg, s.base, gen, extras, seed,
        submit_walk=lambda seeds, w, k: s.server.submit_walk(
            seeds, weights=w, steps=k),
        make_plan=make_plan, submit_update=lambda p: s.server.submit_update(p),
    )
    s.traffic.warmup(s.batch_max)
    log(f"warm-up done at {time.monotonic() - T0:.2f}s ({s.compiles.count} "
        f"programs compiled or loaded)")
    return s


def window(s, seconds: float, trace: bool):
    """The measured window, traced or not; waits for the mix's
    ``drain_s`` past its end, then stops the server."""
    import jax

    w = SimpleNamespace(seconds=float(seconds))
    reqs = s.traffic.plan(seconds)
    s.setup_s = time.monotonic() - T0
    log(f"set-up done at {s.setup_s:.2f}s")
    w.stats0 = s.server.stats()
    c0 = s.compiles.count
    w.trace_dir = os.path.join(s.state_dir, "trace")
    if trace:
        jax.profiler.start_trace(w.trace_dir)
    t_trace = time.perf_counter()
    w.start, w.end = s.traffic.run(seconds, reqs, float(s.mix.get("drain_s", 60)))
    w.stats1 = s.server.stats()
    w.final_stats = s.server.stop(drain=False, timeout=600.0)
    w.traced_s = time.perf_counter() - t_trace
    if trace:
        jax.profiler.stop_trace()
    s.traffic.settle_rest()
    w.compiles = s.compiles.count - c0
    w.peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in s.devices)
    log(f"window {w.end - w.start:.2f}s, closed {w.traced_s - seconds:.2f}s "
        f"after its end; {len(s.traffic.log.walks)} walks, "
        f"{len(s.traffic.log.updates)} updates, {w.compiles} programs "
        f"compiled or loaded; peak {w.peak}")
    return w


def finish(s, w, trace: bool) -> dict:
    """Compare with the reference, reduce the trace, read the metrics."""
    from chipbench import check as check_mod
    from chipbench import reference
    from chipbench import trace as trace_mod

    got_keys, got_wgt = program_edges(s.dg.rep)
    s.dg.close()
    wal = reference.read_wal(s.wal_dir)
    s.server = s.dg = None
    gc.collect()
    t0 = time.monotonic()
    result = check_mod.compare(
        s.base, s.traffic, got_keys, got_wgt, wal,
        int(s.mix["walk"]["steps"]), s.cfg["correct_limits"],
        want_bytes=trace,
    )
    log(f"reference checks took {time.monotonic() - t0:.2f}s")
    summary = None
    if trace:
        summary = trace_mod.reduce_file(trace_mod.find_xplane(w.trace_dir),
                                        w.traced_s)
        log(f"trace: busy {summary.busy_s:.3f}s of {summary.window_s:.3f}s; "
            f"programs {sorted(summary.modules.items(), key=lambda kv: -kv[1])[:12]}")
    shutil.rmtree(s.state_dir, ignore_errors=True)

    dev = s.devices[0]
    log_ = s.traffic.log
    ctx = SimpleNamespace(
        setup_s=s.setup_s, seconds=w.seconds, start=w.start, end=w.end,
        walks=log_.walks, updates=log_.updates, stats0=w.stats0,
        stats1=w.stats1, final_stats=w.final_stats, batch_max=s.batch_max, compiles_in_window=w.compiles,
        trace=summary, walk_bytes=result["walk_bytes"],
        peaks=trace_mod.load_peaks(s.root).get(dev.device_kind),
    )
    metrics = {}
    for m in s.plan["metrics"]:
        value = manifest.load_metric(s.root, m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    reqs = log_.walks + log_.updates
    out = {
        "correct": bool(result["correct"]),
        "attempted": len(reqs),
        "failed": sum(r.status != "served" for r in reqs),
        "metrics": metrics,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(s.devices), "memory_peak_bytes": w.peak,
        },
    }
    if summary is not None:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = result["checks"]
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, **kw) -> dict:
    """One run of ``workload``; returns the result object."""
    s = build(root, workload, seed, trace=trace, **kw)
    w = window(s, seconds, trace)
    return finish(s, w, trace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        plan = manifest.cell_plan(manifest.load_benchmark(ROOT), args.workload,
                                  bool(args.trace))
    except manifest.ManifestError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chipbench: the program under test is not at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chipbench: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    chips = int(plan["cell"]["chips"])
    if len(jax.devices()) < chips:
        print(f"chipbench: {args.workload} needs {chips} chips, JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    log(f"compile cache {use_compile_cache(ROOT)}")
    out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
