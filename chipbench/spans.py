"""The served program's host spans, on the device trace's timeline.

    python -m chipbench.spans --workload <cell> --seed <n> --seconds <s>

The program marks its host work with ``jax.profiler.TraceAnnotation``.
The profiler writes those spans onto the ``/host:CPU`` plane of the same
``.xplane.pb`` that holds the device planes, on the same clock, one
line per thread; request identifiers ride in the name as ``#k=v,...#``.
This module keeps the spans of ``SPANS`` and ignores every other host
event (JAX's own, the Python tracer's ``$file:line`` ones).  It adds to
the device summary of ``trace.py``:

- ``spans``: per span name, (count, seconds);
- ``idle_by_span``: the device's idle seconds split by the program span
  open at that moment.  On each thread the innermost open span counts;
  across threads a work span comes before a wait span, and among spans
  of one kind the first in ``SPANS``.  ``none`` holds the idle time no
  span covers.  The entries sum to the window's idle seconds;
- ``idle_host_busy_s``: the idle seconds under a work span, when the
  host, not the lack of requests, kept the device waiting;
- each idle gap's name gains ``; host: <span>`` for a span that covers
  more than half of it.

The traced window runs ``window_s`` seconds from the earliest event
in the file.

Run as a program, it makes one traced run of a cell (``run.py``'s
set-up, window and comparison), copies each request's timestamps off its
ticket (``copy_timestamps``), and prints the run's result line with the
per-layer metrics of ``METRICS`` added beside the cell's own, the
decompositions of the p95 walk and of the p95 ack, and ``host_spans``
and ``idle_by_span`` in the breakdown.  Nothing here loads JAX on import.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace  # noqa: E402

#: the program's spans, work spans first: (name, is a wait)
SPANS = (
    ("serve.walk.prepare", False),
    ("serve.walk.device", False),
    ("serve.walk.fetch", False),
    ("durable.wal_append", False),
    ("digraph.plan", False),
    ("slot_update.dispatch", False),
    ("serve.seal", False),
    ("serve.dispatch.wait", True),
    ("serve.writer.wait", True),
)
_RANK = {name: i for i, (name, _wait) in enumerate(SPANS)}
WAITS = frozenset(name for name, wait in SPANS if wait)
NONE = "none"
HOST_PLANE = "/host:CPU"
#: per-layer metrics read from the spans and the ticket timestamps
METRICS = (
    ("walk_queue_p95_ms", "ms"), ("walk_host_ms", "ms"),
    ("wal_append_ms", "ms"), ("update_dispatch_ms", "ms"),
    ("seal_ms", "ms"), ("idle_host_busy", "%"),
)
#: ticket timestamps copied onto a request's record
TIMESTAMPS = ("submitted_at", "taken_at", "done_at", "applied_at", "acked_at")


def span_name(event_name: str) -> str:
    """``serve.seal#gen=3#`` -> ``serve.seal``."""
    return event_name.split("#", 1)[0]


@dataclasses.dataclass
class SpanSummary(trace.Summary):
    #: span name -> (count, seconds)
    spans: dict = dataclasses.field(default_factory=dict)
    #: span name or ``none`` -> idle device seconds, averaged over devices
    idle_by_span: dict = dataclasses.field(default_factory=dict)
    idle_host_busy_s: float = 0.0

    def breakdown(self) -> dict:
        out = super().breakdown()
        out["host_spans"] = [[n, cs[1]] for n, cs in sorted(
            self.spans.items(), key=lambda kv: -kv[1][1])[:10]]
        out["idle_by_span"] = [[n, s] for n, s in sorted(
            self.idle_by_span.items(), key=lambda kv: -kv[1])]
        return out


def _label(open_by_line: list) -> str:
    """The span an instant is charged to, from each thread's open spans
    ``(start, rank, name)``."""
    inner = [max(spans)[2] for spans in open_by_line if spans]
    work = [n for n in inner if n not in WAITS]
    pick = work or inner
    return min(pick, key=_RANK.__getitem__) if pick else NONE


def label_segments(lines: list, t0: int, t1: int) -> list:
    """``[(start, end, label), ...]`` covering ``[t0, t1)`` from each
    thread's spans ``[(start, end, name), ...]``."""
    edges = []
    for li, spans in enumerate(lines):
        for s, e, name in spans:
            s, e = max(s, t0), min(e, t1)
            if s < e:
                edges.append((s, 1, li, (s, _RANK[name], name)))
                edges.append((e, 0, li, (s, _RANK[name], name)))
    edges.sort(key=lambda x: (x[0], x[1]))
    open_by_line = [[] for _ in lines]
    segs, at = [], t0
    for t, opening, li, span in edges:
        if t > at:
            segs.append((at, t, _label(open_by_line)))
            at = t
        if opening:
            open_by_line[li].append(span)
        else:
            open_by_line[li].remove(span)
    if at < t1:
        segs.append((at, t1, NONE))
    return segs


def _overlaps(intervals: list, segs: list) -> dict:
    """Nanoseconds of each label's segments inside ``intervals`` (both
    sorted and non-overlapping)."""
    out: dict = {}
    j = 0
    for s, e in intervals:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b, label = segs[k]
            out[label] = out.get(label, 0) + min(b, e) - max(a, s)
            k += 1
    return out


def reduce_space(space, window_s: float) -> SpanSummary:
    """``trace.reduce_space`` with the program's host spans added."""
    base = trace.reduce_space(space, window_s)
    lines, devices, t0 = [], [], None
    for plane in space.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                kept = []
                for ev in line.events:
                    t0 = ev.start_ns if t0 is None else min(t0, ev.start_ns)
                    name = span_name(ev.name)
                    if name in _RANK:
                        kept.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     name))
                if kept:
                    lines.append(kept)
        elif trace.DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if line.name == "XLA Ops":
                        ops.append(iv)
                        t0 = iv[0] if t0 is None else min(t0, iv[0])
                    elif line.name == "XLA Modules":
                        mods.append((*iv, trace.module_name(ev.name)))
            devices.append((trace.union_length(ops)[1], sorted(mods)))
    t1 = t0 + window_s * 1e9
    spans: dict = {}
    for line in lines:
        for s, e, name in line:
            n, sec = spans.get(name, (0, 0.0))
            spans[name] = (n + 1, sec + (e - s) * 1e-9)
    segs = label_segments(lines, t0, t1)
    idle: dict = {}
    gaps = []
    for merged, mods in devices:
        busy = [(max(s, t0), min(e, t1)) for s, e in merged
                if e > t0 and s < t1]
        bounds = [t0] + [x for iv in busy for x in iv] + [t1]
        free = [(a, b) for a, b in zip(bounds[::2], bounds[1::2]) if b > a]
        for label, ns in _overlaps(free, segs).items():
            idle[label] = idle.get(label, 0.0) + ns * 1e-9 / len(devices)
        between = sorted(((s1 - e0, e0, s1) for (_s0, e0), (s1, _e1)
                          in zip(merged, merged[1:])), reverse=True)[:10]
        for length, e0, s1 in between:
            name = (f"idle after {trace._program_at(mods, e0 - 1)}, "
                    f"before {trace._program_at(mods, s1)}")
            cover = _overlaps([(e0, s1)], segs)
            cover.pop(NONE, None)
            if cover:
                top = max(cover, key=cover.get)
                if 2 * cover[top] > length:
                    name += f"; host: {top}"
            gaps.append((name, length * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(trace.Summary)}
    fields["gaps"] = gaps
    return SpanSummary(
        **fields, spans=spans, idle_by_span=idle,
        idle_host_busy_s=sum(s for n, s in idle.items()
                             if n != NONE and n not in WAITS),
    )


def reduce_file(path: str, window_s: float) -> SpanSummary:
    from jax.profiler import ProfileData

    return reduce_space(ProfileData.from_file(path), window_s)


# -- what the metric readers share ---------------------------------------
def span_seconds(ctx, *names):
    """Seconds inside the named spans over the traced window, or None
    without a trace of spans or where none of them ran."""
    spans = getattr(ctx.trace, "spans", None)
    if not spans or not any(spans.get(n, (0, 0.0))[0] for n in names):
        return None
    return sum(spans.get(n, (0, 0.0))[1] for n in names)


def timestamps(records, *names):
    """Each served record's ``names`` timestamps, or None where a served
    record lacks one."""
    out = []
    for r in records:
        if r.status != "served":
            continue
        ts = [getattr(r, n, None) for n in names]
        if None in ts:
            return None
        out.append(ts)
    return out


def copy_timestamps(rec, ticket) -> None:
    """Put the ticket's timestamps on its record (None where it has
    none)."""
    for name in TIMESTAMPS:
        setattr(rec, name, getattr(ticket, name, None))


# -- one traced run --------------------------------------------------------
def _at_p95(records, *stamps):
    """The served record at the client's p95 latency (from due to done)
    among those that carry ``stamps``, and that latency in seconds; None
    where there is none."""
    import numpy as np

    done = [r for r in records if r.status == "served"
            and all(getattr(r, s, None) is not None for s in stamps)]
    if not done:
        return None
    lat = np.array([r.done - r.due for r in done])
    i = int(np.argsort(lat, kind="stable")[
        int(np.ceil(0.95 * (len(lat) - 1)))])
    return done[i], float(lat[i])


def _p95_walk_parts(walks) -> dict | None:
    """The walk at the client's p95: its client latency and, on the
    server's clock, its queue wait and service time."""
    at = _at_p95(walks, "taken_at", "done_at")
    if at is None:
        return None
    w, lat = at
    return {"client_ms": 1e3 * lat,
            "queue_ms": 1e3 * (w.taken_at - w.submitted_at),
            "service_ms": 1e3 * (w.done_at - w.taken_at)}


def _p95_ack_parts(updates) -> dict | None:
    """The update at the client's p95 ack latency: its client latency
    and, on the server's clock, its wait in the writer's queue, its
    apply (the group's earlier members, WAL append and fused update),
    and its wait from applied to acked by a sealed generation."""
    at = _at_p95(updates, "taken_at", "applied_at", "acked_at")
    if at is None:
        return None
    u, lat = at
    return {"client_ms": 1e3 * lat,
            "queue_ms": 1e3 * (u.taken_at - u.submitted_at),
            "apply_ms": 1e3 * (u.applied_at - u.taken_at),
            "seal_wait_ms": 1e3 * (u.acked_at - u.applied_at)}


def traced_run(root: str, workload: str, seed: int, seconds: float,
               **kw) -> dict:
    """One traced run of ``workload`` with the host spans read."""
    from chipbench import manifest, run

    s = run.build(root, workload, seed, trace=True, **kw)
    settle = s.traffic._settle

    def settle_with_timestamps(rec, ticket, now):
        settle(rec, ticket, now)
        copy_timestamps(rec, ticket)

    s.traffic._settle = settle_with_timestamps
    w = run.window(s, seconds, True)
    summary = reduce_file(trace.find_xplane(w.trace_dir), w.traced_s)
    out = run.finish(s, w, True)
    ctx = SimpleNamespace(
        trace=summary, walks=s.traffic.log.walks,
        updates=s.traffic.log.updates, stats0=w.stats0, stats1=w.stats1,
        final_stats=w.final_stats,
    )
    suffix = workload.split(".", 1)[1]
    for quantity, unit in METRICS:
        name = f"{quantity}.{suffix}"
        value = manifest.load_metric(root, name).read(ctx)
        if value is not None:
            out["metrics"][name] = {"value": float(value), "unit": unit}
    out["breakdown"] = summary.breakdown()
    out["walk_p95_parts"] = _p95_walk_parts(s.traffic.log.walks)
    out["ack_p95_parts"] = _p95_ack_parts(s.traffic.log.updates)
    out["checks"] = out.pop("checks")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from chipbench import run

    if jax.default_backend() != "tpu":
        print(f"chipbench.spans: needs a TPU; JAX found platform "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    run.use_compile_cache(ROOT)
    out = traced_run(ROOT, args.workload, args.seed, args.seconds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
