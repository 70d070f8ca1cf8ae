"""From a profiler trace to device metrics.

The JAX profiler writes an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``.  Each device is a plane named
``/device:TPU:<i>``; its ``XLA Modules`` line holds one event per run of
a compiled program (named after the jitted function, ``jit_<name>``),
and its ``XLA Ops`` line one event per operation.

- busy time is the union of the operation intervals on a device,
  averaged over the devices traced;
- a program's device time is the sum of its module events;
- the idle gaps are the stretches between busy intervals, each named by
  the programs on either side (the program has no host spans yet).

Nothing here loads a TPU library: the trace is read with the profiler's
own reader.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the served walk program's module name
WALK_PROGRAM = "jit_slot_walk_multi_blocked"
_MODULE_ID = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_fn(123)`` -> ``jit_fn``."""
    return _MODULE_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.9 = f32[8,128]{1,0} fusion(...)`` -> ``%fusion.9 = f32[8,128]``."""
    head, _, rest = event_name.partition(" = ")
    return f"{head} = {rest.split('{')[0].split(' ')[0]}" if rest else head


def union_length(intervals) -> tuple:
    """(total length of the union, the merged intervals) of
    ``[(start, end), ...]``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


@dataclass
class Summary:
    busy_s: float
    window_s: float
    devices: int
    #: device seconds per program (module) name, over all devices
    modules: dict = field(default_factory=dict)
    #: (op name, device seconds), most first
    top_ops: list = field(default_factory=list)
    #: (description, seconds), longest first
    gaps: list = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {
            "device_ops": [[n, s] for n, s in self.top_ops[:10]],
            "idle_gaps": [[n, s] for n, s in self.gaps[:10]],
        }


def _program_at(mod_iv, t) -> str:
    """The last program that started at or before ``t``."""
    best = "no program"
    for s, _e, n in mod_iv:
        if s > t:
            break
        best = n
    return best


def reduce_space(space, window_s: float) -> Summary:
    """Reduce a loaded ``ProfileData`` whose traced window lasted
    ``window_s`` seconds on the host clock."""
    busy_total, devices = 0.0, 0
    modules: dict = {}
    ops: dict = {}
    gaps = []
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        devices += 1
        op_iv, mod_iv = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    op_iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    name = op_name(ev.name)
                    ops[name] = ops.get(name, 0.0) + ev.duration_ns * 1e-9
            elif line.name == "XLA Modules":
                for ev in line.events:
                    name = module_name(ev.name)
                    modules[name] = modules.get(name, 0.0) + ev.duration_ns * 1e-9
                    mod_iv.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
        busy, merged = union_length(op_iv)
        busy_total += busy * 1e-9
        mod_iv.sort()
        longest = sorted(
            ((s1 - e0, e0, s1) for (_s0, e0), (s1, _e1) in zip(merged, merged[1:])),
            reverse=True,
        )[:10]
        for length, e0, s1 in longest:
            gaps.append((f"idle after {_program_at(mod_iv, e0 - 1)}, "
                         f"before {_program_at(mod_iv, s1)}", length * 1e-9))
    if devices == 0:
        raise ValueError("the trace holds no TPU device plane")
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        busy_s=busy_total / devices, window_s=float(window_s),
        devices=devices, modules=modules,
        top_ops=sorted(ops.items(), key=lambda kv: -kv[1]), gaps=gaps,
    )


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_file(path: str, window_s: float) -> Summary:
    from jax.profiler import ProfileData

    return reduce_space(ProfileData.from_file(path), window_s)


def load_peaks(root: str) -> dict:
    """The peaks table, keyed by ``device_kind``."""
    with open(os.path.join(root, "chipbench", "peaks.json")) as f:
        return json.load(f)["devices"]


def roofline_share(bytes_needed: float, seconds: float,
                   bytes_per_s: float) -> float:
    """The share of the memory roofline a program reached: the least time
    its bytes need at peak bandwidth, over the time it took."""
    return bytes_needed / bytes_per_s / seconds
