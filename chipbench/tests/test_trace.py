"""The trace reducer and the roofline arithmetic, on a trace written out
by hand and on a small trace recorded on a TPU v5e."""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from chipbench import manifest, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: two programs on one device: walk ops [0, 3) and [2, 5) us, update op
#: [8, 10) us, offsets from 1 us; busy 7 us, one 3 us gap between them
HAND = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 2000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_slot_walk_multi_blocked(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_fn(9)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "custom-call.2" } }
}
planes { id: 2 name: "/host:CPU" }
"""


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData

    return trace.reduce_space(ProfileData.from_text_proto(HAND), 20e-6)


def test_busy_idle_and_program_times_by_hand(hand):
    assert hand.devices == 1
    assert hand.busy_s == pytest.approx(7e-6)
    assert hand.idle_share == pytest.approx(1 - 7 / 20)
    assert hand.modules == pytest.approx(
        {"jit_slot_walk_multi_blocked": 5e-6, "jit_fn": 2e-6})
    assert hand.top_ops[0][0] == "fusion.1"
    assert trace.op_name("%fusion.9 = f32[8,128]{1,0:T(8,128)} fusion(%a)") == \
        "%fusion.9 = f32[8,128]"
    assert hand.top_ops[0][1] == pytest.approx(5e-6)
    (gap,) = hand.gaps
    assert gap[0] == "idle after jit_slot_walk_multi_blocked, before jit_fn"
    assert gap[1] == pytest.approx(3e-6)
    b = hand.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_roofline_arithmetic_by_hand(hand):
    # 819 MB in 2 ms at 819 GB/s: the least time is 1 ms, half the roofline
    assert trace.roofline_share(819e6, 2e-3, 819e9) == pytest.approx(0.5)
    peaks = trace.load_peaks(ROOT)["TPU v5 lite"]
    ctx = SimpleNamespace(trace=hand, walk_bytes=819e9 * 1e-6,
                          peaks=peaks)
    share = manifest.load_metric(ROOT, "walk_roofline.saturated").read(ctx)
    # 1 us of bytes at peak over the walk program's 5 us
    assert share == pytest.approx(20.0)
    ctx.updates = [SimpleNamespace(status="served")] * 4
    ms = manifest.load_metric(ROOT, "update_device_ms.walks").read(ctx)
    assert ms == pytest.approx(2e-3 / 4)
    idle = manifest.load_metric(ROOT, "device_idle_share.saturated").read(ctx)
    assert idle == pytest.approx(65.0)


def test_readers_give_nothing_without_a_trace():
    ctx = SimpleNamespace(trace=None, walk_bytes=None, peaks=None, updates=[])
    for name in ("walk_roofline.walks", "update_device_ms.walks",
                 "device_idle_share.walks", "walk_device_ms.walks"):
        assert manifest.load_metric(ROOT, name).read(ctx) is None


def test_a_trace_without_a_device_is_refused():
    from jax.profiler import ProfileData

    with pytest.raises(ValueError):
        trace.reduce_space(ProfileData.from_text_proto(
            'planes { id: 2 name: "/host:CPU" }'), 1.0)


def test_importing_the_reducer_loads_no_jax():
    code = "import sys; import chipbench.trace; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out.stderr


def test_a_trace_recorded_on_a_v5e():
    """``tiny_matmul`` and ``tiny_sum``, two jitted functions, three
    times each on one TPU v5e under the JAX profiler.  The values were
    computed by hand from the file: the union of the operation
    intervals on a 1 ns timeline, and the module events summed."""
    s = trace.reduce_file(os.path.join(DATA, "tiny_v5e.xplane.pb"), 0.05)
    assert s.devices == 1
    assert s.busy_s == pytest.approx(6_265_028e-9)
    assert s.idle_share == pytest.approx(1 - 6_265_028e-9 / 0.05)
    assert s.modules == pytest.approx(
        {"jit_tiny_matmul": 75_367e-9, "jit_tiny_sum": 6_189_742e-9})
    assert [round(g[1] * 1e9) for g in s.gaps[:3]] == \
        [12_152_978, 11_866_310, 11_740_720]
    assert s.gaps[0][0] == "idle after jit_tiny_matmul, before jit_tiny_sum"
    assert s.top_ops[0][0] == "%fusion = f32[8,8192,128]"
