"""The program's host spans on the device timeline: a trace written out
by hand, the v5e trace, the readers of the span metrics, and the traced
run that feeds them."""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from chipbench import manifest, spans, trace
from chipbench.tests.test_trace import DATA, HAND, ROOT

#: ``HAND``'s device (busy [1, 6) and [9, 11) us) under host spans, in us:
#: the dispatcher prepares [0.5, 1), waits on the device [1, 6), fetches
#: [6, 6.2) and waits for a request [6.2, 12) inside a Python tracer
#: event; the writer waits [0.5, 6.5), appends [6.5, 7), plans [7, 7.4),
#: dispatches [7.4, 11) beside a JAX event, and seals [11, 12).  The
#: window is 12 us from the first event, 0.5 us: [0.5, 12.5).
HOST = """
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 11500000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 200000 }
    events { metadata_id: 5 offset_ps: 6200000 duration_ps: 5800000 }
  }
  lines {
    id: 2
    name: "python"
    events { metadata_id: 6 offset_ps: 500000 duration_ps: 6000000 }
    events { metadata_id: 7 offset_ps: 6500000 duration_ps: 500000 }
    events { metadata_id: 8 offset_ps: 7000000 duration_ps: 400000 }
    events { metadata_id: 9 offset_ps: 7400000 duration_ps: 3600000 }
    events { metadata_id: 10 offset_ps: 11000000 duration_ps: 1000000 }
  }
  lines {
    id: 3
    name: "tf_XLAPjRtCpuClient"
    events { metadata_id: 11 offset_ps: 7400000 duration_ps: 200000 }
  }
  event_metadata { key: 1 value { id: 1 name: "$serve.py:800 _dispatch_loop" } }
  event_metadata { key: 2 value { id: 2 name: "serve.walk.prepare#batch=1#" } }
  event_metadata { key: 3 value { id: 3 name: "serve.walk.device#batch=1,gen=0,walks=1#" } }
  event_metadata { key: 4 value { id: 4 name: "serve.walk.fetch#batch=1,gen=0,walks=1#" } }
  event_metadata { key: 5 value { id: 5 name: "serve.dispatch.wait" } }
  event_metadata { key: 6 value { id: 6 name: "serve.writer.wait" } }
  event_metadata { key: 7 value { id: 7 name: "durable.wal_append#seq=1#" } }
  event_metadata { key: 8 value { id: 8 name: "digraph.plan" } }
  event_metadata { key: 9 value { id: 9 name: "slot_update.dispatch" } }
  event_metadata { key: 10 value { id: 10 name: "serve.seal#gen=1#" } }
  event_metadata { key: 11 value { id: 11 name: "PjitFunction(fn)" } }
}
"""
WINDOW_S = 12e-6


def _space(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def hand():
    text = HAND.replace('planes { id: 2 name: "/host:CPU" }', HOST)
    return spans.reduce_space(_space(text), WINDOW_S)


def _us(d):
    return {k: pytest.approx(v * 1e-6) for k, v in d.items()}


def test_span_sums_by_hand(hand):
    assert {n: c for n, (c, _s) in hand.spans.items()} == \
        {n: 1 for n, _wait in spans.SPANS}
    assert {n: s for n, (_c, s) in hand.spans.items()} == _us({
        "serve.walk.prepare": 0.5, "serve.walk.device": 5.0,
        "serve.walk.fetch": 0.2, "serve.dispatch.wait": 5.8,
        "serve.writer.wait": 6.0, "durable.wal_append": 0.5,
        "digraph.plan": 0.4, "slot_update.dispatch": 3.6,
        "serve.seal": 1.0,
    })
    # the device side is trace.py's own
    assert hand.busy_s == pytest.approx(7e-6)
    assert hand.modules == pytest.approx(
        {"jit_slot_walk_multi_blocked": 5e-6, "jit_fn": 2e-6})


def test_idle_split_by_span_by_hand(hand):
    """Work before wait across threads, the first wait of ``SPANS``
    between two waits, ``none`` where no span is open."""
    assert hand.idle_by_span == _us({
        "serve.walk.prepare": 0.5, "serve.walk.fetch": 0.2,
        "serve.dispatch.wait": 0.3, "durable.wal_append": 0.5,
        "digraph.plan": 0.4, "slot_update.dispatch": 1.6,
        "serve.seal": 1.0, "none": 0.5,
    })
    idle = hand.window_s - hand.busy_s
    assert sum(hand.idle_by_span.values()) == pytest.approx(idle)
    assert hand.idle_host_busy_s == pytest.approx(4.2e-6)


def test_gap_names_the_span_that_covers_most_of_it(hand):
    # slot_update.dispatch holds 1.6 of the 3 us gap
    (gap,) = hand.gaps
    assert gap[0] == ("idle after jit_slot_walk_multi_blocked, before jit_fn; "
                      "host: slot_update.dispatch")
    assert gap[1] == pytest.approx(3e-6)
    b = hand.breakdown()
    assert set(b) == {"device_ops", "idle_gaps", "host_spans", "idle_by_span"}
    assert b["host_spans"][0] == ["serve.writer.wait", pytest.approx(6e-6)]
    assert len(b["host_spans"]) == 9
    assert b["idle_by_span"][0] == ["slot_update.dispatch",
                                    pytest.approx(1.6e-6)]


def test_a_gap_no_span_covers_keeps_its_name():
    """Half a gap is not most of it; the gap without spans is trace.py's."""
    segs = spans.label_segments(
        [[(0, 10, "serve.seal")], [(0, 20, "serve.writer.wait")]], 0, 20)
    assert segs == [(0, 10, "serve.seal"), (10, 20, "serve.writer.wait")]
    # the recorded trace runs 0.33 s from its first (Python tracer) event
    s = spans.reduce_file(os.path.join(DATA, "tiny_v5e.xplane.pb"), 0.33)
    base = trace.reduce_file(os.path.join(DATA, "tiny_v5e.xplane.pb"), 0.33)
    assert s.gaps == base.gaps and s.spans == {}
    assert s.idle_by_span == {"none": pytest.approx(0.33 - base.busy_s)}
    assert s.idle_host_busy_s == 0.0
    # a program without spans (the parent commit's) reads nothing
    assert _read("idle_host_busy", _ctx(s)) is None


def _walk(submitted, taken, status="served"):
    return SimpleNamespace(status=status, submitted_at=submitted,
                           taken_at=taken)


def _ctx(summary, walks=()):
    return SimpleNamespace(
        trace=summary, walks=list(walks),
        updates=[SimpleNamespace(status="served"),
                 SimpleNamespace(status="rejected")],
        stats0={"batches": 3, "seals": 5},
        final_stats={"batches": 4, "seals": 6},
    )


def _read(name, ctx):
    return manifest.load_metric(ROOT, f"{name}.walks").read(ctx)


def test_the_six_readers_by_hand(hand):
    walks = [_walk(0.0, 0.2), _walk(0.0, 1.0), _walk(0.5, 0.6),
             SimpleNamespace(status="rejected")]
    ctx = _ctx(hand, walks)
    want = {
        "walk_queue_p95_ms": 1000.0,        # the highest of 0.1, 0.2, 1.0 s
        "walk_host_ms": 0.7e-3,             # (0.5 + 0.2) us / 1 batch
        "wal_append_ms": 0.5e-3,            # 0.5 us / 1 acked batch
        "update_dispatch_ms": 3.6e-3,
        "seal_ms": 1.0e-3,                  # 1 us / 1 seal
        "idle_host_busy": 35.0,             # 4.2 of 12 us
    }
    assert {q for q, _unit in spans.METRICS} == set(want)
    for name, value in want.items():
        assert _read(name, ctx) == pytest.approx(value), name


@pytest.mark.parametrize("name", [q for q, _unit in spans.METRICS])
def test_readers_give_nothing_without_spans_or_timestamps(name, hand):
    from jax.profiler import ProfileData

    old = trace.reduce_space(ProfileData.from_text_proto(HAND), WINDOW_S)
    for summary in (None, old):
        assert _read(name, _ctx(summary, [_walk(None, None)])) is None
    if name == "walk_queue_p95_ms":
        assert _read(name, _ctx(hand, [_walk(0.0, None)])) is None
        assert _read(name, _ctx(hand, [SimpleNamespace(status="served")])) \
            is None


def test_timestamps_are_copied_off_tickets():
    rec = SimpleNamespace()
    spans.copy_timestamps(rec, SimpleNamespace(submitted_at=1.0, taken_at=2.0,
                                               done_at=3.0))
    assert (rec.submitted_at, rec.taken_at, rec.done_at) == (1.0, 2.0, 3.0)
    assert rec.applied_at is None and rec.acked_at is None


def _served(due, done, **stamps):
    return SimpleNamespace(status="served", due=due, done=done, **stamps)


def test_p95_parts_of_a_walk_and_an_ack_by_hand():
    """The record at the client's p95 (``method="higher"``) is split on
    the server's clock; a record without the stamps is left out."""
    walks = [_served(0.0, 1.0 + i, submitted_at=10.0, taken_at=10.5 + i,
                     done_at=11.0 + i) for i in range(3)]
    walks.append(_served(0.0, 9.0, taken_at=None))
    assert spans._p95_walk_parts(walks) == {
        "client_ms": 3000.0, "queue_ms": 2500.0, "service_ms": 500.0}
    ups = [_served(0.0, 2.0, submitted_at=5.0, taken_at=5.25,
                   applied_at=5.5, acked_at=6.5),
           _served(0.0, 1.0, submitted_at=5.0, taken_at=5.0,
                   applied_at=5.5, acked_at=5.75),
           SimpleNamespace(status="rejected", due=0.0, done=9.0)]
    assert spans._p95_ack_parts(ups) == {
        "client_ms": 2000.0, "queue_ms": 250.0, "apply_ms": 250.0,
        "seal_wait_ms": 1000.0}
    assert spans._p95_ack_parts(ups[2:]) is None
    assert spans._p95_ack_parts([_served(0.0, 1.0, taken_at=1.0,
                                         applied_at=None, acked_at=None)]) \
        is None


def test_a_traced_run_reads_the_span_metrics(monkeypatch):
    """The run's wiring on the CPU at a tiny size: real tickets give the
    records their timestamps; the hand trace stands in for the chip's,
    which the CPU does not write."""
    from chipbench.tests import test_harness

    text = HAND.replace('planes { id: 2 name: "/host:CPU" }', HOST)
    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace, "reduce_file", lambda p, w: trace.reduce_space(
        _space(HAND), WINDOW_S))
    monkeypatch.setattr(spans, "reduce_file", lambda p, w: spans.reduce_space(
        _space(text), WINDOW_S))
    cell = "osm-road.walks"
    out = spans.traced_run(ROOT, cell, test_harness.SEED, 1.5,
                           overrides=test_harness._tiny(cell))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert m["walk_queue_p95_ms.walks"]["value"] >= 0
    assert m["idle_host_busy.walks"]["value"] == pytest.approx(35.0)
    assert m["walk_device_ms.walks"]["unit"] == "ms"
    assert {"host_spans", "idle_by_span"} <= set(out["breakdown"])
    parts = out["walk_p95_parts"]
    assert parts["queue_ms"] >= 0 and parts["service_ms"] > 0
    parts = out["ack_p95_parts"]
    assert min(parts.values()) >= 0 and parts["seal_wait_ms"] > 0


def test_importing_spans_loads_no_jax():
    code = "import sys; import chipbench.spans; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out.stderr


def test_no_tpu_no_result(capsys):
    assert spans.main(["--workload", "osm-road.walks", "--seed", "1",
                       "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
