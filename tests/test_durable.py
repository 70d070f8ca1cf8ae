"""Durability pipeline (DESIGN.md §13): WAL journal, checkpoint/restore
bit-parity, crash-recovery sweeps over every representation × injection
point, the kernel fallback chain, and the cross-layer invariant audit."""
import json
import os
import shutil

import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.core import REPRESENTATIONS, csr as csr_mod, edgebatch, updates
from repro.kernels import fallback
from repro.runtime import durable, faultinject

N_V = 48
CRASH_POINTS = ("durable.pre_append", "durable.post_append", "durable.post_apply")


@pytest.fixture(autouse=True)
def _clean_fault_state():
    faultinject.disarm()
    fallback.BREAKER.reset()
    fallback.LAST_USED.clear()
    yield
    faultinject.disarm()
    fallback.BREAKER.reset()
    fallback.LAST_USED.clear()


@pytest.fixture(scope="module")
def base_csr():
    rng = np.random.default_rng(11)
    m = 220
    return csr_mod.from_coo(
        rng.integers(0, N_V, m),
        rng.integers(0, N_V, m),
        rng.random(m).astype(np.float32),
        n=N_V,
    )


def make_plans(k=6, seed=7, n=N_V):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        ib = edgebatch.from_arrays(
            rng.integers(0, n, 12),
            rng.integers(0, n, 12),
            rng.random(12).astype(np.float32),
        )
        db = edgebatch.from_arrays(rng.integers(0, n, 6), rng.integers(0, n, 6))
        out.append(updates.plan_update(inserts=ib, deletes=db))
    return out


def dense_oracle(rep):
    c = rep.to_csr()
    return (
        np.asarray(c.offsets),
        np.asarray(c.dst)[: c.m],
        np.asarray(c.wgt)[: c.m],
    )


def assert_bit_parity(a, b):
    for x, y in zip(dense_oracle(a), dense_oracle(b)):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# WAL record / journal mechanics
# ---------------------------------------------------------------------------


def test_wal_record_roundtrip():
    plan = make_plans(1)[0]
    rec = durable.encode_record(9, 77, plan)
    seq, nv, (qs, qd, qw, ql) = durable.decode_record(
        rec[: durable._HEADER.size], rec[durable._HEADER.size :]
    )
    assert (seq, nv) == (9, 77)
    np.testing.assert_array_equal(qs, plan.q_src)
    np.testing.assert_array_equal(qd, plan.q_dst)
    np.testing.assert_array_equal(qw, plan.q_wgt)
    np.testing.assert_array_equal(ql, plan.q_del)


def test_journal_append_replay_rotation(tmp_path):
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal, segment_bytes=256)  # force rotation
    plans = make_plans(5)
    seqs = [j.append(p, N_V) for p in plans]
    assert seqs == [1, 2, 3, 4, 5]
    assert len(j.segments()) > 1  # each ~240-byte record rotates
    j.close()
    j2 = durable.UpdateJournal(wal, segment_bytes=256)
    got = list(j2.replay())
    assert [s for s, _, _ in got] == seqs
    for (_, nv, (qs, qd, qw, ql)), p in zip(got, plans):
        assert nv == N_V
        np.testing.assert_array_equal(qs, p.q_src)
        np.testing.assert_array_equal(qw, p.q_wgt)
    assert [s for s, _, _ in j2.replay(after=3)] == seqs[3:]
    assert j2.next_seq == 6  # reopen resumes the sequence
    j2.close()


def test_journal_truncate_through(tmp_path):
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal, segment_bytes=256)
    for p in make_plans(6):
        j.append(p, N_V)
    n_before = len(j.segments())
    assert n_before >= 3
    j.truncate_through(6)
    # everything but the append-target segment is redundant
    assert len(j.segments()) == 1
    # the surviving records still replay cleanly
    assert all(s <= 6 for s, _, _ in j.replay())
    j.close()


def test_torn_tail_repaired_on_recovery_open(tmp_path):
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal)
    for p in make_plans(3):
        j.append(p, N_V)
    j.close()
    seg = j.segments()[-1]
    faultinject.tear_tail(seg, 10)  # torn mid-record at the tail
    j2 = durable.UpdateJournal(wal, repair=True)
    assert [s for s, _, _ in j2.replay()] == [1, 2]  # record 3 cut
    assert j2.next_seq == 3  # its sequence number is reused
    j2.close()


def test_corrupt_record_raises(tmp_path):
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal)
    for p in make_plans(3):
        j.append(p, N_V)
    j.close()
    seg = j.segments()[0]
    # flip a payload byte of the FIRST record: complete but rotten
    faultinject.corrupt_byte(seg, durable._HEADER.size + 3)
    with pytest.raises(durable.WalCorruptError):
        list(durable.UpdateJournal(wal).replay())
    # repair refuses too — truncating would drop acknowledged updates
    with pytest.raises(durable.WalCorruptError):
        durable.UpdateJournal(wal, repair=True)


def test_scan_next_seq_reads_final_segment_only(tmp_path):
    """Opening a journal must not decode the whole log: a rotten byte in
    an EARLIER segment is invisible to the open (filenames carry
    first_seq, only the final segment is walked) but still fatal to a
    full replay."""
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal, segment_bytes=256)
    for p in make_plans(6):
        j.append(p, N_V)
    j.close()
    segs = j.segments()
    assert len(segs) >= 3
    faultinject.corrupt_byte(segs[0], durable._HEADER.size + 3)
    j2 = durable.UpdateJournal(wal, segment_bytes=256)  # opens fine
    assert j2.next_seq == 7
    with pytest.raises(durable.WalCorruptError):
        list(j2.replay())  # the full decode still sees the rot
    j2.close()


def test_scan_next_seq_torn_final_segment(tmp_path):
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal, segment_bytes=256)
    for p in make_plans(4):
        j.append(p, N_V)
    j.close()
    faultinject.tear_tail(j.segments()[-1], 10)
    # without repair the torn record is simply not counted
    j2 = durable.UpdateJournal(wal, segment_bytes=256)
    assert j2.next_seq == 4
    j2.close()


def test_journal_fsync_rotation_durable(tmp_path):
    """fsync=True also fsyncs the WAL directory after each rotation (the
    new segment NAME must survive power loss, not just its bytes)."""
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal, segment_bytes=256, fsync=True)
    plans = make_plans(5)
    for p in plans:
        j.append(p, N_V)
    assert len(j.segments()) > 1  # rotation happened under fsync
    assert [s for s, _, _ in j.replay()] == [1, 2, 3, 4, 5]
    j.close()


def test_group_append_one_flush_one_segment(tmp_path):
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal, segment_bytes=256)
    plans = make_plans(4)
    f0 = j.flushes
    seqs = j.append_group(plans, [N_V] * 4)
    assert seqs == [1, 2, 3, 4] and j.flushes - f0 == 1
    # a group never splits across segments: all records in one file
    assert len(j.segments()) == 1
    got = list(j.replay())
    assert [s for s, _, _ in got] == seqs
    for (_, _, (qs, _, _, _)), p in zip(got, plans):
        np.testing.assert_array_equal(qs, p.q_src)
    # the NEXT group rotates first (segment is over budget), then lands
    j.append_group(make_plans(2, seed=5), [N_V] * 2)
    assert len(j.segments()) == 2
    assert [s for s, _, _ in j.replay()] == [1, 2, 3, 4, 5, 6]
    j.close()


# ---------------------------------------------------------------------------
# WAL segment-write hardening (ENOSPC / short write, §17 satellite)
# ---------------------------------------------------------------------------
def test_wal_disk_full_rolls_back_and_retries(tmp_path):
    """A failed segment write surfaces as WalDiskFullError with the
    prior segment contents intact and the sequence NOT burned — the
    same journal object retries the same plan under the same seq."""
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal)
    plans = make_plans(3)
    j.append(plans[0], N_V)
    seq0, flushes0 = j.next_seq, j.flushes
    size0 = os.path.getsize(j.segments()[-1])
    faultinject.arm("wal.write", times=1)
    with pytest.raises(durable.WalDiskFullError):
        j.append(plans[1], N_V)
    assert j.next_seq == seq0  # the failed record's seq is reusable
    assert j.flushes == flushes0  # no flush accounted for a dead write
    assert os.path.getsize(j.segments()[-1]) == size0  # truncated back
    assert [s for s, _, _ in j.replay()] == [1]  # prior record intact
    seq = j.append(plans[1], N_V)  # retry on the SAME handle
    assert seq == seq0
    assert [s for s, _, _ in j.replay()] == [1, 2]
    # ...and a reopened journal agrees (the reopened "ab" handle works)
    j.append(plans[2], N_V)
    j.close()
    j2 = durable.UpdateJournal(wal)
    assert [s for s, _, _ in j2.replay()] == [1, 2, 3]
    j2.close()


def test_wal_disk_full_group_append_atomic(tmp_path):
    """append_group is one buffered write: a disk-full fault loses the
    WHOLE group atomically, and the retry reuses the same seqs."""
    wal = str(tmp_path / "wal")
    j = durable.UpdateJournal(wal)
    base = make_plans(2, seed=3)
    j.append_group(base, [N_V] * 2)
    group = make_plans(3, seed=4)
    faultinject.arm("wal.write", times=1)
    with pytest.raises(durable.WalDiskFullError):
        j.append_group(group, [N_V] * 3)
    assert j.next_seq == 3
    assert [s for s, _, _ in j.replay()] == [1, 2]  # no torn group suffix
    assert j.append_group(group, [N_V] * 3) == [3, 4, 5]
    assert [s for s, _, _ in j.replay()] == [1, 2, 3, 4, 5]
    j.close()


# ---------------------------------------------------------------------------
# checkpoint manager: stale sweep, legacy manifests, diff chains
# ---------------------------------------------------------------------------
def test_clean_stale_sweeps_tmp_dirs(tmp_path):
    cd = str(tmp_path / "ckpt")
    ckpt.save_arrays(cd, 0, {"a": np.arange(4)})
    os.makedirs(os.path.join(cd, ".tmp_ckpt_dead1", "sub"))
    os.makedirs(os.path.join(cd, ".tmp_ckpt_dead2"))
    removed = ckpt.clean_stale(cd)
    assert sorted(removed) == [".tmp_ckpt_dead1", ".tmp_ckpt_dead2"]
    assert not [n for n in os.listdir(cd) if n.startswith(".tmp_ckpt_")]
    # committed steps are untouched, and a second sweep is a no-op
    assert ckpt.all_steps(cd) == [0]
    assert ckpt.clean_stale(cd) == []


def test_legacy_flat_manifest_restores(tmp_path):
    """Pre-§14 manifests (no "shards" key, flat keys/shapes/dtypes) must
    keep restoring through every entry point."""
    cd = str(tmp_path / "ckpt")
    d = os.path.join(cd, "step_0000000007")
    os.makedirs(d)
    arrays = {"dst": np.arange(10, dtype=np.int32), "deg": np.ones(5, np.int64)}
    np.savez(os.path.join(d, "shard_0.npz"), **arrays)
    manifest = {
        "step": 7,
        "keys": sorted(arrays),
        "shapes": {k: list(v.shape) for k, v in arrays.items()},
        "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
    }
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    got, step = ckpt.restore_arrays(cd)
    assert step == 7
    np.testing.assert_array_equal(got["dst"], arrays["dst"])
    shards, _ = ckpt.restore_arrays_sharded(cd)
    assert list(shards) == [0]
    np.testing.assert_array_equal(shards[0]["deg"], arrays["deg"])
    # diff-aware chain restore treats it as a full base too
    trees, _ = ckpt.restore_arrays_diff(cd)
    np.testing.assert_array_equal(trees[0]["dst"], arrays["dst"])
    with pytest.raises(FileNotFoundError):
        ckpt.restore_arrays(cd, shard_id=1)


def test_manager_diff_chain_and_crc_gate(tmp_path):
    cd = str(tmp_path / "ckpt")
    rng = np.random.default_rng(3)
    a0 = {"dst": rng.integers(0, 99, 9000).astype(np.int32),
          "deg": rng.integers(0, 9, 300).astype(np.int64)}
    ckpt.save_arrays_sharded(cd, 0, {0: dict(a0)})
    a1 = {k: v.copy() for k, v in a0.items()}
    a1["dst"][4096 // 4 + 1] = 777  # second 16 KiB chunk
    # hash-compare diff, then a ranged-hint diff on top of it
    ckpt.save_arrays_diff(cd, 1, {0: a1})
    a2 = {k: v.copy() for k, v in a1.items()}
    a2["deg"][5] = 42
    hint = {0: {"dst": "clean", "deg": np.array([[5, 6]])}}
    p2 = ckpt.save_arrays_diff(cd, 2, {0: a2}, dirty=hint)
    man = ckpt._read_manifest(p2)
    assert man["kind"] == "diff" and man["base_step"] == 1
    for s, want in ((0, a0), (1, a1), (2, a2)):
        trees, _ = ckpt.restore_arrays_diff(cd, step=s)
        for k in want:
            np.testing.assert_array_equal(trees[0][k], want[k])
    # a digest that disagrees with the patched bytes must fail the gate
    man_path = os.path.join(p2, "manifest.json")
    man["shards"]["0"]["chunks"]["deg"][0] ^= 0xFF
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.raises(ValueError, match="CRC"):
        ckpt.restore_arrays_diff(cd, step=2)


def test_diff_rotation_keeps_chain_base(tmp_path):
    cd = str(tmp_path / "ckpt")
    a = {"x": np.arange(64, dtype=np.int64)}
    ckpt.save_arrays_sharded(cd, 0, {0: dict(a)})
    for s in (1, 2, 3, 4):
        ckpt.save_arrays_diff(cd, s, {0: dict(a)}, keep=2)
    steps = ckpt.all_steps(cd)
    assert 0 in steps  # the full base survives keep=2
    trees, _ = ckpt.restore_arrays_diff(cd)
    np.testing.assert_array_equal(trees[0]["x"], a["x"])
    # a NEW full step re-anchors; old chain becomes rotatable
    ckpt.save_arrays_sharded(cd, 5, {0: dict(a)}, keep=2)
    ckpt.save_arrays_sharded(cd, 6, {0: dict(a)}, keep=2)
    assert ckpt.all_steps(cd) == [5, 6]


# ---------------------------------------------------------------------------
# diff-chain pathologies (§17 satellite): a damaged or missing BASE must
# fail the restore atomically with a diagnosable error, never patch
# garbage; rotation must never orphan a kept diff's base mid-chain
# ---------------------------------------------------------------------------
def _diff_chain(tmp_path, nshards=1):
    cd = str(tmp_path / "ckpt")
    rng = np.random.default_rng(9)
    shards0 = {
        s: {"dst": rng.integers(0, 99, 4000).astype(np.int32) + s,
            "deg": rng.integers(0, 9, 64).astype(np.int64)}
        for s in range(nshards)
    }
    ckpt.save_arrays_sharded(cd, 0, {s: dict(t) for s, t in shards0.items()})
    shards1 = {s: {k: v.copy() for k, v in t.items()}
               for s, t in shards0.items()}
    for s in shards1:
        shards1[s]["dst"][7] = 12345 + s
    ckpt.save_arrays_diff(cd, 1, {s: dict(t) for s, t in shards1.items()})
    return cd, shards1


def test_restore_diff_corrupt_base_manifest_json(tmp_path):
    cd, _ = _diff_chain(tmp_path)
    man = os.path.join(cd, "step_0000000000", "manifest.json")
    with open(man, "w") as f:
        f.write('{"step": 0, "kind": "fu')  # torn JSON
    with pytest.raises(ValueError, match="corrupt"):
        ckpt.restore_arrays_diff(cd, step=1)
    with pytest.raises(ValueError, match="corrupt"):
        ckpt.restore_shard_diff(cd, 0, step=1)


def test_restore_diff_base_payload_digest_gate(tmp_path):
    """A base whose manifest digests disagree with its payload bytes is
    untrusted — the restore aborts BEFORE applying any diff patch."""
    cd, _ = _diff_chain(tmp_path)
    man_path = os.path.join(cd, "step_0000000000", "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    man["shards"]["0"]["chunks"]["dst"][0] ^= 0xFF
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.raises(ValueError, match="aborted before patching"):
        ckpt.restore_arrays_diff(cd, step=1)
    # the base itself (no chain, no patching) still restores by bytes
    assert ckpt.restore_arrays(cd, step=0) is not None


def test_restore_diff_missing_base_step(tmp_path):
    cd, _ = _diff_chain(tmp_path)
    shutil.rmtree(os.path.join(cd, "step_0000000000"))
    with pytest.raises((FileNotFoundError, ValueError)):
        ckpt.restore_arrays_diff(cd, step=1)
    with pytest.raises((FileNotFoundError, ValueError)):
        ckpt.restore_shard_diff(cd, 0, step=1)


def test_restore_shard_diff_matches_full_restore(tmp_path):
    cd, want = _diff_chain(tmp_path, nshards=2)
    full, step_f = ckpt.restore_arrays_diff(cd, step=1)
    for sid in (0, 1):
        arrays, step = ckpt.restore_shard_diff(cd, sid, step=1)
        assert step == step_f == 1
        for k in want[sid]:
            np.testing.assert_array_equal(arrays[k], want[sid][k])
            np.testing.assert_array_equal(arrays[k], full[sid][k])
    with pytest.raises(FileNotFoundError):
        ckpt.restore_shard_diff(cd, 7, step=1)


def test_rotation_never_orphans_mid_chain_base(tmp_path):
    """keep=N counts CHAIN-CLOSED prefixes: a kept diff's base must
    survive rotation even when an unrelated newer full exists."""
    cd = str(tmp_path / "ckpt")
    a = {"x": np.arange(32, dtype=np.int64)}
    ckpt.save_arrays_sharded(cd, 0, {0: dict(a)})
    ckpt.save_arrays_diff(cd, 1, {0: dict(a)}, keep=2)
    ckpt.save_arrays_sharded(cd, 2, {0: dict(a)}, keep=2)
    ckpt.save_arrays_diff(cd, 3, {0: dict(a)}, keep=2)
    steps = ckpt.all_steps(cd)
    # every surviving diff's base chain is closed
    for s in steps:
        man = ckpt._read_manifest(os.path.join(cd, f"step_{s:010d}"))
        if man.get("kind") == "diff":
            assert man["base_step"] in steps, f"diff {s} orphaned"
        trees, got = ckpt.restore_arrays_diff(cd, step=s)
        assert got == s and trees  # every kept step restores


# ---------------------------------------------------------------------------
# boundary validation
# ---------------------------------------------------------------------------


def test_edgebatch_rejects_nonfinite_weight():
    with pytest.raises(ValueError, match="non-finite"):
        edgebatch.from_arrays(
            np.array([0, 1]), np.array([1, 2]),
            np.array([1.0, np.nan], np.float32),
        )
    with pytest.raises(ValueError, match="non-finite"):
        edgebatch.from_arrays(
            np.array([0]), np.array([1]), np.array([np.inf], np.float32)
        )


def test_plan_from_canonical_rejects_unsorted_and_negative():
    with pytest.raises(ValueError, match="sorted"):
        updates.plan_from_canonical(
            np.array([1, 0], np.int32), np.array([0, 0], np.int32),
            np.ones(2, np.float32), np.zeros(2, bool),
        )
    with pytest.raises(ValueError, match="negative"):
        updates.plan_from_canonical(
            np.array([-1, 0], np.int32), np.array([0, 0], np.int32),
            np.ones(2, np.float32), np.zeros(2, bool),
        )
    with pytest.raises(ValueError, match="length"):
        updates.plan_from_canonical(
            np.array([0], np.int32), np.array([0, 1], np.int32),
            np.ones(2, np.float32), np.zeros(2, bool),
        )


def _nan_plan():
    # plan_from_canonical defers value checks to validate()/apply()
    return updates.plan_from_canonical(
        np.array([0, 1], np.int32), np.array([1, 2], np.int32),
        np.array([1.0, np.nan], np.float32), np.array([False, False]),
    )


@pytest.mark.parametrize("name", list(REPRESENTATIONS))
def test_apply_rejects_nan_weight_every_rep(name, base_csr):
    g = REPRESENTATIONS[name].from_csr(base_csr)
    with pytest.raises(ValueError, match="non-finite"):
        g.apply(_nan_plan())


def test_validate_vertex_bound_replay_only():
    plan = updates.plan_from_canonical(
        np.array([5], np.int32), np.array([7], np.int32),
        np.ones(1, np.float32), np.zeros(1, bool),
    )
    plan.validate()  # unbounded: fine (apply grows the vertex set)
    with pytest.raises(ValueError, match="bound"):
        plan.validate(num_vertices=7)  # replay watermark says <= 6


# ---------------------------------------------------------------------------
# checkpoint bit-parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(REPRESENTATIONS))
def test_checkpoint_roundtrip_bit_parity(name, base_csr, tmp_path):
    cls = REPRESENTATIONS[name]
    g = cls.from_csr(base_csr)
    plans = make_plans(4, seed=3)
    for p in plans[:2]:
        g, _ = g.apply(p)
    d = str(tmp_path / "ck")
    ckpt.save_arrays(d, 0, g.state_tree())
    arrays, step = ckpt.restore_arrays(d)
    h = cls.from_state_tree(arrays)
    assert_bit_parity(g, h)
    # the restored instance keeps applying in lockstep — exact state, not
    # just an equivalent edge set (arena geometry included)
    for p in plans[2:]:
        g, _ = g.apply(p)
        h, _ = h.apply(p)
    assert_bit_parity(g, h)
    np.testing.assert_array_equal(
        np.asarray(g.reverse_walk(3)), np.asarray(h.reverse_walk(3))
    )


# ---------------------------------------------------------------------------
# crash-recovery sweeps
# ---------------------------------------------------------------------------


def run_crash(cls, base_csr, tmp_path, point, kcrash=3, n_plans=6, seed=7):
    """Drive a durable stream into a crash at ``point``; return
    (recovered DurableGraph, uncrashed twin rep, remaining plans)."""
    wal, ck = str(tmp_path / "wal"), str(tmp_path / "ckpt")
    plans = make_plans(n_plans, seed=seed)
    g = durable.DurableGraph(cls.from_csr(base_csr), wal, ck)
    crashed = False
    for i, p in enumerate(plans):
        if i == kcrash:
            faultinject.arm(point)
        try:
            g.apply(p)
        except faultinject.SimulatedCrash:
            crashed = True
            break
        finally:
            faultinject.disarm()
    assert crashed
    g.close()
    r = durable.DurableGraph.recover(wal, ck)
    # pre-append: the crashed apply never hit the log; post-*: it did
    upto = kcrash if point == "durable.pre_append" else kcrash + 1
    twin = cls.from_csr(base_csr)
    for p in plans[:upto]:
        twin, _ = twin.apply(p)
    return r, twin, plans[upto:]


@pytest.mark.parametrize("point", CRASH_POINTS)
@pytest.mark.parametrize("name", list(REPRESENTATIONS))
def test_crash_recovery_bit_parity(name, point, base_csr, tmp_path):
    r, twin, rest = run_crash(REPRESENTATIONS[name], base_csr, tmp_path, point)
    assert_bit_parity(r.rep, twin)
    np.testing.assert_array_equal(
        np.asarray(r.rep.reverse_walk(3)), np.asarray(twin.reverse_walk(3))
    )
    # the recovered stream keeps going — and stays in lockstep
    for p in rest:
        r.apply(p)
        twin, _ = twin.apply(p)
    assert_bit_parity(r.rep, twin)
    r.close()


def test_crash_with_torn_tail(base_csr, tmp_path):
    cls = REPRESENTATIONS["digraph"]
    wal, ck = str(tmp_path / "wal"), str(tmp_path / "ckpt")
    plans = make_plans(4)
    g = durable.DurableGraph(cls.from_csr(base_csr), wal, ck)
    for p in plans:
        g.apply(p)
    g.close()
    # the final append itself was torn mid-write: record 4 is damaged
    faultinject.tear_tail(g.journal.segments()[-1], 7)
    r = durable.DurableGraph.recover(wal, ck)
    twin = cls.from_csr(base_csr)
    for p in plans[:3]:
        twin, _ = twin.apply(p)
    assert r.seq == 3
    assert_bit_parity(r.rep, twin)
    r.close()


def test_interrupted_checkpoint_leaves_debris_and_recovers(base_csr, tmp_path):
    cls = REPRESENTATIONS["lazy"]
    wal, ck = str(tmp_path / "wal"), str(tmp_path / "ckpt")
    plans = make_plans(3)
    g = durable.DurableGraph(cls.from_csr(base_csr), wal, ck)
    for p in plans[:2]:
        g.apply(p)
    faultinject.arm("checkpoint.pre_rename")
    with pytest.raises(faultinject.SimulatedCrash):
        g.checkpoint()
    faultinject.disarm()
    g.close()
    debris = [n for n in os.listdir(ck) if n.startswith(".tmp_ckpt_")]
    assert debris  # a real crash leaves the tmp dir behind
    r = durable.DurableGraph.recover(wal, ck)
    assert not [n for n in os.listdir(ck) if n.startswith(".tmp_ckpt_")]
    twin = cls.from_csr(base_csr)
    for p in plans[:2]:
        twin, _ = twin.apply(p)
    assert_bit_parity(r.rep, twin)  # step-0 base + full WAL replay
    r.close()


def test_auto_checkpoint_prunes_wal(base_csr, tmp_path):
    cls = REPRESENTATIONS["coo"]
    wal, ck = str(tmp_path / "wal"), str(tmp_path / "ckpt")
    g = durable.DurableGraph(
        cls.from_csr(base_csr), wal, ck,
        checkpoint_every=2, segment_bytes=256,
    )
    plans = make_plans(6, seed=5)
    for p in plans:
        g.apply(p)
    assert ckpt.latest_step(ck) == 6
    assert len(g.journal.segments()) == 1  # pruned behind the checkpoint
    g.close()
    r = durable.DurableGraph.recover(wal, ck)
    twin = cls.from_csr(base_csr)
    for p in plans:
        twin, _ = twin.apply(p)
    assert_bit_parity(r.rep, twin)
    r.close()


def test_hypothesis_random_crash_sweep(base_csr, tmp_path):
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    names = list(REPRESENTATIONS)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def sweep(seed):
        sched = faultinject.FaultSchedule(seed, CRASH_POINTS)
        kcrash, point = sched.plan(4)
        cls = REPRESENTATIONS[names[seed % len(names)]]
        base = str(tmp_path / f"s{seed}")
        os.makedirs(base, exist_ok=True)
        try:
            r, twin, _ = run_crash(
                cls, base_csr, __import__("pathlib").Path(base), point,
                kcrash=kcrash, n_plans=5, seed=seed,
            )
            assert_bit_parity(r.rep, twin)
            np.testing.assert_array_equal(
                np.asarray(r.rep.reverse_walk(2)),
                np.asarray(twin.reverse_walk(2)),
            )
            r.close()
        finally:
            shutil.rmtree(base, ignore_errors=True)

    sweep()


# ---------------------------------------------------------------------------
# kernel fallback chain
# ---------------------------------------------------------------------------


def test_slot_update_falls_back_to_ref(base_csr):
    cls = REPRESENTATIONS["digraph"]
    g = cls.from_csr(base_csr)
    twin = cls.from_csr(base_csr)
    plan = make_plans(1, seed=13)[0]
    # kill both xla tries (attempt + retry) -> chain lands on host ref
    faultinject.arm("slot_update.xla", times=2)
    g, _ = g.apply(plan)
    faultinject.disarm()
    assert fallback.LAST_USED["slot_update"] == "ref"
    twin, _ = twin.apply(plan)
    assert_bit_parity(g, twin)
    # breaker re-promotes xla after its cooldown; parity must hold across
    # the ref->xla seam on the SAME graph state
    fallback.BREAKER.reset()
    p2 = make_plans(1, seed=14)[0]
    g, _ = g.apply(p2)
    twin, _ = twin.apply(p2)
    assert fallback.LAST_USED["slot_update"] == "xla"
    assert_bit_parity(g, twin)


def test_slot_walk_falls_back_to_ref(base_csr):
    cls = REPRESENTATIONS["chunked"]
    g = cls.from_csr(base_csr)
    clean = np.asarray(g.reverse_walk(3))
    faultinject.arm("slot_walk.xla", times=2)
    out = np.asarray(g.reverse_walk(3))
    faultinject.disarm()
    assert fallback.LAST_USED["slot_walk"] == "ref"
    np.testing.assert_allclose(out, clean, rtol=1e-5, atol=1e-5)


def test_forced_pallas_failure_completes_via_xla(base_csr, monkeypatch):
    """ISSUE acceptance: a Pallas failure mid-stream completes through the
    xla link without raising."""
    from repro.kernels.slot_update import ops as _su_ops

    orig = _su_ops.fused_apply

    def force_pallas(*args, **kw):
        kw["backend"] = "pallas"
        return orig(*args, **kw)

    monkeypatch.setattr(_su_ops, "fused_apply", force_pallas)
    cls = REPRESENTATIONS["digraph"]
    g = cls.from_csr(base_csr)
    twin = cls.from_csr(base_csr)
    plan = make_plans(1, seed=21)[0]
    # both pallas tries die before launch; xla completes the dispatch
    faultinject.arm("slot_update.pallas", times=2)
    g, _ = g.apply(plan)
    faultinject.disarm()
    assert fallback.LAST_USED["slot_update"] == "xla"
    st = fallback.BREAKER.state(("slot_update", "pallas"))
    assert st is not None and st["trips"] >= 1  # breaker tripped open
    monkeypatch.setattr(_su_ops, "fused_apply", orig)
    twin, _ = twin.apply(plan)
    assert_bit_parity(g, twin)


def test_breaker_cooldown_and_repromotion():
    t = {"now": 0.0}
    br = fallback.CircuitBreaker(cooldown=1.0, max_cooldown=8.0, clock=lambda: t["now"])
    key = ("site", "xla")
    assert br.available(key)
    br.trip(key)
    assert not br.available(key)  # open
    t["now"] = 1.1
    assert br.available(key)  # half-open: cooldown expired, probe allowed
    br.trip(key)  # probe failed: exponential backoff (2.0s now)
    t["now"] = 2.0
    assert not br.available(key)
    t["now"] = 3.2
    assert br.available(key)
    br.record_success(key)  # probe succeeded: full re-promotion
    assert br.state(key) is None
    br.trip(key)  # next trip starts from the base cooldown again
    t["now"] = 3.2 + 1.1
    assert br.available(key)


def test_run_chain_exhaustion_raises():
    def attempt(b):
        raise RuntimeError(f"{b} down")

    br = fallback.CircuitBreaker(clock=lambda: 0.0)
    with pytest.raises(fallback.FallbackExhausted):
        fallback.run_chain("site2", "xla", attempt, breaker=br)


def test_simulated_crash_not_swallowed_by_chain(base_csr):
    """SimulatedCrash is a BaseException: the fallback chain must let a
    process-kill fly instead of retrying around it."""
    cls = REPRESENTATIONS["digraph"]
    g = cls.from_csr(base_csr)
    faultinject.arm("slot_update.xla", exc=faultinject.SimulatedCrash)
    with pytest.raises(faultinject.SimulatedCrash):
        g.apply(make_plans(1, seed=31)[0])
    faultinject.disarm()


def test_steady_state_untouched_by_chain(base_csr):
    """No fault armed -> the primary backend serves every dispatch and the
    breaker holds no state (the <15%-overhead guarantee's control side)."""
    cls = REPRESENTATIONS["digraph"]
    g = cls.from_csr(base_csr)
    for p in make_plans(3, seed=17):
        g, _ = g.apply(p)
        g.reverse_walk(2)
    assert fallback.LAST_USED.get("slot_update") == "xla"
    assert fallback.LAST_USED.get("slot_walk") in (None, "xla")
    assert fallback.BREAKER.state(("slot_update", "xla")) is None


# ---------------------------------------------------------------------------
# invariant audit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(REPRESENTATIONS))
def test_audit_passes_on_live_stream(name, base_csr):
    g = REPRESENTATIONS[name].from_csr(base_csr)
    for p in make_plans(3, seed=23):
        g, _ = g.apply(p)
    stats = faultinject.audit(g)
    assert stats["m"] == g.to_csr().m
    assert stats["blocks"] >= 1


def test_audit_detects_edge_count_drift(base_csr):
    g = REPRESENTATIONS["digraph"].from_csr(base_csr)
    g.m += 1  # simulated accounting corruption
    with pytest.raises(faultinject.AuditError, match="rep.m"):
        faultinject.audit(g)


def test_audit_detects_image_geometry_corruption(base_csr):
    g = REPRESENTATIONS["vector2d"].from_csr(base_csr)
    img = g.to_walk_image()
    img.degs[0] += 1  # degree drift: live-count / payload checks trip
    with pytest.raises(faultinject.AuditError):
        img.audit()


def _pallas_walk(base_csr):
    from repro.kernels.slot_walk import ops as sw_ops

    img = REPRESENTATIONS["digraph"].from_csr(base_csr).to_walk_image()
    return np.asarray(
        sw_ops.slot_walk_image(img, 2, backend="pallas", interpret=True)
    )


def test_injected_pallas_fault_counts_fallthrough_and_logs_once(
    base_csr, caplog, monkeypatch
):
    """A tripped pallas walk falls through visibly: the per-site counter
    counts every fall-through and the first trip logs its cause once."""
    clean = _pallas_walk(base_csr)
    caplog.set_level("WARNING", logger=fallback.__name__)
    monkeypatch.setattr(fallback.BREAKER, "clock", lambda: 0.0)  # stays open
    faultinject.arm("slot_walk.pallas", times=2)
    out = _pallas_walk(base_csr)  # both pallas tries die -> trip -> xla
    faultinject.disarm()
    assert fallback.LAST_USED["slot_walk"] == "xla"
    assert fallback.BREAKER.fallthroughs["slot_walk"] == 1
    _pallas_walk(base_csr)  # breaker open: skips pallas, no second log
    assert fallback.BREAKER.fallthroughs["slot_walk"] == 2
    logged = [r for r in caplog.records if "slot_walk" in r.getMessage()]
    assert len(logged) == 1 and "pallas" in logged[0].getMessage()
    assert logged[0].exc_info is not None  # the cause rides the record
    np.testing.assert_allclose(out, clean, rtol=1e-5)


def test_clean_pallas_run_leaves_fallthrough_counter_zero(base_csr, caplog):
    caplog.set_level("WARNING", logger=fallback.__name__)
    _pallas_walk(base_csr)
    assert fallback.LAST_USED["slot_walk"] == "pallas"
    assert sum(fallback.BREAKER.fallthroughs.values()) == 0
    assert not caplog.records
