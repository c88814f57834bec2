"""Verified persistence: checksummed block snapshots, oracle/epoch save-load
byte-identity, corruption quarantine semantics, and the WAL framing contract
(torn-tail truncation vs mid-log corruption refusal).
"""
import os

import numpy as np
import pytest

from repro.build.engine import build_distribution_labels
from repro.dynamic import DynamicOracle
from repro.ft import inject
from repro.graph.generators import random_dag
from repro.persist import (
    CorruptSnapshotError,
    WriteAheadLog,
    load_blocks,
    load_epoch,
    load_oracle,
    save_blocks,
    save_epoch,
    save_oracle,
    snapshot_meta,
)
from repro.persist.wal import KIND_DELETE, KIND_INSERT, KIND_PUBLISH, RECORD_SIZE

ORACLE_FIELDS = ("L_out", "L_in", "out_len", "in_len", "hop_rank")


@pytest.fixture
def oracle():
    return build_distribution_labels(random_dag(130, 420, seed=4), impl="wave")


# ------------------------------------------------------------------ blocks

def test_blocks_round_trip(tmp_path):
    arrays = {"a": np.arange(100, dtype=np.int32).reshape(10, 10),
              "b.00001": np.zeros(0, dtype=np.int64)}
    p = save_blocks(str(tmp_path / "snap"), arrays, {"tag": 7})
    got, meta, bad = load_blocks(p)
    assert bad == [] and meta == {"tag": 7}
    assert got["a"].tobytes() == arrays["a"].tobytes()
    assert got["b.00001"].shape == (0,)
    assert snapshot_meta(p) == {"tag": 7}


def test_blocks_flip_bit_strict_raises_naming_block(tmp_path):
    p = save_blocks(str(tmp_path / "snap"), {"x": np.arange(512)})
    inject.flip_bit(os.path.join(p, "x.npy"), seed=2)
    with pytest.raises(CorruptSnapshotError, match="'x'.*crc mismatch"):
        load_blocks(p)
    with pytest.warns(UserWarning, match="quarantining"):
        got, _, bad = load_blocks(p, strict=False)
    assert bad == ["x"] and got["x"] is None


def test_blocks_manifest_tamper_fatal_even_nonstrict(tmp_path):
    p = save_blocks(str(tmp_path / "snap"), {"x": np.arange(8)})
    mpath = os.path.join(p, "manifest.json")
    with open(mpath) as f:
        txt = f.read()
    with open(mpath, "w") as f:
        f.write(txt.replace('"x.npy"', '"y.npy"'))
    with pytest.raises(CorruptSnapshotError, match="manifest hash mismatch"):
        load_blocks(p, strict=False)


def test_blocks_atomic_crash_before_rename_preserves_previous(tmp_path):
    p = str(tmp_path / "snap")
    save_blocks(p, {"x": np.arange(4)}, {"gen": 1})
    with pytest.raises(inject.SimulatedFailure):
        with inject.active(inject.Injector({"persist.pre_rename": 0})):
            save_blocks(p, {"x": np.arange(9)}, {"gen": 2})
    got, meta, _ = load_blocks(p)
    assert meta == {"gen": 1} and got["x"].shape == (4,)


# ------------------------------------------------------------------ oracle

def test_oracle_save_load_byte_identical(tmp_path, oracle):
    p = save_oracle(str(tmp_path / "oracle"), oracle, row_block=64)
    got = load_oracle(p)
    for f in ORACLE_FIELDS:
        assert getattr(got, f).tobytes() == getattr(oracle, f).tobytes(), f


def test_oracle_corrupt_row_block_quarantines_those_rows(tmp_path, oracle):
    # row_block=64 over n=130 rows -> blocks 00000..00002; corrupt the middle
    p = save_oracle(str(tmp_path / "oracle"), oracle, row_block=64)
    inject.flip_bit(os.path.join(p, "L_out.00001.npy"), seed=1)
    with pytest.raises(CorruptSnapshotError, match="L_out.00001"):
        load_oracle(p)
    with pytest.warns(UserWarning):
        got, report = load_oracle(p, strict=False)
    assert not report.clean and report.bad_blocks == ["L_out.00001"]
    want = np.zeros(oracle.n, dtype=bool)
    want[64:128] = True
    assert np.array_equal(report.quarantine_out, want)
    assert not report.quarantine_in.any()
    # rows outside the quarantine are intact, quarantined rows zero-filled
    assert got.L_out[:64].tobytes() == oracle.L_out[:64].tobytes()
    assert not got.L_out[64:128].any()


def test_oracle_corrupt_len_block_quarantines_whole_side(tmp_path, oracle):
    p = save_oracle(str(tmp_path / "oracle"), oracle)
    inject.flip_bit(os.path.join(p, "in_len.npy"), seed=3)
    with pytest.warns(UserWarning):
        _, report = load_oracle(p, strict=False)
    assert report.quarantine_in.all() and not report.quarantine_out.any()


def test_epoch_save_load_round_trip(tmp_path, rng):
    n = 60
    src, dst = rng.integers(0, n, 170), rng.integers(0, n, 170)
    from repro.graph.csr import from_edges

    dyn = DynamicOracle(from_edges(n, src, dst))
    ep = dyn._epochs[dyn._epoch]
    p = save_epoch(str(tmp_path / "epoch"), ep)
    got = load_epoch(p)
    assert got.epoch == ep.epoch
    assert np.array_equal(got.comp, ep.comp)
    assert np.array_equal(got.level, ep.level)
    for f in ORACLE_FIELDS:
        assert getattr(got.oracle, f).tobytes() == getattr(ep.oracle, f).tobytes()
    # comp corruption is fatal even non-strict: no safe fallback for the map
    inject.flip_bit(os.path.join(p, "comp.npy"), seed=5)
    with pytest.raises(CorruptSnapshotError, match="comp"):
        load_epoch(p, strict=False)


@pytest.mark.parametrize("mode", ["strict", "quarantine"])
def test_snapshot_in_another_scc_order_refused(tmp_path, mode):
    """Label rows saved under another SCC numbering (snapshots from before
    the order was recorded used Tarjan's) must be refused, not served."""
    from repro.core.api import build_oracle, oracle_from_snapshot
    from repro.graph.csr import from_edges
    from repro.graph.reach import reachable_set
    from repro.persist.oracle_io import _oracle_arrays

    rng = np.random.default_rng(8)
    g = from_edges(80, rng.integers(0, 80, 200), rng.integers(0, 80, 200))
    co = build_oracle(g, backend="host")
    fresh = save_oracle(str(tmp_path / "fresh"), co.oracle)
    back = oracle_from_snapshot(g, fresh, mode=mode, backend="host")
    q = rng.integers(0, g.n, size=(300, 2))
    truth = np.array([u == v or reachable_set(g, int(u))[v] for u, v in q])
    assert np.array_equal(back.serve(q), truth)

    arrays, meta = _oracle_arrays(co.oracle, 4096)  # no recorded order
    legacy = save_blocks(str(tmp_path / "legacy"), arrays, meta)
    with pytest.raises(CorruptSnapshotError, match="'tarjan' order"):
        oracle_from_snapshot(g, legacy, mode=mode, backend="host")
    load_oracle(legacy)  # a plain load, bound to no condensation, still works


def test_budget_snapshots_refuse_another_scc_order(tmp_path):
    from repro.core.api import build_oracle
    from repro.persist.oracle_io import _oracle_arrays
    from repro.serve.budget import BudgetController, label_bytes

    co = build_oracle(random_dag(100, 340, seed=16))
    arrays, meta = _oracle_arrays(co.oracle, 4096)
    meta["comp_order"] = "tarjan"
    path = save_blocks(str(tmp_path / "full"), arrays, meta)
    with pytest.raises(CorruptSnapshotError, match="'tarjan' order"):
        BudgetController(co.engine, budget_bytes=label_bytes(co.oracle) // 2,
                         snapshot_path=path, retain_full=False)

    from repro.persist import load_budgeted, save_budgeted
    from repro.serve.budget import truncate_store

    st = truncate_store(co.oracle, budget_bytes=label_bytes(co.oracle) // 2)
    budgeted = save_budgeted(str(tmp_path / "budgeted"), st)
    assert load_budgeted(budgeted).rank_cut == st.rank_cut
    arrays, meta, _ = load_blocks(budgeted)
    del meta["comp_order"]
    legacy = save_blocks(str(tmp_path / "legacy"), arrays, meta)
    with pytest.raises(CorruptSnapshotError, match="'tarjan' order"):
        load_budgeted(legacy, strict=False)


def test_oracle_kind_mismatch_refused(tmp_path):
    p = save_blocks(str(tmp_path / "other"), {"x": np.arange(3)}, {"kind": "zzz"})
    with pytest.raises(CorruptSnapshotError, match="expected a ReachabilityOracle"):
        load_oracle(p)


# --------------------------------------------------------------------- WAL

def test_wal_append_replay_and_seq_filter(tmp_path):
    w = WriteAheadLog(str(tmp_path / "wal.bin"))
    w.append(KIND_INSERT, 1, 2)
    w.append(KIND_DELETE, 3, 4)
    mark_seq = w.publish_marker(epoch=1)
    w.append(KIND_INSERT, 5, 6)
    w.close()

    w2 = WriteAheadLog(str(tmp_path / "wal.bin"))
    recs = w2.replay()
    assert [(r.kind, r.u, r.v) for r in recs] == [
        (KIND_INSERT, 1, 2), (KIND_DELETE, 3, 4),
        (KIND_PUBLISH, 1, -1), (KIND_INSERT, 5, 6)]
    assert [r.seq for r in recs] == [0, 1, 2, 3]
    assert recs[2].is_publish
    tail = w2.replay(after_seq=mark_seq)
    assert [(r.u, r.v) for r in tail] == [(5, 6)]
    assert w2.last_seq == 3  # scan on open recovered the cursor
    w2.close()


def test_wal_torn_tail_truncated_with_warning(tmp_path):
    path = str(tmp_path / "wal.bin")
    w = WriteAheadLog(path)
    w.append(KIND_INSERT, 1, 2)
    w.append(KIND_INSERT, 3, 4)
    w.close()
    with open(path, "r+b") as f:  # crash mid-append: half a record
        f.seek(0, os.SEEK_END)
        f.write(b"\x01garbage")
    with pytest.warns(UserWarning, match="torn tail"):
        w2 = WriteAheadLog(path)
    assert [(r.u, r.v) for r in w2.replay()] == [(1, 2), (3, 4)]
    assert os.path.getsize(path) == 2 * RECORD_SIZE  # tail physically removed
    # the log stays appendable after truncation
    w2.append(KIND_DELETE, 5, 6)
    assert w2.replay()[-1].seq == 2
    w2.close()


def test_wal_mid_log_corruption_refused_loudly(tmp_path):
    path = str(tmp_path / "wal.bin")
    w = WriteAheadLog(path)
    for i in range(4):
        w.append(KIND_INSERT, i, i + 1)
    w.close()
    inject.flip_bit(path, offset=RECORD_SIZE + 3)  # record #1, good ones follow
    with pytest.raises(CorruptSnapshotError, match="mid-log corruption"):
        WriteAheadLog(path)


def test_wal_reset_truncates(tmp_path):
    w = WriteAheadLog(str(tmp_path / "wal.bin"))
    w.append(KIND_INSERT, 1, 2)
    w.reset()
    assert w.last_seq == -1 and w.replay() == []
    assert w.append(KIND_INSERT, 7, 8) == 0
    w.close()
