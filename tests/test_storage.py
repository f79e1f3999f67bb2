"""Storage layer tests: MVCC semantics (datadriven corpus + native-vs-
python differential), LSM flush/compact invariance, randomized history
equivalence, and the scan -> ScanOp -> TPU flow integration.

Mirrors the reference's storage test strategy (SURVEY.md §4.1):
mvcc_history datadriven scripts (storage/mvcc_history_test.go) pin
semantics; randomized op interleavings (storage/metamorphic) catch what
the scripts miss; and the columnar scan is exercised end-to-end into the
execution engine (col_mvcc.go's reason to exist).
"""

import glob
import os

import numpy as np
import pytest

from cockroach_tpu.storage import (
    MVCCStore, NativeEngine, PyEngine, open_engine, run_datadriven,
)
from cockroach_tpu.storage.engine import _load
from cockroach_tpu.util.hlc import HLC, ManualClock, Timestamp

TESTDATA = os.path.join(os.path.dirname(__file__), "testdata", "mvcc")

native_available = _load() is not None
needs_native = pytest.mark.skipif(not native_available,
                                  reason="no C++ toolchain")


def _scripts():
    return sorted(glob.glob(os.path.join(TESTDATA, "*.txt")))


@pytest.mark.parametrize("path", _scripts(),
                         ids=[os.path.basename(p) for p in _scripts()])
def test_datadriven_differential(path):
    """The same script through the native engine and the python model must
    produce byte-identical transcripts."""
    with open(path) as f:
        text = f.read()
    out_py = run_datadriven(text, MVCCStore(engine=PyEngine()))
    if native_available:
        out_native = run_datadriven(text, MVCCStore(engine=NativeEngine()))
        assert out_native == out_py
    # pin a few absolute semantics so both being wrong together fails too
    if os.path.basename(path) == "basic.txt":
        lines = out_py.splitlines()
        assert "get k=1 -> <no version>" in lines[3]      # read below ts
        assert "get k=1 -> 10,100 @5.000000000" in lines[4]
        assert "get k=1 -> 11,110 @10.000000000" in lines[6]
        assert any("scan @20" in l and "1 rows" in l for l in lines)


@needs_native
def test_random_history_differential(rng):
    """Metamorphic: random puts/dels/gets/scans with random timestamps and
    interleaved flushes — native and python models must agree exactly."""
    ne, pe = NativeEngine(flush_threshold=1 << 12), PyEngine()
    keys = [f"k{i:03d}".encode() for i in range(40)]
    for step in range(1500):
        op = rng.integers(0, 10)
        key = keys[rng.integers(0, len(keys))]
        ts = Timestamp(int(rng.integers(1, 50)), int(rng.integers(0, 3)))
        if op < 5:
            val = bytes(rng.integers(0, 256, 16, dtype=np.uint8))
            ne.put(key, ts, val)
            pe.put(key, ts, val)
        elif op < 7:
            ne.delete(key, ts)
            pe.delete(key, ts)
        elif op < 9:
            assert ne.get(key, ts) == pe.get(key, ts), (key, ts)
        else:
            a, b = sorted([keys[rng.integers(0, len(keys))],
                           keys[rng.integers(0, len(keys))]])
            assert ne.scan_keys(a, b, ts) == pe.scan_keys(a, b, ts)
        if step % 200 == 199:
            ne.flush()
    # final full-state comparison at several snapshot timestamps
    for wall in (1, 10, 25, 49):
        ts = Timestamp(wall, 1)
        assert ne.scan_keys(b"", b"", ts) == pe.scan_keys(b"", b"", ts)
        for key in keys:
            assert ne.get(key, ts) == pe.get(key, ts)


@needs_native
def test_scan_resume_pagination():
    st = MVCCStore(engine=NativeEngine(), clock=HLC(ManualClock(10)))
    for pk in range(100):
        st.put(1, pk, [pk, pk * 2])
    got = []
    for chunk in st.scan_chunks(1, 2, capacity=7):
        got.extend(chunk["f0"].tolist())
    assert got == list(range(100))


@needs_native
def test_snapshot_isolation_under_writes():
    """A reader at an old snapshot must not see later writes (the MVCC
    guarantee backing follower reads / AS OF SYSTEM TIME)."""
    clock = HLC(ManualClock(100))
    st = MVCCStore(engine=NativeEngine(), clock=clock)
    for pk in range(20):
        st.put(1, pk, [pk])
    snap = clock.now()
    for pk in range(20):
        st.put(1, pk, [pk + 1000])
    st.put(1, 99, [99])
    old = [c["f0"].tolist() for c in st.scan_chunks(1, 1, 64, ts=snap)]
    new = [c["f0"].tolist() for c in st.scan_chunks(1, 1, 64)]
    assert old == [list(range(20))]
    assert new == [[i + 1000 for i in range(20)] + [99]]


@needs_native
def test_mvcc_scan_feeds_tpu_flow():
    """North-star config #5 shape: MVCC scan -> packed chunks -> device
    aggregation, results checked against direct host computation."""
    from cockroach_tpu.coldata.batch import Field, INT, Schema
    from cockroach_tpu.exec import collect
    from cockroach_tpu.exec.operators import HashAggOp, TopKOp
    from cockroach_tpu.ops.agg import AggSpec
    from cockroach_tpu.ops.sort import SortKey

    rng = np.random.default_rng(7)
    st = MVCCStore(engine=NativeEngine(), clock=HLC(ManualClock(100)))
    vals = rng.integers(0, 1000, 500)
    for pk, v in enumerate(vals):
        st.put(1, pk, [int(v), pk % 7])
    schema = Schema([Field("v", INT), Field("g", INT)])
    scan = st.scan_op(1, schema, capacity=128)
    agg = HashAggOp(scan, ["g"], [AggSpec("sum", "v", "s")])
    res = collect(agg)
    got = dict(zip(res["g"].tolist(), res["s"].tolist()))
    exp = {g: int(vals[np.arange(500) % 7 == g].sum()) for g in range(7)}
    assert got == exp

    scan2 = st.scan_op(1, schema, capacity=128)
    topk = TopKOp(scan2, [SortKey("v", descending=True)], 5)
    res2 = collect(topk)
    assert res2["v"].tolist() == sorted(vals.tolist(), reverse=True)[:5]


@needs_native
def test_ycsb_e_mix_and_topk():
    """YCSB-E ops run and the TPU scan+top-K agrees with a host top-K."""
    from cockroach_tpu.exec import collect
    from cockroach_tpu.workload import ycsb

    st = MVCCStore(engine=NativeEngine(), clock=HLC(ManualClock(1000)))
    rng = np.random.default_rng(3)
    ycsb.load(st, 500, rng)
    ops_per_sec, rows = ycsb.run_e(st, 200, 500, rng)
    assert ops_per_sec > 0 and rows > 0

    flow = ycsb.scan_topk_flow(st, capacity=256, k=10)
    res = collect(flow)
    # host oracle: full scan, top-10 by field0 desc
    all_f0 = []
    for c in st.scan_chunks(ycsb.TABLE_ID, ycsb.N_FIELDS, 1 << 12):
        all_f0.extend(c["f0"].tolist())
    assert res["field0"].tolist() == sorted(all_f0, reverse=True)[:10]


@pytest.mark.parametrize("toolchain, says", [
    ("absent", "no C++ compiler on PATH"),
    ("refuses", "mvcc_engine.cpp:1:1: error: the compiler's own words"),
])
def test_failed_native_build_raises_and_hands_back_no_python_engine(
        tmp_path, monkeypatch, toolchain, says):
    """With no built library, and g++ off the PATH or failing, the default
    store RAISES with the reason (the compiler's stderr): it never comes
    up on PyEngine in silence. PyEngine stays available by name."""
    from cockroach_tpu.storage import engine

    bindir = tmp_path / "bin"
    bindir.mkdir()
    if toolchain == "refuses":
        gxx = bindir / "g++"
        gxx.write_text("#!/bin/sh\n"
                       f"echo \"{says}\" >&2\nexit 1\n")
        gxx.chmod(0o755)
    monkeypatch.setenv("PATH", str(bindir))
    monkeypatch.setattr(engine, "_NATIVE_DIR", str(tmp_path))  # no .so
    monkeypatch.setattr(engine, "_lib", None)
    monkeypatch.setattr(engine, "_lib_err", None)
    for build in (MVCCStore, NativeEngine, open_engine):
        with pytest.raises(RuntimeError) as ei:
            build()
        assert says in str(ei.value)
    assert isinstance(MVCCStore(engine=PyEngine()).engine, PyEngine)
