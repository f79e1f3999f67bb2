"""Tests of the benchmark's own yardstick. Tier-1 collects tests/ only, so
these run by hand and before a chip call:

    JAX_PLATFORMS=cpu python -m pytest benchmark/test_benchmark.py -q \
        -p no:cacheprovider

Nothing here sleeps, and every wait (a socket, the client child) has a
timeout. The rehearsal-scale runs drive benchmark/run.py end to end on the
CPU in a child process each (one process per run, as on the chip).
"""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import bytes_model, manifest, trace_reduce  # noqa: E402
from benchmark.loaders import tpch as tpch_loader  # noqa: E402
from benchmark.loaders import tpch_dbgen  # noqa: E402
from benchmark.reference import tpch_q1, tpch_q3  # noqa: E402
from benchmark.traffic import closed_loop  # noqa: E402

CELLS = [w["name"] for w in manifest.benchmark()["workloads"]]


# ------------------------------------------------------------ manifest --

def test_manifest_meets_the_contract():
    assert manifest.validate(manifest.benchmark()) == []


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(cell):
    bench = manifest.benchmark()
    spec = manifest.cell(cell)
    cfg = manifest.config(manifest.entry(bench, cell)["config"])
    here = manifest.HERE
    assert os.path.exists(os.path.join(
        here, "traffic", spec["traffic_kind"] + ".py"))
    assert os.path.exists(os.path.join(
        here, "loaders", cfg["loader"]["name"] + ".py"))
    for stmt in spec["statements"]:
        assert os.path.exists(os.path.join(
            here, "reference", stmt["reference"] + ".py"))
        if stmt.get("params"):
            assert os.path.exists(os.path.join(
                here, "paramgen", stmt["params"]["kind"] + ".py"))
    for step in cfg["warmup"]:
        assert os.path.exists(os.path.join(here, "warmup", step + ".py"))
    for kind, folder in (("end_to_end", "e2e_metrics"),
                         ("per_layer", "layer_metrics")):
        got = manifest.metrics_for(bench, cell, kind)
        assert got, f"{cell} reports no {kind} metric"
        for m in got:
            assert os.path.exists(os.path.join(here, folder,
                                               m["name"] + ".py"))
    assert sorted(cfg["reduced"]) == sorted(
        next(c for c in bench["configs"]
             if c["name"] == cfg["name"])["reduced"])


def test_a_bad_name_or_unit_is_caught():
    bench = manifest.benchmark()
    bench["end_to_end"][0]["unit"] = "tokens per second"
    bench["workloads"][0]["name"] = "has space"
    errors = manifest.validate(bench)
    assert any("unit" in e for e in errors)
    assert any("workload name" in e for e in errors)


# ------------------------------------------------------- trace reducer --

def _sample():
    with gzip.open(os.path.join(manifest.HERE, "trace_sample.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_trace_reducer_on_the_recorded_trace():
    sample = _sample()
    got = trace_reduce.reduce(sample["events"])
    want = sample["expected"]
    assert got["chips"] == want["chips"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["idle_pct"] == pytest.approx(want["idle_pct"], rel=1e-9)
    assert [n for n, _ in got["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    assert [n for n, _ in got["idle_gaps"]] == \
        [n for n, _ in want["idle_gaps"]]
    assert 0 < got["busy_s"] <= got["window_s"]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_trace_reducer_arithmetic_by_hand():
    # chip 0: [0,4) and [2,6) overlap -> busy 6; gap [6,10) -> op at [10,12)
    # chip 1: [0,2) only. Host span "a" covers the gap's midpoint (8),
    # and so does the shorter "b": the innermost wins.
    ev = {"device": [["x", 0.0, 4e9, 0], ["y", 2e9, 4e9, 0],
                     ["x", 10e9, 2e9, 0], ["z", 0.0, 2e9, 1]],
          "host": [["a", 5e9, 6e9], ["b", 7e9, 2e9]],
          "extent": [0.0, 20e9]}
    got = trace_reduce.reduce(ev)
    assert got["chips"] == 2
    assert got["busy_s"] == pytest.approx((8.0 + 2.0) / 2)
    assert got["idle_pct"] == pytest.approx(75.0)
    assert got["device_ops"] == [["x", 6.0], ["y", 4.0]]
    assert got["idle_gaps"] == [["b", 4.0]]


def test_no_device_operation_reads_as_idle():
    got = trace_reduce.reduce({"device": [], "host": [],
                               "extent": [0.0, 3e9]})
    assert got["busy_s"] == 0.0 and got["idle_pct"] == 100.0


# ------------------------------------------------ traffic determinism --

def _keyed_job(seed):
    """A cell with a parameter stream, as a later point-read cell would
    state it in its file: 64 clients, scrambled-zipfian keys over 1M."""
    return {"statements": [{"name": "read", "protocol": "extended",
                            "sql": "select 1 where 1 = $1",
                            "params": {"kind": "zipf_scrambled",
                                       "n": 1000000, "theta": 0.99}}],
            "seed": seed, "traffic_params": {"clients": 64}}


def test_key_streams_repeat_for_a_seed_and_differ_between_seeds():
    a = closed_loop.key_streams(_keyed_job(2147483999), 50)
    b = closed_loop.key_streams(_keyed_job(2147483999), 50)
    c = closed_loop.key_streams(_keyed_job(2147484000), 50)
    assert a == b
    assert a != c
    assert len(a) == 64 and a[0] != a[1]      # one stream per client
    keys = np.array([k for s in a for (k,) in s])
    assert keys.min() >= 0 and keys.max() < 1_000_000


def test_zipf_is_skewed_and_scrambled():
    keys = np.array([k for s in closed_loop.key_streams(_keyed_job(1), 400)
                     for (k,) in s])
    _vals, counts = np.unique(keys, return_counts=True)
    # theta 0.99 over 1M keys: the hottest key draws about 7% of requests
    assert 0.04 < counts.max() / len(keys) < 0.11
    # scrambled: the hot keys are not the low key numbers
    assert np.median(keys) > 100_000


def test_a_tpch_cell_sends_one_text():
    spec = manifest.cell("tpch-sf1.q1-2streams")
    job = {"statements": spec["statements"], "seed": 3,
           "traffic_params": spec["traffic_params"]}
    assert closed_loop.key_streams(job, 3) == [[(), (), ()]] * 2


# ---------------------------------------- data, references, controls --

@pytest.fixture(scope="module")
def tiny_tpch():
    gen = tpch_dbgen.TPCH(sf=0.01, seed=2147483999)
    data = {t: gen.table(t) for t in ("lineitem", "orders", "customer")}
    dicts = {}
    for t in data:
        for col, pool in gen.schema(t).dicts.items():
            dicts[col] = [str(s) for s in pool]
    return gen, data, dicts


_REPAIRED = {"l_orderkey", "o_orderkey", "o_custkey", "o_orderstatus",
             "o_totalprice", "o_clerk"}


def test_dbgen_agrees_with_the_programs_generator_but_for_the_repairs(
        tiny_tpch):
    from cockroach_tpu.workload.tpch import TPCH

    gen, data, _ = tiny_tpch
    theirs = TPCH(sf=0.01, seed=2147483999)
    differ = set()
    for t, cols in data.items():
        for c, v in theirs.table(t).items():
            if not np.array_equal(cols[c], v):
                differ.add(c)
    assert differ == _REPAIRED


def test_orders_follow_clause_4_2_3(tiny_tpch):
    gen, data, dicts = tiny_tpch
    o, l = data["orders"], data["lineitem"]
    # sparse keys: 8 of every 32 values, the same keys in lineitem
    assert o["o_orderkey"][:10].tolist() == [1, 2, 3, 4, 5, 6, 7, 32, 33, 34]
    assert (o["o_orderkey"] % 32 < 8).all()
    assert o["o_orderkey"].max() == tpch_dbgen.sparse_key(
        np.int64(gen.n_orders))
    assert set(np.unique(l["l_orderkey"])) == set(o["o_orderkey"].tolist())
    # a third of the customers place no order
    assert (o["o_custkey"] % 3 != 0).all()
    assert 1 <= o["o_custkey"].min() and o["o_custkey"].max() <= 1500
    assert len(np.unique(o["o_custkey"])) > 900
    # total price and status follow from the order's lineitems
    F, O, P = (dicts["o_orderstatus"].index(x) for x in "FOP")
    lF = dicts["l_linestatus"].index("F")
    for i in (0, 1, 7, 500, gen.n_orders - 1):
        m = l["l_orderkey"] == o["o_orderkey"][i]
        want = sum(int(e) * (100 - int(d)) // 100 * (100 + int(t)) // 100
                   for e, d, t in zip(l["l_extendedprice"][m],
                                      l["l_discount"][m], l["l_tax"][m]))
        assert o["o_totalprice"][i] == want
        shipped = (l["l_linestatus"][m] == lF)
        assert o["o_orderstatus"][i] == (
            F if shipped.all() else O if not shipped.any() else P)
    share = np.bincount(o["o_orderstatus"], minlength=3) / gen.n_orders
    assert 0.4 < share[F] < 0.55 and 0.4 < share[O] < 0.55 < 1 - share[P]
    assert len(dicts["o_clerk"]) == 10 and dicts["o_clerk"][0] == \
        "Clerk#000000001"
    # a chunk of a table is that slice of the whole table
    part = gen.rows("orders", 100, 300)
    assert all(np.array_equal(part[c], o[c][100:300]) for c in part)


def test_data_is_a_function_of_the_seed(tiny_tpch):
    _gen, data, _ = tiny_tpch
    again = tpch_dbgen.TPCH(sf=0.01, seed=2147483999).table("lineitem")
    other = tpch_dbgen.TPCH(sf=0.01, seed=5).table("lineitem")
    assert np.array_equal(again["l_extendedprice"],
                          data["lineitem"]["l_extendedprice"])
    assert not np.array_equal(other["l_extendedprice"][:1000],
                              data["lineitem"]["l_extendedprice"][:1000])


def test_q1_reference_against_a_row_loop(tiny_tpch):
    _gen, data, dicts = tiny_tpch
    ref = tpch_q1.Reference(data, dicts, {})
    t = data["lineitem"]
    acc = {}
    keep = t["l_shipdate"] <= tpch_q1.CUTOFF
    for rf, ls, q, px, d, tx in zip(
            t["l_returnflag"][keep].tolist(), t["l_linestatus"][keep].tolist(),
            t["l_quantity"][keep].tolist(),
            t["l_extendedprice"][keep].tolist(),
            t["l_discount"][keep].tolist(), t["l_tax"][keep].tolist()):
        a = acc.setdefault((dicts["l_returnflag"][rf],
                            dicts["l_linestatus"][ls]), [0, 0, 0, 0, 0])
        a[0] += q
        a[1] += px
        a[2] += px * (100 - d)
        a[3] += px * (100 - d) * (100 + tx)
        a[4] += 1
    got = ref.answer()
    assert set(got) == set(acc)
    for k, a in acc.items():
        assert got[k][:4] == tuple(a[:4]) and got[k][7] == a[4]


def test_q3_reference_against_a_row_loop(tiny_tpch):
    _gen, data, dicts = tiny_tpch
    ref = tpch_q3.Reference(data, dicts, {})
    c, o, l = data["customer"], data["orders"], data["lineitem"]
    seg = dicts["c_mktsegment"].index("BUILDING")
    bcust = set(c["c_custkey"][c["c_mktsegment"] == seg].tolist())
    orders = {}
    for ok, ck, od, pr in zip(o["o_orderkey"].tolist(),
                              o["o_custkey"].tolist(),
                              o["o_orderdate"].tolist(),
                              o["o_shippriority"].tolist()):
        if od < tpch_q3.DATE and ck in bcust:
            orders[ok] = (od, pr)
    rev = {}
    for ok, px, d, sd in zip(l["l_orderkey"].tolist(),
                             l["l_extendedprice"].tolist(),
                             l["l_discount"].tolist(),
                             l["l_shipdate"].tolist()):
        if sd > tpch_q3.DATE and ok in orders:
            rev[ok] = rev.get(ok, 0) + px * (100 - d)
    want = sorted(((-r, orders[k][0], k) for k, r in rev.items()))[:10]
    assert ref.answer() == [(k, -nr, od, orders[k][1])
                            for nr, od, k in want]


@pytest.mark.parametrize("module,control,params", [
    (tpch_q1, "float32", ()), (tpch_q3, "float32", ())])
def test_tpch_control_fails_and_the_exact_rows_pass(tiny_tpch, module,
                                                    control, params):
    _gen, data, dicts = tiny_tpch
    ref = module.Reference(data, dicts, {})
    exact = ref.control_rows(params, None)
    oks, compared = ref.check([(params, exact)])
    assert oks == [True] and all(c["ok"] for c in compared)
    oks, compared = ref.check([(params, ref.control_rows(params, control))])
    assert oks == [False] and not all(c["ok"] for c in compared)


def test_a_wrong_cell_or_a_missing_row_is_counted(tiny_tpch):
    _gen, data, dicts = tiny_tpch
    ref = tpch_q3.Reference(data, dicts, {})
    exact = ref.control_rows((), None)
    row = list(exact[3])
    row[1] = str(row[1])[:-1] + ("1" if str(row[1])[-1] != "1" else "2")
    oks, compared = ref.check([((), exact[:3] + [tuple(row)] + exact[4:]),
                               ((), exact[:-1])])
    assert oks == [False, False]
    by = {c["name"]: c["value"] for c in compared}
    assert by["cells_mismatched"] >= 1 and by["rows_missing_or_extra"] >= 1


# ------------------------------------- counters, found without an edit --

def test_snapshot_arithmetic_takes_any_counter_and_histogram():
    from benchmark import observe

    base = {"stages": {}, "tiers": {}, "compiles": 0, "cache_loads": 0}
    before = dict(base, counters={"a": 2}, histograms={})
    after = dict(base, counters={"a": 5, "new.counter_total": 7},
                 histograms={"new.delay_seconds": {"count": 4, "sum": 0.5}},
                 stages={"fused.exec": {"seconds": 1.0, "events": 2,
                                        "rows": 0, "bytes": 0}},
                 compiles=1)
    d = observe.delta(before, after)
    assert d["counters"] == {"a": 3, "new.counter_total": 7}
    assert d["histograms"]["new.delay_seconds"] == {"count": 4, "sum": 0.5}
    assert d["stages"]["fused.exec"]["events"] == 2 and d["compiles"] == 1


def test_expect_may_name_a_counter_no_file_knows():
    from benchmark import run

    judged = {"compared": [], "attempted": 12, "failed": 0,
              "lat": [1.0] * 12}
    window = {"compiles": 0, "cache_loads": 0,
              "tiers": {("root", "fused"): 12},
              "stages": {"fused.exec": {"events": 12}},
              "counters": {"their.statements_total": 12}}
    whole = {"stages": {}, "counters": {"their.fallback_total": 0}}
    expect = {"root_span": "root", "tier": "fused",
              "exec_stage": "fused.exec",
              "zero_counters": ["their.fallback_total", "never.registered"],
              "per_statement_counter": "their.statements_total"}
    checks = run.run_checks(judged, expect, window, whole)
    assert all(c["ok"] for c in checks), checks
    whole["counters"]["their.fallback_total"] = 1
    window["counters"]["their.statements_total"] = 11
    bad = [c["name"] for c in run.run_checks(judged, expect, window, whole)
           if not c["ok"]]
    assert bad == ["their.fallback_total_whole_run",
                   "their.statements_total_in_window"]


def test_bytes_model_counts_columns_times_rows_times_width():
    spec = manifest.cell("tpch-sf1.q1-2streams")
    rows = {"lineitem": 1000}
    assert bytes_model.cell_bytes(spec, tpch_loader, rows) == 12 * 1000
    spec = manifest.cell("tpch-sf1.q3-1stream")
    rows = {"lineitem": 1000, "orders": 100, "customer": 10}
    assert bytes_model.cell_bytes(spec, tpch_loader, rows) == \
        11 * 1000 + 11 * 100 + 5 * 10


# ----------------------------- the run, end to end, at rehearsal scale --

def _run(cell, *extra, broken=False, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = ([os.path.abspath(__file__), "--broken-run"] if broken
              else [os.path.join(ROOT, "benchmark", "run.py")])
    p = subprocess.run(
        [sys.executable, *script, "--workload", cell, "--seed", "2147483999", "--seconds", "2",
         *extra], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    return p, [json.loads(ln) for ln in lines]


def test_without_a_tpu_nothing_is_reported():
    p, lines = _run(CELLS[0], "--trace", "0")
    assert p.returncode != 0
    assert not any("correct" in ln for ln in lines)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_agrees_with_the_reference_and_the_control_bites(cell):
    control = manifest.cell(cell)["statements"][0]["control"]
    p, lines = _run(cell, "--trace", "0", "--rehearse", "--control", control)
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"      # never a chip number
    want = {m["name"] for m in manifest.metrics_for(
        manifest.benchmark(), cell, "end_to_end")}
    assert set(last["metrics"]) == want
    assert all(v["value"] > 0 for v in last["metrics"].values())
    ctl = [ln for ln in lines if "control_correct" in ln]
    assert ctl and ctl[0]["control_correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_the_cells_layer_metrics(cell):
    p, lines = _run(cell, "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    want = {m["name"] for m in manifest.metrics_for(
        manifest.benchmark(), cell, "per_layer")}
    # the roofline share needs the chip's peak: a CPU rehearsal has none
    assert set(last["metrics"]) == want - {"stmt_program_roofline"}
    assert 0 < last["device"]["busy_s"] <= last["device"]["window_s"]
    assert last["breakdown"]["device_ops"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_wrong_row_from_the_timed_path_makes_the_run_not_correct(cell):
    """The harness's look for a chip is skipped (--rehearse) and the rest
    of a run is driven with the timed path broken underneath: one value of
    every answer is altered where the session produces it (this file's
    `--broken-run` starts run.py over the broken Session)."""
    p, lines = _run(cell, "--trace", "0", "--rehearse", broken=True)
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is False
    assert last["failed"] == last["attempted"] > 0
    assert "stmt_p50_ms" not in last["metrics"]   # no latency of wrong rows


# ------------------------- the timed path, broken on purpose (test only) --

def _alter(result):
    """One numeric value of a row set, plus one."""
    if not (isinstance(result, tuple) and result and result[0] == "rows"):
        return result
    kind, payload, schema = result
    for name, col in payload.items():
        if name.endswith("__valid") or not len(col):
            continue
        arr = np.array(col)
        if arr.dtype.kind in "iuf":
            arr[0] = arr[0] + 1
            return kind, dict(payload, **{name: arr}), schema
    return result


def _broken_run(argv):
    """run.py over a Session that alters one value of every answer where
    it is produced. Nothing in run.py knows of this."""
    import runpy

    from cockroach_tpu.sql.session import Session

    for method in ("execute", "execute_spec"):
        orig = getattr(Session, method)

        def wrapped(self, *a, _orig=orig, **kw):
            return _alter(_orig(self, *a, **kw))

        setattr(Session, method, wrapped)
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py")] + argv
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    assert sys.argv[1] == "--broken-run", "run this file through pytest"
    _broken_run(sys.argv[2:])
