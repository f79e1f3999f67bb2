"""The CPU rehearsal of `tpch-sf1-q21.q21-1stream` (ISSUE 50), beside the
ones test_benchmark.py holds for every cell of the manifest (its
parametrised tests pick the new cell up from BENCHMARK.json by
themselves; this file holds what is the cell's own). By hand and before a
chip call, as that file:

    JAX_PLATFORMS=cpu python -m pytest benchmark/test_q21_cell.py -q \
        -p no:cacheprovider

The rehearsal runs SF 0.01 (the configuration's `rehearse.loader_args`)
over all 25 nations: the same code as on the chip (tier `fused`, one
whole-query program, NATION's dictionary code its argument), every table
one chunk. A statement of Q21 takes 0.4 s on the CPU backend, so the
window is six seconds where test_benchmark.py's is two: `correct` wants
ten statements (that file's case for this cell fails on the count alone,
as for the Q9 and Q18 cells: PERF.md section 7 (k)). tests/test_q21.py
collects these cases for tier-1, under `one_traced_rehearsal`.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.test_benchmark import _run  # noqa: E402

CELL = "tpch-sf1-q21.q21-1stream"
JOIN_CELLS = ["tpch-sf1-q18.q18-1stream", "tpch-sf1.q3-1stream",
              "tpch-sf1-qgen.q3-1stream", "tpch-sf1-q9.q9-1stream"]
SECONDS = ("--seconds", "6")    # the last --seconds on the line counts


def test_the_manifest_holds_the_cell_and_its_metric():
    bench = manifest.benchmark()
    assert manifest.validate(bench) == []
    entry = manifest.entry(bench, CELL)
    assert entry == bench["workloads"][-1]
    assert (entry["chips"], entry["config"]) == (1, "tpch-sf1-q21")
    assert bench["configs"][-1]["name"] == "tpch-sf1-q21"
    assert bench["configs"][-1]["reduced"] == [
        "sf", "text_columns", "random_streams", "query_set"]
    assert bench["per_layer"][-1] == {
        "name": "join_residual_lanes_m", "unit": "Mlanes",
        "better": "lower", "source": "program_counter",
        "layer": "fused runner", "moves": "stmt_p50_ms",
        "workloads": [CELL]}
    # all four end-to-end metrics, and every per-layer metric Q18's cell
    # reports, by an append to each list
    assert {m["name"] for m in manifest.metrics_for(
        bench, CELL, "end_to_end")} == {"stmt_p50_ms", "stmt_p95_ms",
                                        "stmts_per_s", "setup_s"}
    mine = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                    "per_layer")}
    q18 = {m["name"] for m in manifest.metrics_for(
        bench, "tpch-sf1-q18.q18-1stream", "per_layer")}
    assert mine == q18 | {"join_residual_lanes_m"}
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", ()) and len(m["workloads"]) > 1:
            assert m["workloads"][-1] == CELL
    # the cell's own file: the specification's text, four tables, each
    # column named once, NATION from the stream
    (stmt,) = manifest.cell(CELL)["statements"]
    assert stmt["sql"].count("lineitem") == 3 and "$1" in stmt["sql"]
    assert "not exists" in stmt["sql"] and "<>" in stmt["sql"]
    assert sorted(stmt["reads"]) == sorted(stmt["tables"])
    assert stmt["reads"]["lineitem"] == [
        "l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate"]
    assert stmt["params"] == {"kind": "tpch_qgen_q21"}


def test_traced_rehearsal_is_correct_and_prints_the_new_metric():
    p, lines = _run(CELL, "--trace", "1", "--rehearse", "--control",
                    "half_width", *SECONDS)
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 10
    assert last["device"]["platform"] == "cpu"      # never a chip number
    want = {m["name"] for m in manifest.metrics_for(
        manifest.benchmark(), CELL, "per_layer")}
    # the roofline share needs the chip's peak: a CPU rehearsal has none
    assert set(last["metrics"]) == want - {"stmt_program_roofline"}
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    # at SF 0.01 every table is one chunk: the two subquery joins probe
    # the Shrink's 16,384 lanes against builds shrunk from the aggregates'
    # 131,072 lanes to 32,768 (15,000 order keys by the statistics)
    assert metrics["join_residual_lanes_m"] == 0.098304
    assert metrics["sort_lanes_m"] == 0.905216
    assert metrics["window_restarts"] == 0
    assert metrics["prepared_hit_pct"] == 100
    # ONE image of lineitem (12 B x 131,072 lanes), not three: with
    # orders, supplier and nation 4.7 MB
    assert metrics["prime_mb"] == 4.718592
    (first,) = [ln for ln in lines
                if ln.get("phase") == "first_execution"][0]["statements"]
    assert first["flow_restarts"] == 0 and first["rows"] >= 1
    (profile,) = [ln for ln in lines
                  if ln.get("phase") == "device_profile"][0]["statements"]
    kinds = [op["kind"] for op in profile["operators"]]
    # the two reduction aggregates and the last; five joins; four scans
    assert kinds.count("HashAggOp") == 3 and kinds.count("JoinOp") == 5
    assert kinds.count("ScanOp") == 4
    labels = [op["label"] for op in profile["operators"]]
    assert "semi+residual l_orderkey = __apply0_k0" in labels
    assert "anti+residual l_orderkey = __apply1_k0" in labels
    # the control answers each distinct binding once, and not correctly:
    # by the cells, never by the number of rows alone
    (ctl,) = [ln for ln in lines if "control_correct" in ln]
    assert ctl["control_correct"] is False and 1 <= ctl["responses"] <= 25
    by_name = {c["name"]: c for c in ctl["compared"]}
    assert by_name["cells_mismatched"]["ok"] is False
    # every statement of the window bound its nation as data
    counters = [ln for ln in lines if ln.get("compared")
                == "sql_bind_textual_total_whole_run"]
    assert counters and counters[0]["value"] == 0
