"""The CPU rehearsal of `tpch-sf1-q9-mesh4.q9-1stream` (ISSUE 40), beside
the ones test_benchmark.py holds for every cell of the manifest (its
parametrised tests pick the new cell up from BENCHMARK.json by
themselves; this file holds what is the cell's own). By hand and before a
chip call, as that file:

    JAX_PLATFORMS=cpu python -m pytest benchmark/test_q9_mesh_cell.py -q \
        -p no:cacheprovider

The rehearsal runs a ONE-device mesh at SF 0.01 (the configuration's
`rehearse.loader_args`): the same code as on four chips (tier `dist`, one
shard_map program, the bound pattern's table its replicated argument,
every `dist.*` stage), every table one chunk and every build MIRROR, so
no exchange and `a2a_mb` 0; `tests/test_session_distsql.py` runs four
virtual devices with both BY_HASH joins taken. A statement of Q9 takes
0.2 to 0.5 s on the CPU backend, so the window is six seconds where
test_benchmark.py's is two: `correct` wants ten statements (that file's
case for this cell fails on the count alone, as for the one-chip Q9 cell:
PERF.md section 7 (k)).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.test_benchmark import _run  # noqa: E402

CELL = "tpch-sf1-q9-mesh4.q9-1stream"
CONTROL_CELL = "tpch-sf1-q9.q9-1stream"
SECONDS = ("--seconds", "6")    # the last --seconds on the line counts


def test_the_manifest_holds_the_cell_and_its_two_metrics():
    bench = manifest.benchmark()
    entry = manifest.entry(bench, CELL)
    assert entry["chips"] == 4 and entry["config"] == "tpch-sf1-q9-mesh4"
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["tpch-sf1-mesh4.q3-1stream", CELL]
    assert len(four) <= len(bench["workloads"]) // 2
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["dist_args_ms"]["workloads"] == [CELL]
    assert by_name["dist_sort_lanes_m"]["workloads"] == [
        "tpch-sf1-mesh4.q3-1stream", CELL]
    for name in ("dist_args_ms", "dist_sort_lanes_m"):
        assert by_name[name]["layer"] == "distributed runner"
        assert by_name[name]["moves"] == "stmt_p50_ms"
    # the same statement, bindings and reference as its one-chip control
    mine, control = manifest.cell(CELL), manifest.cell(CONTROL_CELL)
    for key in ("sql", "reference", "control", "params", "protocol",
                "tables", "reads"):
        assert mine["statements"][0][key] == control["statements"][0][key]
    assert mine["traffic_params"] == control["traffic_params"]
    cfg = manifest.config(entry["config"])
    assert cfg["loader"] == {"name": "tpch_mesh_pname",
                             "args": {"sf": 1.0, "chips": 4}}
    assert cfg["session_setup"] == ["set distsql = always"]
    assert cfg["warmup"] == ["qgen_domain"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])


def test_traced_rehearsal_is_correct_and_prints_the_new_metrics():
    p, lines = _run(CELL, "--trace", "1", "--rehearse", *SECONDS)
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"      # never a chip number
    want = {m["name"] for m in manifest.metrics_for(
        manifest.benchmark(), CELL, "per_layer")}
    assert set(last["metrics"]) == want
    assert "stmt_program_roofline" not in want      # no mesh cell has one
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    # one device, every table one chunk of 131,072: the five joins' lanes
    # as on one chip (2 x 262,144 + 3 x (8,192 + 131,072)), no router
    assert metrics["dist_sort_lanes_m"] == 0.94208
    assert metrics["a2a_mb"] == 0
    assert 0 < metrics["dist_args_ms"] < metrics["dist_exec_ms"]
    assert 0 < metrics["bind_like_ms"] < metrics["bind_ms"]
    assert metrics["window_restarts"] == 0
    assert metrics["prepared_hit_pct"] == 100
    (first,) = [ln for ln in lines
                if ln.get("phase") == "first_execution"][0]["statements"]
    assert first["flow_restarts"] == 0 and first["rows"] > 100
    (profile,) = [ln for ln in lines
                  if ln.get("phase") == "device_profile"][0]["statements"]
    kinds = [op["kind"] for op in profile["operators"]]
    assert kinds.count("JoinOp") == 5 and kinds.count("ScanOp") == 6
    assert ("HashAggOp", "merge") in [(op["kind"], op["part"])
                                      for op in profile["operators"]]


def test_the_float32_control_is_not_correct():
    p, lines = _run(CELL, "--trace", "0", "--rehearse", "--control",
                    "float32", *SECONDS)
    assert p.returncode == 0, p.stderr[-2000:]
    assert lines[-1]["correct"] is True
    (ctl,) = [ln for ln in lines if "control_correct" in ln]
    assert ctl["control_correct"] is False and ctl["responses"] >= 10
    by_name = {c["name"]: c for c in ctl["compared"]}
    assert by_name["rows_missing_or_extra"]["ok"] is True
    assert by_name["cells_mismatched"]["ok"] is False
    # every statement of the run bound its pattern as data, on tier dist
    counters = {ln["compared"]: ln for ln in lines
                if isinstance(ln.get("compared"), str)}
    assert counters["sql_bind_textual_total_whole_run"]["value"] == 0
    assert counters["sql_flow_restarts_total_whole_run"]["value"] == 0
    assert counters["root_spans_off_tier"]["value"] == {}
