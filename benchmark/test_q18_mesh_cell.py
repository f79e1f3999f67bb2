"""The CPU rehearsal of `tpch-sf1-q18-mesh4.q18-1stream` (ISSUE 45), beside
the ones test_benchmark.py holds for every cell of the manifest (its
parametrised tests pick the new cell up from BENCHMARK.json by
themselves; this file holds what is the cell's own). By hand and before a
chip call, as that file:

    JAX_PLATFORMS=cpu python -m pytest benchmark/test_q18_mesh_cell.py -q \
        -p no:cacheprovider

The rehearsal runs a ONE-device mesh at SF 0.01 (the configuration's
`rehearse.loader_args`) with QUANTITY 250..253: the same code as on four
chips (tier `dist`, one shard_map program, the bound value its replicated
argument, every `dist.*` stage), every table one chunk and every build
MIRROR, the aggregate's partial the whole answer: no exchange, `a2a_mb`,
`agg_a2a_mb` and `agg_exchange_ms` 0; `tests/test_session_distsql.py`
runs four virtual devices at SF 0.05 with the aggregate routed BY_HASH
and both builds gathered. A statement of Q18 takes 0.2 to 0.3 s on the
CPU backend, so the window is six seconds where test_benchmark.py's is
two: `correct` wants ten statements (that file's case for this cell fails
on the count alone, as for the one-chip Q18 cell: PERF.md section 7 (k)).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.test_benchmark import _run  # noqa: E402

CELL = "tpch-sf1-q18-mesh4.q18-1stream"
CONTROL_CELL = "tpch-sf1-q18.q18-1stream"
SECONDS = ("--seconds", "6")    # the last --seconds on the line counts


def test_the_manifest_holds_the_cell_and_its_two_metrics():
    bench = manifest.benchmark()
    assert manifest.validate(bench) == []
    entry = manifest.entry(bench, CELL)
    assert entry["chips"] == 4 and entry["config"] == "tpch-sf1-q18-mesh4"
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["tpch-sf1-mesh4.q3-1stream",
                    "tpch-sf1-q9-mesh4.q9-1stream", CELL]
    assert len(four) <= len(bench["workloads"]) // 2
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("agg_exchange_ms", "agg_a2a_mb"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "distributed runner"
        assert by_name[name]["moves"] == "stmt_p50_ms"
    mine = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                    "per_layer")}
    q3 = {m["name"] for m in manifest.metrics_for(
        bench, "tpch-sf1-mesh4.q3-1stream", "per_layer")}
    assert mine == q3 | {"agg_exchange_ms", "agg_a2a_mb", "bind_ms",
                         "window_restarts", "dist_args_ms"}
    # the same statement, bindings and reference as its one-chip control
    mine, control = manifest.cell(CELL), manifest.cell(CONTROL_CELL)
    assert mine["statements"] == control["statements"]
    assert mine["traffic_params"] == control["traffic_params"]
    cfg = manifest.config(entry["config"])
    assert cfg["loader"] == {"name": "tpch_mesh_cname",
                             "args": {"sf": 1.0, "chips": 4}}
    assert cfg["session_setup"] == ["set distsql = always"]
    assert cfg["warmup"] == ["qgen_domain"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    assert cfg["rehearse"]["params"] == manifest.config(
        "tpch-sf1-q18")["rehearse"]["params"]


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
    # one device, every table one chunk of 131,072: the int-key
    # aggregate's sort (131,072) and the three joins' lanes as on one chip
    # (131,072 + 4,096, then 16,384 + 131,072, then 131,072 + 16,384), no
    # router
    assert metrics["dist_sort_lanes_m"] == 0.67584
    assert metrics["a2a_mb"] == 0 and metrics["agg_a2a_mb"] == 0
    assert metrics["agg_exchange_ms"] == 0
    assert 0 < metrics["dist_args_ms"] < metrics["dist_exec_ms"]
    assert 0 < metrics["bind_ms"]
    assert metrics["window_restarts"] == 0
    assert metrics["prepared_hit_pct"] == 100
    (first,) = [ln for ln in lines
                if ln.get("phase") == "first_execution"][0]["statements"]
    assert first["flow_restarts"] == 0
    (profile,) = [ln for ln in lines
                  if ln.get("phase") == "device_profile"][0]["statements"]
    ops = {(op["kind"], op["n"]) for op in profile["operators"]}
    assert len([1 for kind, _n in ops if kind == "JoinOp"]) == 3
    assert len([1 for kind, _n in ops if kind == "HashAggOp"]) == 2


def test_the_float32_control_is_not_correct():
    p, lines = _run(CELL, "--trace", "0", "--rehearse", "--control",
                    "float32", *SECONDS)
    assert p.returncode == 0, p.stderr[-2000:]
    assert lines[-1]["correct"] is True
    (ctl,) = [ln for ln in lines if "control_correct" in ln]
    # the control answers each distinct binding once: four of them
    assert ctl["control_correct"] is False and 1 <= ctl["responses"] <= 4
    by_name = {c["name"]: c for c in ctl["compared"]}
    assert by_name["rows_missing_or_extra"]["ok"] is True
    assert by_name["cells_mismatched"]["ok"] is False
    # every statement of the run bound its value as data, on tier dist
    counters = {ln["compared"]: ln for ln in lines
                if isinstance(ln.get("compared"), str)}
    assert counters["sql_bind_textual_total_whole_run"]["value"] == 0
    assert counters["sql_flow_restarts_total_whole_run"]["value"] == 0
    assert counters["root_spans_off_tier"]["value"] == {}
