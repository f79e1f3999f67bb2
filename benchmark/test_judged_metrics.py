"""Which end-to-end metrics judge which cell (ISSUE 48), beside the tests
test_benchmark.py holds for every cell of the manifest. By hand and before
a chip call, as that file:

    JAX_PLATFORMS=cpu python -m pytest benchmark/test_judged_metrics.py -q \
        -p no:cacheprovider

A cell reports an end-to-end metric only if its runs hold the metric's
bound there: an entry of `end_to_end` with a `workloads` list is reported
by the listed cells alone. `tpch-sf1-qgen.q6-2streams` is the one cell
judged on fewer than all four (PERF.md section 2, PR 48: the twelve runs
and the rule); what it no longer reports, and the per-layer metrics that
moved it, its traced run reads under per-layer names of their own, so the
ledger keeps every reading.
"""

from __future__ import annotations

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.test_benchmark import _run  # noqa: E402

ALL_FOUR = ["stmt_p50_ms", "stmt_p95_ms", "stmts_per_s", "setup_s"]
Q6 = "tpch-sf1-qgen.q6-2streams"
Q6_JUDGED_ON = ["stmt_p50_ms", "setup_s"]
# the per-layer metrics that cell alone reports -> what each reads
Q6_KEEPS_VISIBLE = {"client_p95_ms": "stmt_p95_ms",
                    "client_per_s": "stmts_per_s",
                    "slow_stmt_host_ms": "stall_host_ms",
                    "slow_stmt_wait_ms": "stall_wait_ms"}
CELLS = [w["name"] for w in manifest.benchmark()["workloads"]]
CTX = {"client": {"n": 3, "seconds": 2.0, "p50_ms": 5.5, "p95_ms": 5.75,
                  "per_s": 360.25},
       "window": {"histograms": {
           "sql_slow_stmt_host_seconds": {"count": 2, "sum": 0.0625},
           "sql_slow_stmt_wait_seconds": {"count": 1, "sum": 0.125}}}}
NOTHING = {"client": {"n": 0, "seconds": 2.0}, "window": {"histograms": {}}}


def _read(folder, name, ctx):
    return importlib.import_module(f"benchmark.{folder}.{name}").read(ctx)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_is_judged_on_the_metrics_it_can_hold(cell):
    bench = manifest.benchmark()
    assert manifest.validate(bench) == []
    assert [m["name"] for m in bench["end_to_end"]] == ALL_FOUR
    judged = [m["name"] for m in
              manifest.metrics_for(bench, cell, "end_to_end")]
    assert judged == (Q6_JUDGED_ON if cell == Q6 else ALL_FOUR)
    layer = manifest.metrics_for(bench, cell, "per_layer")
    # a per-layer metric moves a metric its cell reports
    assert layer and {m["moves"] for m in layer} <= set(judged)
    for m in bench["end_to_end"]:
        if m["name"] in Q6_JUDGED_ON:
            assert "workloads" not in m
        else:   # the cells that hold it, in the order of `workloads`
            assert m["workloads"] == [c for c in CELLS if c != Q6]
    names = {m["name"] for m in layer}
    for name, twin in Q6_KEEPS_VISIBLE.items():
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [Q6] and m["moves"] == "stmt_p50_ms"
        assert (name in names) == (cell == Q6) == (
            twin not in names | set(judged))
        # the reader hands on the number its twin reads, and nothing
        # where there is nothing to read
        folder = "e2e_metrics" if twin in ALL_FOUR else "layer_metrics"
        assert _read("layer_metrics", name, CTX) == _read(folder, twin, CTX)
        assert _read("layer_metrics", name, NOTHING) is None
    assert _read("layer_metrics", "client_p95_ms", CTX) == 5.75
    assert _read("layer_metrics", "client_per_s", CTX) == 360.25
    assert _read("layer_metrics", "slow_stmt_host_ms", CTX) == 62.5
    assert _read("layer_metrics", "slow_stmt_wait_ms", CTX) == 125.0


def test_validate_refuses_a_metric_that_moves_what_its_cell_does_not_report():
    cell = "tpch-sf1-qgen.q3-1stream"   # window_restarts moves its tail
    bench = manifest.benchmark()
    (tail,) = [m for m in bench["end_to_end"] if m["name"] == "stmt_p95_ms"]
    tail["workloads"].remove(cell)
    errors = manifest.validate(bench)
    assert any("window_restarts" in e and cell in e for e in errors)
    bench = manifest.benchmark()
    for m in bench["end_to_end"]:
        if m["name"] != "setup_s":
            m["workloads"] = [c for c in CELLS if c not in (cell, Q6)]
    for m in bench["per_layer"]:
        m["workloads"] = [c for c in m["workloads"] if c != cell]
    errors = manifest.validate(bench)
    assert any(cell in e and "end-to-end" in e for e in errors)
    assert any(cell in e and "per-layer" in e for e in errors)


def test_the_cells_last_lines_carry_what_the_manifest_says():
    """The cell judged on fewer, end to end at rehearsal scale: the
    untraced line carries exactly the kept end-to-end metrics, the traced
    one the client's tail and rate under their per-layer names; the
    `window` line prints what it printed."""
    p, lines = _run(Q6, "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    assert list(lines[-1]["metrics"]) == Q6_JUDGED_ON
    (window,) = [ln for ln in lines if ln.get("phase") == "window"]
    assert window["client"]["p95_ms"] >= window["client"]["p50_ms"] > 0
    assert window["client"]["per_s"] > 0
    p, lines = _run(Q6, "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    (window,) = [ln for ln in lines if ln.get("phase") == "window"]
    got = {k: v["value"] for k, v in lines[-1]["metrics"].items()}
    want = {m["name"] for m in manifest.metrics_for(
        manifest.benchmark(), Q6, "per_layer")}
    # the roofline share needs the chip's peak: a CPU rehearsal has none
    assert set(got) == want - {"stmt_program_roofline"}
    assert got["client_p95_ms"] == window["client"]["p95_ms"]
    assert got["client_per_s"] == window["client"]["per_s"]
    assert got["slow_stmt_host_ms"] >= 0 and got["slow_stmt_wait_ms"] >= 0
