"""The benchmark's side of ISSUE 49, and the judged-metrics cases of
ISSUE 48 where tier-1 collects them (PERF.md section 7, "Left out of PR
48", (ii)): `benchmark/` holds its own tests, which tier-1 does not run.

ISSUE 49 appends ONE per-layer metric to BENCHMARK.json and adds ONE
file, its reader: `join_scan64_lanes_m`, the lanes a statement's program
still passes through 64-bit scans inside its joins (stage
`fused.join_scan64_lanes`), reported by the four one-chip join cells.
"""

import pytest

from benchmark import manifest
from benchmark.layer_metrics import join_scan64_lanes_m, sort_lanes_m
# collected here by name: one case a cell and the `validate` case
from benchmark.test_judged_metrics import (  # noqa: F401
    test_a_cell_is_judged_on_the_metrics_it_can_hold,
    test_validate_refuses_a_metric_that_moves_what_its_cell_does_not_report,
)
from benchmark.test_judged_metrics import (
    test_the_cells_last_lines_carry_what_the_manifest_says as _q6_lines,
)

# the four of ISSUE 49, and the Q21 cell that ISSUE 50 appended
JOIN_CELLS = ["tpch-sf1-q18.q18-1stream", "tpch-sf1.q3-1stream",
              "tpch-sf1-qgen.q3-1stream", "tpch-sf1-q9.q9-1stream",
              "tpch-sf1-q21.q21-1stream"]
CELLS = [w["name"] for w in manifest.benchmark()["workloads"]]


def test_the_manifest_is_valid_and_the_metric_is_its_entry():
    bench = manifest.benchmark()
    assert manifest.validate(bench) == []
    # the last entry until ISSUE 50 put `join_residual_lanes_m` after it
    assert bench["per_layer"][-2] == {
        "name": "join_scan64_lanes_m", "unit": "Mlanes", "better": "lower",
        "source": "program_counter", "layer": "fused runner",
        "moves": "stmt_p50_ms", "workloads": JOIN_CELLS}
    # the cells that count their joins' sorted lanes count these too
    (sort,) = [m for m in bench["per_layer"] if m["name"] == "sort_lanes_m"]
    assert sort["workloads"] == JOIN_CELLS and sort["unit"] == "Mlanes"


@pytest.mark.parametrize("cell", CELLS)
def test_the_one_chip_join_cells_report_it_and_no_other(cell):
    bench = manifest.benchmark()
    layer = {m["name"] for m in manifest.metrics_for(bench, cell,
                                                     "per_layer")}
    judged = {m["name"] for m in manifest.metrics_for(bench, cell,
                                                      "end_to_end")}
    assert ("join_scan64_lanes_m" in layer) == (cell in JOIN_CELLS)
    if cell in JOIN_CELLS:
        assert "stmt_p50_ms" in judged     # the metric it moves
        assert manifest.entry(bench, cell)["chips"] == 1


@pytest.mark.parametrize("stages,value", [
    # Q3 since PR 49: both joins compact, no lane passes a 64-bit scan
    ({"fused.join_scan64_lanes": {"events": 491, "rows": 0}}, 0.0),
    # Q18: the customer join resorts, 2 x (16,384 + 262,144) a dispatch
    ({"fused.join_scan64_lanes": {"events": 3, "rows": 3 * 557056}},
     0.557056),
    # a window with no dispatch, and a program without the stage (the
    # parent's): nothing to read, and nothing raised
    ({"fused.join_scan64_lanes": {"events": 0, "rows": 0}}, None),
    ({"fused.sort_lanes": {"events": 3, "rows": 33030144}}, None),
    ({}, None),
])
def test_reader(stages, value):
    ctx = {"window": {"stages": stages}}
    assert join_scan64_lanes_m.read(ctx) == value
    if "fused.sort_lanes" in stages:   # its sibling reads its own stage
        assert sort_lanes_m.read(ctx) == 11.010048


def test_q6s_last_lines_carry_what_the_manifest_says(one_traced_rehearsal):
    """benchmark/test_judged_metrics.py's two-rehearsal case (11 s on the
    CPU), under the lock every traced rehearsal of tests/ holds."""
    _q6_lines()
