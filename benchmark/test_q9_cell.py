"""The CPU rehearsal of `tpch-sf1-q9.q9-1stream` (ISSUE 37), beside the
ones test_benchmark.py holds for every cell of the manifest (its
parametrised tests pick the new cell up from BENCHMARK.json by
themselves; this file holds what is the cell's own). By hand and before a
chip call, as that file:

    JAX_PLATFORMS=cpu python -m pytest benchmark/test_q9_cell.py -q \
        -p no:cacheprovider

A statement of Q9 takes 0.3 to 0.4 s on the CPU backend at SF 0.01 (five
joins of 262,144 lanes each: every table is one chunk of the
configuration's 131,072), so the window is longer than that file's two
seconds: `correct` wants ten statements. For the same reason
`test_benchmark.py::test_rehearsal_agrees_with_the_reference_and_the_
control_bites[tpch-sf1-q9.q9-1stream]` FAILS on the CPU (five statements
in its two seconds, every one exact: `statements_correct_in_window` 5 of
10); that file is the accepted benchmark's and a `model_config` PR may
not edit it: the test below is that one at six seconds, and a `benchmark`
PR should give each cell its rehearsal's seconds.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark.test_benchmark import _run  # noqa: E402

CELL = "tpch-sf1-q9.q9-1stream"
SECONDS = ("--seconds", "6")    # the last --seconds on the line counts


def test_traced_rehearsal_is_correct_and_prints_the_new_metrics():
    p, lines = _run(CELL, "--trace", "1", "--rehearse", *SECONDS)
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"      # never a chip number
    want = {m["name"] for m in manifest.metrics_for(
        manifest.benchmark(), CELL, "per_layer")}
    # the roofline share needs the chip's peak: a CPU rehearsal has none
    assert set(last["metrics"]) == want - {"stmt_program_roofline"}
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    # at SF 0.01 every table is one chunk: the Shrink's 8,192 lanes and
    # partsupp's 131,072 under the hashed key, of 1,064,960 sorted lanes
    assert metrics["hash_key_lanes_m"] == 0.139264
    assert metrics["sort_lanes_m"] == 1.06496
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
    # every statement of the window bound its pattern as data
    counters = [ln for ln in lines if ln.get("compared")
                == "sql_bind_textual_total_whole_run"]
    assert counters and counters[0]["value"] == 0
