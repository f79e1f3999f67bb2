"""The cell `tpch-sf1-q18.q18-1stream` rehearsed end to end (ISSUE 33):
benchmark/run.py at the configuration's rehearsal scale (SF 0.01, QUANTITY
250..253, the CPU), traced, with the float32 control beside it. One
process, as the driver starts it; `benchmark/test_benchmark.py` (which
Tier-1 does not collect) rehearses the cells that were there before."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpch-sf1-q18.q18-1stream"


def test_the_q18_cell_rehearses_correct_and_its_control_does_not(
        one_traced_rehearsal):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         # six seconds for the ten statements `correct` wants: a statement
         # takes 0.24 s on the CPU backend since PR 43 (0.15 until then:
         # XLA:CPU's unstable sort is the slower one on the joins' nearly
         # sorted keys; no device number), and more beside five workers
         "--workload", CELL, "--seed", "2147483999", "--seconds", "6",
         "--trace", "1", "--rehearse", "--control", "float32"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=540)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 10
    assert last["device"]["platform"] == "cpu"      # never a chip number
    assert all(c["ok"] for c in lines if "compared" in c
               and isinstance(c["compared"], str))
    (control,) = [ln for ln in lines if ln.get("control") == "float32"]
    assert control["responses"] >= 1
    assert control["control_correct"] is False
    assert [c["ok"] for c in control["compared"]
            if c["name"] == "cells_mismatched"] == [False]
    (first,) = [ln for ln in lines if ln.get("phase") == "first_execution"]
    assert first["statements"][0]["statement"] == "q18_qgen"
    # SF 0.01, one chunk a table: 131,072 + 262,144 + 135,168 + 147,456
    assert last["metrics"]["sort_lanes_m"]["value"] == 0.67584
    assert last["metrics"]["prepared_hit_pct"]["value"] == 100.0
    assert last["metrics"]["window_restarts"]["value"] == 0.0
    assert {"bind_ms", "fused_wait_ms", "device_idle_pct",
            "stmt_host_ms"} <= set(last["metrics"])
    assert last["breakdown"]["device_ops"]
