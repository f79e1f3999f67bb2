"""Profile one warm fused Q3 execution on the TPU and print the top HLO
ops by self time (reads the jax profiler's trace protobuf)."""
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

import cockroach_tpu  # noqa: F401
from cockroach_tpu.exec import collect
from cockroach_tpu.workload import tpch_queries as Q
from cockroach_tpu.workload.tpch import TPCH


from cockroach_tpu.util.settings import Settings, WORKMEM

# bench.py's analytics workmem (BENCH_WORKMEM): without it the default
# 64 MiB declines every materialized fast path and measures the wrong
# engine
Settings().set(WORKMEM, int(os.environ.get("BENCH_WORKMEM",
                                           str(2 << 30))))

sf = float(os.environ.get("SF", "1"))
qname = os.environ.get("QUERY", "q3")
cap = 1 << int(os.environ.get("LOG2_CAP", "20"))
gen = TPCH(sf=sf)
if qname == "q18":
    flow = Q.q18(gen, capacity=cap)
else:
    flow = getattr(Q, qname)(gen, cap)
from cockroach_tpu.exec.operators import ScanOp, walk_operators
workmem = int(os.environ.get("WORKMEM", "0"))
for op in walk_operators(flow):
    if isinstance(op, ScanOp):
        op.resident = True
    if workmem and hasattr(op, "workmem"):
        op.workmem = min(op.workmem, workmem)

t0 = time.perf_counter()
collect(flow)
print(f"{qname} cold {time.perf_counter() - t0:.1f}s", flush=True)
for i in range(2):
    t0 = time.perf_counter()
    collect(flow)
    print(f"{qname} warm {time.perf_counter() - t0:.3f}s", flush=True)

import shutil

tdir = "/tmp/q3trace"
shutil.rmtree(tdir, ignore_errors=True)
with jax.profiler.trace(tdir):
    t0 = time.perf_counter()
    collect(flow)
    print(f"{qname} traced warm {time.perf_counter() - t0:.3f}s", flush=True)

# parse trace.json.gz for device-side events
paths = glob.glob(tdir + "/**/*.trace.json.gz", recursive=True)
print("trace files:", paths)
agg = {}
for p in paths:
    with gzip.open(p, "rt") as f:
        data = json.load(f)
    for ev in data.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        pid_name = ev.get("pid")
        name = ev.get("name", "")
        dur = ev.get("dur", 0)  # us
        agg.setdefault(name, [0, 0])
        agg[name][0] += dur
        agg[name][1] += 1
top = sorted(agg.items(), key=lambda kv: -kv[1][0])[:40]
for name, (dur, cnt) in top:
    print(f"{dur/1e3:9.1f} ms  x{cnt:<5d} {name[:110]}")
