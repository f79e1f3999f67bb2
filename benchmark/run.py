"""benchmark/run.py: one cell of BENCHMARK.json, once, on the attached TPU.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse] [--control <precision>]

One process holds the chip: it builds the store as `cli.cmd_start` does
(MVCCStore on the native engine), loads the cell's tables from --seed,
starts PgServer, executes each statement once (first_exec_s), warms the
cell's shapes, then starts the clients in a CHILD process that never
imports JAX (benchmark/client.py), measures for --seconds, checks every
returned row set against the plain reference, and prints the contract's
one last line. Everything particular to a configuration, a cell, a traffic
kind, a loader, a reference or a per-layer metric is a file found by name
(benchmark/README.md): there is no per-cell branch in this file.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before loading data and prints no result. `--rehearse` is the CPU
rehearsal at the configuration's "rehearse" scale: it runs the same code
end to end and its last line names the platform it ran on ("cpu"), so it
can never pass for a chip run.

--control <precision> also puts the reference, computed in that lower
precision, in the program's place and prints whether the comparison still
passes (it must not); the benchmark's own runs never use it.
"""

from __future__ import annotations

import os
import sys
import time

T_PROCESS_START = time.time()   # setup_s counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

TRACE_DIR = os.path.join(ROOT, "benchmark", ".trace")
TRACE_DELAY_S = 1.0     # into the window before the profiler starts
TRACE_LEN_S = 3.0       # traced sub-window (a whole window's trace of a
#                         3000 stmts/s cell is hundreds of MB)
FIRST_EXEC_TIMEOUT_S = 1150.0


class Refused(Exception):
    """The run cannot be a measurement: exit non-zero, print no result."""


def say(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True, default=str), flush=True)


# ------------------------------------------------------------ set-up ----

def make_store():
    """The store `python -m cockroach_tpu start` builds (cli.cmd_start):
    MVCCStore on the default, native, engine. A Python engine is not the
    deployment."""
    from cockroach_tpu.storage.engine import NativeEngine
    from cockroach_tpu.storage.mvcc import MVCCStore

    store = MVCCStore()
    if not isinstance(store.engine, NativeEngine):
        raise Refused(f"storage engine is {type(store.engine).__name__}, "
                      f"not the native engine")
    return store


def device_record(jax, cell_chips: int, rehearse: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if not rehearse:
        if dev.platform != "tpu":
            raise Refused(f"no TPU: jax.devices()[0].platform = "
                          f"{dev.platform!r}")
        if len(devs) < cell_chips:
            raise Refused(f"the cell asks for {cell_chips} chips, JAX "
                          f"reports {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs) if rehearse else cell_chips}


def peaks_for(kind: str, rehearse: bool):
    table = manifest.load_json(manifest.HERE, "peaks.json")
    if kind in table["devices"]:
        return table["devices"][kind]
    if rehearse:
        return None
    raise Refused(f"device kind {kind!r} is not in benchmark/peaks.json")


def memory_peak(jax, chips: int) -> int:
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def first_executions(obs, addr, spec, job_seed, session_setup):
    """Each statement of the cell once, from this process, before the
    clients exist: the first execution (compile or cache load, prime).
    -> [{statement, first_exec_s, programs, cache_loads, compiled,
    prime_bytes}]; `programs` counts JAX's backend_compile events, which
    fire for a persistent-cache hit too, so compiled = programs - loads."""
    from benchmark import observe, paramgen, wire

    client = wire.WireClient(addr, timeout=FIRST_EXEC_TIMEOUT_S)
    out = []
    try:
        for text in session_setup:
            _rows, code = client.query(text)
            if code is not None:
                raise Refused(f"{text!r}: sqlstate {code}")
        for stmt in spec["statements"]:
            params = paramgen.first(stmt, job_seed)
            before = obs.snapshot()
            t0 = time.perf_counter()
            if stmt.get("protocol", "simple") == "extended":
                rows, code = client.query_extended(stmt["sql"], params)
            else:
                rows, code = client.query(stmt["sql"])
            dt = time.perf_counter() - t0
            d = observe.delta(before, obs.snapshot())
            if code is not None:
                raise Refused(f"first execution of {stmt['name']}: "
                              f"sqlstate {code}")
            prime = sum(int(s.get("bytes", 0))
                        for n, s in d["stages"].items()
                        if n in ("scan.transfer", "serving.image_build",
                                 "resident.h2d"))
            out.append({"statement": stmt["name"], "first_exec_s": dt,
                        "programs": d["compiles"],
                        "cache_loads": d["cache_loads"],
                        "compiled": d["compiles"] - d["cache_loads"],
                        "flow_restarts":
                            d["counters"].get("sql_flow_restarts_total", 0),
                        "prime_bytes": prime, "rows": len(rows)})
    finally:
        client.close()
    return out


# --------------------------------------------------------- the window ----

class TraceWindow:
    """Profiler on for TRACE_LEN_S, starting TRACE_DELAY_S into the
    window, from a thread of its own (the main thread is blocked on the
    client child's pipe)."""

    def __init__(self, jax, seconds: float):
        self.jax = jax
        self.delay = min(TRACE_DELAY_S, seconds / 4)
        self.length = min(TRACE_LEN_S, seconds / 2)
        self.error = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        self._thread.start()

    def _run(self):
        try:
            time.sleep(self.delay)
            # no Python tracer: it hooks every call of a host path that
            # is all Python, and its start and stop stall the server for
            # seconds; TraceAnnotations (host_tracer_level) stay on
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            time.sleep(self.length)
            self.jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported by the run
            self.error = e

    def finish(self) -> dict:
        from benchmark import trace_reduce

        self._thread.join(timeout=300)
        if self._thread.is_alive() or self.error is not None:
            raise Refused(f"the profiler did not finish: {self.error}")
        events = trace_reduce.load_xplane(trace_reduce.find_xplane(TRACE_DIR))
        reduced = trace_reduce.reduce(events)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return reduced


def run_clients(job: dict, on_ready):
    """Start the client child, wait for its warm-up, call
    on_ready(its warm-up report), say go, and return its result once the
    window has closed."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "benchmark", "client.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
        cwd=ROOT)
    try:
        proc.stdin.write(json.dumps(job) + "\n")
        proc.stdin.flush()
        line = proc.stdout.readline()
        if not line:
            raise Refused(f"the client process ended in warm-up "
                          f"(exit {proc.wait(timeout=30)})")
        ready = json.loads(line)["ready"]
        on_ready(ready)
        proc.stdin.write("go\n")
        proc.stdin.flush()
        line = proc.stdout.readline()
        if not line:
            raise Refused(f"the client process ended in the window "
                          f"(exit {proc.wait(timeout=30)})")
        result = json.loads(line)["result"]
        proc.stdin.close()
        if proc.wait(timeout=60) != 0:
            raise Refused(f"the client process exited {proc.returncode}")
        return result
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def percentile(sorted_vals, q: float) -> float:
    """Linear interpolation between closest ranks."""
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo)


def judge_window(spec, loaded, result, seconds, control):
    """Every returned row set against the plain reference. -> (latencies
    of correct statements in the window, attempted, failed, compared,
    control line or None)."""
    refs = []
    for stmt in spec["statements"]:
        mod = importlib.import_module(
            f"benchmark.reference.{stmt['reference']}")
        refs.append(mod.Reference(loaded["data"], loaded["dicts"], stmt))
    responses = result["responses"]
    ok = [False] * len(responses)
    compared = []
    control_line = None
    for si, (stmt, ref) in enumerate(zip(spec["statements"], refs)):
        ids = [i for i, r in enumerate(responses) if r["stmt"] == si]
        clean = [i for i in ids if responses[i]["code"] is None]
        rows_of = lambda i: [tuple(r) for r in responses[i]["rows"]]
        oks, comp = ref.check([(tuple(responses[i]["params"]), rows_of(i))
                               for i in clean])
        for i, good in zip(clean, oks):
            ok[i] = good
        compared += [dict(c, name=f"{stmt['name']}.{c['name']}")
                     for c in comp]
        if control:
            fake = [(tuple(responses[i]["params"]),
                     ref.control_rows(tuple(responses[i]["params"]),
                                      control)) for i in clean]
            c_oks, c_comp = ref.check(fake)
            control_line = {
                "control": control, "statement": stmt["name"],
                "responses": len(fake),
                "control_correct": bool(fake) and all(c_oks),
                "compared": c_comp}
    t_end = result["t_begin"] + seconds
    lat, attempted, failed = [], 0, 0
    quarters = [0, 0, 0, 0]
    # the slowest answers as [seconds into the window, ms, client]: a
    # stall of the whole server shows as one entry per client at one time
    slowest = sorted(([round(t0 - result["t_begin"], 3),
                       round((t1 - t0) * 1e3, 1), client]
                      for client, _si, t0, t1, _rid in result["records"]
                      if t1 <= t_end), key=lambda e: -e[1])
    for _client, _si, t0, t1, rid in result["records"]:
        if t1 > t_end:
            continue  # answered after the window closed: not of this run
        attempted += 1
        quarters[min(3, int(4 * (t1 - result["t_begin"]) / seconds))] += 1
        if ok[rid]:
            lat.append((t1 - t0) * 1e3)
        else:
            failed += 1
    return {"lat": sorted(lat), "attempted": attempted, "failed": failed,
            "compared": compared, "control": control_line,
            "quarters": quarters, "slowest": slowest[:8]}


def run_checks(judged: dict, expect: dict, window: dict, whole: dict):
    """Everything `correct` rests on, each number beside its limit: the
    reference's comparisons, then what the counters say about the path the
    statements took (benchmark/README.md, "expect")."""
    from benchmark import observe

    checks = list(judged["compared"])
    attempted = judged["attempted"]

    def check(name, value, limit, ok):
        checks.append({"name": name, "value": value, "limit": limit,
                       "ok": bool(ok)})

    check("statements_failed", judged["failed"], 0, judged["failed"] == 0)
    check("statements_correct_in_window", len(judged["lat"]), ">=10",
          len(judged["lat"]) >= 10)
    check("backend_compiles_in_window", window["compiles"], 0,
          window["compiles"] == 0)
    check("cache_loads_in_window", window["cache_loads"], 0,
          window["cache_loads"] == 0)
    bad = observe.faults(whole["stages"])
    check("fault_stages_counted", bad, [], not bad)
    roots = {t: c for (n, t), c in window["tiers"].items()
             if n == expect["root_span"]}
    wrong_tier = {t: c for t, c in roots.items()
                  if t != str(expect["tier"])}
    check("root_spans_off_tier", wrong_tier, {}, not wrong_tier)
    spans = sum(roots.values())
    check("root_spans_in_window", spans, f">={attempted}",
          spans >= attempted)
    execs = window["stages"].get(expect["exec_stage"], {}).get("events", 0)
    check(f"{expect['exec_stage']}_events_in_window", execs, ">=1",
          execs >= 1)
    for name in expect.get("zero_counters", ()):
        got = whole["counters"].get(name, 0)  # never registered: never counted
        check(f"{name}_whole_run", got, 0, got == 0)
    if expect.get("per_statement_counter"):
        got = window["counters"].get(expect["per_statement_counter"], 0)
        check(f"{expect['per_statement_counter']}_in_window", got,
              f">={attempted}", got >= attempted)
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's rehearse "
                    "scale; never a measurement")
    ap.add_argument("--control", default=None,
                    help="also judge the reference computed in this lower "
                    "precision (must come out not correct)")
    args = ap.parse_args(argv)

    bench = manifest.benchmark()
    entry = manifest.entry(bench, args.workload)
    spec = manifest.cell(args.workload)
    cfg = manifest.config(entry["config"])
    if spec["config"] != entry["config"]:
        raise Refused("cell file and BENCHMARK.json name different configs")

    import jax

    device = device_record(jax, entry["chips"], args.rehearse)
    peaks = peaks_for(device["kind"], args.rehearse)

    import cockroach_tpu  # noqa: F401 — x64 and the compile-cache resolver
    from cockroach_tpu.sql.pgwire import PgServer
    from cockroach_tpu.util import compile_cache

    from benchmark import observe

    cache_dir = compile_cache.enable_persistent_cache()
    if cache_dir is None:
        raise Refused("the persistent compile cache is not writable")
    obs = observe.Observer(annotate=bool(args.trace))
    say({"phase": "start", "workload": args.workload, "seed": args.seed,
         "device": device, "rehearsal": args.rehearse,
         "compile_cache_dir": cache_dir, "jax": jax.__version__})

    scale = dict(cfg["loader"]["args"])
    session_setup = list(cfg.get("session_setup", ()))
    if args.rehearse:
        scale.update(cfg["rehearse"]["loader_args"])
        session_setup += cfg["rehearse"].get("session_setup", ())
        for stmt in spec["statements"]:
            if stmt.get("params"):
                stmt["params"].update(cfg["rehearse"].get("params", {}))
    tables = sorted({t for s in spec["statements"] for t in s["tables"]})
    loader = importlib.import_module(
        f"benchmark.loaders.{cfg['loader']['name']}")
    store = make_store()
    loaded = loader.load(store, scale, tables, args.seed)
    say({"phase": "load", "tables": loaded["rows"],
         "seconds": loaded["load_s"]})

    pg = PgServer(loaded["catalog"], capacity=int(cfg["capacity"])).start()
    state = {"before": None, "trace": None}
    try:
        first = first_executions(obs, pg.addr, spec, args.seed,
                                 session_setup)
        say({"phase": "first_execution", "statements": first})
        for step in cfg.get("warmup", ()):
            importlib.import_module(
                f"benchmark.warmup.{step}").run(pg, cfg, spec)
        job = {"traffic": spec["traffic_kind"],
               "traffic_params": spec["traffic_params"],
               "statements": spec["statements"], "addr": list(pg.addr),
               "seed": args.seed, "seconds": args.seconds,
               "timeout_s": 120.0, "session_setup": session_setup}

        def on_ready(ready):
            last = sorted(ready.pop("warmup_last_s"))
            say({"phase": "warm", **ready,
                 "warmup_last_round_s": {"median": statistics.median(last),
                                         "max": last[-1]}})
            if ready["warmup_errors"]:
                raise Refused(f"{ready['warmup_errors']} warm-up "
                              f"statements failed")
            state["before"] = obs.snapshot()
            if args.trace:
                state["trace"] = TraceWindow(jax, args.seconds)
                state["trace"].start()

        result = run_clients(job, on_ready)
        after = obs.snapshot()
        reduced = state["trace"].finish() if state["trace"] else None
        device["memory_peak_bytes"] = memory_peak(jax, entry["chips"])
    finally:
        pg.close()

    # ---- after the window: the reference, the checks, the metrics ----
    setup_s = result["window_wall_start"] - T_PROCESS_START
    judged = judge_window(spec, loaded, result, args.seconds, args.control)
    window = observe.delta(state["before"], after)
    whole = obs.snapshot()
    checks = run_checks(judged, spec["expect"], window, whole)
    for c in checks:
        say({"compared": c["name"], "value": c["value"],
             "limit": c["limit"], "ok": c["ok"]})
    if judged["control"] is not None:
        say(judged["control"])
    correct = all(c["ok"] for c in checks)

    lat, attempted, failed = (judged["lat"], judged["attempted"],
                              judged["failed"])
    client = {"n": len(lat), "seconds": args.seconds}
    if lat:
        client.update(p50_ms=percentile(lat, 0.50),
                      p95_ms=percentile(lat, 0.95), max_ms=lat[-1],
                      mean_ms=statistics.fmean(lat),
                      per_s=len(lat) / args.seconds)
    say({"phase": "window", "latency_samples": len(lat),
         "answered_per_quarter": judged["quarters"],
         "slowest": judged["slowest"],
         "attempted": attempted, "failed": failed, "client": client,
         "drained_s": result["drained_s"],
         "window_wall_start": result["window_wall_start"],
         "window_counters": {k: v for k, v in window["counters"].items()
                             if v},
         "window_stages": {n: [s["events"], round(s["seconds"], 4)]
                           for n, s in window["stages"].items()}})

    ctx = {"cell": spec, "config": cfg, "entry": entry, "client": client,
           "setup_s": setup_s, "first": first, "load": loaded,
           "window": window, "whole": whole, "trace": reduced,
           "events": obs.events_between(state["before"], after),
           "peaks": peaks, "loader": loader, "device": device}
    kind, folder = (("per_layer", "layer_metrics") if args.trace
                    else ("end_to_end", "e2e_metrics"))
    metrics = {}
    for m in manifest.metrics_for(bench, args.workload, kind):
        value = importlib.import_module(
            f"benchmark.{folder}.{m['name']}").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Refused as e:
        print(f"benchmark/run.py: {e}; nothing reported", file=sys.stderr)
        rc = 2
    except BaseException:  # noqa: BLE001 — report, then exit non-zero
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads (pgwire accept loops, prewarm) must not hold the
    # process, or the chip, past the last line
    os._exit(rc)
