"""From the profiler's trace (xplane) to numbers: device busy and idle,
the device operations that took most time, and the idle gaps by what the
host was doing. The yardstick for every trace-sourced metric; checked on
the recorded trace beside this file (trace_sample.json.gz,
test_benchmark.py).

Two steps, so that the arithmetic can be tested without a profile file:

  load_xplane(path) -> events
      {"device": [[name, start_ns, dur_ns, chip], ...],
       "host":   [[name, start_ns, dur_ns], ...],
       "extent": [first_start_ns, last_end_ns]}
    device: on a TPU, the events of the "XLA Ops" line of every
    "/device:TPU:<n>" plane; on the CPU backend (rehearsal only) the host
    events that carry an `hlo_op` stat, as chip 0. host: the benchmark's
    own annotations, names starting with "bench." (observe.py). extent:
    the first start and the last end over every event of every plane: the
    profiler records from somewhere inside start_trace() to somewhere
    inside stop_trace(), so the host clock around the two calls is not
    the window the device events lie in; the trace's own extent is.

  reduce(events) -> {"busy_s", "window_s", "idle_pct",
                     "device_ops", "idle_gaps", "chips"}
    window_s is the extent in seconds.
    busy_s is the union of the device-operation intervals of one chip,
    averaged over the chips that ran anything; idle_pct = 100 * (1 -
    busy_s / window_s). device_ops: the ten names with the most summed
    device seconds (chip 0). idle_gaps: every gap between busy intervals
    of chip 0 goes to the innermost host annotation that covers the gap's
    midpoint ("(none)" if no annotation does); the ten names with the most
    summed gap seconds.
"""

from __future__ import annotations

import glob
import os

import numpy as np

_TOP = 10
_MAX_GAPS = 5000
_SKIP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
               "Framework Name Scope", "Source code", "Launch Stats")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _op_name(text: str) -> str:
    """A TPU trace names an operation by its whole HLO line
    ("%sort.163 = (s32[8650752]{...}, ...) sort(...)"): keep the
    instruction's name and its first result shape."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:96]
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape}"[:96]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = [], []
    t_min, t_max = float("inf"), 0.0
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:TPU:"):
            chip = int(pname.rsplit(":", 1)[1].split()[0])
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or [
                ln for ln in lines if ln.name not in _SKIP_LINES]
            for ln in ops:
                for e in ln.events:
                    device.append([_op_name(e.name), float(e.start_ns),
                                   float(e.duration_ns), chip])
                    t_min = min(t_min, e.start_ns)
                    t_max = max(t_max, e.start_ns + e.duration_ns)
        elif pname.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    name = e.name
                    t_min = min(t_min, e.start_ns)
                    t_max = max(t_max, e.start_ns + e.duration_ns)
                    if name.startswith("bench."):
                        host.append([name[6:], float(e.start_ns),
                                     float(e.duration_ns)])
                    elif e.duration_ns > 0 and not name.startswith("end:") \
                            and any(k == "hlo_op" for k, _ in e.stats):
                        device.append([name, float(e.start_ns),
                                       float(e.duration_ns), 0])
    return {"device": device, "host": host,
            "extent": [float(t_min), float(t_max)]}


def _union(starts: np.ndarray, ends: np.ndarray):
    """Sorted, merged intervals."""
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > run_end[:-1]]
    idx = np.flatnonzero(new)
    m_start = s[idx]
    m_end = np.r_[run_end[idx[1:] - 1], run_end[-1]]
    return m_start, m_end


def reduce(events: dict) -> dict:
    dev = events["device"]
    window_s = (events["extent"][1] - events["extent"][0]) / 1e9
    if not dev:
        return {"busy_s": 0.0, "window_s": window_s, "idle_pct": 100.0,
                "device_ops": [], "idle_gaps": [], "chips": 0}
    names = np.array([d[0] for d in dev], dtype=object)
    start = np.array([d[1] for d in dev], dtype=np.float64)
    dur = np.array([d[2] for d in dev], dtype=np.float64)
    chip = np.array([d[3] for d in dev], dtype=np.int64)
    chips = sorted(set(chip.tolist()))
    busy = []
    for c in chips:
        m = chip == c
        ms, me = _union(start[m], start[m] + dur[m])
        busy.append(float((me - ms).sum()) / 1e9)
    busy_s = sum(busy) / len(busy)
    c0 = chip == chips[0]
    totals = {}
    for n, d in zip(names[c0], dur[c0]):
        totals[n] = totals.get(n, 0.0) + float(d) / 1e9
    device_ops = [[n, s] for n, s in sorted(totals.items(),
                                            key=lambda kv: -kv[1])[:_TOP]]
    ms, me = _union(start[c0], start[c0] + dur[c0])
    g_start, g_end = me[:-1], ms[1:]
    g_len = g_end - g_start
    keep = np.argsort(-g_len)[:_MAX_GAPS]
    host = events["host"]
    h_names = [h[0] for h in host]
    h_start = np.array([h[1] for h in host], dtype=np.float64)
    h_end = h_start + np.array([h[2] for h in host], dtype=np.float64)
    by = {}
    for i in keep:
        if g_len[i] <= 0:
            continue
        mid = 0.5 * (g_start[i] + g_end[i])
        who = "(none)"
        if len(host):
            cover = np.flatnonzero((h_start <= mid) & (h_end >= mid))
            if len(cover):
                who = h_names[cover[np.argmin((h_end - h_start)[cover])]]
        by[who] = by.get(who, 0.0) + float(g_len[i]) / 1e9
    idle_gaps = [[n, s] for n, s in sorted(by.items(),
                                           key=lambda kv: -kv[1])[:_TOP]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "device_ops": device_ops, "idle_gaps": idle_gaps,
            "chips": len(chips)}
