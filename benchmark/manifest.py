"""BENCHMARK.json, the cell files and the configuration files: loading,
and the contract's rules on names, units and entries (test_benchmark.py
runs validate() so that a later PR's new entry is checked before the
driver refuses it)."""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell(name: str) -> dict:
    if not NAME.match(name):
        raise ValueError(f"bad workload name {name!r}")
    spec = load_json(HERE, "workloads", name + ".json")
    for s in spec["statements"]:
        if "sql_file" in s:
            with open(os.path.join(HERE, "workloads", s["sql_file"])) as f:
                s["sql"] = " ".join(f.read().split())
    return spec


def config(name: str) -> dict:
    return load_json(HERE, "configs", name + ".json")


def entry(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"{workload!r} is not a workload of BENCHMARK.json "
                   f"({[w['name'] for w in bench['workloads']]})")


def metrics_for(bench: dict, workload: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def _line(text, what, errors):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        errors.append(f"{what}: not one line of 1..200 characters")


def validate(bench: dict) -> list:
    """-> list of faults against the contract (empty when sound)."""
    errors = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != want:
        errors.append(f"keys {sorted(bench)} != {sorted(want)}")
        return errors
    if not (isinstance(bench["run_seconds"], int)
            and 1 <= bench["run_seconds"] <= 51):
        errors.append("run_seconds outside 1..51")
    for word in bench["command"]:
        _line(word, "command word", errors)
    names = set()
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errors.append(f"config {c.get('name')}: keys {sorted(c)}")
        for key in [c["name"]] + list(c["reduced"]):
            if not NAME.match(key):
                errors.append(f"config name/reduced {key!r}")
        _line(c["source"], f"config {c['name']} source", errors)
        _line(c["why"], f"config {c['name']} why", errors)
        if not any(c["file"].startswith(p + "/") for p in bench["paths"]):
            errors.append(f"config file {c['file']} outside paths")
        if not os.path.exists(os.path.join(ROOT, c["file"])):
            errors.append(f"config file {c['file']} missing")
        if c["name"] in names:
            errors.append(f"duplicate name {c['name']}")
        names.add(c["name"])
    cfgs = {c["name"] for c in bench["configs"]}
    cells, pairs = set(), set()
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errors.append(f"workload {w.get('name')}: keys {sorted(w)}")
        for key in (w["name"], w["config"], w["traffic"]):
            if not NAME.match(key):
                errors.append(f"workload name {key!r}")
        if w["config"] not in cfgs:
            errors.append(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            errors.append(f"workload {w['name']}: chips {w['chips']}")
        _line(w["why"], f"workload {w['name']} why", errors)
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            errors.append(f"duplicate workload {w['name']}")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    if cfgs - {w["config"] for w in bench["workloads"]}:
        errors.append("a config is used by no cell")
    mnames = set()
    e2e = {m["name"] for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        errors.append("no setup_s")
    for kind, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                       "source"}),
                       ("per_layer", {"name", "unit", "better", "source",
                                      "layer", "moves"})):
        for m in bench[kind]:
            if set(m) - {"workloads"} != keys:
                errors.append(f"{kind} {m.get('name')}: keys {sorted(m)}")
                continue
            if not NAME.match(m["name"]) or m["name"] in mnames:
                errors.append(f"metric name {m['name']!r}")
            mnames.add(m["name"])
            if not UNIT.match(m["unit"]):
                errors.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                errors.append(f"metric {m['name']}: better")
            if m["source"] not in SOURCES:
                errors.append(f"metric {m['name']}: source")
            if kind == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    errors.append(f"metric {m['name']}: e2e source")
                if not 0.01 <= m["bound"] <= 0.25:
                    errors.append(f"metric {m['name']}: bound")
            else:
                _line(m["layer"], f"metric {m['name']} layer", errors)
                if m["moves"] not in e2e:
                    errors.append(f"metric {m['name']}: moves "
                                  f"{m['moves']!r}")
            for w in m.get("workloads", ()):
                if w not in cells:
                    errors.append(f"metric {m['name']}: unknown cell {w}")
    for w in sorted(cells):
        judged = {m["name"] for m in metrics_for(bench, w, "end_to_end")}
        if "setup_s" not in judged or len(judged) < 2:
            errors.append(f"workload {w}: reports {sorted(judged)}, not "
                          f"setup_s and one more end-to-end metric")
        layer = metrics_for(bench, w, "per_layer")
        if not layer:
            errors.append(f"workload {w}: reports no per-layer metric")
        for m in layer:
            if m["moves"] in e2e - judged:
                errors.append(f"metric {m['name']}: moves {m['moves']}, "
                              f"which {w} does not report")
    return errors
