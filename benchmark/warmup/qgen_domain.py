"""Warm-up step `qgen_domain`: every parameterised statement of the cell,
once at each corner of its parameter domain (paramgen `corners`), through
the extended protocol, before the clients connect.

A deployment that has been up for a day has seen its domain. The
statement's one program is sized from the binding its plan was made at
(the first execution's); if a corner overflows a capacity, the flow
restart, the widening and the compile of the wider program happen here,
in setup_s, and the widened plan is what the window runs. A corner that
errors fails the run.

Before the first corner the step holds the configuration's guarantee
"every parameter bound as data" to what the first executions did: a program
that has counted no value in `sql_bind_params_total` wrote them into the
statement's text, where every corner, and every statement of the window,
is a new text, a new plan and a whole-query compile of minutes. The step
then fails the run at once, instead of compiling its way through the domain
(the cell's `zero_counters` cannot see it: such a program has no
`sql_bind_textual_total` either).

Runs in the server's process (it never touches
JAX beyond asking which platform this is): off the TPU the configuration's
rehearsal set-up statements are sent first, as the harness sends them on
its own connections.
"""

from __future__ import annotations

import importlib

from cockroach_tpu.util.metric import default_registry

from benchmark import wire


def run(pg, config: dict, cell: dict) -> None:
    import jax

    setup = list(config.get("session_setup", ()))
    if jax.devices()[0].platform != "tpu":
        setup += config.get("rehearse", {}).get("session_setup", ())
    bound = {name: m.value() for name, m in default_registry().metrics()
             if name == "sql_bind_params_total"}
    if any(s.get("params") for s in cell["statements"]) \
            and not bound.get("sql_bind_params_total"):
        raise RuntimeError(
            "the first executions bound no parameter as data "
            "(sql_bind_params_total is not counted): this program writes "
            "bound values into the statement's text, so every binding of "
            "this configuration is a new plan and a new compile")
    client = wire.WireClient(pg.addr, timeout=1150.0)
    try:
        for text in setup:
            _rows, code = client.query(text)
            if code is not None:
                raise RuntimeError(f"{text!r}: sqlstate {code}")
        for stmt in cell["statements"]:
            spec = stmt.get("params")
            if not spec:
                continue
            gen = importlib.import_module(
                f"benchmark.paramgen.{spec['kind']}")
            for params in gen.corners(spec):
                _rows, code = client.query_extended(stmt["sql"], params)
                if code is not None:
                    raise RuntimeError(
                        f"warm-up of {stmt['name']} at {params}: "
                        f"sqlstate {code}")
    finally:
        client.close()
