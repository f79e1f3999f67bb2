"""Warm-up steps a configuration asks for by name (its "warmup" list):
benchmark/warmup/<step>.py with run(pg, config, cell), called after the
first executions and before the clients connect. The time it takes counts
as set-up. No configuration asks for one yet."""
