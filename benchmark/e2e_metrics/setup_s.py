"""Process start (first line of run.py) to the first timed statement:
import, load, first execution (compile or cache load), prime, warm-up."""


def read(ctx):
    return ctx["setup_s"]
