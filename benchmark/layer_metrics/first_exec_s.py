"""Layer: plan + compile. Client seconds of the first execution of the
cell's statements in this process (compile or cache load, prime, run);
how many programs were compiled and how many loaded is on the
"first_execution" line."""


def read(ctx):
    return sum(f["first_exec_s"] for f in ctx["first"])
