"""Layer: kernels. The least time the chip could take to read what the
statement has to read (bytes_model.statement_bytes over the HBM peak of
peaks.json) over the device-busy seconds per statement of the traced
window, in percent. Memory-bound by construction: the statements are scans
and joins with next to no arithmetic per byte."""

from benchmark import bytes_model


def read(ctx):
    tr, peaks, per_s = ctx["trace"], ctx["peaks"], ctx["client"].get("per_s")
    if not tr or not peaks or not per_s or tr["busy_s"] <= 0:
        return None
    stmt_bytes = bytes_model.cell_bytes(ctx["cell"], ctx["loader"],
                                        ctx["load"]["rows"])
    if not stmt_bytes:
        return None
    busy_per_stmt = tr["busy_s"] / (per_s * tr["window_s"])
    return 100.0 * (stmt_bytes / peaks["hbm_bytes_per_s"]) / busy_per_stmt
