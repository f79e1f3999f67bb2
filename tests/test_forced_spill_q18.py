"""TPC-H Q18 under a forced spill.

A file of its own (the other spill tests are in tests/test_spill.py): on
an empty compile cache this one test takes minutes, and under `--dist
loadfile` a file is one worker's.
"""

from cockroach_tpu.exec import collect, stats


def test_q18_with_forced_spill():
    """North-star config #4 shape: Q18's big GROUP BY l_orderkey runs
    under a tiny workmem and still matches the oracle (BASELINE.md)."""
    from cockroach_tpu.workload.tpch import TPCH
    from cockroach_tpu.workload import tpch_queries as Q
    from cockroach_tpu.util.settings import Settings, WORKMEM

    s = stats.enable()
    gen = TPCH(sf=0.01)
    settings = Settings()
    old = settings.get(WORKMEM)
    settings.set(WORKMEM, 1 << 14)  # 16 KiB per operator
    try:
        flow = Q.q18(gen, threshold=50, capacity=1024)
        got = collect(flow)
    finally:
        settings.set(WORKMEM, old)
        stats.disable()
    assert (s.stage("agg.grace_spill").events >= 1
            or s.stage("join.grace_spill").events >= 1)
    o18 = Q.q18_oracle(gen, threshold=50)
    got_rows = list(zip(got["o_orderkey"].tolist(), got["sum_qty"].tolist()))
    want = [(ok, q) for cn, ck, ok, od, tp, q in o18]
    assert got_rows == want
