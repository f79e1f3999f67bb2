"""Grace hash join under a tiny workmem against the in-memory join.

A file of its own (the other spill tests are in tests/test_spill.py): on
an empty compile cache this one test takes minutes, and under `--dist
loadfile` a file is one worker's.
"""

import numpy as np

from cockroach_tpu.exec import collect
from cockroach_tpu.exec.operators import JoinOp
from tests.test_spill import _scan, flow_stats  # noqa: F401 (fixture)


def test_grace_join_matches_in_memory(rng, flow_stats):
    n_probe, n_build = 600, 400
    probe = {"pk": rng.integers(0, 200, n_probe).astype(np.int64)}
    build = {"bk": rng.integers(0, 200, n_build).astype(np.int64),
             "bv": np.arange(n_build, dtype=np.int64)}

    big = JoinOp(_scan(probe, 64), _scan(build, 64), ["pk"], ["bk"])
    want = collect(big)

    small = JoinOp(_scan(probe, 64), _scan(build, 64), ["pk"], ["bk"],
                   workmem=64 * 16)  # a single 64-row batch blows it
    got = collect(small)
    assert flow_stats.stage("join.grace_spill").events >= 1
    assert flow_stats.stage("spill.write").rows > 0

    def norm(r):
        return sorted(zip(r["pk"].tolist(), r["bk"].tolist(),
                          r["bv"].tolist()))
    assert norm(got) == norm(want)
    # spill accounting fully released
    from cockroach_tpu.exec.spill import host_spill_monitor
    assert host_spill_monitor().used == 0
