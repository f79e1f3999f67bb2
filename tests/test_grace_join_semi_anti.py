"""Grace hash join, semi and anti, under a tiny workmem.

A file of its own (the other spill tests are in tests/test_spill.py): on
an empty compile cache this one test takes minutes, and under `--dist
loadfile` a file is one worker's.
"""

import numpy as np

from cockroach_tpu.exec import collect
from cockroach_tpu.exec.operators import JoinOp
from tests.test_spill import _scan, flow_stats  # noqa: F401 (fixture)


def test_grace_join_semi_anti(rng, flow_stats):
    probe = {"pk": rng.integers(0, 100, 500).astype(np.int64)}
    build = {"bk": rng.integers(0, 50, 300).astype(np.int64)}
    for how in ("semi", "anti"):
        want = collect(JoinOp(_scan(probe, 64), _scan(build, 64),
                              ["pk"], ["bk"], how=how))
        got = collect(JoinOp(_scan(probe, 64), _scan(build, 64),
                             ["pk"], ["bk"], how=how, workmem=64 * 16))
        assert sorted(got["pk"].tolist()) == sorted(want["pk"].tolist())
