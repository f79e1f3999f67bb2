"""Test harness configuration.

Runs the whole suite on a virtual 8-device CPU mesh (the reference's analog:
logictest's `fakedist` configs run 3 in-process nodes with a fake span
resolver to force distribution without real hardware — SURVEY.md §4.2/§4.6).
Multi-chip sharding paths compile and execute here as they would on a TPU
slice; chip_smoke.py is what runs on the chip.
"""

import os

# Tests run without a chip: force the CPU platform and eight virtual
# devices before any backend initializes — the env var in case jax is not
# yet imported, the config below in case something already imported it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite is compile-dominated (every
# operator x capacity x config is a fresh XLA program), so caching across
# runs is the single biggest iteration-speed lever. A cpu-only directory,
# handed to the one resolver as its default: `.jax_cache` holds programs
# compiled for the chip.
from cockroach_tpu.util.compile_cache import (  # noqa: E402
    enable_persistent_cache,
)

enable_persistent_cache(default=os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".jax_cache_cpu"))

import faulthandler  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# A test that hangs must cost its own result and not the run: the driver
# cuts the whole command at its time limit (twice a run ended at rc 124
# with every worker idle, four tests short, one worker at 0% CPU inside
# tests/test_tpu_compile.py's compiles). pytest-timeout is not installed,
# so each test arms the interpreter's own watchdog at its set-up and
# disarms it after its teardown: past HANG_SECONDS it prints every
# thread's stack and exits the process; xdist reports the test as crashed,
# replaces the worker and runs the rest. The longest FILE takes about 280 s.
HANG_SECONDS = 600


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    faulthandler.dump_traceback_later(HANG_SECONDS, exit=True,
                                      file=sys.__stderr__)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def one_traced_rehearsal():
    """`benchmark/run.py --trace 1` writes its profile under ONE directory
    (benchmark/.trace) and clears it first, so two traced rehearsals at
    once, on two xdist workers, lose each other's trace. A test that runs
    one holds this lock meanwhile: an flock on the benchmark's directory
    itself, which every worker of the checkout sees and which leaves no
    file behind."""
    import fcntl

    fd = os.open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"), os.O_RDONLY)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)    # closing the descriptor drops the lock


@pytest.fixture(autouse=True)
def _resilience_hygiene():
    """Disarm every fault point and close every circuit breaker after each
    test: an armed point (or a breaker tripped by intentional failures)
    would otherwise leak into unrelated tests when an assertion fires
    before the test's own cleanup."""
    yield
    from cockroach_tpu.util import circuit
    from cockroach_tpu.util.fault import registry

    registry().disarm()
    circuit.reset_all()


@pytest.fixture(autouse=True)
def _dist_cache_hygiene():
    """The distributed program + ingest-shard caches are process-wide
    (a warm re-plan is the feature under test); between tests that
    sharing would make compile/ingest event assertions order-dependent,
    so each test starts from its own cold distributed state."""
    yield
    from cockroach_tpu.parallel import dist_flow, ingest

    dist_flow.progs_clear()
    ingest.cache_clear()
