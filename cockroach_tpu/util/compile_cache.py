"""The one place that decides where JAX's persistent compilation cache is.

Whole-query fused programs compile in seconds to minutes (Q3 at SF1: two
minutes for a v5e); the persistent cache makes that a once-per-machine
cost instead of once-per-process, and the shape-bucketed config keys
(exec/fused.py pads scan chunk counts to powers of two) keep the entry
count small. The directory is part of the cache key, so it must not move
between runs: nothing here is made from a temporary name, a pid or the
time, and no other module updates `jax_compilation_cache_dir`.

Resolution, first that applies:

1. `JAX_COMPILATION_CACHE_DIR` in the environment — JAX reads it itself;
   this module then sets NO directory (only the two thresholds), so the
   operator's (or the chip machine's) choice is never overridden;
2. the `sql.tpu.compilation_cache_dir` setting (kept for tests);
3. the caller's `default` (tests/conftest.py: `.jax_cache_cpu`);
4. `<checkout>/.jax_cache`.

`import cockroach_tpu` calls resolve() once (no file I/O at import); a
later call that names a default (conftest, after the import it cannot
precede) re-points the cache, a later call without one leaves it alone.
enable_persistent_cache() — bench, scripts, conftest — also probes the
directory, and an unwritable one is NOT silent: a node quietly compiling cold on
every restart is exactly what this exists to prevent, so it logs a
structured OPS warning and sets the `compile_cache_mounted` gauge to 0.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Optional

from cockroach_tpu.util.settings import COMPILATION_CACHE_DIR, Settings

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")

_resolved: Optional[str] = None  # what the last call settled on


def _mounted_gauge():
    from cockroach_tpu.util.metric import default_registry

    return default_registry().gauge(
        "compile_cache_mounted",
        "1 when the persistent XLA compilation cache is mounted and "
        "writable; 0 when enable_persistent_cache failed (node pays "
        "cold compiles every restart)")


def _warn_unmounted(directory: Optional[str], reason: str) -> None:
    from cockroach_tpu.util.log import Channel, get_logger

    _mounted_gauge().set(0)
    get_logger().structured(
        Channel.OPS, "WARNING", "compile_cache.mount_failed",
        directory=str(directory), reason=reason[:200])


def resolve(default: Optional[str] = None) -> str:
    """Decide where the cache is (module docstring) and tell JAX — the
    two thresholds always, the directory unless the environment names
    one. No file I/O: `import cockroach_tpu` calls this."""
    global _resolved
    import jax

    # cache everything: even sub-second entries add up across the
    # hundreds of per-capacity kernels a run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get(ENV_VAR)
    if env:
        _resolved = env
    elif default is not None or _resolved is None:
        _resolved = os.path.abspath(
            Settings().get(COMPILATION_CACHE_DIR) or default or DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", _resolved)
    return _resolved


def enable_persistent_cache(default: Optional[str] = None) -> Optional[str]:
    """resolve(), then prove the directory writable; returns it, or None
    when it is not — never silently."""
    directory = resolve(default)
    try:
        # probe writability up front: jax's cache writes fail silently at
        # compile time, long after the misconfiguration happened
        os.makedirs(directory, exist_ok=True)
        probe = os.path.join(directory, ".cc_probe")
        with open(probe, "w") as f:
            f.write("ok")
        os.unlink(probe)
    except OSError as e:
        _warn_unmounted(directory, f"unwritable: {e}")
        return None
    _mounted_gauge().set(1)
    return directory


@contextmanager
def persistent_cache_disabled():
    """Compile without reading or writing the persistent cache, wherever
    it is: cold-start measurements, plan-vault round trips (an executable
    that was a cache HIT re-serializes without its symbols on CPU PjRt),
    and compiles for a described — not attached — TPU, which the cache
    can store but never load back. The directory is not touched."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()  # the cache latches at the first compile
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()
