"""Typed settings registry.

Reference: pkg/settings (registry.go, bool.go:138 Register*Setting) — a typed,
named registry of cluster settings. This rebuild keeps the same three tiers
(SURVEY.md §5.6): cluster settings (this registry), session vars
(sql/session.py), process flags. Gossip propagation arrives with the
distribution layer; for now values are process-local.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional


_UNRESOLVED = object()  # sentinel: env override not yet looked up


@dataclass
class _Setting:
    name: str
    default: Any
    description: str
    validate: Optional[Callable[[Any], None]] = None
    # default after the one-time env-override lookup (settings reads sit
    # on per-statement hot paths; rebuilding the env name and probing
    # os.environ on every read costs ~1us vs ~0.1us for this cache)
    resolved: Any = _UNRESOLVED


class Settings:
    """A typed settings registry with env-var overrides (COCKROACH_TPU_*).

    Values are process-global by default (the reference's cluster settings
    are cluster-global; gossip propagation arrives with the distribution
    layer): every `Settings()` handle reads/writes one shared store, so a
    `set()` is visible to operators constructed afterwards. Pass
    `isolated=True` for a private store (tests).
    """

    _registry: Dict[str, _Setting] = {}
    _shared_values: Dict[str, Any] = {}

    def __init__(self, isolated: bool = False):
        self._values: Dict[str, Any] = {} if isolated else Settings._shared_values

    @classmethod
    def register(
        cls,
        name: str,
        default: Any,
        description: str = "",
        validate: Optional[Callable[[Any], None]] = None,
    ) -> str:
        if name in cls._registry:
            raise ValueError(f"setting {name!r} registered twice")
        cls._registry[name] = _Setting(name, default, description, validate)
        return name

    def get(self, name: str) -> Any:
        vals = self._values
        if name in vals:
            return vals[name]
        reg = self._registry[name]
        if reg.resolved is not _UNRESOLVED:
            return reg.resolved
        env = "COCKROACH_TPU_" + name.upper().replace(".", "_")
        if env in os.environ:
            raw = os.environ[env]
            d = reg.default
            try:
                if isinstance(d, bool):
                    val = raw.lower() in ("1", "true", "yes", "on")
                elif isinstance(d, int):
                    val = int(raw)
                elif isinstance(d, float):
                    val = float(raw)
                else:
                    val = raw
            except ValueError as e:
                raise ValueError(f"invalid value for setting {name!r} "
                                 f"from ${env}: {raw!r}") from e
            if reg.validate is not None:
                reg.validate(val)
            reg.resolved = val
            return val
        reg.resolved = reg.default
        return reg.default

    def set(self, name: str, value: Any) -> None:
        reg = self._registry.get(name)
        if reg is None:
            raise KeyError(f"unknown setting {name!r}")
        if reg.validate is not None:
            reg.validate(value)
        self._values[name] = value

    @classmethod
    def all(cls) -> Dict[str, _Setting]:
        return dict(cls._registry)


# Core execution settings (defaults mirror the reference where noted).
# workmem: reference default 64 MiB (execinfra/server_config.go:379); we
# default higher because a TPU flow's working set lives in ~16 GB HBM.
WORKMEM = Settings.register(
    "sql.distsql.temp_storage.workmem",
    512 << 20,
    "per-operator memory budget before spilling",
)
DEFAULT_BATCH_SIZE = Settings.register(
    "sql.tpu.batch_size",
    1 << 16,
    "rows per device batch (reference coldata default 1024; TPU wants 16-64x)",
)
PALLAS = Settings.register(
    "sql.tpu.pallas",
    "auto",
    "Pallas kernel mode: auto (TPU only) | on | interpret (CPU tests) | off",
    validate=lambda v: None if v in ("auto", "on", "interpret", "off")
    else (_ for _ in ()).throw(ValueError(f"bad pallas mode {v!r}")),
)
# The cross-query scan-image cache (exec/scan_cache.py) holds each table's
# stacked device image across plan builds; separate from the per-operator
# resident budget (storage.hbm_cache_bytes) because the two populations
# have different lifetimes: operators die with their flow, cache entries
# die by LRU or storage-write invalidation.
SCAN_IMAGE_CACHE_BUDGET = Settings.register(
    "storage.hbm_scan_image_cache_bytes",
    6 << 30,
    "HBM budget for the cross-query scan-image cache (LRU-evicted)",
)
COMPILATION_CACHE_DIR = Settings.register(
    "sql.tpu.compilation_cache_dir",
    "",
    "persistent XLA compilation cache directory (empty = the checkout's "
    ".jax_cache); the JAX_COMPILATION_CACHE_DIR environment variable "
    "always wins over it (util/compile_cache.py)",
)
# Vector search (sql/plan.py VectorTopK): the ANN arm trades recall for
# latency; exact is the default because it is loss-free and already one
# fused dispatch. nprobe is the recall dial (recall@10 >= 0.9 at the
# default on clustered data; raise it for adversarial distributions).
VECTOR_ANN = Settings.register(
    "sql.vector.ann_topk",
    False,
    "use the clustered-ANN index for ORDER BY <vector distance> LIMIT k "
    "over bare scans (filtered queries always take the exact path)",
)
VECTOR_NPROBE = Settings.register(
    "sql.vector.nprobe",
    4,
    "clusters probed per ANN vector search (recall/latency dial)",
)
