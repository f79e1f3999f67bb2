"""Persistent plan vault: serialized compiled executables on disk.

The persistent XLA cache (util/compile_cache.py) removes the *backend
compile* from a cold process, but a restarted node still pays the full
Python trace + lowering + cache probe per program before the first query
runs, and the XLA cache is opaque — no per-plan visibility, no DDL
hygiene. The vault closes the gap: after `jit(prog).lower(...)` produces
a StableHLO module, we key it by a content digest of the module text plus
the environment fingerprint (jax / jaxlib / platform), and either load a
previously serialized executable (`jax.experimental.serialize_executable`)
or compile once and store the serialized bytes atomically.

Correctness model — a stale artifact can never serve:

- The key IS the program. Any schema change, predicate change, chunk
  bucket change, capacity change, or operator-config change alters the
  lowered module text and therefore the digest; old artifacts simply
  stop being addressable. There is no lookup that could alias two
  different programs short of a sha256 collision.
- The environment fingerprint folds jax/jaxlib versions and the device
  platform into the digest AND is re-checked against the artifact
  header at load time, so an upgraded runtime never deserializes bytes
  produced by another compiler.
- Artifact bodies carry their own sha256 in the header; torn writes,
  truncation, or bit-rot fail the check and the caller falls back to a
  normal compile (`plan_vault_corrupt_total`).
- Artifacts are tagged with the tables the program scans; DDL / ANALYZE
  call `invalidate_tables` to garbage-collect the now-unreachable
  entries eagerly instead of leaving them to rot.

Where `serialize_executable` is unsupported (backend or executable type),
`store` degrades to a no-op and the persistent XLA cache remains the
cold-start backstop.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import threading
import time
from typing import Iterable, List, Optional

from cockroach_tpu.exec import stats
from cockroach_tpu.util import tracing as _tracing
from cockroach_tpu.util.fault import crash_point
from cockroach_tpu.util.metric import default_registry
from cockroach_tpu.util.settings import Settings

PLAN_VAULT_DIR = Settings.register(
    "sql.tpu.plan_vault_dir",
    "",
    "directory for serialized compiled query executables (empty = "
    "disabled); a restarted node loads warm programs instead of paying "
    "trace+compile on the first execution",
)

PLAN_VAULT_MAX_BYTES = Settings.register(
    "sql.plan_vault.max_bytes",
    256 << 20,
    "size quota for plan-vault artifacts; when the directory exceeds it, "
    "least-recently-USED artifacts are evicted (loads refresh recency). "
    "0 disables the quota",
)

_SUFFIX = ".planv"
_MAGIC = "cockroach-tpu-planv1"
# quarantined (.bad) and orphaned-tmp files older than this are GC'd by
# the hygiene sweep — kept briefly for post-mortems, never forever
_STRAY_TTL_S = 3600.0


def _env_fingerprint() -> dict:
    """Compiler/runtime identity an executable is only valid under."""
    import jax
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "?"),
        "platform": jax.devices()[0].platform,
    }


def _execution_devices(compiled) -> list:
    """The devices `compiled` runs on, in device-assignment order.
    serialize_executable does not keep them and deserialize_and_load
    defaults to EVERY device of the backend, so a one-device program
    loaded in a process that sees several fails at its first call
    ("expected N shards"). The vault records them beside `env`."""
    return list(compiled.runtime_executable().local_devices())


class PlanVault:
    """Disk vault of serialized compiled executables, content-addressed
    by lowered-module digest + environment fingerprint."""

    def __init__(self, directory: str):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._mu = threading.Lock()
        reg = default_registry()
        self._hits = reg.counter(
            "plan_vault_hits_total",
            "compiled executables loaded from the plan vault")
        self._misses = reg.counter(
            "plan_vault_misses_total",
            "vault probes that found no usable artifact")
        self._stores = reg.counter(
            "plan_vault_stores_total",
            "compiled executables serialized into the plan vault")
        self._corrupt = reg.counter(
            "plan_vault_corrupt_total",
            "vault artifacts rejected (bad digest / undecodable)")
        self._unsupported = reg.counter(
            "plan_vault_serialize_unsupported_total",
            "executables the backend refused to serialize (persistent "
            "XLA cache remains the fallback)")
        self._store_errors = reg.counter(
            "plan_vault_store_errors_total",
            "stores that failed in OUR code, not in the backend (a wrong "
            "argument, an unpicklable tree): a defect to fix; the query "
            "keeps the program it compiled")
        self._evicted = reg.counter(
            "plan_vault_evicted_total",
            "artifacts evicted by the size quota (LRU) or stray-file GC")
        self.sweep()  # startup hygiene: stale tmp/bad from a dead writer

    # ------------------------------------------------------------- keys --

    def key_for(self, lowered_text: str, extra=None) -> str:
        """Content digest for one lowered program under THIS runtime.

        `lowered.as_text()` is deterministic across processes for the
        same program (verified on this jax), so the digest doubles as a
        cross-restart identity. `extra` mixes additional placement
        identity into the digest — sharded programs pass (mesh shape,
        axis names, shard bucket): the StableHLO of two mesh sizes
        usually differs anyway, but the executable also bakes in device
        assignment the text does not fully pin, so placement is keyed
        explicitly rather than by accident."""
        env = _env_fingerprint()
        h = hashlib.sha256()
        h.update(_MAGIC.encode())
        h.update(json.dumps(env, sort_keys=True).encode())
        if extra is not None:
            h.update(repr(extra).encode())
        h.update(lowered_text.encode())
        return h.hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key + _SUFFIX)

    # ------------------------------------------------------------ probes --

    def load(self, key: str):
        """Deserialized executable for `key`, or None (miss / stale env /
        corrupt). Never raises: a vault problem must degrade to a normal
        compile, not fail the query."""
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                header_line = f.readline()
                body = f.read()
            header = json.loads(header_line.decode())
            if header.get("magic") != _MAGIC:
                raise ValueError("bad magic")
            if header.get("env") != _env_fingerprint():
                # written under another compiler: unusable here (the
                # digest already embeds env, but artifacts can be copied
                # between vault dirs — re-check, never trust the name)
                self._miss(key, reason="env_mismatch")
                return None
            if hashlib.sha256(body).hexdigest() != header.get("sha256"):
                raise ValueError("payload digest mismatch")
            import jax
            from jax.experimental import serialize_executable as _se

            by_id = {d.id: d for d in jax.devices()}
            ids = header.get("devices")
            if not ids or any(i not in by_id for i in ids):
                # compiled for devices this process does not have (or an
                # artifact from before they were recorded): unusable
                # here, not corrupt
                self._miss(key, reason="devices_absent")
                return None
            in_tree, out_tree, payload = pickle.loads(body)
            loaded = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in ids])
        except FileNotFoundError:
            self._miss(key, reason="absent")
            return None
        except Exception as e:  # noqa: BLE001 — any decode/load failure
            self._corrupt.inc()
            stats.add("compile.vault_corrupt")
            _tracing.record("compile.vault_corrupt", key=key[:12],
                            detail=str(e)[:80])
            self._quarantine(path)
            self._miss(key, reason="corrupt")
            return None
        self._hits.inc()
        stats.add("compile.vault_hit")
        _tracing.record("compile.vault_hit", key=key[:12])
        try:
            os.utime(path, None)  # refresh recency: LRU eviction order
        except OSError:
            pass
        return loaded

    def _miss(self, key: str, reason: str) -> None:
        self._misses.inc()
        stats.add("compile.vault_miss")
        _tracing.record("compile.vault_miss", key=key[:12], reason=reason)

    def _quarantine(self, path: str) -> None:
        try:
            os.replace(path, path + ".bad")
        except OSError:
            pass

    # ------------------------------------------------------------ stores --

    def store(self, key: str, compiled, tables: Iterable[str] = ()) -> bool:
        """Serialize `compiled` under `key` (atomic tmp+rename). Returns
        whether an artifact was written. Never raises — the caller holds
        a compiled program that serves with or without an artifact — but
        the two ways to fail keep separate names: the backend refusing
        this executable type (`compile.vault_unsupported`, expected on
        some backends) and a fault of this code
        (`compile.vault_store_error`, never expected)."""
        import jax
        from jax.experimental import serialize_executable as _se

        try:
            devices = _execution_devices(compiled)
            payload, in_tree, out_tree = _se.serialize(compiled)
            # verify the round trip BEFORE persisting: an executable that
            # was itself a persistent-XLA-cache hit serializes without its
            # jit-compiled symbols on the CPU PjRt ("Symbols not found" at
            # deserialize), so an unverified store would plant an artifact
            # that can never load. Refusing here keeps the invariant that
            # anything on disk serves.
            _se.deserialize_and_load(payload, in_tree, out_tree,
                                     execution_devices=devices)
            body = pickle.dumps((in_tree, out_tree, payload))
        except jax.errors.JaxRuntimeError as e:
            # the BACKEND said no (executable type it cannot serialize,
            # symbols it cannot find)
            self._unsupported.inc()
            stats.add("compile.vault_unsupported")
            _tracing.record("compile.vault_unsupported",
                            detail=str(e)[:80])
            return False
        except Exception as e:  # noqa: BLE001 — counted under its own name
            # our call was wrong (an argument serialize_executable does
            # not take, an unpicklable tree)
            import traceback

            from cockroach_tpu.util.log import Channel, get_logger

            self._store_errors.inc()
            stats.add("compile.vault_store_error")
            _tracing.record("compile.vault_store_error",
                            detail=f"{type(e).__name__}: {e}"[:120])
            get_logger().error(
                Channel.OPS, "plan vault store failed in our own call "
                "(artifact not written): {}", traceback.format_exc())
            return False
        header = {
            "magic": _MAGIC,
            "key": key,
            "env": _env_fingerprint(),
            "devices": [d.id for d in devices],
            "tables": sorted(set(str(t) for t in tables if t)),
            "sha256": hashlib.sha256(body).hexdigest(),
            "nbytes": len(body),
        }
        blob = json.dumps(header, sort_keys=True).encode() + b"\n" + body
        path = self._path(key)
        with self._mu:
            try:
                fd, tmp = tempfile.mkstemp(dir=self.directory,
                                           suffix=".tmp")
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                # the crash seam sits between tmp write and rename: a
                # death here must leave only a .tmp the next sweep GCs,
                # never a half-written addressable artifact
                crash_point("vault.store")
                os.replace(tmp, path)
            except OSError as e:
                _tracing.record("compile.vault_store_failed",
                                detail=str(e)[:80])
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return False
            self._enforce_quota()
        self._stores.inc()
        stats.add("compile.vault_store")
        _tracing.record("compile.vault_store", key=key[:12],
                        nbytes=len(body))
        return True

    # ----------------------------------------------------------- hygiene --

    def _enforce_quota(self) -> int:
        """Evict least-recently-used artifacts until the directory fits
        `sql.plan_vault.max_bytes` (mtime = recency: loads utime on hit).
        Caller holds self._mu. Returns artifacts evicted."""
        quota = int(Settings().get(PLAN_VAULT_MAX_BYTES))
        if quota <= 0:
            return 0
        ents = []
        total = 0
        for name in os.listdir(self.directory):
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.directory, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            ents.append((st.st_mtime, st.st_size, path))
            total += st.st_size
        ents.sort()  # oldest recency first
        evicted = 0
        for _mt, sz, path in ents:
            if total <= quota:
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= sz
            evicted += 1
        if evicted:
            self._evicted.inc(evicted)
            stats.add("compile.vault_evicted", n=evicted)
            _tracing.record("compile.vault_evicted", n=evicted,
                            quota=quota)
        return evicted

    def sweep(self, stray_ttl_s: float = _STRAY_TTL_S) -> int:
        """GC quarantined `.bad` artifacts and orphaned `.tmp` files
        older than `stray_ttl_s` (a crashed writer leaves both; neither
        is addressable, both otherwise leak across restarts forever).
        Returns files removed."""
        now = time.time()
        removed = 0
        for name in os.listdir(self.directory):
            if not (name.endswith(".bad") or name.endswith(".tmp")):
                continue
            path = os.path.join(self.directory, name)
            try:
                if now - os.stat(path).st_mtime > stray_ttl_s:
                    os.unlink(path)
                    removed += 1
            except OSError:
                continue
        if removed:
            self._evicted.inc(removed)
            _tracing.record("compile.vault_swept", n=removed)
        return removed

    def entries(self) -> List[dict]:
        """Artifact headers currently on disk (for /_status and tests)."""
        out = []
        for name in sorted(os.listdir(self.directory)):
            if not name.endswith(_SUFFIX):
                continue
            try:
                with open(os.path.join(self.directory, name), "rb") as f:
                    out.append(json.loads(f.readline().decode()))
            except Exception:  # noqa: BLE001 — skip undecodable
                continue
        return out

    def invalidate_tables(self, tables: Iterable[str]) -> int:
        """Delete artifacts tagged with any of `tables` (DDL / ANALYZE
        hygiene). Content-hash keying already guarantees a stale artifact
        can't serve; this reclaims the disk eagerly."""
        doomed = set(str(t) for t in tables)
        n = 0
        for name in os.listdir(self.directory):
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.directory, name)
            try:
                with open(path, "rb") as f:
                    header = json.loads(f.readline().decode())
                if doomed & set(header.get("tables", ())):
                    os.unlink(path)
                    n += 1
            except Exception:  # noqa: BLE001 — sweep must never raise
                continue
        if n:
            stats.add("compile.vault_invalidated", n=n)
        return n

    def clear(self) -> int:
        n = 0
        for name in os.listdir(self.directory):
            if name.endswith(_SUFFIX) or name.endswith(".bad"):
                try:
                    os.unlink(os.path.join(self.directory, name))
                    n += 1
                except OSError:
                    pass
        return n


_vault_mu = threading.Lock()
_vault: Optional[PlanVault] = None
_vault_dir: Optional[str] = None


def plan_vault() -> Optional[PlanVault]:
    """Process-wide vault for the configured directory, or None when the
    `sql.tpu.plan_vault_dir` setting is empty (disabled)."""
    global _vault, _vault_dir
    directory = Settings().get(PLAN_VAULT_DIR)
    if not directory:
        return None
    directory = os.path.abspath(directory)
    with _vault_mu:
        if _vault is None or _vault_dir != directory:
            try:
                _vault = PlanVault(directory)
                _vault_dir = directory
            except OSError as e:
                _tracing.record("compile.vault_unavailable",
                                detail=str(e)[:80])
                return None
        return _vault
