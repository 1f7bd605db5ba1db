"""Persistent AOT compile cache for serving programs (``jax.export``).

The cold-start problem: a restarted serving replica owns an empty jit
cache, so its first request at every (compile key, bucket) pays a full
Python trace + XLA compile — exactly the tail-latency spike a
time-critical tier cannot afford. This module closes it in two layers:

1. **Python/trace layer** — each serving program (a lane's chunk-advance,
   a flush bucket's ``solve_many``) is traced ONCE via
   ``jax.export.export(jax.jit(fn))(*specs)``, serialized, and written to
   ``<dir>/<sha1(key)>.jaxexport``. A later process (or a restarted
   replica) deserializes the blob and calls ``jax.jit(exported.call)``
   instead of re-tracing the original Python — the original function body
   never runs again. Exported programs replay the captured StableHLO
   bit-for-bit, so the cached program's outputs are bitwise identical to
   the freshly traced one (verified in tests/test_serving.py).
2. **XLA layer** — a cache with a directory calls
   ``use_persistent_cache()``, which places JAX's persistent compilation
   cache (``JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
   ``<checkout>/.jax_cache``), so the backend compile of a replayed module
   is a disk hit on restart too.

Keys are the serving layer's hetero-aware compile keys (strings built
from ``SolveRequest.group_key``-style tuples) plus the program shape; the
manifest records the jax version and backend and the whole cache is
ignored on mismatch (serialized modules are not portable across them).

Observability: the cache counts ``aot_hits`` / ``aot_misses`` and —
the honest "zero recompiles" signal — ``trace_events``: the build
function is wrapped so its body increments the counter, and a body only
executes while JAX is tracing it. A warm replica serving its first
request reports ``trace_events == 0``.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Callable, Dict, Optional

CACHE_ENV = "REPRO_COMPILE_CACHE"
_MANIFEST = "manifest.json"
XLA_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache — fixed, because the directory is part of what a
#: cache entry is found by: a path that moved between runs would never hit.
DEFAULT_XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache")


def use_persistent_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no directory. Otherwise the cache goes to ``DEFAULT_XLA_CACHE_DIR``.
    Either way every program is persisted: JAX's default thresholds skip
    compiles under one second, and most programs here compile faster than
    that. Entry points call this before their first compile."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get(XLA_CACHE_ENV)
    if env:
        return env
    path = os.path.normpath(DEFAULT_XLA_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _fingerprint() -> Dict[str, str]:
    import jax
    return {"jax": jax.__version__, "backend": jax.default_backend()}


_SERIALIZATION_REGISTERED = False


def _ensure_serialization_registry() -> None:
    """Register the engine NamedTuples with ``jax.export`` so exported
    programs whose signatures carry them can serialize. Stable names keep
    blobs readable across processes; idempotent."""
    global _SERIALIZATION_REGISTERED
    if _SERIALIZATION_REGISTERED:
        return
    from jax import export

    from repro.core.multi_swarm import ProblemRows, SwarmBatch
    from repro.core.pso import HeteroRow, SwarmState
    for cls, name in ((SwarmBatch, "repro.core.multi_swarm.SwarmBatch"),
                      (ProblemRows, "repro.core.multi_swarm.ProblemRows"),
                      (SwarmState, "repro.core.pso.SwarmState"),
                      (HeteroRow, "repro.core.pso.HeteroRow")):
        try:
            export.register_namedtuple_serialization(
                cls, serialized_name=name)
        except ValueError:
            pass    # already registered (re-import, repeated init)
    _SERIALIZATION_REGISTERED = True


class CompileCache:
    """Disk-backed store of exported (AOT-traced) serving programs.

    ``path=None`` reads ``REPRO_COMPILE_CACHE``; if that is unset too the
    cache is memory-only (still deduplicates traces within one process,
    nothing persists). A cache with a directory also places JAX's
    persistent compilation cache (``use_persistent_cache``). ``metrics`` is
    an optional
    ``repro.serving.metrics.ServingMetrics`` sink for the hit/miss/trace
    counters (kept locally as well, so the cache is usable standalone).
    """

    def __init__(self, path: Optional[str] = None, metrics=None):
        self.path = path if path is not None else os.environ.get(CACHE_ENV)
        if self.path:
            use_persistent_cache()
        self.metrics = metrics
        self._mem: Dict[str, Callable] = {}
        self.aot_hits = 0
        self.aot_misses = 0
        self.trace_events = 0
        self._manifest: Optional[dict] = None

    # -- bookkeeping -------------------------------------------------------
    def _count(self, name: str, k: int = 1) -> None:
        setattr(self, name, getattr(self, name) + k)
        if self.metrics is not None:
            self.metrics.inc(name, k)

    def _counted(self, fn: Callable) -> Callable:
        def traced_body(*args):
            # Runs only while JAX traces it — the recompile detector.
            self._count("trace_events")
            return fn(*args)
        return traced_body

    @staticmethod
    def _file_key(key: str) -> str:
        return hashlib.sha1(key.encode()).hexdigest()

    # -- manifest ----------------------------------------------------------
    def _load_manifest(self) -> dict:
        if self._manifest is not None:
            return self._manifest
        fp = _fingerprint()
        doc = {"fingerprint": fp, "entries": {}}
        if self.path:
            try:
                with open(os.path.join(self.path, _MANIFEST)) as f:
                    on_disk = json.load(f)
                if on_disk.get("fingerprint") == fp:
                    doc = on_disk
            except (OSError, ValueError):
                pass
        self._manifest = doc
        return doc

    def _save_manifest(self) -> None:
        if not self.path or self._manifest is None:
            return
        try:
            os.makedirs(self.path, exist_ok=True)
            tmp = os.path.join(self.path, f".{_MANIFEST}.{os.getpid()}")
            with open(tmp, "w") as f:
                json.dump(self._manifest, f, indent=1, sort_keys=True)
            os.replace(tmp, os.path.join(self.path, _MANIFEST))
        except OSError:
            pass    # the cache is an optimization; never fail a solve

    # -- the cache ---------------------------------------------------------
    def get(self, key: str, build: Callable, *specs) -> Callable:
        """The compiled program for ``key``, building at most once ever.

        ``build`` is the pure function to trace and ``specs`` are its
        example arguments (arrays or ``jax.ShapeDtypeStruct`` pytrees).
        Resolution order: in-process memo -> disk blob (deserialize, no
        re-trace) -> fresh ``jax.export`` (trace once, persist).
        """
        import jax
        from jax import export

        _ensure_serialization_registry()
        hit = self._mem.get(key)
        if hit is not None:
            self._count("aot_hits")
            return hit
        blob = self._load_blob(key)
        if blob is not None:
            try:
                call = jax.jit(export.deserialize(blob).call)
                self._mem[key] = call
                self._count("aot_hits")
                return call
            except Exception:
                pass    # corrupt/stale blob: fall through and rebuild
        self._count("aot_misses")
        exported = export.export(jax.jit(self._counted(build)))(*specs)
        self._store_blob(key, exported.serialize())
        call = jax.jit(exported.call)
        self._mem[key] = call
        return call

    def _load_blob(self, key: str) -> Optional[bytes]:
        if not self.path:
            return None
        man = self._load_manifest()
        entry = man["entries"].get(self._file_key(key))
        if entry is None:
            return None
        try:
            with open(os.path.join(self.path, entry["file"]), "rb") as f:
                return f.read()
        except OSError:
            return None

    def _store_blob(self, key: str, blob: bytes) -> None:
        if not self.path:
            return
        h = self._file_key(key)
        fname = f"{h}.jaxexport"
        try:
            os.makedirs(self.path, exist_ok=True)
            tmp = os.path.join(self.path, f".{fname}.{os.getpid()}")
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, os.path.join(self.path, fname))
        except OSError:
            return
        man = self._load_manifest()
        man["entries"][h] = {"key": key, "file": fname, "bytes": len(blob)}
        self._save_manifest()

    def prewarm(self) -> int:
        """Deserialize every on-disk blob into the in-process memo (replica
        startup). Returns how many programs are now servable without a
        trace; backend compiles of the replayed modules additionally hit
        JAX's persistent compilation cache."""
        import jax
        from jax import export

        if not self.path:
            return 0
        _ensure_serialization_registry()
        man = self._load_manifest()
        for h, entry in list(man["entries"].items()):
            key = entry["key"]
            if key in self._mem:
                continue
            try:
                with open(os.path.join(self.path, entry["file"]), "rb") as f:
                    blob = f.read()
                self._mem[key] = jax.jit(export.deserialize(blob).call)
            except Exception:
                continue
        return len(self._mem)

    def snapshot(self) -> dict:
        return {"path": self.path, "programs": len(self._mem),
                "aot_hits": self.aot_hits, "aot_misses": self.aot_misses,
                "trace_events": self.trace_events}
