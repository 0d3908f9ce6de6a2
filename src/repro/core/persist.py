"""Disk-backed persistent compiled-plan cache.

The serving tier's in-memory plan cache (service.py) dies with the
process: a restarted ``QueryService`` re-traces and re-XLA-compiles
every template from scratch, and compilation dominates small-query
latency by orders of magnitude (BENCH_serving.json's cold vs warm
columns). This module makes compiled executables survive restarts,
modeled on JAX's own compilation cache: fingerprint-keyed on-disk
artifacts, loaded instead of compiled when — and only when — the
environment that produced them still holds.

Layout: one file per entry under the cache directory, named by the
SHA-256 of the *entry key* — the parameter-erased plan signature
(prepared.py) combined with everything else the in-memory cache keys
on: the resolved ``ExecConfig`` capacity/kernel-policy signature,
executor mode, partition count and batch width. The **environment
fingerprint** (jax/jaxlib versions, backend, device kind/count, the
kernel-policy env overrides, partitioning, and a digest of the
database's device tables and dictionaries) is deliberately NOT part
of the file name: a stale entry must be *found* and *invalidated* —
visible in the ``persist_invalidations`` counter — not silently
missed, so a mismatched environment is provably never served.

File format (all-or-nothing, torn writes detected):

    MAGIC(8) | sha256(body)(32) | body = pickle({fingerprint, key,
                                                 schema, exchange_bytes,
                                                 payload, in_tree,
                                                 out_tree})

``payload`` is the XLA executable bytes from
``jax.experimental.serialize_executable.serialize``; ``in_tree`` /
``out_tree`` are its pickled PyTreeDefs. ``schema`` is the
``CompiledPlan`` column schema captured at trace time — strings can't
flow through the compiled fn, so the schema must persist beside the
executable; ``exchange_bytes`` (the logical bytes the plan's
collectives move per run) is fixed at trace time the same way. Every failure mode — missing file, torn write, checksum
mismatch, unpicklable body, foreign format version, fingerprint
mismatch, undeserializable executable — degrades to a normal
trace+compile; corruption deletes the entry so the next lookup is a
clean miss.

Writes are atomic (temp file + ``os.replace``) so a crashed store
never leaves a half-entry behind, and a ``max_bytes`` bound prunes
oldest-first by modification time.

No jax at import time beyond the lazy helpers (``pack_compiled`` /
``load_executable`` import inside), matching the obs-layer
convention.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
from typing import Optional

#: bump when the entry layout changes — old files then read as
#: fingerprint mismatches (invalidated, recompiled, overwritten)
FORMAT_VERSION = 2

_MAGIC = b"RPLANC01"
_SUFFIX = ".plan"


# ---------------------------------------------------------------------------
# Fingerprinting: what must match for a cached executable to be safe
# ---------------------------------------------------------------------------


def env_fingerprint() -> dict:
    """Process-environment half of the fingerprint: everything that
    changes generated code without appearing in the plan signature or
    the ExecConfig — compiler versions, backend, device model, and the
    kernel-policy environment overrides (``resolve_kernel_policy``
    reads them at compile time, so two processes differing only in
    ``REPRO_FORCE_JNP`` compile different executables for equal
    keys)."""
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "format": FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "?"),
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "?",
        "device_count": len(devices),
        "force_jnp": os.environ.get("REPRO_FORCE_JNP", ""),
        "kernel_interpret": os.environ.get("REPRO_KERNEL_INTERPRET",
                                           ""),
    }


def db_digest(db, tables: dict) -> str:
    """Digest of everything the database bakes into a trace: device
    table shapes/dtypes (static shapes ARE the compiled program) plus
    the full name- and string-dictionary contents — sids and name ids
    are baked into compiled constants (predicate comparisons, path
    steps, segment spaces), so two databases that disagree on any
    dictionary entry must never share executables. Float table
    *content* flows in as runtime arguments and is deliberately
    excluded: reloading the same-shaped data is the restart case this
    cache exists for."""
    import jax

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tables)[0]
    for path, leaf in leaves:
        h.update(repr((str(path), tuple(leaf.shape),
                       str(leaf.dtype))).encode())
    for dic in (db.names, db.strings):
        h.update(b"\x00dict")
        for s in dic._strings:
            h.update(s.encode("utf-8", "surrogatepass"))
            h.update(b"\x00")
    return h.hexdigest()


def service_fingerprint(db, tables: dict, mode: str,
                        num_partitions: int) -> dict:
    """The full fingerprint a QueryService stamps on / checks against
    every entry."""
    fp = env_fingerprint()
    fp["mode"] = mode
    fp["partitions"] = num_partitions
    fp["db"] = db_digest(db, tables)
    return fp


def entry_key(sig: str, cfg, mode: str, num_partitions: int,
              batch: Optional[int]) -> str:
    """Stable content address of one compiled variant — the on-disk
    mirror of the in-memory cache key (minus the profile flag: profile
    variants are never persisted). ``cfg`` must be the *resolved*
    config (kernel tri-states pinned), so a policy flip produces a
    different address instead of a false hit."""
    raw = repr((sig, cfg.cap_key(), mode, num_partitions, batch))
    return hashlib.sha256(raw.encode()).hexdigest()


# ---------------------------------------------------------------------------
# Executable (de)serialization
# ---------------------------------------------------------------------------


def pack_compiled(cp) -> Optional[dict]:
    """CompiledPlan -> persistable entry body, or None when this
    executable cannot be serialized (not AOT-compiled, or the backend
    lacks executable serialization) — the caller then simply skips
    persistence; serving is unaffected."""
    import jax
    from jax.experimental import serialize_executable as jse

    if not isinstance(cp.fn, jax.stages.Compiled):
        return None
    try:
        payload, in_tree, out_tree = jse.serialize(cp.fn)
        return {
            "schema": dict(cp.schema),
            "exchange_bytes": cp.exchange_bytes,
            "payload": payload,
            "in_tree": pickle.dumps(in_tree),
            "out_tree": pickle.dumps(out_tree),
        }
    except Exception:
        # e.g. "Compilation does not support serialization" on
        # backends without unloaded-executable support
        return None


def load_executable(entry: dict):
    """Entry body -> a callable ``jax.stages.Compiled`` with the same
    calling convention as the original jitted fn. Raises on any
    malformed entry — callers treat that as an invalidation."""
    from jax.experimental import serialize_executable as jse

    return jse.deserialize_and_load(entry["payload"],
                                    pickle.loads(entry["in_tree"]),
                                    pickle.loads(entry["out_tree"]))


# ---------------------------------------------------------------------------
# The on-disk cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DiskCacheInfo:
    """Host-side observability snapshot of the cache directory."""
    entries: int
    bytes: int
    path: str


class PlanDiskCache:
    """Checksummed, fingerprint-checked, size-bounded directory of
    serialized plan executables. Thread-compatible in the repo's
    single-writer serving model; crash-safe via atomic renames."""

    def __init__(self, path: str,
                 max_bytes: Optional[int] = None) -> None:
        self.path = path
        self.max_bytes = max_bytes
        os.makedirs(path, exist_ok=True)

    def _file(self, key: str) -> str:
        return os.path.join(self.path, key + _SUFFIX)

    # -- read ------------------------------------------------------------

    def lookup(self, key: str,
               fingerprint: dict) -> tuple[str, Optional[dict]]:
        """-> ("hit", entry) | ("miss", None) | ("invalid", None).

        "invalid" covers every unsafe-to-serve state — torn write,
        checksum mismatch, foreign format, fingerprint mismatch — and
        DELETES the entry, so the persistent tier degrades to a normal
        compile (which re-stores a fresh entry) rather than crashing
        or serving a wrong executable."""
        f = self._file(key)
        try:
            with open(f, "rb") as fh:
                blob = fh.read()
        except OSError:
            return "miss", None
        body = self._validate(blob, key, fingerprint)
        if body is None:
            self.invalidate(key)
            return "invalid", None
        return "hit", body

    @staticmethod
    def _validate(blob: bytes, key: str,
                  fingerprint: dict) -> Optional[dict]:
        if len(blob) < len(_MAGIC) + 32 or not blob.startswith(_MAGIC):
            return None
        digest = blob[len(_MAGIC):len(_MAGIC) + 32]
        body_bytes = blob[len(_MAGIC) + 32:]
        if hashlib.sha256(body_bytes).digest() != digest:
            return None
        try:
            body = pickle.loads(body_bytes)
        except Exception:
            return None
        if not isinstance(body, dict) or body.get("key") != key:
            return None
        if body.get("fingerprint") != fingerprint:
            return None
        return body

    # -- write -----------------------------------------------------------

    def store(self, key: str, fingerprint: dict,
              entry: dict) -> Optional[int]:
        """Atomically persist one entry; returns the number of older
        entries pruned to honor ``max_bytes`` (None when the store
        itself failed — a read-only or full disk must not take serving
        down with it)."""
        body = dict(entry)
        body["key"] = key
        body["fingerprint"] = fingerprint
        body_bytes = pickle.dumps(body, protocol=pickle.HIGHEST_PROTOCOL)
        blob = _MAGIC + hashlib.sha256(body_bytes).digest() + body_bytes
        tmp = self._file(key) + f".tmp-{os.getpid()}"
        try:
            with open(tmp, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, self._file(key))
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass
            return None
        return self._prune()

    def invalidate(self, key: str) -> None:
        try:
            os.remove(self._file(key))
        except OSError:
            pass

    def _prune(self) -> int:
        """Drop oldest entries (by mtime — LRU-ish without touching
        reads) until the directory fits ``max_bytes``."""
        if self.max_bytes is None:
            return 0
        ents = []
        for name in os.listdir(self.path):
            if not name.endswith(_SUFFIX):
                continue
            f = os.path.join(self.path, name)
            try:
                st = os.stat(f)
            except OSError:
                continue
            ents.append((st.st_mtime, st.st_size, f))
        total = sum(sz for _, sz, _ in ents)
        pruned = 0
        for _, sz, f in sorted(ents):
            if total <= self.max_bytes:
                break
            try:
                os.remove(f)
            except OSError:
                continue
            total -= sz
            pruned += 1
        return pruned

    # -- observability ---------------------------------------------------

    def info(self) -> DiskCacheInfo:
        n = size = 0
        for name in os.listdir(self.path):
            if name.endswith(_SUFFIX):
                f = os.path.join(self.path, name)
                try:
                    size += os.stat(f).st_size
                except OSError:
                    continue
                n += 1
        return DiskCacheInfo(entries=n, bytes=size, path=self.path)


__all__: list[str] = [
    "FORMAT_VERSION", "PlanDiskCache", "DiskCacheInfo",
    "env_fingerprint", "db_digest", "service_fingerprint",
    "entry_key", "pack_compiled", "load_executable",
]
