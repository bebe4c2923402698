"""Content-addressed on-disk compilation cache.

One entry per compiled program, named ``<sha256>.pcc``. The layout is a
fixed header carrying CRC32s for both the JSON meta block and the
payload, so torn writes and bit-rot are detected on read:

    magic ``PTPCC001`` | u32 meta_len | u32 meta_crc | u64 payload_len |
    u32 payload_crc | meta (JSON) | payload

Durability + concurrency contract (reuses the round-9 machinery):

- **Atomic publish** — entries are written to a same-directory temp file
  and published with :func:`framework.io.atomic_replace` (``os.replace``
  + directory fsync, ``io.rename_fail`` fault point honored). Concurrent
  writers of the same key are last-wins; both wrote identical content by
  construction (the key is content-addressed), so either winner is
  correct.
- **Quarantine, never crash** — a corrupt or torn entry is moved into
  ``quarantine/`` (atomic rename; unlinked if even that fails) and the
  lookup reports a miss, so the caller silently recompiles. Cache damage
  can cost time, never correctness.
- **LRU size budget** — ``FLAGS_compile_cache_size_mb`` bounds the entry
  bytes. Recency rides on entry mtimes (``get`` bumps them with one
  ``utime`` — no per-hit manifest rewrite, so fleet replicas sharing a
  directory don't clobber each other); the JSON manifest records
  publish-time metadata, is written once per ``put``, publishes
  atomically, and is advisory — missing or torn, everything still works
  from a directory scan.

Instrumented through ``observability``: ``paddle_tpu_pcc_hits_total`` /
``paddle_tpu_pcc_misses_total`` (labeled by call site), the
``paddle_tpu_pcc_bytes`` gauge, ``paddle_tpu_pcc_time_saved_seconds``,
and quarantine/eviction counters, with spans for lookup and publish.
"""
from __future__ import annotations

import json
import os
import struct
import time
import zlib
from typing import Dict, List, Optional, Tuple

from ..core import flags
from ..fault import inject as _inject
from ..observability import metrics as _metrics
from ..observability import trace as _trace

__all__ = ["CompileCache", "get_cache", "enabled", "cache_dir",
           "cache_root", "enable_jax_cache", "record_time_saved"]

_MAGIC = b"PTPCC001"
_HEADER = struct.Struct("<IIQI")   # meta_len, meta_crc, payload_len, payload_crc
_MANIFEST = "manifest.json"
_QUARANTINE = "quarantine"

# the compile_cache* flags are registered in core/flags.py so set_flags
# works before this package is first imported

_m_hits = _metrics.counter(
    "paddle_tpu_pcc_hits_total",
    "Persistent compilation cache hits (a compile skipped), labeled by "
    "call site: to_static, sot, artifact.", labelnames=("site",))
_m_misses = _metrics.counter(
    "paddle_tpu_pcc_misses_total",
    "Persistent compilation cache misses (entry absent, incompatible, or "
    "quarantined), labeled by call site.", labelnames=("site",))
_m_bytes = _metrics.gauge(
    "paddle_tpu_pcc_bytes",
    "Total bytes of live persistent compilation cache entries.")
_m_time_saved = _metrics.counter(
    "paddle_tpu_pcc_time_saved_seconds",
    "Cumulative compile wall time skipped by persistent cache hits (the "
    "miss-time compile cost recorded in each entry's meta).")
_m_quarantined = _metrics.counter(
    "paddle_tpu_pcc_quarantined_total",
    "Cache entries moved to quarantine after failing CRC/structure "
    "verification.", labelnames=("reason",))
_m_evicted = _metrics.counter(
    "paddle_tpu_pcc_evicted_total",
    "Cache entries evicted by the LRU size budget.")
_m_errors = _metrics.counter(
    "paddle_tpu_pcc_errors_total",
    "Cache operations abandoned on unexpected errors (the compile path "
    "continued without the cache).", labelnames=("op",))


def enabled() -> bool:
    return bool(flags.get_flag("compile_cache"))


_JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_root() -> str:
    """The one directory every on-disk cache of this checkout lives
    under: ``$JAX_COMPILATION_CACHE_DIR`` where the machine sets it,
    else ``<checkout>/.jax_cache``. The path is FIXED (never a temp
    dir, pid or timestamp): it is part of JAX's cache key, so a
    directory that moves never hits, and a machine that sets the
    variable is the only place a later run finds this run's entries."""
    env = os.environ.get(_JAX_CACHE_ENV)
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def enable_jax_cache() -> str:
    """Turn on JAX's persistent compilation cache at :func:`cache_root`
    and return that directory. Entry points that compile on the chip
    (``chip_smoke.py``, ``benchmark/run.py``, the ``tools/`` chip scripts)
    call this BEFORE their first compile. With the variable set JAX reads it
    itself and nothing is set here."""
    _trace.mark_backend()
    root = cache_root()
    if not os.environ.get(_JAX_CACHE_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", root)
    return root


def cache_dir() -> str:
    d = flags.get_flag("compile_cache_dir")
    if d:
        return os.path.expanduser(str(d))
    env = os.environ.get("PADDLE_TPU_COMPILE_CACHE_DIR")
    if env:
        return os.path.expanduser(env)
    return os.path.join(cache_root(), "paddle_tpu", "pcc")


def record_time_saved(seconds: float) -> None:
    if seconds and seconds > 0:
        _m_time_saved.inc(float(seconds))


class CompileCache:
    """One cache directory. Cheap to construct; all state is on disk."""

    def __init__(self, directory: Optional[str] = None,
                 size_limit_mb: Optional[int] = None):
        self.directory = directory or cache_dir()
        self._size_limit_mb = size_limit_mb

    # ------------------------------------------------------------- layout
    def _path(self, key: str) -> str:
        return os.path.join(self.directory, f"{key}.pcc")

    def _manifest_path(self) -> str:
        return os.path.join(self.directory, _MANIFEST)

    def size_limit_bytes(self) -> int:
        mb = self._size_limit_mb
        if mb is None:
            mb = int(flags.get_flag("compile_cache_size_mb"))
        return max(int(mb), 1) * (1 << 20)

    # ------------------------------------------------------------ read
    def get(self, key: str, site: str = "other"
            ) -> Optional[Tuple[dict, bytes]]:
        """Return ``(meta, payload)`` or None. Verifies both CRCs; any
        damage quarantines the entry and reports a miss — a corrupt cache
        must cost a recompile, never a crash."""
        path = self._path(key)
        with _trace.span(f"pcc_lookup:{site}", "compile",
                         {"key": key[:12]}):
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except OSError:
                _m_misses.inc(site=site)
                return None
            entry = self._decode(data)
            if entry is None:
                self._quarantine(path, "corrupt")
                _m_misses.inc(site=site)
                return None
        # LRU touch: bump the entry's mtime (one utimensat) instead of
        # rewriting the manifest — a SOT-heavy startup does hundreds of
        # hits, and fleet replicas sharing a dir must not clobber each
        # other's bookkeeping per lookup
        try:
            os.utime(path)
        except OSError:
            pass
        _m_hits.inc(site=site)
        return entry

    def _decode(self, data: bytes) -> Optional[Tuple[dict, bytes]]:
        head = len(_MAGIC) + _HEADER.size
        if len(data) < head or data[:len(_MAGIC)] != _MAGIC:
            return None
        meta_len, meta_crc, payload_len, payload_crc = _HEADER.unpack(
            data[len(_MAGIC):head])
        if len(data) != head + meta_len + payload_len:
            return None
        meta_bytes = data[head:head + meta_len]
        payload = data[head + meta_len:]
        if zlib.crc32(meta_bytes) != meta_crc or \
                zlib.crc32(payload) != payload_crc:
            return None
        try:
            meta = json.loads(meta_bytes)
        except ValueError:
            return None
        if not isinstance(meta, dict):
            return None
        return meta, payload

    def _quarantine(self, path: str, reason: str) -> None:
        """Move a damaged entry aside (atomic) so it is never re-read;
        keep the bytes for post-mortems instead of deleting evidence."""
        _m_quarantined.inc(reason=reason)
        qdir = os.path.join(self.directory, _QUARANTINE)
        try:
            os.makedirs(qdir, exist_ok=True)
            dst = os.path.join(
                qdir, f"{os.path.basename(path)}.{os.getpid()}"
                f".{int(time.time() * 1e3)}")
            os.replace(path, dst)
        except OSError:
            try:
                os.unlink(path)
            except OSError:
                pass

    # ------------------------------------------------------------ write
    def put(self, key: str, payload: bytes, meta: dict) -> bool:
        """Atomically publish one entry, then enforce the LRU budget.
        Returns False (and leaves no partial file) on any failure — the
        caller already holds the compiled program, so a failed publish
        only costs the NEXT process a recompile."""
        from ..framework.io import atomic_replace

        meta = dict(meta)
        meta.setdefault("created", time.time())
        meta_bytes = json.dumps(meta, sort_keys=True).encode()
        blob = (_MAGIC
                + _HEADER.pack(len(meta_bytes), zlib.crc32(meta_bytes),
                               len(payload), zlib.crc32(payload))
                + meta_bytes + payload)
        path = self._path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        with _trace.span("pcc_publish", "compile",
                         {"key": key[:12], "bytes": len(blob)}):
            try:
                os.makedirs(self.directory, exist_ok=True)
                with open(tmp, "wb") as f:
                    trunc = _inject.peek("pcc.write_truncate_after_bytes")
                    if trunc is not None:
                        keep = int(trunc.get("after_bytes", 0))
                        f.write(blob[:keep])
                        f.flush()
                        _inject.fire("pcc.write_truncate_after_bytes")
                        raise OSError(
                            f"injected truncation after {keep} bytes")
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                atomic_replace(tmp, path)
            except (OSError, ValueError):
                _m_errors.inc(op="put")
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return False
        self._record_put(key, len(blob))
        try:
            self.enforce_budget()
        except OSError:
            _m_errors.inc(op="evict")
        return True

    # --------------------------------------------------------- manifest
    def _read_manifest(self) -> Dict[str, dict]:
        try:
            with open(self._manifest_path()) as f:
                m = json.load(f)
            return m if isinstance(m, dict) else {}
        except (OSError, ValueError):
            return {}

    def _write_manifest(self, m: Dict[str, dict]) -> None:
        """Best-effort atomic rewrite; last-wins between processes. The
        manifest only steers LRU order — losing an update degrades
        eviction fairness, nothing else."""
        from ..framework.io import atomic_replace

        path = self._manifest_path()
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(self.directory, exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(m, f)
            atomic_replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def _record_put(self, key: str, nbytes: int) -> None:
        """Manifest bookkeeping, written once per publish (LRU recency
        itself rides on entry mtimes, bumped by ``get``)."""
        try:
            m = self._read_manifest()
            m[key] = {"bytes": int(nbytes), "created": time.time()}
            self._write_manifest(m)
        except Exception:
            _m_errors.inc(op="touch")

    # ---------------------------------------------------------- listing
    def entries(self) -> List[dict]:
        """Live entries, oldest-used first: [{key, bytes, used, path}].
        Recency comes from entry mtimes (``get`` bumps them), so the
        listing needs no manifest read and tolerates a torn one."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        for name in sorted(names):
            if not name.endswith(".pcc"):
                continue
            key = name[:-len(".pcc")]
            path = os.path.join(self.directory, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            out.append({"key": key, "bytes": st.st_size,
                        "used": float(st.st_mtime), "path": path})
        out.sort(key=lambda e: e["used"])
        total = sum(e["bytes"] for e in out)
        _m_bytes.set(float(total))
        return out

    def total_bytes(self) -> int:
        return sum(e["bytes"] for e in self.entries())

    def entry_meta(self, key: str) -> Optional[dict]:
        got = self._decode_file(self._path(key))
        return got[0] if got else None

    def _decode_file(self, path: str) -> Optional[Tuple[dict, bytes]]:
        try:
            with open(path, "rb") as f:
                return self._decode(f.read())
        except OSError:
            return None

    # --------------------------------------------------------- eviction
    def enforce_budget(self, limit_bytes: Optional[int] = None) -> int:
        """Evict least-recently-used entries past the budget; returns the
        number evicted. Safe under concurrency: eviction is unlink-based
        and a racing reader that loses simply recompiles."""
        limit = self.size_limit_bytes() if limit_bytes is None \
            else int(limit_bytes)
        live = self.entries()
        total = sum(e["bytes"] for e in live)
        evicted = 0
        manifest = None
        for e in live:
            if total <= limit:
                break
            try:
                os.unlink(e["path"])
            except OSError:
                continue
            total -= e["bytes"]
            evicted += 1
            _m_evicted.inc()
            if manifest is None:
                manifest = self._read_manifest()
            manifest.pop(e["key"], None)
        if manifest is not None:
            self._write_manifest(manifest)
        _m_bytes.set(float(max(total, 0)))
        return evicted

    def clear(self) -> int:
        """Drop every entry (and the manifest); returns entries removed."""
        n = 0
        for e in self.entries():
            try:
                os.unlink(e["path"])
                n += 1
            except OSError:
                pass
        try:
            os.unlink(self._manifest_path())
        except OSError:
            pass
        _m_bytes.set(0.0)
        return n


_singleton: Optional[CompileCache] = None


def get_cache() -> CompileCache:
    """Process-wide cache bound to the flag-configured directory (a new
    object is handed out if the directory flag changed — tests repoint
    the cache at tmp dirs)."""
    global _singleton
    target = cache_dir()
    if _singleton is None or _singleton.directory != target:
        _singleton = CompileCache(target)
    return _singleton
