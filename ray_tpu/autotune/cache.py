"""Persistent kernel-autotune cache: JSON-lines, keyed per shape, shared.

One record per line, schema-versioned:

    {"v": 1, "op": "flash_attention", "backend": "tpu:tpuv5litepod",
     "key": "B=2|S=4096|N=12|H=64|dtype=bfloat16|causal=1",
     "config": {"block_q": 1024, "block_k": 1024}, "ms": 56.9,
     "meta": {...}, "ts": 1754380000.0}

Records are keyed by ``(op, backend fingerprint, canonical shape key)``;
for the same full key, the LAST line wins, so a re-tune is a plain append.
Durability rules (same discipline as the spill files / BENCH_LASTGOOD):

* **append** is a single ``write()`` to an ``O_APPEND`` fd — concurrent
  processes interleave whole lines, never bytes;
* **rewrite** (compaction) goes through tmp + fsync + ``os.replace`` so a
  kill mid-compact can never destroy the only copy;
* **load** skips lines that fail to parse (the torn tail of a crashed
  append) and records with a foreign schema version — a corrupt cache
  degrades to a cold cache, it never raises into the kernel call path.

The file lives at ``$RT_AUTOTUNE_CACHE`` (default ``.autotune.jsonl`` at
the root of the checkout, git-ignored: what a fresh tree does follows from
the tree) and is shared across processes:
``lookup`` re-stats the file (throttled) and reloads when another process
appended, so a sweep in one process is visible to trainers in another
without restarts.

This module imports neither jax nor the cluster runtime at module level —
the raylet reads counters from it and must stay light.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ray_tpu.autotune import metrics as _am

SCHEMA_VERSION = 1
DEFAULT_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".autotune.jsonl")

# How often lookup() is willing to re-stat the backing file for changes
# made by OTHER processes.  The stat is cheap but the kernel call path is
# hot, so it is throttled rather than per-call.
RELOAD_THROTTLE_S = 0.5


def cache_path() -> str:
    return os.path.expanduser(
        os.environ.get("RT_AUTOTUNE_CACHE") or DEFAULT_PATH)


def canon_dtype(dtype: Any) -> str:
    """Canonical dtype string ("bfloat16", "float32", ...) for key
    normalization — accepts strings, numpy/jax dtypes, and jnp scalar
    types, without importing jax."""
    try:
        import numpy as np
        return str(np.dtype(dtype))
    except Exception:
        return str(dtype)


def norm_batch(B: int) -> int:
    """Batch is bucketed to the next power of two: timings are much more
    sensitive to (S, N, H, dtype) than to small batch deltas, and the
    bucket keeps one sweep reusable across nearby batches."""
    B = max(1, int(B))
    return 1 << (B - 1).bit_length()


def attention_key(B: int, S: int, N: int, H: int, dtype: Any,
                  causal: bool = True) -> str:
    """Canonical shape key shared by every attention-family op (flash,
    splash, ring, dense, and the variant-crossover records)."""
    return (f"B={norm_batch(B)}|S={int(S)}|N={int(N)}|H={int(H)}"
            f"|dtype={canon_dtype(dtype)}|causal={int(bool(causal))}")


def backend_fingerprint() -> str:
    """Identity of the measuring backend.  CPU is always interpret mode
    (one fingerprint regardless of host), real backends carry the device
    kind and count — a cache tuned on v5e must not drive a v4 pod.
    Imports jax lazily; falls back to a degenerate fingerprint when no
    backend is importable (cache tests without jax)."""
    try:
        import jax
        b = jax.default_backend()
        if b == "cpu":
            return "cpu:interpret"
        devs = jax.devices()
        kind = str(getattr(devs[0], "device_kind", "") or b)
        return f"{b}:{kind.lower().replace(' ', '')}x{len(devs)}"
    except Exception:
        return "unknown"


class AutotuneCache:
    """In-memory view over one JSON-lines cache file (see module doc)."""

    def __init__(self, path: Optional[str] = None):
        self.path = os.path.expanduser(path) if path else cache_path()
        self._lock = threading.RLock()
        self._records: Dict[Tuple[str, str, str], dict] = {}
        self._stat: Optional[Tuple[int, int]] = None
        self._last_stat_t = 0.0
        self.corrupt_lines = 0
        self._load()

    # ------------------------------------------------------------- load

    def _file_stat(self):
        try:
            st = os.stat(self.path)
            return (st.st_size, st.st_mtime_ns)
        except OSError:
            return None

    def _load(self) -> None:
        with self._lock:
            self._records.clear()
            self.corrupt_lines = 0
            self._stat = self._file_stat()
            self._last_stat_t = time.monotonic()
            if self._stat is None:
                return
            try:
                with open(self.path, "r", encoding="utf-8") as f:
                    data = f.read()
            except OSError:
                return
            for line in data.splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                    if not isinstance(rec, dict):
                        raise ValueError("not a record")
                except Exception:
                    # Torn tail of a crashed append, or garbage: a corrupt
                    # line costs itself, not the cache.
                    self.corrupt_lines += 1
                    continue
                if rec.get("v") != SCHEMA_VERSION:
                    continue
                try:
                    k = (str(rec["op"]), str(rec["backend"]),
                         str(rec["key"]))
                except KeyError:
                    self.corrupt_lines += 1
                    continue
                self._records[k] = rec        # last line wins

    def maybe_reload(self) -> None:
        """Pick up appends from other processes (throttled stat)."""
        with self._lock:
            now = time.monotonic()
            if now - self._last_stat_t < RELOAD_THROTTLE_S:
                return
            self._last_stat_t = now
            if self._file_stat() != self._stat:
                self._load()

    # ------------------------------------------------------------ query

    def lookup(self, op: str, key: str, backend: Optional[str] = None,
               count: bool = True) -> Optional[dict]:
        """Best record for (op, backend, key) or None.  ``count=False``
        suppresses the hit/miss counters for repeat consultations the
        caller already memoized once."""
        backend = backend or backend_fingerprint()
        self.maybe_reload()
        with self._lock:
            rec = self._records.get((op, backend, key))
        if count:
            _am.bump("autotune_cache_hits" if rec is not None
                     else "autotune_cache_misses")
        return rec

    def records(self):
        with self._lock:
            return list(self._records.values())

    def __len__(self):
        with self._lock:
            return len(self._records)

    # ------------------------------------------------------------ write

    def put(self, op: str, key: str, config: dict, ms: float,
            meta: Optional[dict] = None,
            backend: Optional[str] = None) -> dict:
        """Append one record (atomic whole-line append) and adopt it
        in-memory."""
        backend = backend or backend_fingerprint()
        rec = {"v": SCHEMA_VERSION, "op": op, "backend": backend,
               "key": key, "config": config,
               "ms": round(float(ms), 4) if ms is not None else None,
               "ts": round(time.time(), 3)}
        if meta:
            rec["meta"] = meta
        line = json.dumps(rec, sort_keys=True) + "\n"
        with self._lock:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            # O_APPEND + one write(): concurrent appenders interleave
            # whole lines.  (A torn line from a crash mid-write is
            # tolerated by _load.)
            fd = os.open(self.path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                         0o644)
            try:
                os.write(fd, line.encode("utf-8"))
            finally:
                os.close(fd)
            self._records[(op, backend, key)] = rec
            self._stat = self._file_stat()
        return rec

    def rewrite(self) -> int:
        """Compact the file to one line per key (drops superseded
        records, corrupt lines, and foreign schema versions).  tmp +
        fsync + rename: a kill mid-compact leaves the old file intact.
        Returns the number of records written."""
        with self._lock:
            self._load()                      # fold in foreign appends
            tmp = self.path + ".tmp"
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            with open(tmp, "w", encoding="utf-8") as f:
                for rec in self._records.values():
                    f.write(json.dumps(rec, sort_keys=True) + "\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
            self.corrupt_lines = 0
            self._stat = self._file_stat()
            return len(self._records)


_CACHES: Dict[str, AutotuneCache] = {}
_caches_lock = threading.Lock()


def get_cache(path: Optional[str] = None) -> AutotuneCache:
    """Process-wide cache singleton per resolved path (the env var may
    legitimately change between tests)."""
    p = os.path.expanduser(path) if path else cache_path()
    with _caches_lock:
        c = _CACHES.get(p)
        if c is None:
            c = _CACHES[p] = AutotuneCache(p)
        return c
