"""Persistent shape-keyed autotune cache (ISSUE 7 tentpole, part 2).

Every tile-size sweep this repo has run (`tools/conv_tune.py`,
`tools/flash_tune.py`, `tools/matmul_tune.py`) used to evaporate after
the run: the numbers went into a PROFILE_*.md table and a human carried
the winners back into kernel defaults by hand.  This module makes
tuning persistent and self-applying:

- Sweep tools ``record()`` their best configuration per
  (kernel, shape, dtype, backend) into ONE JSON file under
  ``FLAGS_autotune_cache_dir``.
- Kernel lowerings ``lookup()`` the cache at compile time (trace time —
  compile-cache-miss cadence, zero per-step cost) and shape their
  Pallas grid/block specs from the hit; a miss falls back to the
  built-in defaults, so the cache is purely an accelerant.
- ``fingerprint()`` rides the executor compile-cache key: a re-tuned
  cache can never serve a stale executable.

The cache file is human-readable JSON (inspect/edit/commit it per rig);
a corrupt or missing file degrades to defaults without error — tuning
state must never be able to sink a training run.
"""
from __future__ import annotations

import json
import os
import threading
import time

__all__ = ["lookup", "record", "fingerprint", "cache_path", "entries",
           "make_key", "default_backend", "invalidate"]

CACHE_FILE = "autotune_cache.json"

_lock = threading.RLock()
# (path, mtime_ns) -> parsed entries; in-process writes bump _version so
# the executor compile-cache key changes even before the file mtime is
# re-read
_loaded = {"path": None, "mtime": None, "entries": {}}
_version = 0


def _dir():
    from paddle_tpu.core.flags import FLAGS

    return getattr(FLAGS, "autotune_cache_dir", "") or ""


def cache_path():
    """Path of the cache file, or None when the flag is unset."""
    d = _dir()
    return os.path.join(d, CACHE_FILE) if d else None


def default_backend():
    """Platform the computation will run on ('tpu'/'cpu'/...): the
    kernels' own platform pick."""
    from paddle_tpu.core.place import target_platform

    return target_platform()


def make_key(kernel, shape, dtype, backend):
    """'kernel|128x64x256|float32|tpu' — the one canonical key form."""
    if isinstance(shape, (list, tuple)):
        shape = "x".join(str(int(s)) for s in shape)
    return "|".join((str(kernel), str(shape), str(dtype), str(backend)))


def _load():
    """Parsed entries of the current cache file, mtime-memoized.
    Missing or corrupt file -> {} (and the bad state is remembered so a
    broken file is not re-parsed on every lookup)."""
    path = cache_path()
    if path is None:
        return {}
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        mtime = -1
    with _lock:
        if _loaded["path"] == path and _loaded["mtime"] == mtime:
            return _loaded["entries"]
        entries_ = {}
        if mtime != -1:
            try:
                with open(path) as f:
                    data = json.load(f)
                if isinstance(data, dict):
                    raw = data.get("entries", data)
                    if isinstance(raw, dict):
                        entries_ = {k: v for k, v in raw.items()
                                    if isinstance(v, dict)}
            except Exception:
                entries_ = {}   # corrupt -> defaults, never an error
        _loaded.update(path=path, mtime=mtime, entries=entries_)
        return entries_


def invalidate():
    """Forget the memoized file state (tests flip FLAGS mid-process)."""
    with _lock:
        _loaded.update(path=None, mtime=None, entries={})


def entries():
    """All cached entries ({key: entry dict}); {} when disabled."""
    return dict(_load())


def lookup(kernel, shape, dtype, backend=None):
    """The tuned config dict for (kernel, shape, dtype, backend), or
    None.  Called at trace time by kernel lowerings; a miss means
    'use the built-in defaults'."""
    if not _dir():
        return None
    if backend is None:
        backend = default_backend()
    e = _load().get(make_key(kernel, shape, dtype, backend))
    if not e:
        return None
    cfg = e.get("config")
    return dict(cfg) if isinstance(cfg, dict) else None


def record(kernel, shape, dtype, config, ms=None, backend=None,
           source=None):
    """Persist a sweep winner.  Read-modify-write under the module lock
    with a crash-safe atomic replace; no-op (returns False) when
    FLAGS_autotune_cache_dir is unset."""
    global _version

    path = cache_path()
    if path is None:
        return False
    if backend is None:
        backend = default_backend()
    key = make_key(kernel, shape, dtype, backend)
    entry = {"config": dict(config)}
    if ms is not None:
        entry["ms"] = round(float(ms), 4)
    if source:
        entry["source"] = str(source)
    entry["recorded_unix"] = int(time.time())
    with _lock:
        from paddle_tpu.core.fsutil import atomic_write

        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        cur = dict(_load())
        cur[key] = entry
        atomic_write(path, json.dumps(
            {"version": 1, "entries": cur}, indent=1, sort_keys=True))
        _version += 1
        invalidate()
    return True


def fingerprint():
    """Token for the executor compile-cache key: changes whenever the
    cache directory, the file on disk, or an in-process record() does —
    so lowerings that consulted the cache are recompiled, never reused
    stale.  Cheap: one stat when enabled, a constant when not."""
    d = _dir()
    if not d:
        return ("", 0, 0)
    path = os.path.join(d, CACHE_FILE)
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        mtime = -1
    return (d, mtime, _version)
