"""Content-addressed encoded-tensor cache (ISSUE 2 satellite).

`analyze` / `corpus` replays re-encode the same stored histories on every
invocation; for a big store the host encode dominates the warm path the
compile cache just made cheap. This cache persists the encoder's OUTPUT —
the padded int32 event tensor — keyed by a sha256 over the encoder's
INPUT (the translated op sequence's (type, f, value, process) fields,
the model name, and the requested slot width), so an unchanged history
loads its tensor instead of re-pairing/re-encoding.

The cache is OFF unless activated (the CLI activates it for `analyze` /
`corpus`, with `--no-encode-cache` as the escape hatch); library callers
pay one module-global read. Entries are plain npz files
(EncodedHistory.to_arrays) written atomically, safe under concurrent
replays. A hash is a pure function of the encoder's observable input, so
a cache hit is bit-identical to a fresh encode; corrupt/unreadable
entries fall through to a re-encode, never an error.
"""

from __future__ import annotations

import hashlib
import os
import tempfile
from contextlib import contextmanager
from pathlib import Path
from typing import Iterator, Optional, Sequence

import numpy as np

from ..obs import get_metrics
from ..ops.encode import ENCODING_VERSION, EncodedHistory
from ..ops.limits import limits
from ..ops.op import keyed_value

CACHE_DIRNAME = ".encode-cache"

_active_root: Optional[Path] = None
_refresh: bool = False

# Stores between size-capped GC sweeps (gc() stats the whole cache dir,
# so store() amortizes it instead of paying the scan per entry).
_GC_EVERY = 32
_stores_since_gc = 0


def activate(root: str | os.PathLike | None,
             refresh: bool = False) -> tuple[Optional[Path], bool]:
    """Point the cache at `root` (created lazily); None deactivates.
    `refresh=True` bypasses lookups but still writes entries — the
    `--reencode` contract: re-encode everything from source AND replace
    whatever the cache held. Returns the previous (root, refresh) so
    callers can restore them."""
    global _active_root, _refresh
    prev = (_active_root, _refresh)
    _active_root = Path(root) if root is not None else None
    _refresh = bool(refresh)
    return prev


def active_root() -> Optional[Path]:
    return _active_root


@contextmanager
def activated(root: str | os.PathLike | None,
              refresh: bool = False) -> Iterator[None]:
    prev_root, prev_refresh = activate(root, refresh)
    try:
        yield
    finally:
        activate(prev_root, prev_refresh)


def history_fingerprint(history: Sequence, model_name: str,
                        k_slots: int, keyed: bool = False) -> str:
    """sha256 over exactly the fields the encoder consumes (encode.py
    pair_history: type, f, value, process — time/index never reach the
    tensors), plus the codec (model), requested slot width, and the
    encoder version (an encoder fix invalidates every entry). `keyed`
    reads each value as the encoder does (op.keyed_value), so one key's
    records hash as their unwrapped copies (split_by_key) do."""
    h = hashlib.sha256()
    h.update(f"v{ENCODING_VERSION}|{model_name}|{k_slots}".encode())
    for op in history:
        v = keyed_value(op) if keyed else op.value
        h.update(f"\n{op.type}|{op.f}|{v!r}|{op.process!r}".encode())
    return h.hexdigest()


def _entry_path(fingerprint: str) -> Optional[Path]:
    if _active_root is None:
        return None
    return _active_root / f"{fingerprint}.npz"


def lookup(history: Sequence, model_name: str, k_slots: int,
           keyed: bool = False) -> Optional[EncodedHistory]:
    """Cached EncodedHistory for this (history, model, k_slots), or None
    (cache inactive, refresh mode, miss, or unreadable entry). An
    inactive cache hashes nothing."""
    if _refresh or _active_root is None:
        return None
    path = _entry_path(
        history_fingerprint(history, model_name, k_slots, keyed))
    m = get_metrics()
    try:
        with np.load(path) as z:
            enc = EncodedHistory.from_arrays(z)
    except Exception:   # missing or torn entry: re-encode, never fail
        m.counter("encode.cache_misses").add(1)
        return None
    m.counter("encode.cache_hits").add(1)
    try:
        # Touch for the size-capped GC's LRU (mtime) ordering: a hit
        # is a use, so hot entries survive collection.
        os.utime(path)
    except OSError:
        pass
    return enc


def store(history: Sequence, model_name: str, k_slots: int,
          enc: EncodedHistory, keyed: bool = False) -> None:
    """Persist an encoding under its input fingerprint (atomic replace:
    concurrent replays of the same store race benignly)."""
    if _active_root is None:
        return
    path = _entry_path(
        history_fingerprint(history, model_name, k_slots, keyed))
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".npz")
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez_compressed(f, **enc.to_arrays())
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError:
        pass   # the cache is an optimization, never a failure mode
    global _stores_since_gc
    _stores_since_gc += 1
    if _stores_since_gc >= _GC_EVERY:
        _stores_since_gc = 0
        gc()


def gc(cap_mb: Optional[int] = None) -> int:
    """Size-capped LRU collection (ISSUE 20 satellite): while the
    cache's on-disk bytes exceed ``encode_cache_cap_mb`` (0 = the
    seed's unbounded growth), evict least-recently-USED entries —
    mtime order; lookup() touches its hit, so hot entries survive.
    Concurrent-pod safe: writers land entries via O_EXCL mkstemp +
    atomic replace, so the sweep never sees a half-written named
    entry, and a concurrently vanished file (another pod's GC, or a
    replace) is skipped, never an error. Returns the eviction count
    (`encode.cache_evictions` on the registry)."""
    root = _active_root
    if root is None:
        return 0
    if cap_mb is None:
        cap_mb = limits().encode_cache_cap_mb
    if cap_mb <= 0:
        return 0
    cap = int(float(cap_mb) * (1 << 20))
    entries = []
    total = 0
    try:
        it = list(root.iterdir())
    except OSError:
        return 0
    for p in it:
        if not p.name.endswith(".npz"):
            continue
        try:
            st = p.stat()
        except OSError:
            continue   # vanished under a concurrent pod's sweep
        entries.append((st.st_mtime, st.st_size, p))
        total += st.st_size
    if total <= cap:
        return 0
    evicted = 0
    for _, size, p in sorted(entries):
        if total <= cap:
            break
        try:
            p.unlink()
        except OSError:
            continue   # already gone: the other pod won the race
        total -= size
        evicted += 1
    if evicted:
        get_metrics().counter("encode.cache_evictions").add(evicted)
    return evicted
