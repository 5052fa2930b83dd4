"""store/spill.py — the out-of-core spill tier (ISSUE 20 tentpole).

The resumable-carry discipline (wgl2/wgl3 chunked kernels, stream
watermarks, the incremental ElleGraph) bounds DEVICE memory per chunk;
this module extends the same discipline to the HOST. Three pieces:

  * :class:`SpillDir` — an atomic, digest-framed blob store next to the
    content-addressed encode cache. Every read/write is counted on the
    ``spill.*`` registry families.
  * :class:`FrontierCodec` framing (:func:`encode_frontier` /
    :func:`decode_frontier`) — spilled wgl2/wgl3 frontier checkpoints,
    compressed with the PR 10 canon quotient: a CANONICAL frontier
    row's fired bits inside each equal-effect class are packed into the
    class's lowest slots (ops/canon.py), so those bits are fully
    determined by a per-class fired COUNT. The encoder verifies the
    packed-low invariant per row per class and stores counts + a
    residual table with the class bits cleared; rows that fail the
    check (non-canonical carries, invalid lanes) keep their raw words.
    Decoding is bit-identical by construction — the residual is exact
    and the class bits are a deterministic function of the counts. A
    sha256 digest frames every blob: a torn/truncated checkpoint reads
    as ABSENT (recompute), never as data.
  * :class:`SpillWindow` — the bounded in-RAM tier: blobs write through
    to disk immediately (crash-durable) and stay resident until the
    window exceeds its byte budget (sized from ``host_rss_budget_mb``),
    then the oldest RAM copies drop (``spill.evictions``); a get() that
    misses RAM re-reads the disk tier.

Routing policy (:func:`spill_active`): ``host_spill_mode`` 0 = auto
(spill only when the caller's working-set estimate exceeds
``host_rss_budget_mb``), 1 = off (the seed's all-RAM behaviour),
2 = force (the bench/test lane). Verdicts are bit-identical in every
mode — the spill tier moves bytes, never meaning.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import resource
import sys
import tempfile
from contextlib import contextmanager
from pathlib import Path
from typing import Iterator, Optional

import numpy as np

from ..obs import get_metrics
from ..ops.limits import limits

SPILL_DIRNAME = ".spill"

_MAGIC = b"JTSPILL1"
_DIGEST_LEN = 32


def rss_mb() -> float:
    """This process's peak RSS so far, in MiB (``ru_maxrss`` is KiB on
    Linux, bytes on macOS). Callers wanting a ceiling on a LANE take
    the delta of two samples — the absolute peak includes every
    allocation since process start."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    div = 1 << 20 if sys.platform == "darwin" else 1 << 10
    return peak / div


def spill_active(estimate_mb: Optional[float] = None) -> bool:
    """Whether the out-of-core tier should engage: forced on (mode 2),
    forced off (mode 1), or — in auto — only when the caller's
    working-set estimate exceeds the host RSS budget."""
    lim = limits()
    if lim.host_spill_mode == 1:
        return False
    if lim.host_spill_mode == 2:
        return True
    return estimate_mb is not None \
        and estimate_mb > lim.host_rss_budget_mb


# -- canon-quotient frontier codec ------------------------------------------

def classes_from_pairs(pairs: Optional[np.ndarray]) -> list[list[int]]:
    """Equal-effect bit classes at one history step, from that step's
    canon compare-exchange pair row (ops/canon.py canon_pairs[t]):
    connected components (size >= 2) of the pair graph. The selection
    network canon_pairs emits connects every lo<hi pair inside a class,
    so components ARE the classes."""
    if pairs is None:
        return []
    arr = np.asarray(pairs).reshape(-1, 2)
    parent: dict[int, int] = {}

    def find(x: int) -> int:
        while parent.setdefault(x, x) != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for lo, hi in arr:
        lo, hi = int(lo), int(hi)
        if lo < 0 or hi < 0:
            continue
        ra, rb = find(lo), find(hi)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
    groups: dict[int, list[int]] = {}
    for x in parent:
        groups.setdefault(find(x), []).append(x)
    return sorted(sorted(g) for g in groups.values() if len(g) > 1)


def _class_bits(masks: np.ndarray, cls: list[int]) -> np.ndarray:
    """bool[n, len(cls)]: each row's fired bit per class member."""
    cols = [(masks[:, b // 32] >> np.uint32(b % 32)) & np.uint32(1)
            for b in cls]
    return np.stack(cols, axis=1).astype(bool)


def _clear_class_bits(masks: np.ndarray, cls: list[int],
                      rows: np.ndarray) -> None:
    for b in cls:
        masks[rows, b // 32] &= np.uint32(~(np.uint32(1) << (b % 32))
                                          & 0xFFFFFFFF)


def _set_packed_bits(masks: np.ndarray, cls: list[int],
                     rows: np.ndarray, counts: np.ndarray) -> None:
    for j, b in enumerate(cls):
        hit = rows[counts > j]
        masks[hit, b // 32] |= np.uint32(1) << np.uint32(b % 32)


def encode_frontier(states: np.ndarray, masks: np.ndarray,
                    valid: np.ndarray, *,
                    classes: Optional[list[list[int]]] = None,
                    meta: Optional[dict] = None,
                    mode: Optional[int] = None) -> bytes:
    """Serialize one frontier checkpoint (states i32[F], masks
    u32[F, W], valid bool[F] — the wgl2 carry layout) into a
    digest-framed blob. `classes` are the equal-effect bit classes at
    the checkpoint step (:func:`classes_from_pairs`); when the valid
    rows satisfy the canonical packed-low invariant, class bits are
    stored as per-class counts (the canon-quotient compression),
    otherwise the raw words are kept. `mode` defaults to the
    ``spill_compress_mode`` knob: 1 pins raw, 2 refuses the raw
    fallback (raises on a non-canonical frontier — the codec test
    lane). Round-trips bit-identically in every mode."""
    if mode is None:
        mode = limits().spill_compress_mode
    states = np.ascontiguousarray(states, dtype=np.int32)
    masks = np.ascontiguousarray(masks, dtype=np.uint32)
    valid = np.ascontiguousarray(valid, dtype=bool)
    raw_bytes = states.nbytes + masks.nbytes + valid.nbytes
    rows = np.flatnonzero(valid)
    use_canon = bool(classes) and mode != 1 and rows.size > 0
    counts: Optional[np.ndarray] = None
    residual = masks
    if use_canon:
        vm = masks[rows]
        ok = all(len(c) < 256 for c in classes)
        cols = []
        for cls in classes:
            if not ok:
                break
            bits = _class_bits(vm, cls)
            cnt = bits.sum(axis=1)
            # Packed-low invariant: the fired bits must be exactly the
            # class's lowest `cnt` members (canonical rows only).
            expect = np.arange(len(cls))[None, :] < cnt[:, None]
            if not np.array_equal(bits, expect):
                ok = False
                break
            cols.append(cnt.astype(np.uint8))
        if ok and cols:
            counts = np.stack(cols, axis=1)
            residual = masks.copy()
            for cls in classes:
                _clear_class_bits(residual, cls, rows)
        elif mode == 2:
            raise ValueError(
                "spill_compress_mode=2 (force-canonical) but the "
                "frontier is not canonically packed — run with "
                "dedup_mode canonicalization or compress_mode 0/1")
        else:
            use_canon = False
    payload = io.BytesIO()
    arrays = {"states": states, "residual": residual,
              "valid": np.packbits(valid)}
    if counts is not None:
        arrays["counts"] = counts
    np.savez_compressed(payload, **arrays)
    payload = payload.getvalue()
    header = {
        "v": 1,
        "mode": "canon" if use_canon else "raw",
        "f": int(states.shape[0]),
        "w": int(masks.shape[1]) if masks.ndim == 2 else 0,
        "classes": classes if use_canon else None,
        "meta": meta or {},
        "raw_bytes": int(raw_bytes),
    }
    hdr = json.dumps(header, separators=(",", ":")).encode()
    body = _MAGIC + len(hdr).to_bytes(4, "big") + hdr + payload
    return body + hashlib.sha256(body).digest()


def decode_frontier(blob: Optional[bytes]) -> Optional[dict]:
    """Inverse of :func:`encode_frontier`: ``{"states", "masks",
    "valid", "meta", "mode", "raw_bytes"}`` — or None for a torn,
    truncated, or digest-failing blob (the caller recomputes; a bad
    checkpoint can degrade throughput, never a verdict)."""
    if blob is None or len(blob) < len(_MAGIC) + 4 + _DIGEST_LEN:
        return None
    body, digest = blob[:-_DIGEST_LEN], blob[-_DIGEST_LEN:]
    if not body.startswith(_MAGIC) \
            or hashlib.sha256(body).digest() != digest:
        return None
    try:
        hlen = int.from_bytes(body[len(_MAGIC):len(_MAGIC) + 4], "big")
        hdr = json.loads(body[len(_MAGIC) + 4:len(_MAGIC) + 4 + hlen])
        with np.load(io.BytesIO(body[len(_MAGIC) + 4 + hlen:])) as z:
            states = z["states"]
            masks = z["residual"].copy()
            valid = np.unpackbits(
                z["valid"], count=int(hdr["f"])).astype(bool)
            counts = z["counts"] if "counts" in z.files else None
    except Exception:
        return None
    if hdr["mode"] == "canon" and counts is not None:
        rows = np.flatnonzero(valid)
        for j, cls in enumerate(hdr["classes"]):
            _set_packed_bits(masks, [int(b) for b in cls], rows,
                             counts[:, j].astype(np.int64))
    return {"states": states, "masks": masks, "valid": valid,
            "meta": hdr.get("meta") or {}, "mode": hdr["mode"],
            "raw_bytes": int(hdr.get("raw_bytes") or 0)}


def spill_frontier(sdir: "SpillDir", name: str, states, masks, valid, *,
                   classes: Optional[list[list[int]]] = None,
                   meta: Optional[dict] = None) -> Optional[Path]:
    """Encode + write one frontier checkpoint, updating the
    ``spill.compress_ratio`` gauge (raw packed bytes over stored
    bytes — >1 means the canon-quotient codec beat raw)."""
    blob = encode_frontier(np.asarray(states), np.asarray(masks),
                           np.asarray(valid), classes=classes, meta=meta)
    raw = (np.asarray(states).nbytes + np.asarray(masks).nbytes
           + np.asarray(valid).nbytes)
    if len(blob) > 0:
        get_metrics().gauge("spill.compress_ratio").set(
            round(raw / len(blob), 4))
    return sdir.write(name, blob)


def load_frontier(sdir: "SpillDir", name: str) -> Optional[dict]:
    """Read + decode one frontier checkpoint; None when absent, torn,
    or digest-failing (the caller recomputes)."""
    return decode_frontier(sdir.read(name))


# -- the disk tier ----------------------------------------------------------

class SpillDir:
    """Digest-framed blob store for the out-of-core tier. Writes are
    atomic (mkstemp + os.replace — a crash mid-spill leaves either the
    previous entry or a tmp file, never a torn named entry; the codec
    digest catches everything else). Every transfer is counted on the
    spill.* families."""

    def __init__(self, root: str | os.PathLike):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)

    def path(self, name: str) -> Path:
        return self.root / name

    def write(self, name: str, blob: bytes) -> Optional[Path]:
        path = self.path(name)
        try:
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except OSError:
            return None   # spill is an optimization tier, not a fault
        m = get_metrics()
        m.counter("spill.writes").add(1)
        m.counter("spill.bytes_written").add(len(blob))
        return path

    def append(self, name: str, blob: bytes) -> bool:
        """Unframed append spool (streamed edge runs): NOT atomic and
        NOT digest-framed — spools are same-call scratch, never
        checkpoints, so a crash discards the whole spool rather than
        resuming from it. Same counter accounting as write()."""
        try:
            with open(self.path(name), "ab") as f:
                f.write(blob)
        except OSError:
            return False
        m = get_metrics()
        m.counter("spill.writes").add(1)
        m.counter("spill.bytes_written").add(len(blob))
        return True

    def read(self, name: str) -> Optional[bytes]:
        try:
            blob = self.path(name).read_bytes()
        except OSError:
            return None
        m = get_metrics()
        m.counter("spill.reads").add(1)
        m.counter("spill.bytes_read").add(len(blob))
        return blob

    def delete(self, name: str) -> None:
        try:
            self.path(name).unlink()
        except OSError:
            pass

    def names(self) -> list[str]:
        try:
            return sorted(p.name for p in self.root.iterdir()
                          if p.is_file() and not p.name.endswith(".tmp"))
        except OSError:
            return []


class SpillWindow:
    """The bounded in-RAM tier over a :class:`SpillDir`: put() writes
    through to disk immediately (crash-durable) and keeps the blob
    resident; past the byte budget the OLDEST resident copies drop
    (``spill.evictions``) — eviction is free, the disk already has the
    bytes. get() serves RAM hits without I/O and re-reads the disk
    tier on a miss."""

    def __init__(self, sdir: SpillDir,
                 budget_mb: Optional[float] = None):
        self.sdir = sdir
        if budget_mb is None:
            budget_mb = limits().host_rss_budget_mb / 4
        self.budget_bytes = int(budget_mb * (1 << 20))
        self._ram: dict[str, bytes] = {}
        self._ram_bytes = 0

    def put(self, name: str, blob: bytes) -> None:
        self.sdir.write(name, blob)
        old = self._ram.pop(name, None)
        if old is not None:
            self._ram_bytes -= len(old)
        self._ram[name] = blob
        self._ram_bytes += len(blob)
        self._evict()

    def _evict(self) -> None:
        m = None
        while self._ram_bytes > self.budget_bytes and len(self._ram) > 1:
            name = next(iter(self._ram))
            self._ram_bytes -= len(self._ram.pop(name))
            if m is None:
                m = get_metrics()
            m.counter("spill.evictions").add(1)

    def get(self, name: str) -> Optional[bytes]:
        blob = self._ram.get(name)
        if blob is not None:
            return blob
        return self.sdir.read(name)

    @property
    def resident_bytes(self) -> int:
        return self._ram_bytes


# -- session routing --------------------------------------------------------
# Like the encode cache, the spill tier is OFF unless activated (the
# bench long-haul lane and the CLI activate it); library callers pay
# one module-global read. wgl2/wgl3 consult `active_spill()` +
# `spill_active()` before spilling their chunk checkpoints.

_active_dir: Optional[SpillDir] = None


def activate_spill(root: str | os.PathLike | None) -> Optional[SpillDir]:
    """Point the spill tier at `root` (created lazily); None
    deactivates. Returns the PREVIOUS SpillDir for save/restore."""
    global _active_dir
    prev = _active_dir
    _active_dir = SpillDir(root) if root is not None else None
    return prev


def active_spill() -> Optional[SpillDir]:
    return _active_dir


@contextmanager
def spilling(root: str | os.PathLike | None) -> Iterator[Optional[SpillDir]]:
    global _active_dir
    prev = activate_spill(root)
    try:
        yield _active_dir
    finally:
        _active_dir = prev
