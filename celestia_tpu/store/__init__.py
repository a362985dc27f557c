"""Durable EDS block store — the third tier behind `PagedEdsCache`.

One file per height (`<height>.ctps`) holding the three artifacts a
restarted node needs to serve `/sample` + `/proof/share` without
re-extending anything:

    EDS row-group pages   the SAME row-group granularity the paged
                          device cache uses (ADR-017), written at
                          FIXED offsets so a fault-in reads one page
                          record, never the square
    row-tree levels       the device-computed NMT node levels
                          (ADR-019) that seed byte-identical
                          `NmtRowProver`s after restart (optional —
                          crypto-free embedders persist without them)
    DAH                   the served DataAvailabilityHeader JSON, so
                          post-restart `/dah` bytes equal the
                          pre-restart bytes exactly

Every record payload carries its own CRC32C (same engine as the cache
tiers, `integrity.crc32c`): a read whose checksum mismatches raises
`IntegrityError` + `record_sdc("store.read")` — torn or rotted data is
never returned. Writes are atomic (temp file + rename), so a crash
mid-put leaves at worst a `.tmp` orphan, never a half-indexed height.

Durability contract (specs/store.md §Durability contract, ADR-026): in
durable mode a put is ACKNOWLEDGED DURABLE only after data fsync +
rename + parent-directory fsync — the dirsync is what makes the rename
itself survive power loss. Every persisted byte crosses the `FsShim`
syscall boundary (write/fsync/rename/dirsync/unlink), which both fires
the `store.*` fault sites and gives the powercut explorer
(store/powercut.py) its interposition point: it records the ordered
effect trace of put/compact sequences and replays a kill at every
prefix under a simulated page cache, gating the recovery invariants.

Disk-fault degradation: an `OSError` with `errno.ENOSPC` (real or
injected `enospc` kind) flips the store to STICKY READ-ONLY —
`store_read_only` gauge 1, one warn log, best-effort `.tmp` cleanup,
optional emergency compact — and subsequent puts are skipped
(`store_put_aborted_total{reason=read_only}`) so the node keeps
serving from the cache tiers instead of crash-looping the put path.
Recovery: puts periodically re-probe (the put itself is the probe,
one per `reprobe_interval_s`), or `try_recover()` probes explicitly;
`/readyz` surfaces the state through its `store_writable` check.

Re-index (`reindex()`) is how a restarted node adopts the directory:
damaged files — truncated tail records, corrupt headers, CRC-mismatched
pages, duplicate heights — are SKIPPED with a
`store_reindex_skipped_total{reason=...}` bump, never a startup crash.

Compaction (`compact()`, ADR-023) keeps a long-running backend bounded
on disk: given a byte budget it evicts whole COLD heights — lowest
first, never the newest `keep_recent` — by dropping the index entry
first (under `_index_lock`) and unlinking the file after. Retained
files are never rewritten, so surviving DAH bytes are identical before
and after a compaction. A reader racing an eviction sees the ordinary
"height not in store" KeyError (the read paths map a vanished file to
the same miss), never a torn record.

Layout (specs/store.md is the normative format doc):

    header (64 bytes, fixed):
      magic=CTPS u32-version height k share_size rows_per_page
      page_count dah_len levels_len dah_crc levels_crc page_slot
      header_crc (CRC32C over the preceding 52 bytes)
    DAH JSON bytes        (dah_len,   crc = dah_crc)
    levels blob           (levels_len, crc = levels_crc; 0 = absent)
    page records, fixed offsets:
      record i at  64 + dah_len + levels_len + i * (16 + page_slot)
      record header: nbytes u32, crc u32, reserved u64
      payload: nbytes bytes of row-major uint8 shares, zero-padded to
      page_slot (slot = rows_per_page * 2k * share_size)

Fault sites (specs/faults.md): `store.write` fires once per `put`
before the file lands (corrupt/bitflip rules mangle the first page
payload AFTER its CRC was computed — the on-disk-rot drill; a
`short_write` rule lands only a seeded prefix of the file and fails
the put); `store.read` fires on every page read with the bytes in
hand (bitflip rules mangle them BEFORE the CRC check, so the drill
proves detection, not luck). The syscall quartet `store.fsync` /
`store.rename` / `store.dirsync` / `store.unlink` fires inside the
`FsShim` at the matching kernel boundary — `enospc` / `fsync_fail`
rules there strike exactly where the real failure would.

Stdlib-importable: numpy is imported lazily inside the methods that
touch share bytes, mirroring node/eds_cache.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import errno
import json
import os
import pathlib
import struct
import threading
import time

from celestia_tpu import faults, tracing
from celestia_tpu.integrity import IntegrityError, crc32c, record_sdc
from celestia_tpu.log import logger
from celestia_tpu.telemetry import metrics

log = logger("store")

MAGIC = b"CTPS"
VERSION = 1
SUFFIX = ".ctps"

_HEADER = struct.Struct("<4sIQIIIIIIIII")  # 52 bytes of fields
_HEADER_CRC = struct.Struct("<I")
HEADER_SIZE = 64  # fields + crc, zero-padded
_RECORD = struct.Struct("<IIQ")  # nbytes, crc, reserved
RECORD_HEADER_SIZE = _RECORD.size

DEFAULT_ROWS_PER_PAGE = 8


@dataclasses.dataclass(frozen=True)
class StoreEntry:
    """One indexed height: everything a fault-in needs to seek straight
    to a page record without re-reading the header."""

    path: pathlib.Path
    height: int
    k: int
    share_size: int
    rows_per_page: int
    page_count: int
    page_slot: int
    dah_len: int
    levels_len: int
    dah_crc: int
    levels_crc: int

    @property
    def page_base(self) -> int:
        return HEADER_SIZE + self.dah_len + self.levels_len

    def page_offset(self, index: int) -> int:
        return self.page_base + index * (RECORD_HEADER_SIZE + self.page_slot)

    def page_rows(self, index: int) -> int:
        width = 2 * self.k
        lo = index * self.rows_per_page
        return min(self.rows_per_page, width - lo)


def _pack_header(entry_fields: dict) -> bytes:
    raw = _HEADER.pack(
        MAGIC, VERSION, entry_fields["height"], entry_fields["k"],
        entry_fields["share_size"], entry_fields["rows_per_page"],
        entry_fields["page_count"], entry_fields["dah_len"],
        entry_fields["levels_len"], entry_fields["dah_crc"],
        entry_fields["levels_crc"], entry_fields["page_slot"],
    )
    raw += _HEADER_CRC.pack(crc32c(raw))
    return raw.ljust(HEADER_SIZE, b"\x00")


def pack_levels(levels) -> bytes:
    """Serialize the per-height row-tree node levels
    (`ops/extend_tpu.eds_row_levels_device` output: one uint8 array of
    90-byte NMT nodes per tree level, leaves first)."""
    import numpy as np

    out = [struct.pack("<I", len(levels))]
    for lvl in levels:
        arr = np.ascontiguousarray(np.asarray(lvl, dtype=np.uint8))
        rows, nodes, width = arr.shape
        out.append(struct.pack("<III", rows, nodes, width))
        out.append(arr.tobytes())
    return b"".join(out)


def unpack_levels(blob: bytes):
    import numpy as np

    (count,) = struct.unpack_from("<I", blob, 0)
    off = 4
    levels = []
    for _ in range(count):
        rows, nodes, width = struct.unpack_from("<III", blob, off)
        off += 12
        size = rows * nodes * width
        arr = np.frombuffer(blob, dtype=np.uint8, count=size, offset=off)
        levels.append(arr.reshape(rows, nodes, width).copy())
        off += size
    return levels


class FsShim:
    """Syscall-boundary shim: every byte the store persists crosses one
    of these methods. Each fires its `store.*` fault site
    (specs/faults.md) before touching the kernel, so `enospc` /
    `fsync_fail` / `short_write` rules strike exactly where a real
    kernel failure would. This is ALSO the powercut explorer's
    interposition point: store/powercut.py swaps a recording shim onto
    a store instance to capture the ordered effect trace it replays
    crashes over."""

    def open_w(self, path, **ctx):
        return open(path, "wb")

    def fsync(self, f, *, path, **ctx) -> None:
        faults.fire("store.fsync", path=str(path), **ctx)
        with tracing.span("store.fsync"):
            os.fsync(f.fileno())
        metrics.incr_counter("store_sync_total", kind="fsync")

    def replace(self, src, dst, **ctx) -> None:
        faults.fire("store.rename", src=str(src), dst=str(dst), **ctx)
        os.replace(src, dst)

    def dirsync(self, dirpath, **ctx) -> None:
        faults.fire("store.dirsync", path=str(dirpath), **ctx)
        with tracing.span("store.dirsync"):
            fd = os.open(dirpath, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        metrics.incr_counter("store_sync_total", kind="dirsync")

    def unlink(self, path, *, missing_ok: bool = True, **ctx) -> None:
        faults.fire("store.unlink", path=str(path), **ctx)
        pathlib.Path(path).unlink(missing_ok=missing_ok)


_FS = FsShim()


def write_durable(path, parts, *, fs: FsShim = _FS, durable: bool = True,
                  truncate=None, **ctx) -> None:
    """Land one whole file so that a crash leaves the old file or the
    new one under ``path``, never a torn one: write ``parts`` (bytes,
    or an iterable of bytes) to a `.tmp` sibling, fsync it, rename it
    over ``path``, fsync the directory (specs/store.md §Durability
    contract). ``durable=False`` keeps the tmp+rename and skips both
    syncs. ``truncate`` (a `store.write` short_write fault) lands only a
    prefix of the body and fails like a real torn write. On any failure
    the `.tmp` is unlinked and the error re-raised."""
    if isinstance(parts, (bytes, bytearray, memoryview)):
        parts = (parts,)
    path = pathlib.Path(path)
    tmp = path.with_name(path.name + ".tmp")
    try:
        with fs.open_w(tmp, **ctx) as f:
            if truncate is not None:
                f.write(truncate(b"".join(parts)))
                f.flush()
                err = faults.DiskFault(
                    errno.EIO, f"short write persisting {path.name}")
                err.short_write = True
                raise err
            for part in parts:
                f.write(part)
            f.flush()
            if durable:
                fs.fsync(f, path=tmp, **ctx)
        fs.replace(tmp, path, **ctx)
        if durable:
            # the rename itself is not crash-durable until the parent
            # directory's entry is (ADR-026): without this dirsync an
            # acknowledged file can vanish after power loss — the bug
            # the powercut explorer finds
            fs.dirsync(path.parent, **ctx)
    except BaseException:
        try:
            fs.unlink(tmp, missing_ok=True, **ctx)
        except (OSError, faults.FaultError):
            pass  # disk too sick to even unlink; reindex ignores .tmp
        raise


class BlockStore:
    """CRC32C-guarded on-disk block store under one directory.

    The index (`_index`, height -> StoreEntry, plus the skip counters)
    is guarded by `_index_lock` — declared in the specs/serving.md lock
    order between the cache locks and the leaf locks. File I/O and CRC
    math run UNLOCKED: records are immutable once renamed into place,
    so readers only need the entry snapshot."""

    def __init__(self, root: str | os.PathLike, *, durable: bool = True,
                 reprobe_interval_s: float = 5.0,
                 emergency_compact_bytes: int | None = None):
        self.root = pathlib.Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        # durable=False skips the per-put fsync AND dirsync (atomic
        # tmp+rename is kept, so a torn write still can't surface):
        # soak/CI harnesses producing thousands of heights are
        # fsync-bound otherwise. Production nodes never pass this.
        self.durable = bool(durable)
        # syscall boundary (FsShim); powercut.py swaps in a recorder
        self._fs = _FS
        # read-only degradation state machine (module docstring):
        # _read_only is a GIL-atomic bool read unlocked on hot paths;
        # transitions and the reprobe clock are under _index_lock.
        self.reprobe_interval_s = float(reprobe_interval_s)
        self.emergency_compact_bytes = emergency_compact_bytes
        self._read_only = False
        self._read_only_reason: str | None = None
        self._reprobe_after = 0.0
        self._index_lock = threading.Lock()
        self._index: dict[int, StoreEntry] = {}
        self._skipped: dict[str, int] = {}
        self._page_reads = 0
        self._puts = 0
        self._write_errors = 0
        self._put_aborts = 0
        self._compactions = 0
        self._evicted = 0

    # -- write ---------------------------------------------------------- #

    def put_eds(self, height: int, eds_np, original_width: int, *,
                dah_doc: dict, levels=None,
                rows_per_page: int = DEFAULT_ROWS_PER_PAGE) -> StoreEntry:
        """Persist one height: the host EDS array split into row-group
        pages, the served DAH JSON, and (optionally) the device
        row-tree levels. Atomic — the height is visible only after the
        rename, and a re-put replaces the old file in one step.

        In durable mode the height is ACKNOWLEDGED DURABLE only when
        this returns: data fsync + rename + parent-dir fsync have all
        happened (specs/store.md §Durability contract). Read-only mode
        (ENOSPC degradation) skips the put and returns None — except
        one put per ``reprobe_interval_s``, which runs as the recovery
        probe and clears the degradation if it lands."""
        with tracing.span("store.put_eds", height=height):
            return self._put_eds(height, eds_np, original_width,
                                 dah_doc=dah_doc, levels=levels,
                                 rows_per_page=rows_per_page)

    def _put_eds(self, height: int, eds_np, original_width: int, *,
                 dah_doc: dict, levels, rows_per_page: int):
        import numpy as np

        # lint: allow(C005) reason=_read_only is a GIL-atomic bool read; transitions serialize under _index_lock and a one-read-stale value only delays (never corrupts) a single put
        if self._read_only:
            now = time.monotonic()
            with self._index_lock:
                reprobe = now >= self._reprobe_after
                if reprobe:  # this put becomes the probe; peers skip
                    self._reprobe_after = now + self.reprobe_interval_s
            if not reprobe:
                metrics.incr_counter("store_put_aborted_total",
                                     reason="read_only")
                return None

        arr = np.ascontiguousarray(np.asarray(eds_np, dtype=np.uint8))
        width, _w2, share_size = arr.shape
        if width != 2 * original_width:
            raise ValueError(
                f"EDS width {width} != 2*k for k={original_width}")
        rows_per_page = max(1, min(int(rows_per_page), width))
        page_count = -(-width // rows_per_page)
        page_slot = rows_per_page * width * share_size

        dah_bytes = json.dumps(dah_doc, sort_keys=True).encode()
        levels_bytes = pack_levels(levels) if levels else b""
        pages = []
        for i in range(page_count):
            lo = i * rows_per_page
            hi = min(lo + rows_per_page, width)
            payload = arr[lo:hi].tobytes()
            pages.append((payload, crc32c(payload)))

        fields = {
            "height": height, "k": original_width,
            "share_size": share_size, "rows_per_page": rows_per_page,
            "page_count": page_count, "dah_len": len(dah_bytes),
            "levels_len": len(levels_bytes), "dah_crc": crc32c(dah_bytes),
            "levels_crc": crc32c(levels_bytes), "page_slot": page_slot,
        }
        path = self.root / f"{height}{SUFFIX}"
        try:
            # the write drill: corrupt/bitflip rules mangle the first
            # page payload AFTER its CRC was computed — rot-on-disk
            # that the next read MUST catch. Fired before any bytes
            # land, INSIDE the abort scope, so enospc/error strikes
            # count as aborted puts like the real thing. A short_write
            # rule returns a truncator instead: only a seeded prefix of
            # the file body lands, then the put fails like a real torn
            # write.
            truncate = None
            flip = faults.fire("store.write", height=height,
                               pages=page_count)
            if flip is not None and getattr(flip, "short_write", False):
                truncate = flip
            elif flip is not None and pages:
                pages[0] = (flip(pages[0][0]), pages[0][1])
            parts = [_pack_header(fields), dah_bytes, levels_bytes]
            for payload, crc in pages:
                parts.append(_RECORD.pack(len(payload), crc, 0))
                parts.append(payload.ljust(page_slot, b"\x00"))
            write_durable(path, parts, fs=self._fs, durable=self.durable,
                          truncate=truncate, height=height)
        except Exception as exc:
            self._abort_put(exc)
            raise
        if self._read_only:  # this put was the recovery probe, and won
            self._exit_read_only()
        entry = StoreEntry(path=path, **fields)
        with self._index_lock:
            self._index[height] = entry
            self._puts += 1
        metrics.incr_counter("store_put_total")
        metrics.incr_counter("store_put_bytes_total",
                             entry.page_offset(entry.page_count))
        self._publish()
        return entry

    def _abort_put(self, exc: BaseException) -> None:
        """Mid-put failure (`write_durable` has already unlinked the
        `.tmp`): classify + count the abort, and flip read-only on
        ENOSPC."""
        if getattr(exc, "short_write", False):
            reason = "short_write"
        elif isinstance(exc, OSError) and exc.errno == errno.ENOSPC:
            reason = "enospc"
        else:
            reason = "error"
        with self._index_lock:
            self._write_errors += 1
            self._put_aborts += 1
        metrics.incr_counter("store_write_error_total")
        metrics.incr_counter("store_put_aborted_total", reason=reason)
        if reason == "enospc":
            self._enter_read_only("enospc")

    # -- read-only degradation (ENOSPC state machine) ------------------- #

    @property
    def read_only(self) -> bool:
        # lint: allow(C005) reason=GIL-atomic bool snapshot for telemetry/readiness; transitions serialize under _index_lock
        return self._read_only

    @property
    def read_only_reason(self) -> str | None:
        # lint: allow(C005) reason=GIL-atomic str reference snapshot; written only inside _index_lock, a stale read mislabels one readiness detail at worst
        return self._read_only_reason

    def force_read_only(self, reason: str = "operator") -> None:
        """Operations hook: degrade to read-only WITHOUT an automatic
        put-side reprobe (recovery needs an explicit `try_recover`) —
        how a fleet worker models its disk being pulled out from under
        it (node/fleet.py `readonly` command)."""
        self._enter_read_only(reason)
        with self._index_lock:
            self._reprobe_after = float("inf")

    def try_recover(self) -> bool:
        """Explicit writability probe — the recovery edge of the
        read-only state machine. Writes, fsyncs and unlinks a tiny
        probe file through the same FsShim the put path uses, so
        injected disk faults at those sites keep the store read-only
        exactly as a still-full disk would. True = writable now."""
        if not self._read_only:
            return True
        probe = self.root / ".writable.probe"
        try:
            with self._fs.open_w(probe) as f:
                f.write(b"ok")
                f.flush()
                if self.durable:
                    self._fs.fsync(f, path=probe)
            self._fs.unlink(probe, missing_ok=True)
        except (OSError, faults.FaultError):
            with self._index_lock:
                self._reprobe_after = (time.monotonic()
                                       + self.reprobe_interval_s)
            return False
        self._exit_read_only()
        return True

    def _enter_read_only(self, reason: str) -> None:
        with self._index_lock:
            first = not self._read_only
            self._read_only = True
            self._read_only_reason = reason
            self._reprobe_after = (time.monotonic()
                                   + self.reprobe_interval_s)
        metrics.set_gauge("store_read_only", 1.0)
        if not first:
            return  # sticky: re-strikes only push the reprobe clock
        metrics.incr_counter("store_read_only_total")
        log.warn("store degraded to read-only", reason=reason,
                 root=str(self.root))
        self._cleanup_tmp()
        if self.emergency_compact_bytes:
            try:  # free what we can so reads keep their hot window
                self.compact(int(self.emergency_compact_bytes))
            except (OSError, faults.FaultError):
                pass

    def _exit_read_only(self) -> None:
        with self._index_lock:
            if not self._read_only:
                return
            self._read_only = False
            self._read_only_reason = None
            self._reprobe_after = 0.0
        metrics.set_gauge("store_read_only", 0.0)
        metrics.incr_counter("store_read_only_recovered_total")
        log.info("store writable again", root=str(self.root))

    def _cleanup_tmp(self) -> None:
        """Free what a full disk can still give back: abandoned `.tmp`
        orphans (unlink needs no free space on mainstream filesystems)."""
        for tmp in self.root.glob(f"*{SUFFIX}.tmp"):
            try:
                self._fs.unlink(tmp, missing_ok=True)
            except (OSError, faults.FaultError):
                pass

    # -- re-index ------------------------------------------------------- #

    def reindex(self, deep: bool = True) -> dict:
        """Scan the directory and rebuild the height index — the
        restart path. Damaged files are skipped with a
        `store_reindex_skipped_total{reason=...}` bump (reasons:
        bad_header, truncated, page_crc, duplicate), never a crash.
        `deep` additionally verifies every page record's CRC (the
        default: CI stores are small; pass False to adopt a large
        archive lazily and let per-read CRC checks catch rot)."""
        found: dict[int, StoreEntry] = {}
        skipped: dict[str, int] = {}

        def skip(path: pathlib.Path, reason: str) -> None:
            skipped[reason] = skipped.get(reason, 0) + 1
            metrics.incr_counter("store_reindex_skipped_total",
                                 reason=reason)
            log.warn("store re-index skipped file", file=path.name,
                     reason=reason)

        for path in sorted(self.root.glob(f"*{SUFFIX}")):
            entry = self._read_header(path)
            if entry is None:
                skip(path, "bad_header")
                continue
            expected = entry.page_offset(entry.page_count)
            try:
                size = path.stat().st_size
            except OSError:
                skip(path, "bad_header")
                continue
            if size < expected:
                skip(path, "truncated")
                continue
            if entry.height in found:
                skip(path, "duplicate")
                continue
            if deep and not self._verify_pages(entry):
                skip(path, "page_crc")
                continue
            found[entry.height] = entry
        with self._index_lock:
            self._index = found
            for reason, n in skipped.items():
                self._skipped[reason] = self._skipped.get(reason, 0) + n
        self._publish()
        report = {"heights": len(found), "skipped": skipped}
        log.info("store re-indexed", root=str(self.root), **report)
        return report

    # -- compaction ----------------------------------------------------- #

    def compact(self, byte_budget: int, *, keep_recent: int = 16) -> dict:
        """Evict whole cold heights until the store fits `byte_budget`
        (ADR-023's GC policy). Lowest heights go first — the DAS-cold
        tail — and the newest `keep_recent` heights are NEVER evicted
        even over budget, so the hot serving window survives a
        too-small budget. Eviction order: drop the index entry under
        `_index_lock`, then unlink the file unlocked — a racing reader
        holding the stale entry maps the vanished file to the ordinary
        KeyError miss. Retained files are untouched: their DAH and
        page bytes are identical before and after."""
        byte_budget = int(byte_budget)
        with self._index_lock:
            heights = sorted(self._index)
            sizes = {h: self._index[h].page_offset(
                self._index[h].page_count) for h in heights}
        total = sum(sizes.values())
        bytes_before = total
        protected = set(heights[-keep_recent:]) if keep_recent > 0 \
            else set()
        victims: list[int] = []
        for h in heights:
            if total <= byte_budget:
                break
            if h in protected:
                continue
            victims.append(h)
            total -= sizes[h]
        evicted: list[int] = []
        freed = 0
        for h in victims:
            with self._index_lock:
                entry = self._index.pop(h, None)
            if entry is None:
                continue  # lost a race with a concurrent compaction
            try:
                self._fs.unlink(entry.path, missing_ok=True, height=h)
            except OSError:
                pass  # the index drop already hid the height
            evicted.append(h)
            freed += sizes[h]
            metrics.incr_counter("store_compact_evicted_total")
        if evicted and self.durable:
            # make the unlinks crash-durable in one directory sync; a
            # lost unlink would only resurrect an already-evicted
            # height after a crash (re-adopted by reindex, re-evicted
            # by the next compaction), so failure here is a warn, not
            # an error
            try:
                self._fs.dirsync(self.root)
            except (OSError, faults.FaultError):
                log.warn("compact dirsync failed", root=str(self.root))
        with self._index_lock:
            self._compactions += 1
            self._evicted += len(evicted)
        metrics.incr_counter("store_compact_total")
        self._publish()
        report = {
            "budget": byte_budget, "evicted": len(evicted),
            "evicted_heights": evicted, "bytes_before": bytes_before,
            "bytes_after": bytes_before - freed, "bytes_freed": freed,
            "over_budget": bytes_before - freed > byte_budget,
        }
        if evicted:
            log.info("store compacted", **{k: v for k, v in
                                           report.items()
                                           if k != "evicted_heights"})
        return report

    def _read_header(self, path: pathlib.Path) -> StoreEntry | None:
        try:
            with open(path, "rb") as f:
                raw = f.read(HEADER_SIZE)
        except OSError:
            return None
        if len(raw) < _HEADER.size + _HEADER_CRC.size:
            return None
        fields = raw[: _HEADER.size]
        (stored_crc,) = _HEADER_CRC.unpack_from(raw, _HEADER.size)
        if crc32c(fields) != stored_crc:
            return None
        (magic, version, height, k, share_size, rows_per_page,
         page_count, dah_len, levels_len, dah_crc, levels_crc,
         page_slot) = _HEADER.unpack(fields)
        if magic != MAGIC or version != VERSION:
            return None
        if k <= 0 or rows_per_page <= 0 or page_count <= 0:
            return None
        return StoreEntry(
            path=path, height=height, k=k, share_size=share_size,
            rows_per_page=rows_per_page, page_count=page_count,
            page_slot=page_slot, dah_len=dah_len, levels_len=levels_len,
            dah_crc=dah_crc, levels_crc=levels_crc,
        )

    def _verify_pages(self, entry: StoreEntry) -> bool:
        try:
            with open(entry.path, "rb") as f:
                for i in range(entry.page_count):
                    f.seek(entry.page_offset(i))
                    rec = f.read(RECORD_HEADER_SIZE)
                    nbytes, crc, _r = _RECORD.unpack(rec)
                    payload = f.read(nbytes)
                    if len(payload) != nbytes or crc32c(payload) != crc:
                        return False
        except (OSError, struct.error):
            return False
        return True

    # -- read ----------------------------------------------------------- #

    def entry(self, height: int) -> StoreEntry | None:
        with self._index_lock:
            return self._index.get(height)

    def heights(self) -> list[int]:
        with self._index_lock:
            return sorted(self._index)

    def __contains__(self, height: int) -> bool:
        with self._index_lock:
            return height in self._index

    def __len__(self) -> int:
        with self._index_lock:
            return len(self._index)

    def _require(self, height: int) -> StoreEntry:
        entry = self.entry(height)
        if entry is None:
            raise KeyError(f"height {height} not in store")
        return entry

    @staticmethod
    @contextlib.contextmanager
    def _evictable(height: int):
        """Map a file that vanished under a racing `compact()` to the
        ordinary height-miss KeyError — never a FileNotFoundError leak."""
        try:
            yield
        except FileNotFoundError:
            raise KeyError(
                f"height {height} not in store (evicted)") from None

    def read_page(self, height: int, index: int):
        """One page record -> (uint8 array (rows, 2k, share_size),
        payload CRC32C). ONE seek + one bounded read — never the
        square. CRC mismatch (rot, torn write, injected flip) raises
        `IntegrityError` after `record_sdc("store.read")`; the caller
        never sees mangled shares."""
        import numpy as np

        entry = self._require(height)
        if not (0 <= index < entry.page_count):
            raise IndexError(
                f"page {index} out of range ({entry.page_count} pages)")
        with self._evictable(height), open(entry.path, "rb") as f:
            f.seek(entry.page_offset(index))
            nbytes, crc, _r = _RECORD.unpack(f.read(RECORD_HEADER_SIZE))
            payload = f.read(nbytes)
        # the read drill: a bitflip rule mangles the bytes BEFORE the
        # CRC check — detection proves the guard, not luck
        flip = faults.fire("store.read", height=height, page=index)
        if flip is not None:
            payload = bytes(flip(payload))
        if len(payload) != nbytes or crc32c(payload) != crc:
            record_sdc("store.read")
            metrics.incr_counter("store_read_corrupt_total")
            err = IntegrityError(
                f"store page CRC mismatch at height {height} page "
                f"{index} — refusing to serve torn data")
            err.site = "store.read"
            raise err
        with self._index_lock:
            self._page_reads += 1
        metrics.incr_counter("store_page_read_total")
        rows = entry.page_rows(index)
        arr = np.frombuffer(payload, dtype=np.uint8).reshape(
            rows, 2 * entry.k, entry.share_size)
        return arr, crc

    def page_crcs(self, height: int) -> list[int]:
        """Every page record's stored CRC (header reads only) — what a
        store-seeded cache page adopts before its first fault-in."""
        entry = self._require(height)
        crcs = []
        with self._evictable(height), open(entry.path, "rb") as f:
            for i in range(entry.page_count):
                f.seek(entry.page_offset(i))
                _n, crc, _r = _RECORD.unpack(f.read(RECORD_HEADER_SIZE))
                crcs.append(crc)
        return crcs

    def read_dah(self, height: int) -> dict:
        """The stored DataAvailabilityHeader JSON doc — byte-identical
        to what the node served before restart."""
        entry = self._require(height)
        with self._evictable(height), open(entry.path, "rb") as f:
            f.seek(HEADER_SIZE)
            raw = f.read(entry.dah_len)
        if len(raw) != entry.dah_len or crc32c(raw) != entry.dah_crc:
            record_sdc("store.read")
            metrics.incr_counter("store_read_corrupt_total")
            err = IntegrityError(
                f"store DAH CRC mismatch at height {height}")
            err.site = "store.read"
            raise err
        return json.loads(raw)

    def read_levels(self, height: int):
        """The stored row-tree node levels, or None when the height was
        persisted without them (crypto-free embedders)."""
        entry = self._require(height)
        if entry.levels_len == 0:
            return None
        with self._evictable(height), open(entry.path, "rb") as f:
            f.seek(HEADER_SIZE + entry.dah_len)
            raw = f.read(entry.levels_len)
        if len(raw) != entry.levels_len or crc32c(raw) != entry.levels_crc:
            record_sdc("store.read")
            metrics.incr_counter("store_read_corrupt_total")
            err = IntegrityError(
                f"store levels CRC mismatch at height {height}")
            err.site = "store.read"
            raise err
        return unpack_levels(raw)

    # -- introspection -------------------------------------------------- #

    def stats(self) -> dict:
        with self._index_lock:
            heights = sorted(self._index)
            skipped = dict(self._skipped)
            page_reads = self._page_reads
            puts = self._puts
            write_errors = self._write_errors
            put_aborts = self._put_aborts
            compactions = self._compactions
            evicted = self._evicted
            nbytes = sum(e.page_offset(e.page_count)
                         for e in self._index.values())
        return {
            "kind": "blockstore",
            "root": str(self.root),
            "heights": len(heights),
            "height_lo": heights[0] if heights else None,
            "height_hi": heights[-1] if heights else None,
            "bytes": nbytes,
            "puts": puts,
            "page_reads": page_reads,
            "write_errors": write_errors,
            "put_aborts": put_aborts,
            "read_only": self._read_only,
            "read_only_reason": self._read_only_reason,
            "compactions": compactions,
            "evicted": evicted,
            "reindex_skipped": skipped,
        }

    def _publish(self) -> None:
        with self._index_lock:
            n = len(self._index)
            nbytes = sum(e.page_offset(e.page_count)
                         for e in self._index.values())
        metrics.set_gauge("store_heights", float(n))
        metrics.set_gauge("store_bytes", float(nbytes))
        metrics.set_gauge("store_read_only",
                          1.0 if self._read_only else 0.0)
