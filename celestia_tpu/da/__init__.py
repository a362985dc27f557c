"""Extended data square + DataAvailabilityHeader.

Reference semantics: pkg/da/data_availability_header.go and the rsmt2d
extension layout (Q1 = row-extend Q0, Q2 = col-extend Q0, Q3 = row-extend
Q2), with NMT row/column roots per pkg/wrapper/nmt_wrapper.go: leaves are
namespace-prefixed shares, where Q0 cells keep their own namespace and all
parity cells use the parity namespace.

This module is the host-path implementation (numpy + hashlib). The TPU path
(celestia_tpu.ops.extend_tpu) produces bit-identical results on-device.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading

import numpy as np

from celestia_tpu import namespace as ns
from celestia_tpu import tracing
from celestia_tpu.appconsts import (
    DEFAULT_SQUARE_SIZE_UPPER_BOUND,
    MIN_SQUARE_SIZE,
    NAMESPACE_SIZE,
    SHARE_SIZE,
)
from celestia_tpu.ops import gf256
from celestia_tpu.ops.nmt_host import merkle_root, nmt_root
from celestia_tpu.telemetry import metrics

PARITY_NS = ns.PARITY_SHARES_NAMESPACE.bytes

MAX_EXTENDED_SQUARE_WIDTH = DEFAULT_SQUARE_SIZE_UPPER_BOUND * 2
MIN_EXTENDED_SQUARE_WIDTH = MIN_SQUARE_SIZE * 2


class ExtendedDataSquare:
    """2k×2k erasure-extended share matrix, row-major uint8 (2k, 2k, 512).

    The backing bytes may live on an accelerator: `from_device` wraps a
    device buffer (jax array) and the host copy is fetched lazily on
    first `.data` access. The node's TPU ExtendBlock path relies on this
    — proposal/verify flows only ever need the DAH roots, so the 32 MB
    EDS crosses the interconnect only when the block store actually
    serves shares (ref: app/extend_block.go:14 recomputes the EDS
    post-consensus for storage; here storage holds the device handle).

    While device-resident, `row(i)` / `col(j)` / `share(r, c)` are
    SLICED reads: the device cuts the requested axis/cell and only that
    slice crosses to host (ops/transfers, specs/transfers.md) — a DAS
    sample costs one row, not the square. Whole-square consumers
    (`row_roots`, `flattened_shares`, `.data`) still do the single bulk
    fetch, after which every accessor serves from host memory."""

    # sliced rows/cols kept per instance so a DAS burst re-sampling the
    # same axis (one row serves up to 2k samples) hits host memory, not
    # the interconnect; tiny — the full square stays off-host
    _SLICE_CACHE_AXES = 8

    def __init__(self, squares: np.ndarray | None, original_width: int):
        self._data = squares
        self._device = None
        self._slice_cache: dict[tuple[str, int], list[bytes]] = {}
        # concurrent /sample handlers share one instance: the insert +
        # FIFO-evict below must not interleave (a bare dict pop races a
        # concurrent insert mid-iteration)
        self._slice_lock = threading.Lock()
        self.original_width = original_width

    @classmethod
    def from_device(cls, device_buffer, original_width: int) -> "ExtendedDataSquare":
        """Wrap a (2k, 2k, 512) device array without fetching it."""
        eds = cls(None, original_width)
        eds._device = device_buffer
        return eds

    @property
    def data(self) -> np.ndarray:
        if self._data is None:
            self._data = np.asarray(self._device)  # one lazy fetch
        return self._data

    @data.setter
    def data(self, value: np.ndarray) -> None:
        self._data = value
        # the device copy no longer matches — drop it, or device_data
        # consumers (repair_eds prefers it) would repair stale bytes
        self._device = None
        with self._slice_lock:
            self._slice_cache.clear()

    @property
    def device_data(self):
        """The device buffer when this EDS is device-resident (else None);
        repair consumes this handle directly to avoid a host round-trip."""
        return self._device

    @property
    def width(self) -> int:
        return 2 * self.original_width

    def _sliced_axis(self, kind: str, idx: int) -> list[bytes]:
        """One row/col of a device-resident square WITHOUT materializing
        the full EDS: the device cuts the slice (ops/transfers jitted
        dynamic-slice) and only w·512 bytes cross the interconnect —
        the DAS serving unit. Byte-identical to the full-fetch path
        (tests pin this across k and edge indices)."""
        key = (kind, idx)
        with self._slice_lock:
            cached = self._slice_cache.get(key)
        if cached is not None:
            return cached
        from celestia_tpu.ops import transfers

        # the transfer itself runs unlocked (it may block on the device
        # dispatcher); worst case two racers fetch the same immutable
        # slice once each and the second insert wins
        if kind == "row":
            arr = transfers.eds_row(self._device, idx)
        else:
            arr = transfers.eds_col(self._device, idx)
        cells = [arr[t].tobytes() for t in range(self.width)]
        with self._slice_lock:
            if len(self._slice_cache) >= self._SLICE_CACHE_AXES:
                self._slice_cache.pop(next(iter(self._slice_cache)))
            self._slice_cache[key] = cells
        return cells

    def row(self, i: int) -> list[bytes]:
        if self._data is None and self._device is not None:
            return self._sliced_axis("row", i)
        return [self.data[i, j].tobytes() for j in range(self.width)]

    def col(self, j: int) -> list[bytes]:
        if self._data is None and self._device is not None:
            return self._sliced_axis("col", j)
        return [self.data[i, j].tobytes() for i in range(self.width)]

    def rows_batch(self, indices: list[int]) -> list[list[bytes]]:
        """Several rows at once, in `indices` order. Device-resident
        squares fetch the distinct cache-missing rows as ONE vmapped
        sliced read (`transfers.eds_rows_batch`, ADR-017) instead of a
        dynamic-slice dispatch per row; byte-identical to per-row
        `row()` either way."""
        if self._data is not None or self._device is None:
            return [self.row(i) for i in indices]
        out: dict[int, list[bytes]] = {}
        misses: list[int] = []
        with self._slice_lock:
            for i in sorted(set(indices)):
                hit = self._slice_cache.get(("row", i))
                if hit is not None:
                    out[i] = hit
                else:
                    misses.append(i)
        if misses:
            from celestia_tpu.ops import transfers

            batch = transfers.eds_rows_batch(self._device, misses)
            with self._slice_lock:
                for t, i in enumerate(misses):
                    cells = [batch[t, c].tobytes()
                             for c in range(self.width)]
                    out[i] = cells
                    if len(self._slice_cache) >= self._SLICE_CACHE_AXES:
                        self._slice_cache.pop(
                            next(iter(self._slice_cache)))
                    self._slice_cache[("row", i)] = cells
        return [out[i] for i in indices]

    def share(self, r: int, c: int) -> bytes:
        """One cell. Device-resident squares transfer 512 bytes (or ride
        an already-fetched sliced row/col), never the full square."""
        if self._data is None and self._device is not None:
            # both probes under the lock: a concurrent FIFO eviction in
            # _sliced_axis/rows_batch mutates the dict mid-read (the
            # torn-read celestia-lint C005 pins; see ADR-016 regression
            # note). The 512-byte transfer below stays unlocked.
            with self._slice_lock:
                row_hit = self._slice_cache.get(("row", r))
                col_hit = self._slice_cache.get(("col", c))
            if row_hit is not None:
                return row_hit[c]
            if col_hit is not None:
                return col_hit[r]
            from celestia_tpu.ops import transfers

            return transfers.eds_share(self._device, r, c).tobytes()
        return self.data[r, c].tobytes()

    def flattened_shares(self) -> list[bytes]:
        # whole-square read: one full fetch beats w sliced transfers
        _ = self.data
        return [
            self.data[i, j].tobytes()
            for i in range(self.width)
            for j in range(self.width)
        ]

    def row_roots(self) -> list[bytes]:
        # roots consume every cell — materialize once, then host rows
        _ = self.data
        metrics.incr_counter("host_nmt_roots_total", self.width)
        with tracing.span("extend.nmt.rows", backend="host",
                          width=self.width):
            return [_axis_root(self.row(i), i, self.original_width)
                    for i in range(self.width)]

    def col_roots(self) -> list[bytes]:
        _ = self.data
        metrics.incr_counter("host_nmt_roots_total", self.width)
        with tracing.span("extend.nmt.cols", backend="host",
                          width=self.width):
            return [_axis_root(self.col(j), j, self.original_width)
                    for j in range(self.width)]


def erasured_leaf_namespace(
    axis_index: int, share_index: int, cell: bytes, k: int
) -> bytes:
    """The wrapper's quadrant rule for ONE leaf
    (pkg/wrapper/nmt_wrapper.go:93-114): the share's own namespace in
    Q0, the parity namespace otherwise. The single source of the rule —
    roots, range/absence proofs, and fraud-proof verification all
    consume it (directly or via erasured_axis_leaves)."""
    if axis_index < k and share_index < k:
        return cell[:NAMESPACE_SIZE]
    return PARITY_NS


def erasured_axis_leaves(
    cells: list[bytes], axis_index: int, k: int
) -> list[bytes]:
    """Namespaced NMT leaves of one row/column: leaf = ns ‖ share with ns
    per erasured_leaf_namespace."""
    return [
        erasured_leaf_namespace(axis_index, share_index, cell, k) + cell
        for share_index, cell in enumerate(cells)
    ]


def _axis_root(cells: list[bytes], axis_index: int, k: int) -> bytes:
    return nmt_root(erasured_axis_leaves(cells, axis_index, k))


def extend_shares(shares: list[bytes] | np.ndarray) -> ExtendedDataSquare:
    """shares: k*k row-major 512-byte shares. ref: pkg/da/data_availability_header.go:65"""
    if isinstance(shares, np.ndarray):
        if shares.dtype != np.uint8:
            raise ValueError(f"shares array must be uint8, got {shares.dtype}")
        flat = shares.reshape(-1, SHARE_SIZE)
        count = flat.shape[0]
    else:
        count = len(shares)
        flat = np.frombuffer(b"".join(shares), dtype=np.uint8).reshape(count, -1)
    k = int(round(count**0.5))
    if count == 0 or k * k != count or (k & (k - 1)) != 0:
        raise ValueError(f"number of shares must be a square power of two, got {count}")
    if k > DEFAULT_SQUARE_SIZE_UPPER_BOUND:
        raise ValueError(f"square size {k} exceeds max {DEFAULT_SQUARE_SIZE_UPPER_BOUND}")
    if flat.shape[1] != SHARE_SIZE:
        raise ValueError(f"shares must be {SHARE_SIZE} bytes")

    with tracing.span("extend.rs", backend="host", k=k):
        q0 = flat.reshape(k, k, SHARE_SIZE)
        eds = np.zeros((2 * k, 2 * k, SHARE_SIZE), dtype=np.uint8)
        eds[:k, :k] = q0
        # Q1: extend each original row. leopard_encode is row-batched: shape
        # (k shards, size); here the "shards" axis is the column index.
        for i in range(k):
            eds[i, k:] = gf256.leopard_encode(q0[i])
        # Q2: extend each original column.
        for j in range(k):
            eds[k:, j] = gf256.leopard_encode(q0[:, j])
        # Q3: extend the Q2 rows (rsmt2d extends the extended rows horizontally).
        for i in range(k, 2 * k):
            eds[i, k:] = gf256.leopard_encode(eds[i, :k])
        return ExtendedDataSquare(eds, k)


@dataclasses.dataclass
class DataAvailabilityHeader:
    row_roots: list[bytes]
    column_roots: list[bytes]
    _hash: bytes | None = dataclasses.field(default=None, compare=False, repr=False)

    def hash(self) -> bytes:
        """Merkle root over (row_roots ‖ column_roots).
        ref: pkg/da/data_availability_header.go:92-108"""
        if self._hash is None:
            with tracing.span("extend.dah", backend="host",
                              roots=len(self.row_roots) * 2):
                self._hash = merkle_root(
                    list(self.row_roots) + list(self.column_roots)
                )
        return self._hash

    def to_json(self) -> dict:
        """Wire shape shared by the /dah route and fraud-proof wires."""
        return {
            "row_roots": [r.hex() for r in self.row_roots],
            "column_roots": [c.hex() for c in self.column_roots],
        }

    @classmethod
    def from_json(cls, d: dict) -> "DataAvailabilityHeader":
        return cls(
            [bytes.fromhex(r) for r in d["row_roots"]],
            [bytes.fromhex(c) for c in d["column_roots"]],
        )

    def validate_basic(self) -> None:
        if len(self.column_roots) != len(self.row_roots):
            raise ValueError(
                "unequal number of row and column roots: "
                f"row {len(self.row_roots)} col {len(self.column_roots)}"
            )
        if len(self.row_roots) < MIN_EXTENDED_SQUARE_WIDTH:
            raise ValueError(
                f"minimum valid DataAvailabilityHeader has at least "
                f"{MIN_EXTENDED_SQUARE_WIDTH} row roots"
            )
        if len(self.row_roots) > MAX_EXTENDED_SQUARE_WIDTH:
            raise ValueError(
                f"maximum valid DataAvailabilityHeader has at most "
                f"{MAX_EXTENDED_SQUARE_WIDTH} row roots"
            )
        if len(self.hash()) != 32:
            raise ValueError(f"wrong hash: expected 32 bytes, got {len(self.hash())}")

    def square_size(self) -> int:
        return len(self.row_roots) // 2


def new_data_availability_header(eds: ExtendedDataSquare) -> DataAvailabilityHeader:
    dah = DataAvailabilityHeader(eds.row_roots(), eds.col_roots())
    dah.hash()
    return dah


def min_data_availability_header() -> DataAvailabilityHeader:
    """DAH of a block with one tail-padding share.
    ref: pkg/da/data_availability_header.go:179"""
    from celestia_tpu.shares import tail_padding_share

    eds = extend_shares([tail_padding_share().to_bytes()])
    return new_data_availability_header(eds)


def nil_dah_hash() -> bytes:
    return hashlib.sha256(b"").digest()
