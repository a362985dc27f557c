"""The plain reference for what a validator with a home directory keeps
on disk: one height's store file (`store/<h>.ctps`, read from the
normative layout in specs/store.md) and its block record
(`blocks/<h>.json`), each checked against the reference's own square,
EDS, NMT row levels and DAH for the block's transactions.

Independent of the program: numpy, struct, json and a CRC32C
(google_crc32c), with the square, the extension and the hashes from the
benchmark reference; nothing imported from celestia_tpu.
"""

from __future__ import annotations

import json
import os
import struct

import google_crc32c
import numpy as np

from benchmark import reference

MAGIC = b"CTPS"
VERSION = 1
HEADER = struct.Struct("<4sIQIIIIIIIII")  # the 52 bytes header_crc covers
HEADER_SIZE = 64
RECORD = struct.Struct("<IIQ")  # nbytes, crc, reserved


def crc32c(data: bytes) -> int:
    return int(google_crc32c.value(bytes(data)))


class StoreFileError(ValueError):
    """The file breaks the layout or a checksum."""


def read_store_file(path: str) -> dict:
    """Every part of one `.ctps` file, each checksum verified: header
    fields, the DAH document, the row levels as a list of (rows, nodes,
    width) uint8 arrays (None when absent), and the pages as a list of
    (rows, 2k, share_size) uint8 arrays."""
    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) < HEADER_SIZE:
        raise StoreFileError("shorter than the header")
    fields = raw[:HEADER.size]
    (header_crc,) = struct.unpack_from("<I", raw, HEADER.size)
    if crc32c(fields) != header_crc:
        raise StoreFileError("header crc")
    (magic, version, height, k, share_size, rows_per_page, page_count,
     dah_len, levels_len, dah_crc, levels_crc, page_slot) = HEADER.unpack(fields)
    if magic != MAGIC or version != VERSION:
        raise StoreFileError(f"magic {magic!r} version {version}")
    width = 2 * k
    if page_count != -(-width // rows_per_page) or page_slot != rows_per_page * width * share_size:
        raise StoreFileError("page geometry")
    off = HEADER_SIZE
    dah_raw = raw[off:off + dah_len]
    if len(dah_raw) != dah_len or crc32c(dah_raw) != dah_crc:
        raise StoreFileError("dah crc")
    off += dah_len
    levels_raw = raw[off:off + levels_len]
    if len(levels_raw) != levels_len or crc32c(levels_raw) != levels_crc:
        raise StoreFileError("levels crc")
    off += levels_len
    pages = []
    for i in range(page_count):
        rec = off + i * (RECORD.size + page_slot)
        if rec + RECORD.size > len(raw):
            raise StoreFileError(f"page {i} truncated")
        nbytes, crc, _reserved = RECORD.unpack_from(raw, rec)
        rows = min(rows_per_page, width - i * rows_per_page)
        payload = raw[rec + RECORD.size:rec + RECORD.size + nbytes]
        if nbytes != rows * width * share_size or len(payload) != nbytes:
            raise StoreFileError(f"page {i} length")
        if crc32c(payload) != crc:
            raise StoreFileError(f"page {i} crc")
        pages.append(np.frombuffer(payload, np.uint8).reshape(rows, width, share_size))
    return {"height": height, "k": k, "rows_per_page": rows_per_page,
            "dah": json.loads(dah_raw),
            "levels": _unpack_levels(levels_raw) if levels_len else None,
            "pages": pages}


def _unpack_levels(blob: bytes) -> list[np.ndarray]:
    (count,) = struct.unpack_from("<I", blob, 0)
    off = 4
    levels = []
    for _ in range(count):
        rows, nodes, width = struct.unpack_from("<III", blob, off)
        off += 12
        size = rows * nodes * width
        if off + size > len(blob):
            raise StoreFileError("levels truncated")
        levels.append(np.frombuffer(blob, np.uint8, size, off).reshape(rows, nodes, width))
        off += size
    if off != len(blob):
        raise StoreFileError("levels trailing bytes")
    return levels


def row_levels(eds: np.ndarray) -> list[list[list[bytes]]]:
    """For each row of the EDS, every level of its NMT: leaves first,
    the root last (a list of 90-byte nodes per level)."""
    w = eds.shape[0]
    k = w // 2
    out = []
    for i in range(w):
        cells = [eds[i, j].tobytes() for j in range(w)]
        level = [reference.leaf_hash(reference.leaf_namespace(i, j, c, k), c)
                 for j, c in enumerate(cells)]
        tree = [level]
        while len(level) > 1:
            level = [reference.node_hash(level[n], level[n + 1])
                     for n in range(0, len(level), 2)]
            tree.append(level)
        out.append(tree)
    return out


def _col_roots(eds: np.ndarray) -> list[bytes]:
    w = eds.shape[0]
    k = w // 2
    roots = []
    for j in range(w):
        leaves = [(reference.leaf_namespace(i, j, eds[i, j].tobytes(), k),
                   eds[i, j].tobytes()) for i in range(w)]
        roots.append(reference.nmt_root(leaves))
    return roots


def check_height(home: str, height: int, txs: list[bytes], data_hash: bytes,
                 k: int) -> dict:
    """One committed height of a home directory against the reference.

    store_ok: `store/<height>.ctps` exists, every checksum passes, its
    pages equal the reference EDS byte for byte, its DAH equals the
    reference DAH (whose hash is data_hash), and its row levels equal
    the reference's NMT node levels, every level of every row.
    record_ok: `blocks/<height>.json` parses, and its height, data hash
    and transactions are the committed block's.
    Runs in a worker process (no JAX)."""
    out = {"height": height, "store_ok": False, "record_ok": False, "error": None}
    try:
        with open(os.path.join(home, "blocks", f"{height}.json")) as f:
            rec = json.load(f)
        out["record_ok"] = (rec["height"] == height
                            and bytes.fromhex(rec["data_hash"]) == data_hash
                            and [bytes.fromhex(t) for t in rec["txs"]] == list(txs))
    except (OSError, ValueError, KeyError, TypeError) as e:
        out["error"] = f"block record: {type(e).__name__}: {e}"
    try:
        stored = read_store_file(os.path.join(home, "store", f"{height}.ctps"))
    except (OSError, ValueError, struct.error) as e:
        out["error"] = f"store: {type(e).__name__}: {e}"
        return out
    try:
        eds = reference.extend(reference.construct(txs))
    except (ValueError, KeyError, IndexError) as e:
        out["error"] = f"reference layout: {e}"
        return out
    w = eds.shape[0]
    if stored["height"] != height or stored["k"] != k or w != 2 * k:
        out["error"] = "store: header names another height or size"
        return out
    pages_ok = np.array_equal(np.concatenate(stored["pages"]), eds)
    trees = row_levels(eds)
    roots = [tree[-1][0] for tree in trees]
    cols = _col_roots(eds)
    dah_ok = (stored["dah"] == {"row_roots": [r.hex() for r in roots],
                                "column_roots": [c.hex() for c in cols]}
              and reference.dah_hash(roots, cols) == data_hash)
    levels = stored["levels"]
    levels_ok = levels is not None and len(levels) == len(trees[0])
    for n in range(len(trees[0]) if levels_ok else 0):
        want = np.frombuffer(b"".join(b"".join(tree[n]) for tree in trees),
                             np.uint8).reshape(w, w >> n, 90)
        levels_ok = levels_ok and np.array_equal(levels[n], want)
    out["store_ok"] = bool(pages_ok and dah_ok and levels_ok)
    if not out["store_ok"]:
        out["error"] = f"store: pages {pages_ok} dah {dah_ok} levels {levels_ok}"
    return out
