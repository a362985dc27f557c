"""ctypes bindings for the native (C++) host runtime in native/.

The library is compiled on first use with g++ -O3 -march=native (no
pip/pkg deps; the toolchain is part of the base image) from the
committed native/*.cc sources only. The built file's name carries a
hash of those sources, the compiler flags and the host fingerprint
(ops._machine_fingerprint), so a stale library, or one a copy of this
checkout brought from a CPU with other features, is never loaded: a
missing key means a fresh build. Falls back cleanly — callers check
`available()` and use the numpy host path (celestia_tpu.da) when the
toolchain is missing.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import subprocess

import numpy as np

from celestia_tpu.appconsts import SHARE_SIZE

_NATIVE_DIR = pathlib.Path(__file__).resolve().parent.parent / "native"
_BUILD_DIR = _NATIVE_DIR / "build"
_SOURCES = (_NATIVE_DIR / "leopard.cc", _NATIVE_DIR / "nmt.cc")
_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC")

_lib = None
_load_error: str | None = None

NMT_NODE_SIZE = 90


def build_key(sources=_SOURCES) -> str:
    """Digest of the source bytes, the compiler flags and the host
    fingerprint: the library built from exactly these, on this kind of
    host, is the only one that may load."""
    from celestia_tpu.ops import _machine_fingerprint

    h = hashlib.sha256()
    for path in sources:
        h.update(pathlib.Path(path).read_bytes())
    h.update(" ".join(_FLAGS).encode())
    h.update(_machine_fingerprint().encode())
    return h.hexdigest()[:16]


def _lib_path() -> pathlib.Path:
    return _BUILD_DIR / f"libcelestia_native-{build_key()}.so"


def _build(lib_path: pathlib.Path) -> None:
    _BUILD_DIR.mkdir(exist_ok=True)
    # build beside the target and rename: concurrent processes never
    # load a half-written library
    tmp = lib_path.with_suffix(f".{os.getpid()}.tmp")
    cmd = ["g++", *_FLAGS, "-o", str(tmp), *map(str, _SOURCES)]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, lib_path)


def _load():
    global _lib, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    try:
        lib_path = _lib_path()
        if not lib_path.exists():
            _build(lib_path)
        lib = ctypes.CDLL(str(lib_path))
        lib.leo_encode.argtypes = [
            ctypes.c_int, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.eds_extend.argtypes = [
            ctypes.c_int, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.eds_nmt_roots.argtypes = [
            ctypes.c_int, ctypes.c_size_t, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.merkle_root.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.leo_decode.argtypes = [
            ctypes.c_int, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.eds_repair.argtypes = [
            ctypes.c_int, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.eds_repair.restype = ctypes.c_int
        _lib = lib
    except Exception as e:  # noqa: BLE001 — toolchain may be absent
        _load_error = str(e)
    return _lib


def available() -> bool:
    return _load() is not None


def leo_encode(data: np.ndarray) -> np.ndarray:
    """(k, shard_size) uint8 -> (k, shard_size) parity."""
    lib = _load()
    k, size = data.shape
    if k & (k - 1):
        raise ValueError("k must be a power of two")
    out = ctypes.create_string_buffer(k * size)
    lib.leo_encode(k, size, np.ascontiguousarray(data).tobytes(), out)
    return np.frombuffer(out.raw, dtype=np.uint8).reshape(k, size).copy()


def eds_extend(q0: np.ndarray) -> np.ndarray:
    """(k, k, 512) uint8 -> (2k, 2k, 512) EDS."""
    lib = _load()
    k = q0.shape[0]
    w = 2 * k
    out = ctypes.create_string_buffer(w * w * SHARE_SIZE)
    lib.eds_extend(k, SHARE_SIZE, np.ascontiguousarray(q0).tobytes(), out)
    return np.frombuffer(out.raw, dtype=np.uint8).reshape(w, w, SHARE_SIZE).copy()


def eds_nmt_roots(eds: np.ndarray) -> tuple[list[bytes], list[bytes]]:
    """(2k, 2k, 512) EDS -> (row_roots, col_roots), 90-byte NMT roots."""
    lib = _load()
    w = eds.shape[0]
    k = w // 2
    rows = ctypes.create_string_buffer(w * NMT_NODE_SIZE)
    cols = ctypes.create_string_buffer(w * NMT_NODE_SIZE)
    lib.eds_nmt_roots(k, SHARE_SIZE, np.ascontiguousarray(eds).tobytes(), rows, cols)
    row_roots = [rows.raw[i * NMT_NODE_SIZE : (i + 1) * NMT_NODE_SIZE] for i in range(w)]
    col_roots = [cols.raw[i * NMT_NODE_SIZE : (i + 1) * NMT_NODE_SIZE] for i in range(w)]
    return row_roots, col_roots


def merkle_root(items: list[bytes]) -> bytes:
    lib = _load()
    if items:
        sizes = {len(i) for i in items}
        if len(sizes) != 1:
            raise ValueError("merkle_root requires equal-size items")
        item_size = sizes.pop()
    else:
        item_size = 0
    out = ctypes.create_string_buffer(32)
    lib.merkle_root(b"".join(items), len(items), item_size, out)
    return out.raw


def leo_decode(cells: np.ndarray, present: np.ndarray) -> np.ndarray:
    """Single-axis Leopard erasure decode: (2k, B) cells + (2k,) bool
    presence -> repaired (2k, B). The native analogue of
    ops/gf256.leopard_decode (klauspost Leopard decode role)."""
    lib = _load()
    n, size = cells.shape
    k = n // 2
    if int(np.count_nonzero(present)) < k:
        raise ValueError("not enough shards to decode")
    buf = ctypes.create_string_buffer(np.ascontiguousarray(cells).tobytes(), n * size)
    lib.leo_decode(
        k, size, buf, np.ascontiguousarray(present, dtype=np.uint8).tobytes()
    )
    return np.frombuffer(buf.raw, dtype=np.uint8).reshape(n, size).copy()


def eds_repair(eds: np.ndarray, present: np.ndarray) -> np.ndarray:
    """Repair a (2k, 2k, B) EDS given a (2k, 2k) bool presence mask —
    the native CPU rsmt2d.Repair baseline (BASELINE config 4). Raises
    da.repair.UnrepairableError when the pattern is not decodable (the
    same contract as the host and TPU implementations)."""
    lib = _load()
    w = eds.shape[0]
    size = eds.shape[2]
    buf = ctypes.create_string_buffer(
        np.ascontiguousarray(eds).tobytes(), w * w * size
    )
    mask = ctypes.create_string_buffer(
        np.ascontiguousarray(present, dtype=np.uint8).tobytes(), w * w
    )
    rc = lib.eds_repair(w // 2, size, buf, mask)
    if rc != 0:
        from celestia_tpu.da.repair import UnrepairableError

        raise UnrepairableError(
            "impossible to recover: erasure pattern not decodable"
        )
    return np.frombuffer(buf.raw, dtype=np.uint8).reshape(w, w, size).copy()


def extend_and_root_native(shares: np.ndarray):
    """Full native ExtendBlock: (k,k,512) -> (eds, row_roots, col_roots, dah)."""
    eds = eds_extend(shares)
    rows, cols = eds_nmt_roots(eds)
    dah = merkle_root(rows + cols)
    return eds, rows, cols, dah
