"""Pallas TPU kernel for the GF(2) bit-matmul Reed-Solomon encode.

The XLA spelling (rs_tpu.rs_encode_rows) materialises the unpacked bit
tensor (8x the input) and the int32 accumulator (32x) in HBM between the
unpack, dot, mask and pack stages — ~0.5 GB of traffic per encode of an
8 MB square. This kernel keeps the whole chain in VMEM per tile:

    load uint8 tile -> unpack to bit-lanes -> MXU int8 matmul against the
    encode bit-matrix -> mask mod 2 -> pack bits to bytes -> store uint8

so HBM sees only the 8 MB in and 8 MB out (plus the 1 MB matrix, resident
across grid steps), and the MXU runs the (8k x 8k) x (8k x TN)
contraction at int8 throughput.

Layout contract (chosen so the *column* encode — the one the EDS quadrant
chain needs twice via transposes — is the native layout):

    encode2d(x2, m2): x2 (k, N) uint8, shard axis leading; lanes N are any
    flattening of (row, byte) positions. Returns (k, N) parity.

FUSED extend+hash (ADR-019): `encode2d_hash` runs the same bit-matmul
and then, while the parity tile is still in VMEM, builds each produced
512-byte cell's NMT leaf message (0x00 ‖ parity-ns ‖ cell, 542 B) and
runs the unrolled SHA-256 schedule from ops/sha256_pallas._sha_core on
it — so the 32-byte leaf digests leave the kernel alongside the parity
bytes and the unpacked bit planes / padded message tensor (~38 MB at
k=128) never exist in HBM. `leaf_digests2d` is the companion kernel for
cells that already exist (Q0, whose namespaces vary per cell). Both
kernels share the pure-jnp tile math (`_encode_math`, `_leaf_digest_math`)
with the eager `*_reference` spellings the CPU parity tests run — the
bytes the tests pin are the bytes the device computes.

Reference provenance: the encode matrix is rs_tpu.encode_bit_matrix (the
GF(2)-expanded Leopard matrix, pkg/appconsts/global_consts.go:92 selects
the Leopard codec); bit-exactness is asserted against the XLA path in
tests/test_extend_tpu.py and tests/test_fused_roots.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from celestia_tpu import devledger
from celestia_tpu import namespace as ns
from celestia_tpu.appconsts import NAMESPACE_SIZE, SHARE_SIZE
from celestia_tpu.ops import rs_tpu
from celestia_tpu.ops.sha256_jax import pad_tail

# Lane-tile width. VMEM per grid step at k=128:
#   x tile (128, TN) 128 KB, bits (1024, TN) 1 MB, m2 1 MB,
#   acc int32 (1024, TN) 4 MB, out (128, TN) 128 KB  ->  ~6.5 MB.
# The fused hash stage adds (ADR-019's budget table):
#   message words u32 (144, 2k) 147 KB at k=128, schedule + 8 state
#   lanes ~300 KB transient, digests out (1, 8, 2k) 8 KB  ->  ~7.0 MB.
_TILE_N = 1024

# Below this square size the (8k, 8k) operands are too small to tile the
# MXU/VPU well; k=16 is the floor where the contraction axis (8k = 128)
# still fills Mosaic's int8 minimum tile of (32, 128) sublanes — lowered
# from 32 so the governance-default neighbourhood k∈{32,64} (and the
# k=16 rung below it) rides the kernel path end to end (ADR-019).
_MIN_K = 16

# NMT leaf message for a PARITY cell: 0x00 ‖ parity namespace ‖ cell.
# Every cell the encode produces is a parity cell (Q1/Q2/Q3), so the
# 30-byte prefix is a kernel constant.
_PARITY_PREFIX = np.concatenate([
    np.array([0], dtype=np.uint8),
    np.frombuffer(ns.PARITY_SHARES_NAMESPACE.bytes, dtype=np.uint8),
])
_LEAF_MSG_LEN = 1 + NAMESPACE_SIZE + SHARE_SIZE  # 542
_LEAF_TAIL = pad_tail(_LEAF_MSG_LEN)  # 34 B: 0x80, zeros, bit-length
_LEAF_WORDS = (_LEAF_MSG_LEN + len(_LEAF_TAIL)) // 4  # 144 = 9 blocks
# namespaces ride to the leaf-hash kernel padded to a lane-friendly width
NS_PAD = 32


def _encode_math(x, m2):
    """The bit-matmul tile math, pure jnp: (k, T) uint8 data + (8k, 8k)
    int8 matrix -> (k, T) uint8 parity. This EXACT body is what both the
    plain and the fused kernel run on their VMEM tiles, and what the
    eager CPU reference spellings execute."""
    k = x.shape[0]
    x = x.astype(jnp.int32)  # (k, T)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (k, 8, x.shape[-1]), 1)
    bits = ((x[:, None, :] >> shifts) & 1).reshape(8 * k, x.shape[-1])
    acc = jax.lax.dot_general(
        m2,
        bits.astype(jnp.int8),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (8k, T)
    pbits = (acc & 1).reshape(k, 8, x.shape[-1])
    # same bit weights as the unpack: shift bit b back to position b
    packed = (pbits << shifts).sum(axis=1)
    return packed.astype(jnp.uint8)


def _const_rows(vals, n_lanes: int) -> jnp.ndarray:
    """(len(vals), n_lanes) uint32 rows, each row the broadcast of one
    Python int. Built from scalars with iota/where: Mosaic refuses a
    kernel that captures an array constant, so message bytes that are
    the same for every cell (parity prefix, SHA tail) are spelled this
    way inside the kernels."""
    row = jax.lax.broadcasted_iota(jnp.int32, (len(vals), n_lanes), 0)
    out = jnp.zeros((len(vals), n_lanes), dtype=jnp.uint32)
    for j, v in enumerate(vals):
        if v:
            out = jnp.where(row == j, np.uint32(v), out)
    return out


def _leaf_digest_math(cells, prefix30):
    """SHA-256 leaf digests of whole cells, entirely in registers/VMEM.

    cells: (k, T) uint8, T a multiple of SHARE_SIZE — nc = T/512 complete
    cells per row. prefix30: (30, nc·k) uint32 byte lanes (0x00 ‖ 29-byte
    namespace per cell). Returns 8 digest-word vectors of shape (nc·k,).

    Lanes are ordered (cell c, row r) -> c·k + r: each cell column is a
    (k, 512) slice transposed to byte-position-major (512, k), so the
    message bytes land as (576, nc·k), fold to (144, 4, nc·k), and the
    big-endian combine is a sublane reduction the VPU vectorizes across
    all cell lanes at once (the sha256_pallas layout contract)."""
    from celestia_tpu.ops.sha256_pallas import _sha_core

    k, t = cells.shape
    nc = t // SHARE_SIZE
    n_lanes = k * nc
    body = jnp.concatenate(
        [
            cells[:, c * SHARE_SIZE:(c + 1) * SHARE_SIZE].astype(jnp.uint32).T
            for c in range(nc)
        ],
        axis=1,
    )  # (512, nc·k)
    tail = _const_rows([int(v) for v in _LEAF_TAIL], n_lanes)
    msg = jnp.concatenate([prefix30, body, tail], axis=0)  # (576, lanes)
    b = msg.reshape(_LEAF_WORDS, 4, n_lanes)
    words = (
        (b[:, 0] << np.uint32(24))
        | (b[:, 1] << np.uint32(16))
        | (b[:, 2] << np.uint32(8))
        | b[:, 3]
    )  # (144, lanes) big-endian, 9 blocks
    return _sha_core(words)


def _parity_prefix(n_lanes: int) -> jnp.ndarray:
    return _const_rows([int(v) for v in _PARITY_PREFIX], n_lanes)


def _ns_prefix(ns_lanes) -> jnp.ndarray:
    """(NS_PAD, nc·k) uint8 lane-major namespaces -> (30, nc·k) uint32
    message-prefix lanes (0x00 ‖ ns)."""
    nsb = ns_lanes[:NAMESPACE_SIZE].astype(jnp.uint32)
    zero = jnp.zeros((1, nsb.shape[1]), dtype=jnp.uint32)
    return jnp.concatenate([zero, nsb], axis=0)


def _store_digests(d_ref, state) -> None:
    for i in range(8):
        d_ref[0, i, :] = state[i]


def _encode_kernel(x_ref, m2_ref, o_ref):
    o_ref[...] = _encode_math(x_ref[...], m2_ref[...])


def _fused_kernel(x_ref, m2_ref, o_ref, d_ref):
    """Encode + leaf-hash in ONE pass: the parity tile never leaves VMEM
    between the pack stage and the SHA rounds. Every produced cell is a
    parity cell, so its namespace is a kernel constant."""
    packed = _encode_math(x_ref[...], m2_ref[...])
    o_ref[...] = packed
    n_lanes = packed.shape[1] // SHARE_SIZE * packed.shape[0]
    _store_digests(d_ref, _leaf_digest_math(packed, _parity_prefix(n_lanes)))


def _leaf_kernel(x_ref, ns_ref, d_ref):
    """Leaf-hash EXISTING cells (Q0) with per-cell namespaces."""
    _store_digests(d_ref, _leaf_digest_math(x_ref[...], _ns_prefix(ns_ref[0])))


# Digests leave the kernels lane-major, one (8, nct·k) block per grid
# step: Mosaic tiles a block's last two dimensions by (8, 128) unless
# they span the whole array, so the grid index rides a leading axis
# and the (tiny) relayout to (k, nc, 8) happens in XLA.
def _digest_spec(pl, k: int, nct: int):
    return pl.BlockSpec((1, 8, nct * k), lambda i: (i, 0, 0))


def _digest_shape(k: int, n: int):
    grid, tile = _grid_tile(n)
    return jax.ShapeDtypeStruct((grid, 8, tile // SHARE_SIZE * k), jnp.uint32)


def _digests_to_cells(d, k: int) -> jnp.ndarray:
    """(grid, 8, nct·k) kernel digests -> (k, nc, 8): lane c·k + r of
    grid step i is cell (r, i·nct + c)."""
    grid, _, lanes = d.shape
    nct = lanes // k
    return (
        d.reshape(grid, 8, nct, k)
        .transpose(3, 0, 2, 1)
        .reshape(k, grid * nct, 8)
    )


def _ns_to_lanes(ns_pad, k: int, tile: int):
    """(k, nc, NS_PAD) padded namespaces -> (grid, NS_PAD, nct·k) in the
    kernel's lane order (inverse of _digests_to_cells' cell mapping)."""
    nct = tile // SHARE_SIZE
    grid = ns_pad.shape[1] // nct
    return (
        ns_pad.reshape(k, grid, nct, NS_PAD)
        .transpose(1, 3, 2, 0)
        .reshape(grid, NS_PAD, nct * k)
    )


def _grid_tile(n: int) -> tuple[int, int]:
    grid = n // _TILE_N if n % _TILE_N == 0 and n >= _TILE_N else 1
    return grid, n // grid


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("rs_pallas.encode2d")
def _encode2d_call(k: int, n: int, interpret: bool):
    from jax.experimental import pallas as pl

    grid, tile = _grid_tile(n)
    return pl.pallas_call(
        _encode_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((k, tile), lambda i: (0, i)),
            pl.BlockSpec((8 * k, 8 * k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((k, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.uint8),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("rs_pallas.fused")
def _fused_call(k: int, n: int, interpret: bool):
    from jax.experimental import pallas as pl

    grid, tile = _grid_tile(n)
    nct = tile // SHARE_SIZE  # cells per row per tile
    return pl.pallas_call(
        _fused_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((k, tile), lambda i: (0, i)),
            pl.BlockSpec((8 * k, 8 * k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, tile), lambda i: (0, i)),
            _digest_spec(pl, k, nct),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), jnp.uint8),
            _digest_shape(k, n),
        ],
        interpret=interpret,
    )


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("rs_pallas.leaf")
def _leaf_call(k: int, n: int, interpret: bool):
    from jax.experimental import pallas as pl

    grid, tile = _grid_tile(n)
    nct = tile // SHARE_SIZE
    return pl.pallas_call(
        _leaf_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((k, tile), lambda i: (0, i)),
            pl.BlockSpec((1, NS_PAD, nct * k), lambda i: (i, 0, 0)),
        ],
        out_specs=_digest_spec(pl, k, nct),
        out_shape=_digest_shape(k, n),
        interpret=interpret,
    )


def supported(k: int, n_lanes: int) -> bool:
    return k >= _MIN_K and n_lanes % 128 == 0


def fused_supported(k: int, n_lanes: int) -> bool:
    """The fused extend+hash stage additionally needs whole cells per
    lane tile (so each grid step hashes complete leaf messages)."""
    return (
        supported(k, n_lanes)
        and n_lanes % SHARE_SIZE == 0
        and _grid_tile(n_lanes)[1] % SHARE_SIZE == 0
    )


def encode2d(x2: jnp.ndarray, m2: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """(k, N) uint8 data shards -> (k, N) parity shards (Leopard GF(2^8))."""
    k, n = x2.shape
    return _encode2d_call(k, n, interpret)(x2, m2.astype(jnp.int8))


def encode2d_hash(x2: jnp.ndarray, m2: jnp.ndarray, interpret: bool = False):
    """Fused encode + NMT leaf hash: (k, N) uint8 data shards ->
    ((k, N) parity shards, (k, N/512, 8) uint32 leaf digest words).

    digests[i, c] = SHA-256(0x00 ‖ parity-ns ‖ parity[i, 512c:512(c+1)])
    — the NMT leaf digest of every produced cell, computed before the
    parity tile ever leaves VMEM (ADR-019)."""
    k, n = x2.shape
    parity, d = _fused_call(k, n, interpret)(x2, m2.astype(jnp.int8))
    return parity, _digests_to_cells(d, k)


def pad_namespaces(ns_cells: jnp.ndarray) -> jnp.ndarray:
    """(k, nc, 29) uint8 per-cell namespaces -> (k, nc, NS_PAD) kernel
    input (zero-padded; the kernel reads only the first 29 lanes)."""
    return jnp.pad(
        ns_cells, ((0, 0), (0, 0), (0, NS_PAD - ns_cells.shape[-1]))
    )


def leaf_digests2d(x2: jnp.ndarray, ns_pad: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """NMT leaf digests of EXISTING cells: (k, N) uint8 cell bytes +
    (k, N/512, NS_PAD) padded namespaces -> (k, N/512, 8) uint32."""
    k, n = x2.shape
    d = _leaf_call(k, n, interpret)(x2, _ns_to_lanes(ns_pad, k, _grid_tile(n)[1]))
    return _digests_to_cells(d, k)


# ------------------------------------------------------------------ #
# Eager CPU reference spellings. pallas interpret mode internally jits,
# and XLA:CPU takes minutes on _sha_core's unrolled straight-line graph
# (see ops/sha256_pallas.sha256_words) — so the parity tests run the
# SAME tile math eagerly, tile-by-tile, exactly as the grid would.


def encode2d_hash_reference(x2, m2, tile=None):
    """Eager spelling of encode2d_hash for CPU parity tests.

    `tile` overrides the kernel's grid tile width (default: the exact
    tiling the device program uses). The math is lane-independent, so
    any whole-cell tile yields byte-identical output; the smoke gate
    passes tile=n to trade per-op dispatch count for width and stay
    inside its time budget."""
    x2 = jnp.asarray(x2)
    m2i = jnp.asarray(m2).astype(jnp.int8)
    k, n = x2.shape
    if tile is None:
        grid, tile = _grid_tile(n)
    else:
        assert n % tile == 0 and tile % SHARE_SIZE == 0
        grid = n // tile
    parity, digests = [], []
    for i in range(grid):
        xt = x2[:, i * tile:(i + 1) * tile]
        p = _encode_math(xt, m2i)
        parity.append(np.asarray(p))
        digests.append(jnp.stack(
            _leaf_digest_math(p, _parity_prefix(k * (tile // SHARE_SIZE)))
        ))
    return (
        np.concatenate(parity, axis=1),
        np.asarray(_digests_to_cells(jnp.stack(digests), k)),
    )


def leaf_digests2d_reference(x2, ns_pad, tile=None):
    """Eager spelling of leaf_digests2d for CPU parity tests (`tile`
    as in encode2d_hash_reference)."""
    x2 = jnp.asarray(x2)
    ns_pad = jnp.asarray(ns_pad)
    k, n = x2.shape
    if tile is None:
        grid, tile = _grid_tile(n)
    else:
        assert n % tile == 0 and tile % SHARE_SIZE == 0
        grid = n // tile
    ns_lanes = _ns_to_lanes(ns_pad, k, tile)
    out = []
    for i in range(grid):
        xt = x2[:, i * tile:(i + 1) * tile]
        out.append(jnp.stack(_leaf_digest_math(xt, _ns_prefix(ns_lanes[i]))))
    return np.asarray(_digests_to_cells(jnp.stack(out), k))


def extend_square(q0: jnp.ndarray, m2: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """(k, k, 512) uint8 -> (2k, 2k, 512) EDS, all-VMEM encode per tile.

    Quadrant chain per rsmt2d (see celestia_tpu.da): Q1 = row-extend Q0,
    Q2 = col-extend Q0, Q3 = row-extend Q2. Column extension contracts
    over the leading (row) axis, which is this kernel's native layout;
    row extension transposes in and out (XLA handles the 8 MB transposes).
    """
    k, _, b = q0.shape
    n = k * b

    def col_encode(q):  # contract over rows: native layout
        return encode2d(q.reshape(k, n), m2, interpret).reshape(k, k, b)

    def row_encode(q):  # contract over cols: transpose to (cols, rows, B)
        qt = jnp.swapaxes(q, 0, 1)
        pt = encode2d(qt.reshape(k, n), m2, interpret).reshape(k, k, b)
        return jnp.swapaxes(pt, 0, 1)

    q1 = row_encode(q0)
    q2 = col_encode(q0)
    q3 = row_encode(q2)
    top = jnp.concatenate([q0, q1], axis=1)
    bottom = jnp.concatenate([q2, q3], axis=1)
    return jnp.concatenate([top, bottom], axis=0)
