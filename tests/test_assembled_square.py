"""Device square assembly from the blob arena (extend_tpu._assemble_square)
against the host-built square, byte for byte, and its DAH against the
host DAH, with the blobs placed in the arena where the row arithmetic
is tightest: at offsets no slot rule would give, and ending at the
arena's last byte, so a cell's second row lies past the arena. The
structural test keeps the assembly free of loops and of gathers
narrower than a row, the two forms that cost the TPU a per-byte or
per-cell program."""

import functools
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from celestia_tpu import blob as blob_pkg
from celestia_tpu import da
from celestia_tpu import namespace as ns
from celestia_tpu import square as square_pkg
from celestia_tpu.app import App
from celestia_tpu.appconsts import (
    CONTINUATION_SPARSE_SHARE_CONTENT_SIZE as CONT,
    FIRST_SPARSE_SHARE_CONTENT_SIZE as FIRST,
    NAMESPACE_SIZE,
    SHARE_SIZE,
)
from celestia_tpu.crypto import PrivateKey
from celestia_tpu.ops import extend_tpu
from celestia_tpu.ops.blob_pool import blob_key
from celestia_tpu.shares import to_bytes
from celestia_tpu.shares.splitters import sparse_shares_needed
from celestia_tpu.tx import Fee, sign_tx
from celestia_tpu.x.blob.types import estimate_gas, new_msg_pay_for_blobs

# blob sizes per square size: the share boundaries (1, 477-479, 960,
# 961, FIRST + n·CONT ± 1), single-share blobs, and blobs long enough
# to push the square to k
SIZES = {
    4: (1, 477, 478, 479),
    8: (960, 961, FIRST + 5 * CONT - 1, FIRST + 5 * CONT,
        FIRST + 5 * CONT + 1, 1),
    16: (1, FIRST + 30 * CONT - 1, FIRST + 30 * CONT,
         FIRST + 30 * CONT + 1, 477, 961),
    32: (FIRST + 150 * CONT, FIRST + 150 * CONT + 1, 40_000, 1, 479),
}


def _blob_txs(sizes, seed):
    key = PrivateKey.from_secret(b"assemble-signer")
    addr = key.bech32_address()
    rng = np.random.default_rng(seed)
    txs = []
    for i, size in enumerate(sizes):
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        b = blob_pkg.new_blob(ns.new_v0(b"asm" + i.to_bytes(4, "big")),
                              data, 0)
        gas = estimate_gas([size])
        tx = sign_tx(key, [new_msg_pay_for_blobs(addr, b)], "asm-1", 0, i,
                     Fee(amount=gas, gas_limit=gas))
        txs.append(blob_pkg.marshal_blob_tx(tx.marshal(), [b]))
    return txs


class _Pool:
    """The arena as App._assemble_pack reads it: offsets by blob key."""

    def __init__(self, offsets):
        self.offsets = offsets

    def offset_of(self, key):
        return self.offsets.get(key)


def _place(layout):
    """Arena bytes and offsets for the laid-out blobs: placed in reverse
    square order at offsets off any 512-byte or 4 KB boundary, the
    square's first blob last, ending at the arena's final byte with its
    last cell's payload inside the arena's last, partial row. The
    smallest blob between the first and the last stays out of the
    arena, so its cells and the padding around it are host cells
    between resident blobs."""
    blobs = [blob for _start, blob in layout]
    middle = range(1, len(blobs) - 1)
    missing = min(middle, key=lambda i: len(blobs[i].data))
    order = [i for i in reversed(range(len(blobs))) if i != missing]
    offsets, chunks, cursor = {}, [], 0
    for n, i in enumerate(order):
        data = blobs[i].data
        if n < len(order) - 1:
            off = cursor + 13 + 517 * n
        else:
            shares = sparse_shares_needed(len(data))
            last = 0 if shares == 1 else FIRST + (shares - 2) * CONT
            row = -(-(cursor + last - 3) // SHARE_SIZE)
            off = row * SHARE_SIZE + 3 - last
        chunks.append((off, data))
        offsets[blob_key(data)] = (off, len(data))
        cursor = off + len(data)
    # junk between the slots: a payload read one byte too far shows
    arena = np.random.default_rng(cursor).integers(0, 256, cursor,
                                                   dtype=np.uint8)
    for off, data in chunks:
        arena[off : off + len(data)] = np.frombuffer(data, np.uint8)
    return arena, offsets


def _device_square(arena, packed, k):
    """_assemble_square on the pack, with padding rows in every block."""
    host_shares, host_pos, host_row, starts, nsh, offs, lens, ns_table = packed
    s, nb, nh = k * k, len(starts), len(host_pos)
    bm = np.zeros((4, nb + 3), np.int32)
    bm[0] = s
    bm[:, :nb] = np.stack([starts, nsh, offs, lens])
    nslen = np.zeros((nb + 3, NAMESPACE_SIZE + 4), np.uint8)
    nslen[:nb, :NAMESPACE_SIZE] = ns_table
    nslen[:nb, NAMESPACE_SIZE:] = np.asarray(lens, ">u4").view(
        np.uint8).reshape(nb, 4)
    hsp = np.full((2, nh + 2), s, np.int32)
    hsp[:, :nh] = np.stack([host_pos, host_row])
    fn = jax.jit(functools.partial(extend_tpu._assemble_square, k=k))
    return np.asarray(fn(jnp.asarray(arena), jnp.asarray(host_shares), bm,
                         hsp, nslen))


@pytest.fixture(scope="module", params=sorted(SIZES))
def case(request):
    k = request.param
    square, _kept, builder = square_pkg.build_ex(
        _blob_txs(SIZES[k], seed=k), 1, 128)
    assert square_pkg.square_size(len(square)) == k
    layout = builder.blob_layout()
    arena, offsets = _place(layout)
    packed = App._assemble_pack(SimpleNamespace(blob_pool=_Pool(offsets)),
                                square, builder, k)
    assert packed is not None
    starts, host_pos = packed[3], packed[1]
    assert len(starts) == len(SIZES[k]) - 1
    assert ((host_pos > starts[0]) & (host_pos < starts[-1])).any()
    assert len(arena) % SHARE_SIZE and len(arena) % SHARE_SIZE <= 3 + CONT
    return k, square, arena, packed


def test_device_square_is_the_host_square(case):
    k, square, arena, packed = case
    host = np.frombuffer(b"".join(to_bytes(square)), np.uint8)
    dev = _device_square(arena, packed, k)
    assert dev.shape == (k, k, SHARE_SIZE)
    bad = np.flatnonzero((dev.reshape(-1, SHARE_SIZE)
                          != host.reshape(-1, SHARE_SIZE)).any(axis=1))
    assert bad.size == 0, f"cells differ from the host square: {bad[:8]}"


def test_assembled_dah_is_the_host_dah(case):
    k, square, arena, packed = case
    host_dah = da.new_data_availability_header(
        da.extend_shares(to_bytes(square)))
    rows, cols = extend_tpu.assembled_roots(jnp.asarray(arena), *packed, k)
    assert [r.tobytes() for r in rows] == host_dah.row_roots
    assert [c.tobytes() for c in cols] == host_dah.column_roots


@pytest.mark.parametrize("n", [8, 1024])
def test_rotate_left_is_a_roll(n):
    rng = np.random.default_rng(n)
    window = rng.integers(0, 256, (64, n), dtype=np.uint8)
    shift = rng.integers(-2 * n, 2 * n, 64).astype(np.int32)
    shift[:4] = (0, n - 1, -1, n)
    got = np.asarray(jax.jit(extend_tpu._rotate_left)(window, shift))
    want = np.stack([np.roll(w, -int(t)) for w, t in zip(window, shift)])
    np.testing.assert_array_equal(got, want)


def _gathers(text):
    """(operand type, index type, slice sizes) of each stablehlo.gather."""
    out = []
    for line in text.splitlines():
        if "stablehlo.gather" not in line:
            continue
        sizes = re.search(r"slice_sizes = array<i64: ([\d, ]+)>", line)
        types = re.search(r":\s*\((tensor<[^>]+>), (tensor<[^>]+>)\)", line)
        out.append((types.group(1), types.group(2),
                    [int(x) for x in sizes.group(1).split(",")]))
    return out


def _dims(tensor_type):
    return [int(x) for x in re.findall(r"(\d+)x", tensor_type)]


def test_assembly_has_no_loop_and_gathers_whole_rows():
    """Lowered on the CPU: the assembled-roots program has no `while`
    the roots-only program lacks, gathers the arena as whole 512-byte
    rows, and every per-cell gather takes whole rows of its table."""
    k, n_arena, s = 16, 100_003, 256
    spec = jax.ShapeDtypeStruct
    args = [spec((n_arena,), jnp.uint8), spec((16, SHARE_SIZE), jnp.uint8),
            spec((4, 8), jnp.int32), spec((2, 16), jnp.int32),
            spec((8, NAMESPACE_SIZE + 4), jnp.uint8)]
    builder = extend_tpu._jitted_assembled_roots.__wrapped__.__wrapped__
    text = builder(k, 16, 8, 16, n_arena).lower(*args).as_text()
    m2 = jnp.asarray(extend_tpu.rs_tpu.encode_bit_matrix(k))
    roots_only = jax.jit(
        lambda sq: extend_tpu._rows_cols_only(sq, m2)
    ).lower(spec((k, k, SHARE_SIZE), jnp.uint8)).as_text()
    assert text.count("stablehlo.while") == roots_only.count("stablehlo.while")

    rows = -(-n_arena // SHARE_SIZE)
    arena_gathers = [g for g in _gathers(text) if "ui8" in g[0]
                     and np.prod(_dims(g[0])) in (n_arena, rows * SHARE_SIZE)]
    assert arena_gathers, "no gather reads the arena"
    for operand, _index, sizes in arena_gathers:
        assert sizes == [1, SHARE_SIZE], (operand, sizes)

    per_cell = [g for g in _gathers(text) if _dims(g[1])[:1] == [s]]
    assert len(per_cell) == 3  # blob table, arena rows, host shares
    for operand, _index, sizes in per_cell:
        assert sizes == [1] * (len(sizes) - 1) + [_dims(operand)[-1]], (
            operand, sizes)
