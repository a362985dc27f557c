"""Native C++ runtime byte-parity vs the numpy/hashlib reference path."""

import numpy as np
import pytest

from celestia_tpu import da, native
from celestia_tpu.ops import gf256
from test_extend_tpu import rand_square

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable"
)


class TestNativeParity:
    @pytest.mark.parametrize("k", [1, 2, 8, 32])
    def test_leo_encode(self, k):
        rng = np.random.default_rng(k)
        data = rng.integers(0, 256, size=(k, 96), dtype=np.uint8)
        assert np.array_equal(native.leo_encode(data), gf256.leopard_encode(data))

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_full_pipeline(self, k):
        rng = np.random.default_rng(10 + k)
        sq = rand_square(rng, k)
        eds_h = da.extend_shares(sq)
        dah_h = da.new_data_availability_header(eds_h)
        eds_n, rows, cols, dah = native.extend_and_root_native(sq)
        assert np.array_equal(eds_n, eds_h.data)
        assert rows == eds_h.row_roots()
        assert cols == eds_h.col_roots()
        assert dah == dah_h.hash()

    def test_merkle_root_odd_count(self):
        from celestia_tpu.ops.nmt_host import merkle_root as py_merkle

        items = [bytes([i]) * 90 for i in range(5)]
        assert native.merkle_root(items) == py_merkle(items)


def test_build_key_changes_with_a_source_byte(tmp_path):
    """The library name carries a hash of the committed sources: one
    changed byte means a fresh build, never a stale .so."""
    srcs = []
    for src in native._SOURCES:
        dst = tmp_path / src.name
        dst.write_bytes(src.read_bytes())
        srcs.append(dst)
    key = native.build_key(srcs)
    assert key == native.build_key(native._SOURCES)
    data = bytearray(srcs[1].read_bytes())
    data[len(data) // 2] ^= 1
    srcs[1].write_bytes(bytes(data))
    assert native.build_key(srcs) != key
