"""Transfer-path tests (specs/transfers.md).

Pins the two transfer disciplines introduced with the sliced-serving
PR:

1. sliced device→host EDS reads (`da.ExtendedDataSquare.row/col/share`
   on a device-resident square) are byte-identical to the full-fetch
   path across k, including quadrant-boundary and last-axis edges, and
   stay within the DAS transfer budget (one sample ≤ 2 rows of bytes,
   verified by the `transfer_bytes` counter);
2. chunked overlapped bulk transfers (`ops.transfers.device_put_chunked`
   / `device_get_chunked`) round-trip byte-identically for odd shapes
   and chunk counts, with exact byte telemetry, and the chunked repair
   path stays byte-identical under an armed fault injector.

Slicing/transfer parity is coding-independent, so most tests use raw
random squares (cheap at k=128); only the root-parity test needs a valid
namespace-ordered square.
"""

import numpy as np
import pytest

import jax

from celestia_tpu import da, faults
from celestia_tpu.ops import transfers
from celestia_tpu.telemetry import metrics

from test_extend_tpu import rand_square

SHARE = 512
SLICE_SITES = ("eds.row", "eds.col", "eds.share")


def _sliced_d2h_bytes() -> float:
    """Total device→host bytes moved by the sliced-read sites."""
    return sum(
        metrics.get_counter("transfer_bytes", site=s, direction="d2h")
        for s in SLICE_SITES
    )


def _device_square(k: int, seed: int = 0):
    """Random (2k, 2k, 512) square: host truth + device-resident handle.
    Slicing parity does not depend on the erasure coding, so raw random
    bytes keep the big-k cases cheap."""
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 256, size=(2 * k, 2 * k, SHARE), dtype=np.uint8)
    handle = da.ExtendedDataSquare.from_device(jax.device_put(arr), k)
    return arr, handle


class TestSlicedReads:
    """row/col/share on a device-resident EDS vs the host truth."""

    @pytest.mark.parametrize("k", [4, 16, 64, 128])
    def test_row_col_share_parity(self, k):
        arr, handle = _device_square(k, seed=k)
        w = 2 * k
        # edges: first, odd, quadrant boundary (k-1 | k), last
        idxs = sorted({0, 1, k - 1, k, w - 1})
        for i in idxs:
            assert handle.row(i) == [arr[i, j].tobytes() for j in range(w)]
        # sliced reads must not have materialized the full square
        assert handle._data is None
        for j in idxs:
            assert handle.col(j) == [arr[i, j].tobytes() for i in range(w)]
        for r, c in [(0, 0), (0, w - 1), (w - 1, 0), (k, k - 1), (w - 1, w - 1)]:
            assert handle.share(r, c) == arr[r, c].tobytes()
        assert handle._data is None

    def test_share_rides_cached_axis(self):
        """A share on an already-fetched row/col is served from the host
        cache — zero additional interconnect bytes."""
        arr, handle = _device_square(4, seed=7)
        w = 8
        handle.row(3)
        before = _sliced_d2h_bytes()
        assert handle.share(3, 5) == arr[3, 5].tobytes()
        assert _sliced_d2h_bytes() == before  # row-cache hit
        handle.col(2)
        before = _sliced_d2h_bytes()
        assert handle.share(6, 2) == arr[6, 2].tobytes()
        assert _sliced_d2h_bytes() == before  # col-cache hit
        # a cold cell does transfer — exactly one share
        assert handle.share(1, 6) == arr[1, 6].tobytes()
        assert _sliced_d2h_bytes() == before + SHARE

    def test_slice_cache_bounded(self):
        _, handle = _device_square(4, seed=9)
        for i in range(8):
            handle.row(i)
        assert len(handle._slice_cache) <= handle._SLICE_CACHE_AXES

    def test_host_path_unchanged(self):
        """A host-backed square never touches the transfer counters."""
        arr, _ = _device_square(4, seed=11)
        host = da.ExtendedDataSquare(arr, 4)
        before = _sliced_d2h_bytes()
        assert host.row(5) == [arr[5, j].tobytes() for j in range(8)]
        assert host.share(2, 3) == arr[2, 3].tobytes()
        assert _sliced_d2h_bytes() == before

    def test_roots_match_host_path(self):
        """Whole-square consumers on a lazy handle still produce the
        exact host DAH (they materialize once rather than slicing w
        times); needs a valid namespace-ordered square."""
        rng = np.random.default_rng(21)
        eds = da.extend_shares(rand_square(rng, 4))
        lazy = da.ExtendedDataSquare.from_device(jax.device_put(eds.data), 4)
        assert lazy.row_roots() == eds.row_roots()
        assert lazy.col_roots() == eds.col_roots()


class TestDasTransferBudget:
    """Acceptance pin: serving one DAS sample from a device-resident EDS
    moves ≤ 2 rows' worth of bytes over the interconnect (the /sample
    route fetches the sample's row; a share-only probe moves one cell)."""

    def test_sample_within_two_rows(self):
        k = 16
        w = 2 * k
        arr, handle = _device_square(k, seed=33)
        budget = 2 * w * SHARE
        before = _sliced_d2h_bytes()
        i, j = 5, 17
        row_cells = handle.row(i)  # what rpc /sample/<h>/<i>/<j> serves
        delta = _sliced_d2h_bytes() - before
        assert 0 < delta <= budget
        assert row_cells[j] == arr[i, j].tobytes()
        assert handle._data is None  # the 2 MB square never crossed

    def test_single_share_is_one_cell(self):
        _, handle = _device_square(16, seed=34)
        before = _sliced_d2h_bytes()
        handle.share(9, 30)
        assert _sliced_d2h_bytes() - before == SHARE


class TestChunkedTransfers:
    """device_put_chunked / device_get_chunked vs the monolithic path."""

    @pytest.mark.parametrize(
        "shape", [(7, 13, 5), (16, 16, SHARE), (1, SHARE), (5,), (9, 3)]
    )
    @pytest.mark.parametrize("chunks", [None, 1, 2, 4, 100])
    def test_roundtrip_identity(self, shape, chunks):
        rng = np.random.default_rng(hash((shape, chunks)) % 2**32)
        arr = rng.integers(0, 256, size=shape, dtype=np.uint8)
        dev = transfers.device_put_chunked(arr, site="test.up", chunks=chunks)
        assert np.array_equal(np.asarray(dev), arr)
        back = transfers.device_get_chunked(dev, site="test.down", chunks=chunks)
        assert np.array_equal(back, arr)
        assert back.dtype == arr.dtype

    def test_exact_byte_telemetry(self):
        arr = np.arange(6 * 4, dtype=np.uint8).reshape(6, 4)
        up0 = metrics.get_counter("transfer_bytes", site="t.u", direction="h2d")
        dn0 = metrics.get_counter("transfer_bytes", site="t.d", direction="d2h")
        dev = transfers.device_put_chunked(arr, site="t.u", chunks=3)
        transfers.device_get_chunked(dev, site="t.d", chunks=3)
        assert (
            metrics.get_counter("transfer_bytes", site="t.u", direction="h2d")
            - up0
            == arr.nbytes
        )
        assert (
            metrics.get_counter("transfer_bytes", site="t.d", direction="d2h")
            - dn0
            == arr.nbytes
        )
        # dispatch wall is recorded alongside (value is timing-dependent,
        # presence is the contract)
        assert metrics.get_counter("transfer_ms", site="t.u", direction="h2d") > 0

    def test_bounds_partition_exactly(self):
        # callers clamp chunks to [1, n] before _bounds
        for n in (1, 2, 7, 8, 100):
            for c in {1, min(2, n), min(3, n), n}:
                b = transfers._bounds(n, c)
                assert b[0][0] == 0 and b[-1][1] == n
                assert all(b[i][1] == b[i + 1][0] for i in range(len(b) - 1))
                assert all(hi > lo for lo, hi in b)


class TestChunkedRepairUnderFaults:
    """The chunked-upload/download repair path is byte-identical to the
    host reference, including with the device fault injector armed (the
    make bench-transfers acceptance gate)."""

    def test_repair_parity_with_faults_armed(self):
        from celestia_tpu.ops import repair_tpu

        rng = np.random.default_rng(55)
        eds = da.extend_shares(rand_square(rng, 8))
        present = np.ones((16, 16), dtype=bool)
        erase = rng.choice(16 * 16, size=48, replace=False)
        present.reshape(-1)[erase] = False
        src = np.where(present[..., None], eds.data, 0)
        with faults.inject(
            faults.rule("device.repair", "delay", delay_s=0.001), seed=1337
        ):
            got = repair_tpu.repair_tpu(src, present)
        assert np.array_equal(got, eds.data)

    def test_repair_device_resident_input(self):
        from celestia_tpu.ops import repair_tpu

        rng = np.random.default_rng(56)
        eds = da.extend_shares(rand_square(rng, 4))
        present = np.ones((8, 8), dtype=bool)
        present[2, 1:5] = False
        present[6, 3] = False
        src = np.where(present[..., None], eds.data, 0)
        got = repair_tpu.repair_tpu(jax.device_put(src), present)
        assert np.array_equal(got, eds.data)


class TestArenaSemispace:
    """ADR-007 amendment: aligned halves, the stranded tail, the
    active-half gauge, and put_many parity with sequential put()."""

    def _arena(self, capacity):
        from celestia_tpu.ops.blob_pool import DeviceBlobArena

        return DeviceBlobArena(capacity_bytes=capacity)

    def test_halves_aligned_and_tail_documented(self):
        a = self._arena(12288)  # 12 KB: halves of 4 KB, 4 KB stranded
        assert a._half == 4096
        assert a.tail_bytes == 4096
        b = self._arena(16384)  # 8 KB-multiple: nothing stranded
        assert b._half == 8192 and b.tail_bytes == 0

    def test_active_half_gauge_published(self):
        a = self._arena(16384)
        a.put(b"x" * 100)
        assert metrics.gauges.get("blob_arena_active_half_bytes") == float(
            a._half
        )

    def test_put_many_matches_sequential_put(self):
        # sized so the batch fits one half (put/put_many diverge only
        # when a mid-sequence flip evicts a duplicate's first copy —
        # put_many stages each key once per batch by design)
        rng = np.random.default_rng(77)
        datas = [rng.bytes(int(rng.integers(1, 6000))) for _ in range(3)]
        datas.append(datas[0])  # in-batch duplicate
        datas.append(b"z" * 40000)  # oversized: pad exceeds the half
        a, b = self._arena(65536), self._arena(65536)
        keys_seq = [a.put(d) for d in datas]
        keys_many = b.put_many(datas)
        assert keys_many == keys_seq
        assert b._offsets == a._offsets
        assert np.array_equal(np.asarray(b.arena), np.asarray(a.arena))

    def test_put_many_staging_counted(self):
        before = metrics.get_counter(
            "transfer_bytes", site="arena.stage", direction="h2d"
        )
        a = self._arena(32768)
        a.put_many([b"a" * 10, b"b" * 5000])
        moved = (
            metrics.get_counter(
                "transfer_bytes", site="arena.stage", direction="h2d"
            )
            - before
        )
        assert moved == 4096 + 8192  # padded slot sizes
