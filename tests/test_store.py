"""Block-store suite (celestia_tpu/store, ADR-021, specs/store.md).

Pins the durable third tier's contracts crypto-free on CPU:

  * round-trip: a persisted height reads back byte-identical — every
    page, the served DAH JSON, and the row-tree levels (which must
    seed provers whose proofs are byte-identical to the originals);
  * crash recovery: re-index adopts a damaged directory without ever
    crashing — truncated tails, corrupt pages, duplicate heights,
    garbage files, empty files, and `.tmp` orphans are quarantined
    with the labeled `store_reindex_skipped_total` bump while the
    undamaged neighbors keep serving;
  * read-time refusal: a CRC mismatch raises `IntegrityError` with
    `site="store.read"` and records an SDC detection — torn bytes
    never reach a caller (including through the paged cache);
  * the `store.write` fault site is the rot-on-disk drill: a bitflip
    armed there lands damage the NEXT read must catch;
  * cache integration: `load_from_store` + host-budget spill serve
    every row byte-identical through disk fault-ins.

`make store-smoke` drills the same contracts end to end through the
real node/rpc serving stack; this file pins the layer in isolation.
"""

import os
import shutil

import numpy as np
import pytest

from celestia_tpu import da, faults
from celestia_tpu.integrity import IntegrityError
from celestia_tpu.store import (
    HEADER_SIZE,
    RECORD_HEADER_SIZE,
    BlockStore,
    pack_levels,
    unpack_levels,
)
from celestia_tpu.telemetry import metrics
from celestia_tpu.testutil.chaosnet import chain_shares

CHAOS_SEED = int(os.environ.get("CELESTIA_CHAOS_SEED", "1337"))
K = 4
W = 2 * K


def _block(height: int = 1):
    eds = da.extend_shares(chain_shares(K, height))
    dah = da.new_data_availability_header(eds)
    return eds, dah


def _put(store: BlockStore, height: int = 1, **kw):
    eds, dah = _block(height)
    store.put_eds(height, eds.data, K, dah_doc=dah.to_json(), **kw)
    return eds, dah


class TestRoundTrip:
    def test_pages_read_back_byte_identical(self, tmp_path):
        store = BlockStore(tmp_path)
        eds, _dah = _put(store, 1, rows_per_page=2)
        entry = store.entry(1)
        assert entry is not None and entry.page_count == W // 2
        got = np.concatenate([store.read_page(1, i)[0]
                              for i in range(entry.page_count)])
        assert got.shape == eds.data.shape
        assert np.array_equal(got, eds.data)
        assert store.heights() == [1] and 1 in store and len(store) == 1

    def test_dah_byte_identical(self, tmp_path):
        store = BlockStore(tmp_path)
        _eds, dah = _put(store, 1)
        back = da.DataAvailabilityHeader.from_json(store.read_dah(1))
        assert back.hash() == dah.hash()
        assert store.read_dah(1) == dah.to_json()

    def test_reput_replaces_atomically(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)
        eds2, _dah2 = _put(store, 1)  # same height, fresh bytes
        assert len(store) == 1
        entry = store.entry(1)
        got = np.concatenate([store.read_page(1, i)[0]
                              for i in range(entry.page_count)])
        assert np.array_equal(got, eds2.data)
        assert not list(tmp_path.glob("*.tmp"))

    def test_wrong_width_rejected(self, tmp_path):
        store = BlockStore(tmp_path)
        eds, dah = _block(1)
        with pytest.raises(ValueError):
            store.put_eds(1, eds.data, K + 1, dah_doc=dah.to_json())

    def test_stats_shape(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)
        _put(store, 2)
        store.read_page(1, 0)
        s = store.stats()
        assert s["kind"] == "blockstore"
        assert s["heights"] == 2
        assert (s["height_lo"], s["height_hi"]) == (1, 2)
        assert s["puts"] == 2 and s["page_reads"] == 1
        assert s["bytes"] > 0 and s["write_errors"] == 0


class TestLevelsRoundTrip:
    def test_pack_unpack_identity(self):
        rng = np.random.default_rng(CHAOS_SEED)
        levels = [rng.integers(0, 256, size=(W, n, 90), dtype=np.uint8)
                  for n in (8, 4, 2, 1)]
        back = unpack_levels(pack_levels(levels))
        assert len(back) == len(levels)
        for orig, got in zip(levels, back):
            assert np.array_equal(orig, got)

    def test_stored_levels_seed_byte_identical_provers(self, tmp_path):
        from celestia_tpu.ops import extend_tpu
        from celestia_tpu.proof import NmtRowProver

        store = BlockStore(tmp_path)
        eds, dah = _block(1)
        levels = extend_tpu.eds_row_levels_device(eds.data)
        store.put_eds(1, eds.data, K, dah_doc=dah.to_json(),
                      levels=levels)
        loaded = store.read_levels(1)
        assert loaded is not None and len(loaded) == len(levels)
        for orig, got in zip(levels, loaded):
            assert np.array_equal(np.asarray(orig), got)
        for i in (0, W // 2, W - 1):
            fresh = NmtRowProver.from_node_levels(
                [np.asarray(lvl)[i] for lvl in levels])
            stored = NmtRowProver.from_node_levels(
                [lvl[i] for lvl in loaded])
            assert stored.root() == fresh.root() == dah.row_roots[i]
            p1, p2 = fresh.prove_range(1, 3), stored.prove_range(1, 3)
            assert (p1.start, p1.end, p1.nodes) == (
                p2.start, p2.end, p2.nodes)

    def test_absent_levels_read_as_none(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)  # no levels kwarg
        assert store.read_levels(1) is None


class TestReindexRecovery:
    """A restarted node adopts whatever the crash left behind — damaged
    files are quarantined with a labeled counter bump, NEVER a startup
    crash, and undamaged heights keep serving."""

    def _reindexed(self, root, deep=True):
        fresh = BlockStore(root)
        report = fresh.reindex(deep=deep)
        return fresh, report

    def test_truncated_tail_quarantined(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)
        _put(store, 2)
        before = metrics.get_counter("store_reindex_skipped_total",
                                     reason="truncated")
        entry = store.entry(2)
        with open(entry.path, "r+b") as f:
            f.truncate(entry.page_offset(0) + RECORD_HEADER_SIZE + 4)
        fresh, report = self._reindexed(tmp_path)
        assert 1 in fresh and 2 not in fresh
        assert report["skipped"] == {"truncated": 1}
        assert metrics.get_counter("store_reindex_skipped_total",
                                   reason="truncated") == before + 1

    def test_corrupt_page_quarantined_deep_refused_shallow(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)
        entry = store.entry(1)
        payload_at = entry.page_offset(0) + RECORD_HEADER_SIZE
        with open(entry.path, "r+b") as f:
            f.seek(payload_at)
            byte = f.read(1)
            f.seek(payload_at)
            f.write(bytes([byte[0] ^ 0x01]))
        deep, report = self._reindexed(tmp_path, deep=True)
        assert 1 not in deep and report["skipped"] == {"page_crc": 1}
        # shallow adoption trusts the header; the READ must refuse
        shallow, _ = self._reindexed(tmp_path, deep=False)
        assert 1 in shallow
        sdc0 = metrics.get_counter("sdc_detected_total",
                                   site="store.read")
        corrupt0 = metrics.get_counter("store_read_corrupt_total")
        with pytest.raises(IntegrityError) as exc:
            shallow.read_page(1, 0)
        assert exc.value.site == "store.read"
        assert metrics.get_counter("sdc_detected_total",
                                   site="store.read") == sdc0 + 1
        assert metrics.get_counter("store_read_corrupt_total") \
            == corrupt0 + 1

    def test_duplicate_height_quarantined(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)
        # a second file claiming the same height: first in sorted
        # order wins, the copy is skipped
        shutil.copy(store.entry(1).path, tmp_path / "9.ctps")
        fresh, report = self._reindexed(tmp_path)
        assert fresh.heights() == [1]
        assert report["skipped"] == {"duplicate": 1}

    def test_garbage_empty_and_tmp_orphans(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)
        (tmp_path / "7.ctps").write_bytes(b"not a store file")
        (tmp_path / "8.ctps").write_bytes(b"")
        # a crash mid-put leaves a .tmp orphan: not even scanned
        (tmp_path / "9.ctps.tmp").write_bytes(b"half-written")
        fresh, report = self._reindexed(tmp_path)
        assert fresh.heights() == [1]
        assert report["skipped"] == {"bad_header": 2}

    def test_header_crc_damage_is_bad_header(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)
        with open(store.entry(1).path, "r+b") as f:
            f.seek(8)  # inside the packed header fields
            f.write(b"\xff\xff")
        fresh, report = self._reindexed(tmp_path)
        assert len(fresh) == 0
        assert report["skipped"] == {"bad_header": 1}


class TestWriteDrill:
    def test_store_write_bitflip_caught_at_read(self, tmp_path):
        """The rot-on-disk model: a bitflip at `store.write` mangles a
        page AFTER its CRC was stamped — invisible until the read path
        refuses it."""
        store = BlockStore(tmp_path)
        with faults.inject(
            faults.rule("store.write", "bitflip"), seed=CHAOS_SEED
        ) as inj:
            _put(store, 1)
        assert any(site == "store.write" for _, site, _ in inj.schedule)
        with pytest.raises(IntegrityError) as exc:
            store.read_page(1, 0)
        assert exc.value.site == "store.read"
        # deep re-index quarantines the same damage at startup
        fresh = BlockStore(tmp_path)
        report = fresh.reindex(deep=True)
        assert report["skipped"] == {"page_crc": 1}


class TestCacheIntegration:
    def _device_square(self, eds):
        import jax
        import jax.numpy as jnp

        return da.ExtendedDataSquare.from_device(
            jax.device_put(jnp.asarray(eds.data)), K)

    def _rows_equal(self, paged, eds):
        for i in range(W):
            cells = paged.row(i)
            assert cells == [bytes(eds.data[i, j]) for j in range(W)]

    def test_load_from_store_faults_pages_in(self, tmp_path):
        from celestia_tpu.node.eds_cache import PagedEdsCache

        store = BlockStore(tmp_path)
        eds, _dah = _put(store, 1, rows_per_page=2)
        cache = PagedEdsCache(rows_per_page=2, store=store)
        assert cache.load_from_store(1)
        reads0 = store.stats()["page_reads"]
        self._rows_equal(cache.get(1), eds)
        assert store.stats()["page_reads"] > reads0

    def test_host_budget_spills_then_refaults(self, tmp_path):
        from celestia_tpu.node.eds_cache import PagedEdsCache

        store = BlockStore(tmp_path)
        eds, _dah = _put(store, 1, rows_per_page=2)
        page_bytes = 2 * W * eds.data.shape[2]
        # one-page device budget demotes; one-page host budget spills
        # the persisted host copies back to disk
        cache = PagedEdsCache(rows_per_page=2,
                              device_byte_budget=page_bytes,
                              store=store, host_byte_budget=page_bytes)
        cache.put(1, self._device_square(eds))
        paged = cache.get(1)
        self._rows_equal(paged, eds)
        self._rows_equal(paged, eds)  # second pass re-faults spills
        stats = cache.stats()
        assert stats["page_spills"] > 0
        assert stats["page_store_loads"] > 0

    def test_disk_rot_refused_through_cache(self, tmp_path):
        from celestia_tpu.node.eds_cache import PagedEdsCache

        store = BlockStore(tmp_path)
        _put(store, 1, rows_per_page=2)
        entry = store.entry(1)
        with open(entry.path, "r+b") as f:
            f.seek(entry.page_offset(0) + RECORD_HEADER_SIZE)
            f.write(b"\x00\xff")
        cache = PagedEdsCache(rows_per_page=2, store=store)
        assert cache.load_from_store(1)
        with pytest.raises(IntegrityError):
            cache.get(1).row(0)


class TestFormatConstants:
    def test_header_and_record_sizes_are_pinned(self):
        """specs/store.md documents these offsets; a drive-by change
        here silently orphans every store on disk."""
        assert HEADER_SIZE == 64
        assert RECORD_HEADER_SIZE == 16

    def test_fixed_page_offsets(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1, rows_per_page=2)
        e = store.entry(1)
        assert e.page_base == HEADER_SIZE + e.dah_len + e.levels_len
        for i in range(e.page_count):
            assert e.page_offset(i) == e.page_base + i * (
                RECORD_HEADER_SIZE + e.page_slot)
            assert e.page_rows(i) == 2


class TestWholeFileWrites:
    """`write_durable`, the path of the block record and of every put
    (specs/store.md §Block record) — tmp, fsync, rename, dirsync through
    the store's FsShim, and a `.tmp` that never outlives a failure."""

    def test_durable_sequence_through_the_shim(self, tmp_path):
        from celestia_tpu.store import powercut, write_durable

        rec = powercut.RecordingFs()
        target = tmp_path / "3.json"
        write_durable(target, b'{"height": 3}', fs=rec, height=3)
        assert [(e.kind, e.path or e.dst) for e in rec.trace] == [
            ("open", "3.json.tmp"), ("write", "3.json.tmp"),
            ("fsync", "3.json.tmp"), ("rename", "3.json"),
            ("dirsync", ".")]
        assert target.read_bytes() == b'{"height": 3}'
        assert not (tmp_path / "3.json.tmp").exists()

    def test_non_durable_store_keeps_the_rename_only(self, tmp_path):
        from celestia_tpu.store import powercut, write_durable

        rec = powercut.RecordingFs()
        write_durable(tmp_path / "3.json", b"{}", fs=rec, durable=False)
        assert [e.kind for e in rec.trace] == ["open", "write", "rename"]

    @pytest.mark.parametrize("durable", [True, False])
    def test_sync_counter_counts_each_sync(self, tmp_path, durable):
        from celestia_tpu.store import write_durable

        def syncs():
            return [metrics.get_counter("store_sync_total", kind=kind)
                    for kind in ("fsync", "dirsync")]
        before = syncs()
        write_durable(tmp_path / "3.json", b"{}", durable=durable)
        assert [a - b for a, b in zip(syncs(), before)] == [int(durable)] * 2

    def test_failed_sync_leaves_the_old_file_and_no_tmp(self, tmp_path):
        from celestia_tpu.store import write_durable

        target = tmp_path / "3.json"
        target.write_bytes(b"old")
        with faults.inject(faults.rule("store.fsync", "fsync_fail"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                write_durable(target, b"new")
        assert target.read_bytes() == b"old"
        assert list(tmp_path.iterdir()) == [target]

    def test_put_bytes_counter_is_the_file_size(self, tmp_path):
        store = BlockStore(tmp_path)
        before = metrics.get_counter("store_put_bytes_total")
        _put(store, 1, levels=[np.zeros((W, 1, 90), np.uint8)])
        _put(store, 2)
        sizes = sum(store.entry(h).path.stat().st_size for h in (1, 2))
        assert metrics.get_counter("store_put_bytes_total") - before == sizes
