"""The produce-k128-durable cell at a size a test run holds (k=8): the
driver runs end to end into a temporary home with every check at 0,
each thing the home can get wrong reads not correct under the check
that names it, set-up refuses a home it cannot measure, and the
reference's reader of the store's files agrees with the program's."""

import os
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.reference import durable as reference_durable  # noqa: E402
from test_bm_faults import small_cell  # noqa: E402

CELL = "produce-k128-durable"
SEED = 2**31 + 2903
FAULT_CHECKS = {"skip_persist": "stored_mismatch", "flip_page": "stored_mismatch",
                "stale_dah": "stored_mismatch", "torn_record": "block_record_mismatch",
                "no_fsync": "sync_shortfall"}


def durable_cell(home):
    cell = small_cell(CELL)
    cell.config["home"] = str(home)
    return cell


def run_cell(home, fault=None) -> dict:
    import jax

    from benchmark import run as run_mod

    return run_mod.execute(durable_cell(home), SEED, 1.0, False,
                           {"platform": "cpu", "kind": "cpu"}, jax.devices()[:1],
                           fault=fault, t_start=time.perf_counter())


def test_sound_run_into_a_temporary_home(tmp_path):
    home = tmp_path / "home"
    res = run_cell(home)
    checks = {name: c["value"] for name, c in res["checks"].items()}
    for name in ("stored_mismatch", "block_record_mismatch", "restart_mismatch",
                 "persist_failures", "host_roots", "sync_shortfall", "dah_mismatch",
                 "retained_eds_mismatch", "host_fallbacks"):
        assert checks.pop(name) == 0, name
    assert all(v == 0 for v in checks.values()), checks
    assert res["correct"] is True and res["attempted"] > 0
    assert not home.exists()  # close() removes the home


@pytest.mark.parametrize("fault", sorted(FAULT_CHECKS))
def test_each_fault_reads_not_correct(tmp_path, fault):
    res = run_cell(tmp_path / "home", fault)
    assert res["correct"] is False
    assert res["checks"][FAULT_CHECKS[fault]]["value"] >= 1, res["checks"]
    assert res["checks"]["host_roots"]["value"] == 0


def _driver():
    from benchmark import harness

    return harness.find_cell(CELL).driver


@pytest.mark.parametrize("schedule, short", [
    # (fsyncs = dirsyncs after each block's return, then at the end): n=3
    ([2, 4, 6], 0),   # record and EDS file synced before each return
    ([1, 3, 5], 2),   # the last EDS file never synced
    ([1, 3, 5, 6], 0),  # one block of lag, the last synced before close
    ([1, 2, 4, 6], 4),  # two blocks of lag at the second and third
    ([1, 2, 3, 3], 12),  # no EDS file synced at all
])
def test_sync_shortfall_holds_the_one_block_lag(schedule, short):
    drv = _driver()
    per_block = [(c, c) for c in schedule[:3]]
    final = (schedule[-1],) * 2
    assert drv.sync_shortfall((0, 0), per_block, final) == short


def test_set_up_refuses_a_page_cache_home(tmp_path, monkeypatch):
    drv = _driver()
    monkeypatch.setattr(drv, "filesystem", lambda path: "tmpfs")
    with pytest.raises(RuntimeError, match="tmpfs"):
        drv.setup(durable_cell(tmp_path / "home"), SEED, None)


def test_set_up_refuses_a_full_disk(tmp_path, monkeypatch):
    drv = _driver()
    usage = shutil.disk_usage(tmp_path)
    monkeypatch.setattr(shutil, "disk_usage",
                        lambda path: usage._replace(free=10**6))
    with pytest.raises(RuntimeError, match="bytes free"):
        drv.setup(durable_cell(tmp_path / "home"), SEED, None)


def test_set_up_refuses_a_program_that_hides_host_hashing(tmp_path, monkeypatch):
    drv = _driver()
    monkeypatch.setattr(drv, "host_roots_counted", lambda: False)
    with pytest.raises(RuntimeError, match="host_nmt_roots_total"):
        drv.setup(durable_cell(tmp_path / "home"), SEED, None)
    assert not (tmp_path / "home").exists()


def test_filesystem_names_the_mount_that_holds_the_path(tmp_path):
    drv = _driver()
    assert drv.filesystem("/proc") == "proc"
    assert drv.filesystem(str(tmp_path)) not in ("", "unknown")


def test_reference_reader_agrees_with_the_store(tmp_path):
    from celestia_tpu import da
    from celestia_tpu.ops import extend_tpu
    from celestia_tpu.store import BlockStore
    from celestia_tpu.testutil.chaosnet import chain_shares

    k = 4
    store = BlockStore(tmp_path)
    eds = da.extend_shares(chain_shares(k, 5))
    dah = da.new_data_availability_header(eds)
    levels = extend_tpu.eds_row_levels_device(eds.data)
    entry = store.put_eds(5, eds.data, k, dah_doc=dah.to_json(), levels=levels,
                          rows_per_page=3)
    read = reference_durable.read_store_file(str(entry.path))
    assert (read["height"], read["k"], read["rows_per_page"]) == (5, k, 3)
    assert read["dah"] == store.read_dah(5)
    assert len(read["pages"]) == entry.page_count
    for i, page in enumerate(read["pages"]):
        assert np.array_equal(page, store.read_page(5, i)[0])
    assert np.array_equal(np.concatenate(read["pages"]), eds.data)
    stored = store.read_levels(5)
    assert len(read["levels"]) == len(stored)
    for got, want in zip(read["levels"], stored):
        assert np.array_equal(got, want)
    # the reference's own NMT levels are the device's
    trees = reference_durable.row_levels(np.asarray(eds.data))
    for n, want in enumerate(stored):
        for i, tree in enumerate(trees):
            assert b"".join(tree[n]) == want[i].tobytes()


def test_reference_reader_refuses_a_flipped_page(tmp_path):
    from celestia_tpu import da
    from celestia_tpu.store import RECORD_HEADER_SIZE, BlockStore
    from celestia_tpu.testutil.chaosnet import chain_shares

    store = BlockStore(tmp_path)
    eds = da.extend_shares(chain_shares(2, 1))
    entry = store.put_eds(1, eds.data, 2,
                          dah_doc=da.new_data_availability_header(eds).to_json())
    with open(entry.path, "r+b") as f:
        f.seek(entry.page_offset(0) + RECORD_HEADER_SIZE)
        f.write(b"\xee")
    with pytest.raises(reference_durable.StoreFileError, match="page 0 crc"):
        reference_durable.read_store_file(str(entry.path))
