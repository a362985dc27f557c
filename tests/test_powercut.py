"""Crash-consistency & disk-fault plane (ADR-026, specs/store.md
§Durability contract, specs/faults.md).

Four surfaces under test:

  * the OS-failure fault kinds (`enospc`, `short_write`, `fsync_fail`)
    and their DiskFault errno semantics — injected failures must be
    indistinguishable from real ones to `except OSError` handlers;
  * the put-abort path: any failure mid-put cleans up its `.tmp`,
    counts `store_put_aborted_total{reason}`, and ENOSPC flips the
    store into STICKY read-only with honest gauge/counter accounting
    and probe-gated recovery;
  * the powercut explorer: a clean sweep over the fixed write path,
    and the red-path regression proving the harness still catches the
    missing-dirsync bug the ADR-026 fix fixed;
  * single-fault recovery as a property: any ONE truncation or
    deletion across a 32-height store never crashes reindex(deep=True)
    and never leaves an unservable height indexed.
"""

from __future__ import annotations

import errno
import os
import pathlib

import pytest

from celestia_tpu import faults
from celestia_tpu.store import SUFFIX, BlockStore
from celestia_tpu.store import powercut
from celestia_tpu.telemetry import metrics

CHAOS_SEED = int(os.environ.get("CELESTIA_CHAOS_SEED", "1337"))


def _put(store: BlockStore, h: int, k: int = 2) -> None:
    store.put_eds(h, powercut._synthetic_eds(k, h), k,
                  dah_doc=powercut._synthetic_dah(h, k))


# --------------------------------------------------------------------- #
# OS-failure fault kinds


class TestDiskFaultKinds:
    def test_enospc_raises_oserror_with_real_errno(self, tmp_path):
        store = BlockStore(tmp_path)
        with faults.inject(faults.rule("store.write", "enospc"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError) as ei:
                _put(store, 1)
        assert ei.value.errno == errno.ENOSPC
        assert isinstance(ei.value, faults.FaultError)

    def test_fsync_fail_raises_eio_and_aborts_durable_put(self, tmp_path):
        store = BlockStore(tmp_path, durable=True)
        with faults.inject(faults.rule("store.fsync", "fsync_fail"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError) as ei:
                _put(store, 1)
        assert ei.value.errno == errno.EIO
        assert store.heights() == []
        assert not list(tmp_path.glob(f"*{SUFFIX}.tmp"))
        # an fsync failure is an I/O error, not disk pressure: the
        # store must NOT latch read-only for it
        assert not store.read_only

    def test_short_write_truncates_and_fails_like_a_torn_write(
            self, tmp_path):
        store = BlockStore(tmp_path)
        before = metrics.get_counter("store_put_aborted_total",
                                     reason="short_write")
        with faults.inject(faults.rule("store.write", "short_write"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                _put(store, 1)
        assert metrics.get_counter("store_put_aborted_total",
                                   reason="short_write") == before + 1
        assert store.heights() == []
        assert not list(tmp_path.glob(f"*{SUFFIX}.tmp"))
        assert not store.read_only


# --------------------------------------------------------------------- #
# the put-abort path + ENOSPC sticky read-only


class TestEnospcDegradation:
    def test_enospc_enters_sticky_read_only(self, tmp_path):
        store = BlockStore(tmp_path)
        _put(store, 1)
        ro0 = metrics.get_counter("store_read_only_total")
        ab0 = metrics.get_counter("store_put_aborted_total",
                                  reason="enospc")
        with faults.inject(faults.rule("store.write", "enospc"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                _put(store, 2)
        assert store.read_only and store.read_only_reason == "enospc"
        assert metrics.get_counter("store_read_only_total") == ro0 + 1
        assert metrics.get_counter("store_put_aborted_total",
                                   reason="enospc") == ab0 + 1
        assert metrics.get_gauge("store_read_only") == 1.0
        assert not list(tmp_path.glob(f"*{SUFFIX}.tmp"))
        # pre-degradation heights keep serving
        store.read_dah(1)
        store.read_page(1, 0)

    def test_read_only_puts_skip_without_firing_write_site(self, tmp_path):
        store = BlockStore(tmp_path, reprobe_interval_s=3600.0)
        with faults.inject(faults.rule("store.write", "enospc"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                _put(store, 1)
        skip0 = metrics.get_counter("store_put_aborted_total",
                                    reason="read_only")
        with faults.inject(faults.rule("store.write", "delay",
                                       delay_s=0.0),
                           seed=CHAOS_SEED) as inj:
            assert store.put_eds(
                2, powercut._synthetic_eds(2, 2), 2,
                dah_doc=powercut._synthetic_dah(2, 2)) is None
        assert not inj.schedule, ("a skipped read-only put must not "
                                  "reach the store.write site")
        assert metrics.get_counter("store_put_aborted_total",
                                   reason="read_only") == skip0 + 1

    def test_degradation_cleans_orphaned_tmp_files(self, tmp_path):
        store = BlockStore(tmp_path)
        orphan = tmp_path / f"999{SUFFIX}.tmp"
        orphan.write_bytes(b"abandoned by a previous crash")
        with faults.inject(faults.rule("store.write", "enospc"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                _put(store, 1)
        assert not orphan.exists()

    def test_reprobe_put_is_the_probe_and_recovers(self, tmp_path):
        store = BlockStore(tmp_path, reprobe_interval_s=0.0)
        with faults.inject(faults.rule("store.write", "enospc"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                _put(store, 1)
        assert store.read_only
        rec0 = metrics.get_counter("store_read_only_recovered_total")
        # space is back: the next put IS the probe, and it wins
        _put(store, 2)
        assert not store.read_only
        assert store.heights() == [2]
        assert metrics.get_counter(
            "store_read_only_recovered_total") == rec0 + 1
        assert metrics.get_gauge("store_read_only") == 0.0

    def test_failed_reprobe_re_enters_and_pushes_the_clock(self, tmp_path):
        store = BlockStore(tmp_path, reprobe_interval_s=0.0)
        ro0 = metrics.get_counter("store_read_only_total")
        with faults.inject(faults.rule("store.write", "enospc", times=2),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                _put(store, 1)
            # still full: the probe put strikes again and re-latches
            with pytest.raises(OSError):
                _put(store, 2)
        assert store.read_only
        # a re-strike is the SAME degradation, not a new one
        assert metrics.get_counter("store_read_only_total") == ro0 + 1

    def test_try_recover_probes_through_the_shim(self, tmp_path):
        store = BlockStore(tmp_path)
        with faults.inject(faults.rule("store.write", "enospc"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                _put(store, 1)
        # pressure still on: the probe write rides the real shim sites,
        # so an armed rule keeps the store read-only
        with faults.inject(faults.rule("store.fsync", "fsync_fail"),
                           seed=CHAOS_SEED):
            assert not store.try_recover()
        assert store.read_only
        assert store.try_recover()
        assert not store.read_only
        assert not (tmp_path / ".writable.probe").exists()
        _put(store, 2)
        assert 2 in store.heights()

    def test_operator_force_is_sticky_until_explicit_recover(
            self, tmp_path):
        store = BlockStore(tmp_path, reprobe_interval_s=0.0)
        store.force_read_only("operator")
        assert store.read_only
        assert store.read_only_reason == "operator"
        # even with a zero reprobe interval, puts never self-probe out
        # of an operator hold
        assert store.put_eds(
            1, powercut._synthetic_eds(2, 1), 2,
            dah_doc=powercut._synthetic_dah(1, 2)) is None
        assert store.read_only
        assert store.try_recover()
        assert not store.read_only

    def test_stats_surface_the_degradation(self, tmp_path):
        store = BlockStore(tmp_path)
        with faults.inject(faults.rule("store.write", "enospc"),
                           seed=CHAOS_SEED):
            with pytest.raises(OSError):
                _put(store, 1)
        s = store.stats()
        assert s["read_only"] is True
        assert s["read_only_reason"] == "enospc"
        assert s["put_aborts"] == 1
        assert s["write_errors"] == 1


# --------------------------------------------------------------------- #
# readiness + SLO wiring


class TestReadinessWiring:
    def test_readyz_names_store_writable(self, tmp_path):
        from celestia_tpu.slo import readiness
        from celestia_tpu.testutil.chaosnet import RpcChaosNode

        node = RpcChaosNode(heights=1, k=4, seed=7,
                            store_dir=str(tmp_path))
        ready, checks = readiness(node)
        m = {c["name"]: c["ok"] for c in checks}
        assert ready and m["store_writable"]
        node.store.force_read_only("operator")
        ready, checks = readiness(node)
        m = {c["name"]: c["ok"] for c in checks}
        assert not ready and not m["store_writable"]
        detail = next(c["detail"] for c in checks
                      if c["name"] == "store_writable")
        assert "operator" in detail

    def test_storeless_node_passes_the_check(self):
        from celestia_tpu.slo import readiness
        from celestia_tpu.testutil.chaosnet import RpcChaosNode

        node = RpcChaosNode(heights=1, k=4, seed=7)
        ready, checks = readiness(node)
        assert ready
        assert any(c["name"] == "store_writable" and c["ok"]
                   for c in checks)

    def test_store_writable_objective_breaches_on_the_counter(self):
        from celestia_tpu.slo import SloEngine, default_objectives
        from celestia_tpu.telemetry import Registry

        r = Registry()
        objs = [o for o in default_objectives()
                if o.name == "store_writable"]
        assert objs, "store_writable missing from the default set"
        eng = SloEngine(objs, registry=r)
        assert eng.evaluate()["ok"]
        r.incr_counter("store_read_only_total")
        assert not eng.evaluate()["ok"]


# --------------------------------------------------------------------- #
# the powercut explorer


class TestPowercutExplorer:
    def test_fixed_write_path_sweeps_clean(self):
        rep = powercut.explore()
        assert rep.effects > 0 and rep.states > rep.effects
        assert rep.ok, rep.violations[:5]

    def test_dirsync_regression_missing_dirsync_loses_acked_heights(self):
        """The ADR-026 bug, kept reproducible: without the parent-dir
        fsync after rename, the `lost` variant of any post-ack cut
        reverts the rename and the acknowledged height VANISHES. The
        explorer must keep catching it, or the clean sweep above
        proves nothing."""
        rep = powercut.explore(no_dirsync=True)
        assert not rep.ok
        kinds = {v.kind for v in rep.violations}
        assert "missing_height" in kinds
        lost = [v for v in rep.violations
                if v.kind == "missing_height" and v.variant == "lost"]
        assert lost, "the loss must surface in the lost-cache variant"

    @staticmethod
    def _block_record_trace(tmp_path, *, no_dirsync: bool):
        """Record a home-backed node committing three blocks through a
        recording FsShim (block records only: no retention), with an ack
        at each produce_block return."""
        import json

        from celestia_tpu.app import App
        from celestia_tpu.node import Node

        node = Node(App(), home=str(tmp_path))
        rec = powercut.RecordingFs(no_dirsync=no_dirsync)
        node.store._fs = rec
        for t in (15.0, 30.0, 45.0):
            block = node.produce_block(t)
            rec.ack_put(block.height,
                        tmp_path / "blocks" / f"{block.height}.json")
        assert [json.loads(e.ack[2])["height"] for e in rec.trace
                if e.kind == "ack"] == [1, 2, 3]
        return rec.trace

    @staticmethod
    def _lost_records(trace) -> list[tuple[int, str, int]]:
        """(cut, variant, height) for every acknowledged block record a
        power cut loses or tears; and any record under its final name
        that does not parse."""
        import json

        from celestia_tpu.node.node import Block

        bad = []
        for cut in range(len(trace) + 1):
            acked = {e.ack[1]: e.ack[2] for e in trace[:cut] if e.kind == "ack"}
            for variant in powercut.VARIANTS:
                state = powercut.materialize(trace, cut, variant)
                for h, want in acked.items():
                    if state.get(f"{h}.json") != want:
                        bad.append((cut, variant, h))
                for name, data in state.items():
                    if name.endswith(".json"):
                        try:
                            Block.from_json(json.loads(data))
                        except ValueError:
                            bad.append((cut, variant, int(name[:-5])))
        return bad

    def test_block_records_survive_every_cut(self, tmp_path):
        """The node's block record under the same contract as a put: at
        every prefix of the trace, in every page-cache variant, each
        height acknowledged by produce_block is there byte-identical,
        and no record under its final name is torn."""
        trace = self._block_record_trace(tmp_path, no_dirsync=False)
        kinds = [e.kind for e in trace if e.kind != "ack"]
        assert kinds == ["open", "write", "fsync", "rename", "dirsync"] * 3
        assert self._lost_records(trace) == []

    def test_block_record_without_dirsync_loses_acked_heights(self,
                                                              tmp_path):
        trace = self._block_record_trace(tmp_path, no_dirsync=True)
        lost = self._lost_records(trace)
        assert lost and {v for _c, v, _h in lost} == {"lost"}

    def test_unfsynced_write_is_volatile_in_the_model(self):
        trace = [
            powercut.Effect(kind="open", path="a"),
            powercut.Effect(kind="write", path="a", data=b"hello"),
        ]
        assert powercut.materialize(trace, 2, "lost") == {}
        trace += [powercut.Effect(kind="fsync", path="a"),
                  powercut.Effect(kind="rename", src="a", dst="b")]
        # fsynced data but un-dirsynced metadata: lost drops the entry
        assert powercut.materialize(trace, 4, "lost") == {}
        trace += [powercut.Effect(kind="dirsync", path=".")]
        assert powercut.materialize(trace, 5, "lost") == {"b": b"hello"}
        assert powercut.materialize(trace, 5, "applied") == {"b": b"hello"}

    def test_torn_variant_never_tears_fsynced_writes(self):
        trace = [
            powercut.Effect(kind="open", path="a"),
            powercut.Effect(kind="write", path="a", data=b"abcdefgh"),
            powercut.Effect(kind="fsync", path="a"),
        ]
        # the write was fsynced before the cut: a power cut cannot
        # tear it (that would model a broken kernel)
        assert powercut.materialize(trace, 3, "torn") == {"a": b"abcdefgh"}
        assert powercut.materialize(trace, 2, "torn") == {"a": b"abcd"}


# --------------------------------------------------------------------- #
# single-fault recovery as a property


class TestSingleFaultReindexProperty:
    HEIGHTS = 32

    @pytest.fixture()
    def grown(self, tmp_path):
        store = BlockStore(tmp_path)
        for h in range(1, self.HEIGHTS + 1):
            _put(store, h)
        return tmp_path, store

    def _assert_recovers(self, root: pathlib.Path, mutated: str):
        """reindex(deep=True) must adopt without raising and every
        height it indexes must fully serve."""
        store = BlockStore(root, durable=False)
        store.reindex(deep=True)
        for h in store.heights():
            entry = store.entry(h)
            store.read_dah(h)
            for i in range(entry.page_count):
                store.read_page(h, i)
        return store

    def test_any_single_deletion_recovers(self, grown):
        root, _ = grown
        for path in sorted(root.glob(f"*{SUFFIX}")):
            original = path.read_bytes()
            path.unlink()
            store = self._assert_recovers(root, path.name)
            assert len(store.heights()) == self.HEIGHTS - 1
            path.write_bytes(original)

    def test_any_single_truncation_recovers(self, grown):
        import random

        root, _ = grown
        rng = random.Random(CHAOS_SEED)
        files = sorted(root.glob(f"*{SUFFIX}"))
        for path in rng.sample(files, 8):
            original = path.read_bytes()
            for frac in (0.0, 0.1, 0.5, 0.999):
                cut = int(len(original) * frac)
                path.write_bytes(original[:cut])
                store = self._assert_recovers(root, path.name)
                # the damaged height is either skipped or (at some
                # cuts) still fully servable — never half-indexed
                assert len(store.heights()) >= self.HEIGHTS - 1
            path.write_bytes(original)
        # pristine store adopts everything again
        store = self._assert_recovers(root, "none")
        assert len(store.heights()) == self.HEIGHTS

    def test_garbage_prefix_is_skipped_not_crashed(self, grown):
        root, _ = grown
        victim = sorted(root.glob(f"*{SUFFIX}"))[0]
        victim.write_bytes(os.urandom(512))
        store = self._assert_recovers(root, victim.name)
        assert len(store.heights()) == self.HEIGHTS - 1


@pytest.mark.slow
class TestCompactCrashSweep:
    def test_deeper_workload_sweeps_clean(self):
        """A wider sweep than the smoke gate: more heights, a second
        compaction wave, and re-puts — every crash point of every
        compact unlink/dirsync still never loses a retained height."""

        def workload(store, rec, *, k=2, heights=8):
            for h in range(1, heights + 1):
                store.put_eds(h, powercut._synthetic_eds(k, h), k,
                              dah_doc=powercut._synthetic_dah(h, k))
                rec.ack_put(h, store.root / f"{h}{SUFFIX}")
            store.compact(0, keep_recent=3)
            for h in range(heights + 1, heights + 3):
                store.put_eds(h, powercut._synthetic_eds(k, h), k,
                              dah_doc=powercut._synthetic_dah(h, k))
                rec.ack_put(h, store.root / f"{h}{SUFFIX}")
            store.compact(0, keep_recent=1)
            store.reindex(deep=True)

        rep = powercut.explore(heights=8, workload=workload)
        assert rep.effects > 60
        assert rep.ok, rep.violations[:8]
