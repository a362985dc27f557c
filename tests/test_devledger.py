"""Device runtime ledger (ADR-025, specs/observability.md §Device
runtime ledger).

Covers the compile/retrace watchdog's set arithmetic (warmup compiles,
steady-state retraces, strict raise BEFORE the builder body,
lru-eviction-rebuild-is-not-a-retrace, `key_extra` ambient state), the
unified HBM ledger (weakref owner lifecycle, summed registrations,
broken-owner isolation, the callbacks-run-unlocked contract), the
busy-ratio timeline (integration, clamp, window trim — all on injected
clocks), the publish/debug_doc export surfaces, runtime provenance, and
the PagedEdsCache churn hammer that pins gauge/ground-truth parity
through demote, fault-in, eviction, invalidation, and the
everything-pinned defer path the early-return bug left stale."""

import functools
import gc

import jax
import jax.numpy as jnp
import pytest

from celestia_tpu import da, devledger
from celestia_tpu.node.eds_cache import PagedEdsCache
from celestia_tpu.telemetry import Registry, metrics
from celestia_tpu.testutil.chaosnet import chain_shares


# ---------------------------------------------------------------------- #
# compile/retrace watchdog


class TestWatchdog:
    def test_warmup_builds_are_compiles_not_retraces(self):
        led = devledger.DeviceLedger()
        built = []

        @functools.lru_cache(maxsize=None)
        @led.instrument_builder("t.entry")
        def build(k):
            built.append(k)
            return lambda: k

        assert build(2)() == 2
        assert build(4)() == 4
        assert built == [2, 4]
        assert led.retrace_count() == 0
        assert not led.warm

    def test_fresh_key_after_warmup_is_a_retrace_event(self):
        led = devledger.DeviceLedger()
        led.note_build("t.entry", "(2,)")
        led.end_warmup()
        assert led.note_build("t.entry", "(8,)") is True
        events = led.retraces()
        assert len(events) == 1
        assert events[0]["entry"] == "t.entry"
        assert events[0]["key"] == "(8,)"

    def test_known_key_after_warmup_is_not_a_retrace(self):
        led = devledger.DeviceLedger()
        led.note_build("t.entry", "(2,)")
        led.end_warmup()
        assert led.note_build("t.entry", "(2,)") is False
        assert led.retrace_count() == 0

    def test_first_key_on_a_new_entry_is_never_a_retrace(self):
        """A lazily-constructed subsystem compiling its first entry
        post-warmup is a cold compile, not geometry churn."""
        led = devledger.DeviceLedger()
        led.end_warmup()
        assert led.note_build("t.late", "(2,)") is False
        assert led.retrace_count() == 0

    def test_strict_raises_before_the_builder_body_runs(self):
        led = devledger.DeviceLedger()
        built = []

        @functools.lru_cache(maxsize=None)
        @led.instrument_builder("t.entry")
        def build(k):
            built.append(k)
            return lambda: k

        build(2)
        led.end_warmup()
        with led.strict_retraces():
            with pytest.raises(devledger.RetraceError, match="t.entry"):
                build(16)
        # the raise preceded the build, so the lru never adopted key 16
        assert built == [2]

    def test_lru_evicted_key_rebuilt_is_a_compile_not_a_retrace(self):
        led = devledger.DeviceLedger()
        built = []

        @functools.lru_cache(maxsize=1)
        @led.instrument_builder("t.evict")
        def build(k):
            built.append(k)
            return lambda: k

        build(1)
        build(2)  # evicts key 1 from the lru
        led.end_warmup()
        build(1)  # lru miss -> builder reruns, but the KEY is known
        assert built == [1, 2, 1]
        assert led.retrace_count() == 0

    def test_key_extra_makes_ambient_state_part_of_the_key(self):
        """A mesh flip the args don't carry must read as a distinct
        key — and therefore as a retrace when it happens after warmup."""
        led = devledger.DeviceLedger()
        mesh = {"shape": (8,)}

        @led.instrument_builder("t.mesh", key_extra=lambda: mesh["shape"])
        def build(k):
            return lambda: k

        build(2)
        led.end_warmup()
        build(2)  # same args, same mesh: known key
        assert led.retrace_count() == 0
        mesh["shape"] = (4, 2)
        build(2)  # same args, flipped mesh: fresh key
        assert led.retrace_count() == 1

    def test_begin_warmup_clears_retraces_but_keeps_seen_keys(self):
        led = devledger.DeviceLedger()
        led.note_build("t.entry", "(2,)")
        led.end_warmup()
        led.note_build("t.entry", "(4,)")
        assert led.retrace_count() == 1
        led.begin_warmup()
        assert led.retrace_count() == 0
        assert not led.warm
        led.end_warmup()
        # (4,) was adopted during the previous phase: still known
        assert led.note_build("t.entry", "(4,)") is False
        assert led.note_build("t.entry", "(8,)") is True

    def test_builder_returning_tuple_wraps_only_the_callables(self):
        led = devledger.DeviceLedger()

        @led.instrument_builder("t.tuple")
        def build(k):
            return (lambda: k, {"meta": k}, [lambda: -k])

        fn, meta, inner = build(3)
        assert fn() == 3 and meta == {"meta": 3}
        # list returns wrap elementwise too
        lst = build(5)[2]
        assert lst[0]() == -5

    def test_compile_counter_and_ms_histogram_land_in_telemetry(self):
        led = devledger.DeviceLedger()
        entry = "t.metrics.compile"

        @led.instrument_builder(entry)
        def build(k):
            return lambda: k

        before = metrics.get_counter("xla_compile_total", entry=entry)
        build(2)()  # the FIRST CALL is the timed compile
        assert metrics.get_counter(
            "xla_compile_total", entry=entry) == before + 1
        hist = metrics.get_timing("xla_compile_ms", entry=entry)
        assert hist is not None and hist.count >= 1

    def test_retrace_counter_lands_in_telemetry(self):
        led = devledger.DeviceLedger()
        entry = "t.metrics.retrace"
        led.note_build(entry, "(2,)")
        led.end_warmup()
        before = metrics.get_counter("xla_retrace_total", entry=entry)
        led.note_build(entry, "(4,)")
        assert metrics.get_counter(
            "xla_retrace_total", entry=entry) == before + 1

    def test_reset_watchdog_forgets_everything(self):
        led = devledger.DeviceLedger()
        led.note_build("t.entry", "(2,)")
        led.end_warmup()
        led.note_build("t.entry", "(4,)")
        led.reset_watchdog()
        assert led.retrace_count() == 0 and not led.warm
        led.end_warmup()
        # the entry is forgotten: its next key is a first, not a retrace
        assert led.note_build("t.entry", "(8,)") is False


# ---------------------------------------------------------------------- #
# unified HBM ledger


class TestCompileCacheCounters:
    @pytest.mark.parametrize("entry,label", [("ops.fused", "ops.fused"),
                                             (None, "other")])
    def test_lookups_and_hits_attributed(self, entry, label):
        """jax's cache events land on the in-flight entry, or on
        `other` outside an instrumented builder."""
        from jax import monitoring

        devledger.install_monitoring()
        devledger.install_monitoring()  # idempotent: one listener
        misses = f'xla_compile_cache_miss_total{{entry="{label}"}}'
        hits = f'xla_compile_cache_hit_total{{entry="{label}"}}'
        before = (metrics.counters.get(misses, 0),
                  metrics.counters.get(hits, 0))
        devledger._compiling.entry = entry
        try:
            for _ in range(2):
                monitoring.record_event("/jax/compilation_cache/cache_misses")
            monitoring.record_event("/jax/compilation_cache/cache_hits")
            monitoring.record_event(  # not a hit or a miss
                "/jax/compilation_cache/compile_requests_use_cache")
        finally:
            devledger._compiling.entry = None
        assert metrics.counters.get(misses, 0) - before[0] == 2
        assert metrics.counters.get(hits, 0) - before[1] == 1


class _Owner:
    def __init__(self, n):
        self.n = n

    def device_bytes(self):
        return self.n


class TestByteLedger:
    def test_bound_method_owner_is_dropped_after_collection(self):
        led = devledger.DeviceLedger()
        owner = _Owner(4096)
        led.register_owner("t.cache", owner.device_bytes)
        assert led.snapshot()["owners"]["t.cache"] == 4096
        del owner
        gc.collect()
        snap = led.snapshot()
        assert "t.cache" not in snap["owners"]
        # the dead ref is pruned from the list too, not just skipped
        assert "t.cache" not in led.owner_names()

    def test_plain_callable_is_held_until_unregistered(self):
        led = devledger.DeviceLedger()
        led.register_owner("t.flat", lambda: 128)
        gc.collect()
        assert led.snapshot()["owners"]["t.flat"] == 128
        assert led.unregister_owner("t.flat") == 1
        assert "t.flat" not in led.snapshot()["owners"]

    def test_registrations_under_one_name_sum(self):
        led = devledger.DeviceLedger()
        led.register_owner("t.pool", lambda: 100)
        led.register_owner("t.pool", lambda: 28)
        assert led.snapshot()["owners"]["t.pool"] == 128
        assert led.unregister_owner("t.pool") == 2

    def test_broken_owner_reads_zero_and_does_not_break_the_audit(self):
        led = devledger.DeviceLedger()
        led.register_owner("t.broken", lambda: 1 / 0)
        led.register_owner("t.fine", lambda: 64)
        snap = led.snapshot()
        assert snap["owners"]["t.broken"] == 0
        assert snap["owners"]["t.fine"] == 64

    def test_unattributed_is_the_clamped_live_minus_attributed(self):
        led = devledger.DeviceLedger()
        hoard = jnp.ones((1024 * 1024,), jnp.uint8)
        before = led.snapshot()
        assert before["unattributed_bytes"] >= hoard.nbytes
        led.register_owner("t.hoard", lambda: int(hoard.nbytes))
        after = led.snapshot()
        assert after["owners"]["t.hoard"] == hoard.nbytes
        assert (after["unattributed_bytes"]
                <= before["unattributed_bytes"] - hoard.nbytes + 1024)
        # over-claiming owners clamp at zero, never negative
        led.register_owner("t.liar", lambda: 1 << 60)
        assert led.snapshot()["unattributed_bytes"] == 0

    def test_snapshot_runs_callbacks_with_the_ledger_lock_dropped(self):
        """The leaf-lock contract (specs/serving.md): owner callbacks
        take their subsystem's own locks, so running them under
        `devledger._lock` would invert the declared order. A callback
        that can take the ledger lock proves it was not held."""
        led = devledger.DeviceLedger()
        observed = []

        def cb():
            got = led._lock.acquire(blocking=False)
            if got:
                led._lock.release()
            observed.append(got)
            return 32

        led.register_owner("t.probe", cb)
        led.snapshot()
        assert observed == [True]


# ---------------------------------------------------------------------- #
# busy timeline


class TestBusyTimeline:
    def test_idle_reads_zero(self):
        led = devledger.DeviceLedger(busy_window_s=10.0)
        assert led.busy_ratio(now=100.0) == 0.0

    def test_integrates_exec_durations_over_the_window(self):
        led = devledger.DeviceLedger(busy_window_s=10.0)
        led.note_busy(2.5, now=101.0)
        led.note_busy(2.5, now=104.0)
        assert led.busy_ratio(now=104.0) == pytest.approx(0.5)

    def test_oversubscription_clamps_at_one(self):
        led = devledger.DeviceLedger(busy_window_s=5.0)
        led.note_busy(50.0, now=10.0)
        assert led.busy_ratio(now=10.0) == 1.0

    def test_samples_age_out_of_the_window(self):
        led = devledger.DeviceLedger(busy_window_s=5.0)
        led.note_busy(2.0, now=10.0)
        assert led.busy_ratio(now=10.0) == pytest.approx(0.4)
        assert led.busy_ratio(now=16.0) == 0.0

    def test_negative_durations_are_floored(self):
        led = devledger.DeviceLedger(busy_window_s=5.0)
        led.note_busy(-3.0, now=10.0)
        assert led.busy_ratio(now=10.0) == 0.0


# ---------------------------------------------------------------------- #
# export surfaces


class TestExportSurfaces:
    def test_publish_exports_every_gauge_family(self):
        led = devledger.DeviceLedger(busy_window_s=10.0)
        led.register_owner("t.owner", lambda: 2048)
        led.note_busy(5.0, now=50.0)
        reg = Registry()
        snap = led.publish(reg)
        assert reg.get_gauge("device_ledger_bytes", owner="t.owner") == 2048.0
        assert (reg.get_gauge("device_ledger_unattributed_bytes")
                == float(snap["unattributed_bytes"]))
        assert (reg.get_gauge("device_ledger_live_bytes")
                == float(snap["live_bytes"]))
        assert reg.get_gauge("device_busy_ratio") is not None

    def test_debug_doc_shape_and_retrace_ring(self):
        led = devledger.DeviceLedger()
        led.note_build("t.doc", "(2,)")
        led.end_warmup()
        for n in range(40):
            led.note_build("t.doc", f"({n + 10},)")
        doc = led.debug_doc()
        assert set(doc) == {"compile", "ledger", "busy_ratio", "provenance"}
        assert doc["compile"]["warm"] is True
        assert doc["compile"]["entries"]["t.doc"]["keys"] == 41
        # the doc carries the newest 32 only; the full count stays queryable
        assert len(doc["compile"]["retraces"]) == 32
        assert doc["compile"]["retraces"][-1]["key"] == "(49,)"
        assert led.retrace_count() == 40
        assert isinstance(doc["ledger"]["unattributed_bytes"], int)

    def test_runtime_provenance_carries_host_and_jax_identity(self):
        prov = devledger.runtime_provenance()
        for key in ("python", "machine", "cpus", "host_fingerprint",
                    "jax", "jaxlib", "backend", "n_devices"):
            assert prov.get(key) not in (None, ""), key
        # computed once per process: identical on re-query
        assert devledger.runtime_provenance() == prov


# ---------------------------------------------------------------------- #
# PagedEdsCache churn hammer: gauge/ground-truth parity


def _square(k=4, height=1):
    eds = da.extend_shares(chain_shares(k, height))
    dev = da.ExtendedDataSquare.from_device(
        jax.device_put(jnp.asarray(eds.data)), eds.original_width)
    return eds, dev


class TestPagedCacheGaugeParity:
    """The gauge-drift regression: `eds_cache_device_bytes` must equal
    the cache's actual resident-page bytes after EVERY mutation — the
    everything-pinned eviction defer path used to return before the
    publish, leaving the gauge stale until an unrelated mutation."""

    def _assert_parity(self, cache):
        truth = cache.device_bytes()
        assert metrics.get_gauge("eds_cache_device_bytes") == float(truth)
        with cache._cond:
            assert truth == sum(p.nbytes for p in cache._pages
                                if p.dev is not None)

    def test_churn_hammer_keeps_gauge_exact(self):
        eds, _ = _square()
        page_bytes = 2 * eds.data.shape[1] * eds.data.shape[2]
        cache = PagedEdsCache(rows_per_page=2,
                              device_byte_budget=page_bytes,
                              max_heights=2)
        for round_ in range(3):
            for h in range(1, 4):
                _, dev = _square(4, h)
                cache.put(h, dev)  # height eviction churn (max 2)
                self._assert_parity(cache)
            for h in list(cache._entries):
                paged = cache.get(h)
                for i in range(0, 8, 3):
                    paged.row(i)  # demote + fault-in churn (1-page budget)
                    self._assert_parity(cache)
            victim = next(iter(cache._entries))
            cache.invalidate(victim)
            self._assert_parity(cache)

    def test_everything_pinned_defer_still_publishes(self):
        """Pin every height, then force an over-limit put: eviction
        must defer (no pinned victim) AND the gauge must still be
        refreshed — the early-return left it stale."""
        eds, _ = _square()
        cache = PagedEdsCache(rows_per_page=2, max_heights=2)
        _, d1 = _square(4, 1)
        _, d2 = _square(4, 2)
        cache.put(1, d1)
        cache.put(2, d2)
        with cache.pinned(1), cache.pinned(2):
            # pre-pin the incoming height the way a concurrent reader
            # that won the lock between insert and evict would — with
            # every height borrowed, eviction has no victim and defers
            with cache._cond:
                cache._height_pins[3] += 1
            metrics.set_gauge("eds_cache_device_bytes", -1.0)  # go stale
            _, d3 = _square(4, 3)
            cache.put(3, d3)
            assert len(cache._entries) == 3  # deferred, not evicted
            self._assert_parity(cache)
            with cache._cond:
                cache._height_pins[3] -= 1
        # pins dropped: the next mutation completes the deferred evictions
        _, d4 = _square(4, 4)
        cache.put(4, d4)
        assert len(cache._entries) <= 2
        self._assert_parity(cache)

    def test_pin_hit_path_publishes_fresh_pin_count(self):
        eds, _ = _square()
        cache = PagedEdsCache(rows_per_page=2)
        _, dev = _square(4, 1)
        cache.put(1, dev)
        paged = cache.get(1)
        paged.row(0)  # page 0 touched once
        metrics.set_gauge("eds_cache_pin_count", -1.0)  # go stale
        # a DIFFERENT row of the same resident page: bypasses the row
        # memo and takes the _pin_resident hit path
        paged.row(1)
        assert metrics.get_gauge("eds_cache_pin_count") >= 0.0
        self._assert_parity(cache)

    def test_ledger_audit_reconciles_the_cache_owner(self):
        eds, _ = _square()
        cache = PagedEdsCache(rows_per_page=2)
        _, dev = _square(4, 1)
        cache.put(1, dev)
        cache.get(1).row(0)
        led = devledger.DeviceLedger()
        led.register_owner("eds_cache_paged", cache.device_bytes)
        snap = led.snapshot()
        assert snap["owners"]["eds_cache_paged"] == cache.device_bytes()
        assert snap["live_bytes"] >= snap["owners"]["eds_cache_paged"]
