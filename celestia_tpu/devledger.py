"""Device runtime ledger (ADR-025, specs/observability.md §Device
runtime ledger): who compiled, who owns every device byte, and how busy
the device lane actually is.

ADR-011 names the hot path's defining operational risks — tens-of-
seconds cold compiles, per-process compile-state accumulation, and
geometry-keyed retraces (the per-page-shape gathers of ISSUE 14 are
exactly the page-table-driven compile surface of *Ragged Paged
Attention*) — but nothing WATCHED them at runtime: a production retrace
storm or an unattributed HBM leak was invisible to /metrics, the soak
drift judge, and the scenario verdicts. This module is that watcher,
three planes in one leaf-locked object:

1. **Compile/retrace watchdog.** Every jitted-entry builder in
   ops/{extend_tpu,ragged,rs_pallas,xor_schedule,transfers,blob_pool}
   is wrapped with `instrument_builder(entry)` placed BETWEEN the
   builder's ``functools.lru_cache`` and its body, so the watchdog sees
   exactly the lru misses — one call per distinct shape/dtype/mesh key.
   The returned compiled callable(s) are wrapped so their FIRST
   invocation (where jax actually traces + XLA-compiles) is timed into
   `xla_compile_total{entry}` / the `xla_compile_ms` histogram with a
   trace-id exemplar and an `xla.compile` span. After `end_warmup()`, a
   *new* key on an already-known entry is a **retrace event**:
   `xla_retrace_total{entry}` + a zero-duration `xla.retrace` flight
   annotation, and a `RetraceError` under strict mode (tests, smokes,
   `CELESTIA_STRICT_RETRACE=1`). An lru-evicted key that gets rebuilt
   is a compile but NOT a retrace — the per-entry seen-key set outlives
   the lru cache, mirroring jax's own process-level trace cache.

2. **Unified device-byte ledger.** Every HBM-holding subsystem
   (PagedEdsCache, ResidentEdsCache, DeviceBlobArena, BlockPipeline
   in-flight records) registers an owner with a live-bytes callback at
   construction (weakly, via ``weakref.WeakMethod`` — a collected cache
   unregisters itself). `publish()` exports `device_ledger_bytes{owner}`
   and reconciles the attributed total against ``jax.live_arrays()``:
   the remainder is `device_ledger_unattributed_bytes` — the device-
   side leak detector the RSS gauge can't be, drift-judged by the soak
   scenario (`no_monotone_drift`).

3. **Device-utilization timeline.** The dispatcher owns the device
   stream (ADR-016), so its per-job exec durations fold into a windowed
   `device_busy_ratio` gauge that rides `.ctts` recordings, the
   obs_report dashboard, and the `/debug/device` RPC route.

Lock discipline (specs/serving.md §Lock ordering): ``devledger._lock``
is a LEAF — it is never held across an owner callback, a metric write,
a span emit, or device work. Owner callbacks acquire their subsystem's
own locks (e.g. ``eds_cache._cond``), which rank EARLIER; running them
under the ledger lock would invert the order, so `snapshot()` copies
the owner list under the lock and calls every callback unlocked.

The module stays importable stdlib-only (jax is consulted lazily and
only if something else already imported it), so the stripped crypto-free
environments that import eds_cache/dispatch keep working.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import os
import platform
import sys
import threading
import time
import weakref

from celestia_tpu import tracing
from celestia_tpu import telemetry


# jax.monitoring events of the persistent compilation cache -> counters.
# jax records a miss only when it writes the program to the cache, i.e.
# for programs over jax_persistent_cache_min_compile_time_secs.
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "xla_compile_cache_hit_total",
    "/jax/compilation_cache/cache_misses": "xla_compile_cache_miss_total",
}
# the entry each thread is compiling, read by the one process-wide
# cache-event listener (jax.monitoring listeners cannot be removed, so
# there is one per process, not one per ledger)
_compiling = threading.local()
_listener_installed = False


def install_monitoring() -> None:
    """Count jax persistent-compilation-cache hits and misses (ADR-011's
    `.jax_cache`) via the jax.monitoring event stream, attributed to
    the entry currently compiling, or "other" for a program built
    outside an instrumented builder. Idempotent."""
    global _listener_installed
    from jax import monitoring

    with ledger._lock:
        if _listener_installed:
            return
        _listener_installed = True

    def _listener(event, *args, **kwargs):
        name = _CACHE_EVENTS.get(event)
        if name is not None:
            telemetry.metrics.incr_counter(
                name, entry=getattr(_compiling, "entry", None) or "other")

    monitoring.register_event_listener(_listener)


class RetraceError(RuntimeError):
    """A post-warmup recompile of a known jitted entry under strict
    mode — the geometry churn ADR-011 says must never reach steady
    state."""


def _shape_key(args: tuple, kwargs: dict) -> str:
    """Builder args ARE the shape/dtype/mesh key: every instrumented
    builder is keyed on hashable static config (k, page shape, pad,
    interpret, ...) by its lru_cache, so their repr is the compile
    key."""
    parts = [repr(a) for a in args]
    parts += [f"{k}={v!r}" for k, v in sorted(kwargs.items())]
    return f"({', '.join(parts)})"


def _live_device_bytes() -> int:
    """Total bytes of every live jax array, 0 when jax was never
    imported (stripped environments) — the reconciliation target for
    unattributed-byte accounting."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    try:
        return sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())
    except Exception:  # noqa: BLE001 — accounting must never break serving
        return 0


class DeviceLedger:
    """Process-wide device runtime ledger; see module docstring. All
    three planes share one leaf lock held only around plain-data
    mutation."""

    DEFAULT_BUSY_WINDOW_S = 5.0

    def __init__(self, busy_window_s: float = DEFAULT_BUSY_WINDOW_S):
        self._lock = threading.Lock()
        # -- watchdog state --
        self._seen: dict[str, set] = {}
        self._compiles: collections.Counter = collections.Counter()
        self._retraces: list[dict] = []
        self._warm = False
        self._strict = os.environ.get(
            "CELESTIA_STRICT_RETRACE", "") not in ("", "0")
        # -- byte-ledger state --
        self._owners: list[tuple[str, object]] = []  # (name, weak ref)
        # -- busy-timeline state --
        self.busy_window_s = float(busy_window_s)
        self._busy: collections.deque = collections.deque()  # (t_end, dur)

    # -- compile/retrace watchdog --------------------------------------- #

    def instrument_builder(self, entry: str, key_extra=None):
        """Decorator for a jitted-entry builder, placed BETWEEN the
        builder's ``functools.lru_cache`` and the builder body so the
        instrumented call fires exactly once per distinct key (the lru
        miss). The builder's return value — one compiled callable or a
        tuple/list of them — comes back with each callable wrapped so
        its first invocation is timed as the compile.

        ``key_extra`` appends ambient compile state the args don't
        carry — the mesh-keyed builders pass the active mesh shape, so
        an operator mesh flip shows up as a distinct key (and thus a
        retrace if it happens after warmup)."""

        def deco(builder):
            @functools.wraps(builder)
            def wrapped(*args, **kwargs):
                key = _shape_key(args, kwargs)
                if key_extra is not None:
                    try:
                        key = f"{key}|{key_extra()!r}"
                    except Exception:  # noqa: BLE001
                        pass
                self.note_build(entry, key)  # strict mode raises HERE,
                # before the build, so the lru cache never adopts the key
                out = builder(*args, **kwargs)
                return self._wrap_compiled(entry, key, out)

            return wrapped

        return deco

    def note_build(self, entry: str, key: str) -> bool:
        """Record one builder invocation for (entry, key); returns (and
        under strict mode raises on) whether it was a retrace: the
        entry was known before warmup ended and the key is new."""
        with self._lock:
            seen = self._seen.setdefault(entry, set())
            known = len(seen) > 0
            fresh = key not in seen
            seen.add(key)
            retrace = self._warm and known and fresh
            strict = self._strict
            if retrace:
                self._retraces.append(
                    {"entry": entry, "key": key, "t": time.time()})
        if retrace:
            try:
                telemetry.metrics.incr_counter(
                    "xla_retrace_total", entry=entry)
                now = time.perf_counter()
                # zero-duration flight annotation: /debug/flight shows
                # WHEN the geometry churned relative to the requests
                # around it
                tracing.emit("xla.retrace", now, now, entry=entry, key=key)
            except Exception:  # noqa: BLE001 — telemetry never breaks builds
                pass
            if strict:
                raise RetraceError(
                    f"steady-state retrace on jitted entry {entry!r}: new "
                    f"shape key {key} after warmup (ADR-011: geometry must "
                    f"be stable in steady state)")
        return retrace

    def _wrap_compiled(self, entry: str, key: str, out):
        if callable(out):
            return self._timed_first_call(entry, key, out)
        if isinstance(out, tuple):
            return tuple(
                self._timed_first_call(entry, key, f) if callable(f) else f
                for f in out)
        if isinstance(out, list):
            return [
                self._timed_first_call(entry, key, f) if callable(f) else f
                for f in out]
        return out

    def _timed_first_call(self, entry: str, key: str, fn):
        """Wrap a compiled callable so its first invocation — where the
        trace + XLA compile actually happen — is timed and counted."""
        done = [False]

        def call(*args, **kwargs):
            if done[0]:
                return fn(*args, **kwargs)
            done[0] = True
            return self._timed_compile(entry, key, fn, args, kwargs)

        return call

    def _timed_compile(self, entry: str, key: str, fn, args, kwargs):
        install_monitoring()
        _compiling.entry = entry
        t0 = time.perf_counter()
        sp = tracing.span("xla.compile", entry=entry, key=key)
        try:
            with sp:
                out = fn(*args, **kwargs)
        finally:
            _compiling.entry = None
        wall = time.perf_counter() - t0
        with self._lock:
            self._compiles[entry] += 1
        try:
            telemetry.metrics.incr_counter("xla_compile_total", entry=entry)
            # ms-named family observed in seconds, the rpc_stage_ms
            # convention — the registry renders the _seconds histogram
            telemetry.metrics.observe(
                "xla_compile_ms", wall,
                exemplar=getattr(sp, "trace_id", None), entry=entry)
        except Exception:  # noqa: BLE001
            pass
        return out

    def begin_warmup(self) -> None:
        """Re-enter warmup (a new scenario run / test phase): retraces
        stop being judged and the steady-state event list resets. Seen
        keys are kept — jax's process-level trace cache persists too."""
        with self._lock:
            self._warm = False
            self._retraces.clear()

    def end_warmup(self) -> None:
        """Declare warmup over: from now on a new shape key on a known
        entry is a retrace event."""
        with self._lock:
            self._warm = True

    @property
    def warm(self) -> bool:
        with self._lock:
            return self._warm

    @property
    def strict(self) -> bool:
        with self._lock:
            return self._strict

    @contextlib.contextmanager
    def strict_retraces(self, value: bool = True):
        """Scoped strict mode: retraces raise RetraceError (tests and
        smoke gates)."""
        with self._lock:
            old, self._strict = self._strict, bool(value)
        try:
            yield self
        finally:
            with self._lock:
                self._strict = old

    def retraces(self) -> list[dict]:
        """Steady-state retrace events since the last begin_warmup() —
        the `zero_steadystate_retraces` scenario invariant's input."""
        with self._lock:
            return list(self._retraces)

    def retrace_count(self) -> int:
        with self._lock:
            return len(self._retraces)

    def reset_watchdog(self) -> None:
        """Test helper: forget every entry/key and leave warmup."""
        with self._lock:
            self._seen.clear()
            self._compiles.clear()
            self._retraces.clear()
            self._warm = False

    # -- unified device-byte ledger ------------------------------------- #

    def register_owner(self, name: str, fn) -> str:
        """Register an HBM owner: ``fn() -> int`` returns the owner's
        CURRENT device bytes. Bound methods are held weakly (a collected
        cache drops out of the ledger on the next snapshot); plain
        callables are held strongly until `unregister_owner(name)`.
        Multiple registrations under one name sum into one series."""
        try:
            ref = weakref.WeakMethod(fn)
        except TypeError:
            ref = (lambda f=fn: f)  # strong holder with the ref() shape
        with self._lock:
            self._owners.append((name, ref))
        return name

    def unregister_owner(self, name: str) -> int:
        """Drop every owner registered under `name`; returns how many
        were removed."""
        with self._lock:
            before = len(self._owners)
            self._owners = [o for o in self._owners if o[0] != name]
            return before - len(self._owners)

    def owner_names(self) -> list[str]:
        with self._lock:
            return sorted({name for name, _ in self._owners})

    def snapshot(self) -> dict:
        """One reconciliation pass: per-owner bytes (callbacks run
        UNLOCKED — they take their subsystem's earlier-ranked locks),
        total live jax bytes, and the unattributed remainder."""
        with self._lock:
            owners = list(self._owners)
        per: dict[str, int] = {}
        dead: list[tuple] = []
        for name, ref in owners:
            fn = ref()
            if fn is None:
                dead.append((name, ref))
                continue
            try:
                nbytes = max(0, int(fn()))
            except Exception:  # noqa: BLE001 — one broken owner must not
                nbytes = 0     # take the whole audit down
            per[name] = per.get(name, 0) + nbytes
        if dead:
            with self._lock:
                self._owners = [o for o in self._owners if o not in dead]
        live = _live_device_bytes()
        attributed = sum(per.values())
        return {
            "owners": per,
            "live_bytes": live,
            "attributed_bytes": attributed,
            # jit constants/workspace keep this nonzero — the contract
            # is FLAT in steady state (drift-judged), not zero
            "unattributed_bytes": max(0, live - attributed),
        }

    # -- device-utilization timeline ------------------------------------ #

    def note_busy(self, seconds: float, now: float | None = None) -> None:
        """Fold one device-lane exec duration (dispatcher `_run_job` /
        `_run_batch`) into the busy window."""
        end = time.monotonic() if now is None else now
        with self._lock:
            self._busy.append((end, max(0.0, float(seconds))))
            self._trim_busy_locked(end)

    def busy_ratio(self, now: float | None = None) -> float:
        """Fraction of the trailing window the device lane spent
        executing, clamped to 1.0 (several dispatchers in one process
        can oversubscribe the wall clock)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            self._trim_busy_locked(now)
            total = sum(d for _t, d in self._busy)
        if self.busy_window_s <= 0:
            return 0.0
        return min(1.0, total / self.busy_window_s)

    def _trim_busy_locked(self, now: float) -> None:
        horizon = now - self.busy_window_s
        busy = self._busy
        while busy and busy[0][0] < horizon:
            busy.popleft()

    # -- export surfaces ------------------------------------------------ #

    def publish(self, registry=None) -> dict:
        """Export the gauge plane into `registry` (the process registry
        by default): `device_ledger_bytes{owner}`,
        `device_ledger_unattributed_bytes`, `device_ledger_live_bytes`,
        `device_busy_ratio`. Called from the /metrics route and the
        tsdb scrapers — pull-driven, so nobody scraping costs zero
        cycles. Returns the snapshot it published."""
        reg = registry if registry is not None else telemetry.metrics
        snap = self.snapshot()
        try:
            for name, nbytes in snap["owners"].items():
                reg.set_gauge("device_ledger_bytes", float(nbytes),
                              owner=name)
            reg.set_gauge("device_ledger_unattributed_bytes",
                          float(snap["unattributed_bytes"]))
            reg.set_gauge("device_ledger_live_bytes",
                          float(snap["live_bytes"]))
            reg.set_gauge("device_busy_ratio", self.busy_ratio())
        except Exception:  # noqa: BLE001
            pass
        return snap

    def debug_doc(self) -> dict:
        """The `/debug/device` RPC payload: watchdog state, the byte
        ledger, busy ratio, and runtime provenance."""
        with self._lock:
            entries = {
                entry: {
                    "keys": len(keys),
                    "compiles": int(self._compiles.get(entry, 0)),
                }
                for entry, keys in sorted(self._seen.items())
            }
            retraces = list(self._retraces[-32:])
            warm = self._warm
            strict = self._strict
        return {
            "compile": {
                "warm": warm,
                "strict": strict,
                "entries": entries,
                "retrace_count": len(retraces),
                "retraces": retraces,
            },
            "ledger": self.snapshot(),
            "busy_ratio": self.busy_ratio(),
            "provenance": runtime_provenance(),
        }


@functools.lru_cache(maxsize=1)
def _provenance() -> tuple:
    prov: dict = {
        "python": platform.python_version(),
        "machine": platform.machine(),
        "cpus": os.cpu_count(),
    }
    try:
        from celestia_tpu.ops import _machine_fingerprint

        # the ADR-011 persistent-compile-cache namespace key: same
        # fingerprint = comparable compile/latency series
        prov["host_fingerprint"] = _machine_fingerprint()
    except Exception:  # noqa: BLE001
        pass
    try:
        import jax
        import jaxlib

        prov["jax"] = jax.__version__
        prov["jaxlib"] = jaxlib.__version__
        devices = jax.devices()
        prov["backend"] = devices[0].platform
        prov["device_kind"] = getattr(devices[0], "device_kind", "unknown")
        prov["n_devices"] = len(devices)
    except Exception:  # noqa: BLE001 — stripped env: host fields only
        pass
    return tuple(sorted(prov.items()))


def runtime_provenance() -> dict:
    """Host/runtime identity stamped into bench results, `.ctts`
    recording headers, and scenario reports so longitudinal series are
    comparable across hosts (computed once per process)."""
    return dict(_provenance())


# process-wide singleton (the telemetry.metrics analogue) + module-level
# conveniences the wiring sites use
ledger = DeviceLedger()

instrument_builder = ledger.instrument_builder
note_busy = ledger.note_busy
register_owner = ledger.register_owner
unregister_owner = ledger.unregister_owner
begin_warmup = ledger.begin_warmup
end_warmup = ledger.end_warmup


def publish(registry=None) -> dict:
    return ledger.publish(registry)


def debug_doc() -> dict:
    return ledger.debug_doc()
