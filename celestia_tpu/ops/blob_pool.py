"""Device-resident blob arena — the mempool's blob bytes live in HBM.

The node proposal wall time is dominated by moving the 8 MB square
host→device at PrepareProposal/ProcessProposal time (bench config 8: the
upload alone exceeded the native CPU baseline over the remote device
link of the earlier rounds). But the bulk of a DA square is BLOB bytes, and those bytes are
known long before the proposal: they arrive with the BlobTx at CheckTx.

This module stages them: on mempool admission the node appends each
blob's data into a fixed device arena (async `device_put` + a donated
`dynamic_update_slice` — off the consensus hot path). At proposal time
the device assembles the square itself (ops/extend_tpu.assembled_roots):
only the compact tx/PFB/padding shares, the 34-byte share prefixes, and
int32 offset vectors cross the interconnect — tens of KB instead of MB —
and the extend+NMT pipeline runs fused on the assembled square without
it ever existing host-side.

ref: the reference keeps mempool blobs host-side and re-marshals them
into the square per proposal (pkg/square/builder.go); on a TPU node the
same bytes are already resident where the MXU needs them.
"""

from __future__ import annotations

import functools
import hashlib
import threading

from celestia_tpu import devledger


def blob_key(data: bytes) -> bytes:
    """Identity of pooled blob BYTES (content-addressed, like the CAT
    pool's tx keys): sha256 of the raw blob data."""
    return hashlib.sha256(data).digest()


def _pad_len(n: int) -> int:
    """Arena slots are rounded to 4 KB so the donated update-slice jit
    compiles for a handful of sizes, not one per blob length."""
    return max(4096, (n + 4095) // 4096 * 4096)


@functools.lru_cache(maxsize=16)
@devledger.instrument_builder("blob_pool.insert")
def _jitted_insert(pad: int):
    import jax
    import jax.numpy as jnp

    def insert(arena, chunk, offset):
        return jax.lax.dynamic_update_slice(arena, chunk, (offset,))

    # donating the arena lets XLA update in place instead of copying
    # the whole buffer per insert
    return jax.jit(insert, donate_argnums=(0,))


class DeviceBlobArena:
    """Fixed-size device byte arena with a host-side bump allocator.

    Thread-safe for the node's use (CheckTx threads insert, the proposal
    path reads). Eviction is SEMISPACE: the arena is two halves, the
    bump allocator fills the active one, and overflow flips to the other
    half, evicting only ITS entries — blobs staged in the previous half
    stay resident one more cycle, so a working set larger than the
    arena keeps ~half its blobs warm instead of restaging everything
    (the wholesale-reset sawtooth the round-4 churn bench measured).
    Correctness never depends on residency (the proposal path falls back
    to the plain host-upload route for any blob it cannot find), so the
    arena is purely a transfer cache.
    """

    def __init__(self, capacity_bytes: int = 64 * 1024 * 1024, device=None):
        import jax
        import jax.numpy as jnp

        self.capacity = int(capacity_bytes)
        # Each half is floor(capacity/2) rounded DOWN to 4 KB; a
        # sub-8 KB arena degenerates to one wholesale-reset region
        # (half == 0 would make everything "oversized", so clamp to one
        # slot). When capacity is not a multiple of 8 KB the remainder
        # past the usable region is STRANDED by design — equal aligned
        # halves are what guarantee entries never straddle the flip
        # boundary (ADR-007 amendment). `tail_bytes` makes the waste
        # visible so operators size capacities in 8 KB multiples.
        self._half = max(4096, self.capacity // 2 // 4096 * 4096)
        if self._half > self.capacity:
            self._half = self.capacity
        usable = (
            self._half * 2 if self._half * 2 <= self.capacity else self._half
        )
        self.tail_bytes = self.capacity - usable
        self._device = device
        self._arena = jax.device_put(
            jnp.zeros((self.capacity,), jnp.uint8), device
        )
        self._offsets: dict[bytes, tuple[int, int]] = {}  # key -> (off, len)
        self._base = 0  # active half's base offset
        self._next = 0
        # REENTRANT: the proposal path holds this lock across its whole
        # read (offset lookups -> device dispatch -> root fetch, see
        # App._assembled_proposal_dah) while the nested offset_of calls
        # re-acquire it. Serializing against put() is what makes the
        # donated in-place arena update safe: a concurrent insert would
        # otherwise DELETE the buffer the proposal just dispatched on
        # (donate_argnums), and a half flip would rewrite bytes at
        # offsets the proposal already snapshotted.
        self._lock = threading.RLock()
        # HBM attribution (ADR-025): the arena is a fixed device
        # allocation; registration is weak, so a dropped arena leaves
        # the ledger on the next snapshot
        devledger.register_owner("blob_arena", self.device_bytes)

    def device_bytes(self) -> int:
        """The arena's device footprint (fixed at construction) — the
        devledger owner callback, which runs with NO ledger lock held,
        so taking the arena lock here creates no cross-module edge."""
        with self._lock:
            arena = self._arena
            return (int(getattr(arena, "nbytes", 0))
                    if arena is not None else 0)

    @property
    def lock(self):
        """Hold across a multi-step read (snapshot offsets + dispatch +
        fetch) to exclude concurrent staging; see __init__."""
        return self._lock

    # ---- writes (CheckTx admission path) ----

    def _alloc_locked(self, pad: int) -> int:
        """Bump-allocate `pad` bytes in the active half (caller checked
        pad <= half), flipping when full: activate the other half and
        evict only ITS entries; the half we just filled stays resident
        for one more cycle. Entries never straddle the boundary (pad <=
        half and allocation flips before overflowing)."""
        if self._next + pad > self._base + self._half:
            if self._half * 2 <= self.capacity:
                self._base = self._half - self._base  # 0 <-> half
            else:  # degenerate single-region arena
                self._base = 0
            self._next = self._base
            lo, hi = self._base, self._base + self._half
            self._offsets = {
                k: (o, ln)
                for k, (o, ln) in self._offsets.items()
                if not (lo <= o < hi)
            }
        offset = self._next
        self._next += pad
        return offset

    def _stage_chunk(self, data: bytes):
        """Dispatch the padded blob bytes host→device (async DMA —
        jax.device_put returns before the copy lands) with transfer
        telemetry at site=arena.stage."""
        import numpy as np

        from celestia_tpu.ops import transfers

        pad = _pad_len(len(data))
        chunk = np.zeros((pad,), np.uint8)
        chunk[: len(data)] = np.frombuffer(data, np.uint8)
        return transfers.device_put_chunked(
            chunk, self._device, site="arena.stage"
        )

    def put(self, data: bytes) -> bytes:
        """Stage blob bytes on device; returns the content key.
        Idempotent; flips to the other half when the active one is full
        (transfer cache semantics — see class docstring)."""
        key = blob_key(data)
        pad = _pad_len(len(data))
        with self._lock:
            if key in self._offsets:
                return key
            if pad > self._half:
                return key  # oversized: never resident, always fallback
        # stage with the lock RELEASED: device_put_chunked dispatches
        # per-chunk DMA, and holding _lock across it stalls every
        # proposal-path offset_of() behind one upload (celestia-lint
        # C002). Staging is idempotent, so the re-check below simply
        # drops a duplicate upload if a racer landed the same key.
        dev = self._stage_chunk(data)
        with self._lock:
            if key in self._offsets:
                return key
            offset = self._alloc_locked(pad)
            self._arena = _jitted_insert(pad)(self._arena, dev, offset)
            self._offsets[key] = (offset, len(data))
            self._publish_metrics()
            return key

    def put_many(self, datas: list[bytes]) -> list[bytes]:
        """Stage several blobs with upload/insert overlap: every blob's
        host→device DMA is dispatched FIRST (all async, in flight at
        once), then the donated arena inserts consume them in order —
        blob i+1's bytes stream over the interconnect while blob i's
        insert runs, instead of the strict upload→insert lockstep of
        sequential put() calls. Allocator/flip/dedup semantics are
        identical to put(); returns the content keys in input order."""
        with self._lock:
            plan: list[tuple[bytes, bytes, bool]] = []
            seen: set[bytes] = set()
            for data in datas:
                key = blob_key(data)
                stage = not (
                    key in self._offsets
                    or key in seen
                    or _pad_len(len(data)) > self._half
                )  # False: resident/oversized/dup-in-batch
                if stage:
                    seen.add(key)
                plan.append((key, data, stage))
        # all DMAs dispatched with the lock released (same C002 fix as
        # put(); staging is idempotent and re-checked before insert)
        staged = [
            (key, data, self._stage_chunk(data) if stage else None)
            for key, data, stage in plan
        ]
        with self._lock:
            keys = []
            for key, data, dev in staged:
                if dev is not None and key not in self._offsets:
                    pad = _pad_len(len(data))
                    offset = self._alloc_locked(pad)
                    self._arena = _jitted_insert(pad)(self._arena, dev, offset)
                    self._offsets[key] = (offset, len(data))
                keys.append(key)
            self._publish_metrics()
            return keys

    def _publish_metrics(self) -> None:
        """Operator visibility on /metrics: how much of the mempool's
        blob data is HBM-resident and how full the arena is."""
        try:
            from celestia_tpu.telemetry import metrics

            metrics.set_gauge(
                "blob_arena_resident_bytes",
                float(sum(ln for _o, ln in self._offsets.values())),
            )
            # active-half fill, not the absolute bump pointer (which
            # includes the half's base offset under semispace)
            metrics.set_gauge(
                "blob_arena_used_bytes", float(self._next - self._base)
            )
            metrics.set_gauge("blob_arena_capacity_bytes", float(self.capacity))
            # the denominator fill-ratio dashboards should divide by:
            # used_bytes tops out at the ACTIVE HALF, not capacity —
            # used/capacity plateaus near 50% by design (ADR-007
            # amendment: the half-capacity residency cap)
            metrics.set_gauge(
                "blob_arena_active_half_bytes", float(self._half)
            )
        except Exception:  # noqa: BLE001 — metrics must never break staging
            pass

    def drop(self, key: bytes) -> None:
        """Forget a blob (committed/evicted tx). Space is reclaimed when
        its half next flips — a bump allocator stays trivial and the
        arena is a cache, not a ledger."""
        with self._lock:
            self._offsets.pop(key, None)

    # ---- reads (proposal path) ----

    def offset_of(self, key: bytes) -> tuple[int, int] | None:
        with self._lock:
            return self._offsets.get(key)

    @property
    def arena(self):
        """The device buffer (pass to the assembly program)."""
        # lint: allow(C005) reason=single atomic reference read; proposal assembly pairs it with offset_of() under the lock and tolerates one-generation-stale arenas
        return self._arena

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(ln for _off, ln in self._offsets.values())
