"""Transfer-aware host↔device data movement (specs/transfers.md).

The round-5 scoreboard showed the compute story won and the *transfer*
story lost: repair computed in 8.6 ms but took 3406 ms wall with
transfers, and serving ONE DAS sample from a device-resident EDS forced
the full 32 MB fetch. This module is the single place the repo moves EDS
bytes across the interconnect, with three disciplines:

1. **Sliced reads** — `eds_row` / `eds_col` / `eds_share` fetch exactly
   one row, column, or cell of a device-resident (2k, 2k, B) square via
   a jitted dynamic-slice, so a DAS sample transfers O(w·B) bytes, not
   O(w²·B). The slice is cut ON DEVICE (the index is a traced scalar —
   one compile per square shape, not per index) and only the slice
   crosses to host.

2. **Chunked overlapped bulk transfers** — `device_put_chunked` /
   `device_get_chunked` split a bulk host↔device copy into row-block
   slices dispatched asynchronously (`jax.device_put` is async;
   downloads use `copy_to_host_async` when the runtime provides it), so
   chunk i+1's DMA overlaps chunk i's copy-out/compute instead of one
   monolithic blocking copy. Byte-identical to the monolithic path by
   construction (concatenation of exact slices).

3. **Telemetry** — every movement increments the `transfer_bytes` and
   `transfer_ms` counters labelled by call site and direction, so bench
   and tests can assert transfer *budgets* (e.g. "one DAS sample moves
   ≤ 2 rows"). Metrics never break the hot path (same swallow pattern
   as ops/blob_pool.py).

4. **Integrity** (ADR-015) — when the process-global audit engine
   (celestia_tpu/integrity.py) is enabled, the chunked paths compute a
   CRC-32C per chunk at the SOURCE and verify it at the SINK (readback
   for uploads, cached-value comparison for downloads), retrying the
   damaged chunk exactly once before raising IntegrityError. Every
   chunk also passes the `transfer.chunk` fault site, so a chaos drill
   arms `bitflip` there and the checksum must catch the flipped bit.
   With audits off the only added cost is the site's empty-injector
   check — no checksums, no readbacks, no clocks.

The analogue of the host/device data-movement discipline TPU inference
kernels apply (PAPERS.md, "Ragged Paged Attention"): keep bytes where
the compute is, and move only what the consumer actually reads.
"""

from __future__ import annotations

import functools
import threading
import time

import numpy as np

from celestia_tpu import devledger, faults, integrity, tracing

# Bulk transfers split into row-block chunks of at least this many bytes
# (smaller chunks are dispatch-bound: sub-MB chunks pay more in
# per-dispatch latency than they win in overlap). Not yet measured on
# the chip the driver runs.
MIN_CHUNK_BYTES = 1 << 20
MAX_CHUNKS = 8


def _record(site: str, direction: str, nbytes: int, start: float) -> None:
    """Count a transfer (bytes + dispatch wall-ms) per site/direction.

    For async uploads the ms counter measures time spent *in the call*
    (dispatch wall), not DMA completion — that is the quantity overlap
    is supposed to shrink. Bytes are exact either way.

    The same timing doubles as a finished `transfer.<site>` span
    (tracing.emit): the span's duration and the transfer_ms increment
    come from one measurement, and the span carries the CUMULATIVE
    per-site counters as attributes so a trace shows both this call and
    the running total the budgets are asserted against."""
    try:
        from celestia_tpu.telemetry import metrics

        metrics.incr_counter(
            "transfer_bytes", float(nbytes), site=site, direction=direction
        )
        elapsed = time.perf_counter() - start
        metrics.incr_counter(
            "transfer_ms", elapsed * 1e3, site=site, direction=direction
        )
        # same measurement, histogram form: /metrics gets per-site
        # transfer_seconds buckets next to the running counters
        metrics.observe("transfer", elapsed, site=site, direction=direction)
        # stage attribution (ADR-022): the same measurement feeds the
        # request's d2h/h2d stage when a sink is installed (dispatcher
        # thread, tracing on) — self-guarding no-op otherwise
        tracing.add_stage(direction, elapsed)
        if tracing.enabled():
            tracing.emit(
                f"transfer.{site}", start,
                site=site, direction=direction, bytes=nbytes,
                total_bytes=metrics.get_counter(
                    "transfer_bytes", site=site, direction=direction
                ),
                total_ms=round(metrics.get_counter(
                    "transfer_ms", site=site, direction=direction
                ), 3),
            )
    except Exception:  # noqa: BLE001 — metrics must never break transfers
        pass


def _nbytes(arr) -> int:
    return int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize


def _auto_chunks(nbytes: int, rows: int) -> int:
    return max(1, min(MAX_CHUNKS, rows, nbytes // MIN_CHUNK_BYTES))


def _bounds(n: int, chunks: int) -> list[tuple[int, int]]:
    """Split [0, n) into `chunks` near-equal contiguous row blocks (the
    first n % chunks blocks take the extra row — no alignment needed,
    concatenation restores the exact original)."""
    base, extra = divmod(n, chunks)
    bounds = []
    lo = 0
    for i in range(chunks):
        hi = lo + base + (1 if i < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


# ------------------------------------------------------------------ #
# the device executor (ADR-016): single-owner funneling for sliced reads

# A serving node registers its DeviceDispatcher's `run_device` here so
# sliced reads issued OUTSIDE the dispatcher thread (prober host
# crosschecks, embedded callers, background audits) still execute on
# the one thread that owns the device stream. The hook only engages
# when EXACTLY ONE executor is registered: an in-process multi-node
# topology (two RpcServers in one test process) has no single stream
# owner, so it falls back to the pre-ADR-016 inline reads — correct,
# just unfunneled. Bulk chunked transfers are NOT routed through the
# hook; they belong to the block pipeline, which already serializes on
# the node lock and runs on (or upstream of) the dispatcher.
_device_executors: list = []
_executor_lock = threading.Lock()


def register_device_executor(executor) -> None:
    with _executor_lock:
        if executor not in _device_executors:
            _device_executors.append(executor)


def unregister_device_executor(executor) -> None:
    with _executor_lock:
        try:
            _device_executors.remove(executor)
        except ValueError:
            pass


def _device_executor():
    with _executor_lock:
        return _device_executors[0] if len(_device_executors) == 1 else None


# ------------------------------------------------------------------ #
# sliced device→host reads


@functools.lru_cache(maxsize=1)
@devledger.instrument_builder("transfers.slicers")
def _jitted_slicers():
    """Jitted row/col/cell extractors for a (w, w, B) device square.

    The index arrives as a traced scalar, so jax compiles ONE program
    per square shape (jit specializes on shapes by itself) and every
    index reuses it — the device cuts the slice, and only the slice
    crosses the interconnect."""
    import jax

    def row(dev, i):
        return jax.lax.dynamic_slice_in_dim(dev, i, 1, axis=0)[0]

    def col(dev, j):
        return jax.lax.dynamic_slice_in_dim(dev, j, 1, axis=1)[:, 0]

    def cell(dev, i, j):
        return jax.lax.dynamic_slice(
            dev, (i, j, 0), (1, 1, dev.shape[2])
        )[0, 0]

    return jax.jit(row), jax.jit(col), jax.jit(cell)


def eds_row(dev, i: int, *, site: str = "eds.row") -> np.ndarray:
    """Fetch row i of a device-resident (w, w, B) square: (w, B) host
    bytes, w·B over the wire instead of w²·B. Funnels through the
    registered device executor when one is active (run_device is a
    no-op when the caller IS the dispatcher thread)."""
    executor = _device_executor()
    if executor is not None:
        return executor(lambda: _eds_row_direct(dev, i, site))
    return _eds_row_direct(dev, i, site)


def _eds_row_direct(dev, i: int, site: str) -> np.ndarray:
    start = time.perf_counter()
    row_fn, _, _ = _jitted_slicers()
    out = np.asarray(row_fn(dev, i))
    _record(site, "d2h", out.nbytes, start)
    return out


def eds_col(dev, j: int, *, site: str = "eds.col") -> np.ndarray:
    """Fetch column j of a device-resident (w, w, B) square: (w, B)."""
    executor = _device_executor()
    if executor is not None:
        return executor(lambda: _eds_col_direct(dev, j, site))
    return _eds_col_direct(dev, j, site)


def _eds_col_direct(dev, j: int, site: str) -> np.ndarray:
    start = time.perf_counter()
    _, col_fn, _ = _jitted_slicers()
    out = np.asarray(col_fn(dev, j))
    _record(site, "d2h", out.nbytes, start)
    return out


def eds_share(dev, r: int, c: int, *, site: str = "eds.share") -> np.ndarray:
    """Fetch one (B,) cell of a device-resident square."""
    executor = _device_executor()
    if executor is not None:
        return executor(lambda: _eds_share_direct(dev, r, c, site))
    return _eds_share_direct(dev, r, c, site)


def _eds_share_direct(dev, r: int, c: int, site: str) -> np.ndarray:
    start = time.perf_counter()
    _, _, cell_fn = _jitted_slicers()
    out = np.asarray(cell_fn(dev, r, c))
    _record(site, "d2h", out.nbytes, start)
    return out


# ------------------------------------------------------------------ #
# batched sliced device→host reads (continuous-batching read path)


@functools.lru_cache(maxsize=1)
@devledger.instrument_builder("transfers.batch_slicers")
def _jitted_batch_slicers():
    """Vmapped row/cell extractors for a (w, w, B) device square.

    The index VECTOR arrives as a traced array, so jax compiles one
    program per (square shape, padded batch length) pair. Batch lengths
    are padded to the next power of two before tracing
    (`_pad_pow2`), so a storm of arbitrary batch sizes compiles
    O(log max_batch) programs, not one per size."""
    import jax

    def rows(dev, idx):
        return jax.vmap(
            lambda i: jax.lax.dynamic_slice_in_dim(dev, i, 1, axis=0)[0]
        )(idx)

    def cells(dev, rr, cc):
        return jax.vmap(
            lambda r, c: jax.lax.dynamic_slice(
                dev, (r, c, 0), (1, 1, dev.shape[2])
            )[0, 0]
        )(rr, cc)

    return jax.jit(rows), jax.jit(cells)


def _pad_pow2(seq: list) -> list:
    """Pad a non-empty index list to the next power-of-two length by
    repeating the last element (discarded after the device cut)."""
    n = len(seq)
    m = 1
    while m < n:
        m *= 2
    return seq + [seq[-1]] * (m - n)


def eds_rows_batch(dev, indices, *, site: str = "eds.rows_batch") -> np.ndarray:
    """Fetch rows `indices` of a device-resident (w, w, B) square as ONE
    vmapped sliced read: (n, w, B) host bytes in request order.

    Byte-identical to `[eds_row(dev, i) for i in indices]` — including
    the transfer-byte accounting: only the n requested rows cross the
    wire (the power-of-two pad is cut on device and never fetched), so
    the `transfer_bytes` increment equals the per-call sum."""
    executor = _device_executor()
    if executor is not None:
        return executor(lambda: _eds_rows_batch_direct(dev, indices, site))
    return _eds_rows_batch_direct(dev, indices, site)


def _eds_rows_batch_direct(dev, indices, site: str) -> np.ndarray:
    idx = [int(i) for i in indices]
    if not idx:
        return np.empty((0,) + tuple(int(d) for d in dev.shape[1:]),
                        dtype=np.dtype(dev.dtype))
    start = time.perf_counter()
    import jax.numpy as jnp

    rows_fn, _ = _jitted_batch_slicers()
    padded = jnp.asarray(_pad_pow2(idx), dtype=jnp.int32)
    out_dev = rows_fn(dev, padded)
    _profile_fence(out_dev, site, start, n=len(idx))
    out = np.asarray(out_dev[: len(idx)])
    _record(site, "d2h", out.nbytes, start)
    return out


def _profile_fence(out_dev, entry: str, dispatch_start: float,
                   **attrs) -> None:
    """Fenced device-time profiling (ADR-022, opt-in): when this
    dispatch is profile-sampled, block until the result is ready and
    emit a ``profile.fence`` span covering dispatch→ready — the REAL
    device completion time async dispatch hides. Off by default
    (``tracing.enable_profiling``): a fence serializes the device
    stream, which would cost exactly the overlap ADR-019 measured."""
    if not tracing.profile_sample():
        return
    try:
        import jax

        jax.block_until_ready(out_dev)
        tracing.emit("profile.fence", dispatch_start, entry=entry,
                     fenced=True, **attrs)
    except Exception:  # noqa: BLE001 — profiling must never break serving
        pass


def eds_cells_batch(dev, coords, *, site: str = "eds.cells_batch") -> np.ndarray:
    """Fetch cells `coords` (an iterable of (row, col)) of a
    device-resident square as ONE vmapped sliced read: (n, B) host bytes
    in request order. Byte-identical to per-call `eds_share`, counter
    parity included (see `eds_rows_batch`)."""
    executor = _device_executor()
    if executor is not None:
        return executor(lambda: _eds_cells_batch_direct(dev, coords, site))
    return _eds_cells_batch_direct(dev, coords, site)


def _eds_cells_batch_direct(dev, coords, site: str) -> np.ndarray:
    pts = [(int(r), int(c)) for r, c in coords]
    if not pts:
        return np.empty((0, int(dev.shape[2])), dtype=np.dtype(dev.dtype))
    start = time.perf_counter()
    import jax.numpy as jnp

    _, cells_fn = _jitted_batch_slicers()
    padded = _pad_pow2(pts)
    rr = jnp.asarray([p[0] for p in padded], dtype=jnp.int32)
    cc = jnp.asarray([p[1] for p in padded], dtype=jnp.int32)
    out_dev = cells_fn(dev, rr, cc)
    _profile_fence(out_dev, site, start, n=len(pts))
    out = np.asarray(out_dev[: len(pts)])
    _record(site, "d2h", out.nbytes, start)
    return out


# ------------------------------------------------------------------ #
# chunked overlapped bulk transfers


def device_put_chunked(arr: np.ndarray, device=None, *, site: str,
                       chunks: int | None = None):
    """Upload a host array as async row-block slices; returns the device
    array (byte-identical to a monolithic `jax.device_put`).

    Every `jax.device_put` dispatch returns before its DMA completes, so
    issuing the blocks back-to-back keeps several in flight — the copy
    engine streams block i+1 while i lands — and the device-side
    concatenation is itself async, so the caller's subsequent compute
    (or host-side planning, see repair) overlaps the whole upload."""
    import jax
    import jax.numpy as jnp

    start = time.perf_counter()
    n = int(arr.shape[0])
    nbytes = arr.nbytes
    c = chunks if chunks is not None else _auto_chunks(nbytes, n)
    c = max(1, min(int(c), n)) if n else 1
    eng = integrity.get()
    bounds = [(0, n)] if c <= 1 else _bounds(n, c)
    verify = eng.sample_chunks(len(bounds)) if eng.enabled else ()
    parts = []
    for idx, (lo, hi) in enumerate(bounds):
        block = arr if c <= 1 else np.ascontiguousarray(arr[lo:hi])
        # checksum the PRISTINE source before the wire — the fault site
        # models in-flight damage, which the sink check must catch
        want = integrity.crc32c(block) if idx in verify else None
        flip = faults.fire("transfer.chunk", transfer=site, direction="h2d",
                           index=idx)
        part = jax.device_put(block if flip is None else flip(block),
                              device)
        if want is not None:
            part = _verify_put_chunk(part, block, want, site, idx, device)
        parts.append(part)
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    _record(site, "h2d", nbytes, start)
    return out


def device_put_sharded_rows(arr: np.ndarray, mesh, *, site: str):
    """Upload a host array row-sharded over the mesh's 'sp' axis: each
    row block lands directly on its shard (NamedSharding placement), so
    a mesh-routed extend (specs/parallel.md §Production routing) never
    funnels the whole square through one device and then reshards
    inside the program. One dispatch — the runtime drives the per-shard
    DMAs — with the same telemetry, `transfer.chunk` fault passage, and
    sampled CRC-32C sink verification as `device_put_chunked`."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    start = time.perf_counter()
    sharding = NamedSharding(
        mesh, PartitionSpec("sp", *([None] * (arr.ndim - 1)))
    )
    eng = integrity.get()
    verify = eng.sample_chunks(1) if eng.enabled else ()
    want = integrity.crc32c(arr) if 0 in verify else None
    flip = faults.fire("transfer.chunk", transfer=site, direction="h2d",
                       index=0)
    out = jax.device_put(arr if flip is None else flip(arr), sharding)
    if want is not None:
        out = _verify_put_chunk(out, arr, want, site, 0, sharding)
    _record(site, "h2d", arr.nbytes, start)
    return out


def _verify_put_chunk(part, pristine, want, site, idx, device):
    """Verify one uploaded chunk at the sink (device readback CRC vs
    the source CRC); retry the DMA once from the pristine source before
    raising. Only reached with audits enabled."""
    import jax

    got = integrity.crc32c(np.asarray(part))
    if got == want:
        return part
    integrity.record_sdc("transfer.chunk")
    try:
        from celestia_tpu.telemetry import metrics

        metrics.incr_counter("transfer_retry_total", site=site,
                             direction="h2d")
    except Exception:  # noqa: BLE001
        pass
    # the retry re-drives the wire (and re-passes the fault site: a
    # persistent fault strikes again and the retry fails too)
    flip = faults.fire("transfer.chunk", transfer=site, direction="h2d",
                       index=idx, retry=1)
    part = jax.device_put(pristine if flip is None else flip(pristine),
                          device)
    if integrity.crc32c(np.asarray(part)) != want:
        raise integrity.IntegrityError(
            f"h2d chunk {idx} corrupt after retry at {site} "
            f"(crc {got:#010x} != {want:#010x})"
        )
    return part


def device_get_chunked(dev, *, site: str, chunks: int | None = None) -> np.ndarray:
    """Download a device array as overlapped row-block slices; returns a
    host array byte-identical to `np.asarray(dev)`.

    The device cuts all blocks first (async), every block's D2H DMA is
    started with `copy_to_host_async` (all in flight at once), and the
    host then assembles them in order — block i converts while block
    i+1 is still streaming, instead of one monolithic blocking fetch."""
    import jax

    start = time.perf_counter()
    n = int(dev.shape[0])
    nbytes = _nbytes(dev)
    c = chunks if chunks is not None else _auto_chunks(nbytes, n)
    c = max(1, min(int(c), n)) if n else 1
    if c <= 1:
        dev_parts = [dev]
    else:
        dev_parts = [
            jax.lax.slice_in_dim(dev, lo, hi, axis=0)
            for lo, hi in _bounds(n, c)
        ]
        for p in dev_parts:
            async_copy = getattr(p, "copy_to_host_async", None)
            if async_copy is not None:
                async_copy()
    eng = integrity.get()
    verify = eng.sample_chunks(len(dev_parts)) if eng.enabled else ()
    host_parts = []
    for idx, p in enumerate(dev_parts):
        block = np.asarray(p)
        flip = faults.fire("transfer.chunk", transfer=site, direction="d2h",
                           index=idx)
        if flip is not None:
            block = flip(block)
        if idx in verify:
            block = _verify_get_chunk(block, p, site, idx)
        host_parts.append(block)
    out = host_parts[0] if len(host_parts) == 1 else np.concatenate(
        host_parts, axis=0
    )
    _record(site, "d2h", nbytes, start)
    return out


def _verify_get_chunk(block, dev_part, site, idx):
    """Verify one downloaded chunk at the sink: compare its CRC against
    an independent read of the same device slice; on disagreement retry
    once and accept the two-of-three consensus. Only reached with
    audits enabled."""
    check = np.asarray(dev_part)
    if integrity.crc32c(block) == integrity.crc32c(check):
        return block
    integrity.record_sdc("transfer.chunk")
    try:
        from celestia_tpu.telemetry import metrics

        metrics.incr_counter("transfer_retry_total", site=site,
                             direction="d2h")
    except Exception:  # noqa: BLE001
        pass
    third = np.asarray(dev_part)
    flip = faults.fire("transfer.chunk", transfer=site, direction="d2h",
                       index=idx, retry=1)
    if flip is not None:
        third = flip(third)
    c_third = integrity.crc32c(third)
    if c_third == integrity.crc32c(check):
        return check
    if c_third == integrity.crc32c(block):
        return block
    raise integrity.IntegrityError(
        f"d2h chunk {idx} corrupt after retry at {site}"
    )
