"""Ragged cross-height gather over the paged-EDS page table.

The light-client flash crowd samples the last N heights at once, but a
per-height batch key fragments that workload into N tiny device
dispatches, each paying its own launch + pow2 pad. This module is the
fix (ISSUE 14, borrowing the ragged paged-attention shape): the
`PagedEdsCache` row-group pages already form a page table, so a
mixed-height, mixed-k micro-batch can be answered with per-job
(page ref, row-in-page, length) descriptors and ONE jitted
dynamic-slice gather per page geometry — one dispatch for the common
same-k crowd instead of one per height.

Descriptor contract (see specs/serving.md "Ragged cross-height
batching"):

  * ``page ref``    — the page's device buffer; pages are pinned by the
                      caller (`PagedEdsCache.pages_batch`) across the
                      whole gather, so the buffer cannot be demoted
                      mid-slice.
  * ``row-in-page`` — the row index local to the page
                      (``i - page.row_lo``).
  * ``length``      — the job's TRUE row length in cells (the square
                      width); the device output is sliced to it before
                      D2H, so ``transfer_bytes`` parity with per-call
                      reads holds exactly — padding never crosses the
                      wire.

Pages are bucketed by their exact device shape: the row-extent
(``shape[0]``) is part of the compiled-fn cache key, so a store-loaded
height whose persisted ``rows_per_page`` differs from the cache default
compiles its own program instead of reusing a wrong-geometry one
(wrong row stride) — and the descriptor count is pow2-padded per
bucket, so a storm of arbitrary group sizes compiles O(log max_batch)
programs per geometry, not one per size.
"""

from __future__ import annotations

import contextlib
import functools
import time

import numpy as np

from celestia_tpu import devledger, tracing
from celestia_tpu.ops import transfers
from celestia_tpu.telemetry import metrics


@functools.lru_cache(maxsize=None)
@devledger.instrument_builder("ragged.gather")
def _jitted_gather(page_shape: tuple):
    """One compiled ragged gather per page geometry.

    Keyed on the FULL page shape — the row-extent ``page_shape[0]``
    included — so a store-loaded height with non-default persisted
    ``rows_per_page`` never reuses a program traced for the cache's
    default geometry (jit would also refuse by shape, but the explicit
    key makes the contract visible and pinnable by tests)."""
    import jax

    def gather(stacked, page_idx, row_idx):
        def one(p, r):
            page = jax.lax.dynamic_slice_in_dim(stacked, p, 1, axis=0)[0]
            return jax.lax.dynamic_slice_in_dim(page, r, 1, axis=0)[0]

        return jax.vmap(one)(page_idx, row_idx)

    return jax.jit(gather)


def gather_rows(descs, *, site: str = "eds.ragged") -> list:
    """Answer a ragged cross-height row group in one device dispatch
    per page geometry.

    ``descs`` is a list of ``(dev_page, row_in_page, length)``
    descriptors (pages pre-pinned by the caller). Returns host arrays
    aligned with ``descs``, each ``(length, B)`` — byte-identical to
    per-descriptor `transfers.eds_row` calls, transfer accounting
    included: only the true rows cross the wire."""
    executor = transfers._device_executor()
    if executor is not None:
        return executor(lambda: _gather_rows_direct(descs, site))
    return _gather_rows_direct(descs, site)


def _gather_rows_direct(descs, site: str) -> list:
    if not descs:
        return []
    import jax.numpy as jnp

    out: list = [None] * len(descs)
    # bucket descriptors by exact page geometry — mixed-k heights (and
    # short tail pages) carry different shapes; the dominant same-k
    # crowd lands in exactly one bucket = one dispatch
    buckets: dict[tuple, list[int]] = {}
    for t, (dev, _r, _n) in enumerate(descs):
        shape = tuple(int(d) for d in dev.shape)
        buckets.setdefault(shape, []).append(t)
    for shape, members in buckets.items():
        start = time.perf_counter()
        # flat page-table view: unique pages by buffer identity (many
        # jobs hit the same page; stacking it once is enough)
        pages: list = []
        slot_of: dict[int, int] = {}
        page_idx: list[int] = []
        row_idx: list[int] = []
        for t in members:
            dev, r, _n = descs[t]
            slot = slot_of.get(id(dev))
            if slot is None:
                slot = slot_of[id(dev)] = len(pages)
                pages.append(dev)
            page_idx.append(slot)
            row_idx.append(int(r))
        gather = _jitted_gather(shape)
        stacked = jnp.stack(transfers._pad_pow2(pages))
        pi = jnp.asarray(transfers._pad_pow2(page_idx), dtype=jnp.int32)
        ri = jnp.asarray(transfers._pad_pow2(row_idx), dtype=jnp.int32)
        out_dev = gather(stacked, pi, ri)
        # device-side slice to the true member count BEFORE D2H: the
        # pow2 pad is cut on device and never fetched, so the
        # transfer_bytes increment equals the per-call sum
        host = np.asarray(out_dev[: len(members)])
        transfers._record(site, "d2h", host.nbytes, start)
        for k, t in enumerate(members):
            _dev, _r, n = descs[t]
            out[t] = host[k][: int(n)]
    return out


@contextlib.contextmanager
def ragged_span(heights: int, jobs: int):
    """Observability envelope for one ragged group: the
    ``dispatch_ragged_*`` counters/histogram and the ``dispatch.ragged``
    span (specs/observability.md)."""
    metrics.incr_counter("dispatch_ragged_batch_total")
    metrics.incr_counter("dispatch_ragged_jobs_total", float(jobs))
    metrics.observe("dispatch_ragged_heights", float(heights))
    with tracing.span("dispatch.ragged", heights=heights, jobs=jobs):
        yield
