"""EDS repair (rsmt2d.Repair) on TPU as GF(2) bit-matmuls on the MXU.

Design (the decode counterpart of ops/rs_tpu.py's encode design): the
Leopard erasure decode factors into

    out = Unscale_axis ∘ CORE_n ∘ Scale_axis (codeword bytes)

where CORE_n (IFFT → formal derivative → FFT) is a fixed GF(256)-linear
map depending only on n = 2k — one (8n × 8n) 0/1 matrix over GF(2) shared
by EVERY axis and every erasure pattern — and Scale/Unscale are diagonal
per-position constant multiplies (8×8 bit blocks) derived from the FWHT
error locator. The reference decodes each axis with sequential
table-lookup butterflies (klauspost Leopard, rsmt2d.Repair invoked from
pkg/da/data_availability_header.go context); on TPU the shared core rides
the MXU as one dense int8 contraction batched over all axes at once, and
the tiny pattern-dependent pieces ride the VPU.

The second structural insight: which cells become repairable each sweep
depends only on the presence MASK, never on byte values. So the whole
multi-sweep schedule (row/column orientation, per-axis locators,
write-masks) is computed on the host up front from the initial mask, and
the device runs the planned sweeps without a host round-trip between
them — the "host orchestrates, device transforms" split SURVEY §7 hard
part 4 prescribes.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from celestia_tpu import faults, integrity, tracing
from celestia_tpu.ops import gf256
from celestia_tpu.ops.rs_tpu import expand_bit_matrix, pack_bits, unpack_bits


@functools.lru_cache(maxsize=8)
def decode_bit_matrix(n: int) -> np.ndarray:
    """(8n, 8n) uint8 0/1 matrix of the shared decode core over GF(2)
    (the decode counterpart of rs_tpu.encode_bit_matrix)."""
    return expand_bit_matrix(gf256.decode_core_matrix(n))


@functools.lru_cache(maxsize=1)
def _bitmul_table() -> np.ndarray:
    """(256, 8, 8) 0/1: BITMUL[c][r, q] = bit_r(c * x^q) — the 8×8 GF(2)
    matrix of multiply-by-constant-c, bit lanes LSB-first."""
    consts = np.arange(256, dtype=np.uint8)[:, None]  # (256, 1) GF matrix
    return expand_bit_matrix(consts).reshape(256, 8, 8)


@dataclasses.dataclass
class SweepPlan:
    """One planned decode sweep (all axes of one orientation at once).

    Scale constants travel as BYTES (w·n, ~65 KB at k=128); the device
    expands them to 8×8 bit-matrices by gathering from the resident
    _bitmul_table — 120x less host->device traffic than shipping the
    matrices."""

    transpose: bool  # False: rows are axes; True: columns are axes
    scale_bytes: np.ndarray  # (w, n) uint8 — locator scale constant
    unscale_bytes: np.ndarray  # (w, n) uint8
    write: np.ndarray  # (w, n) bool — cells this sweep recovers (axis order)


def plan_sweeps(present: np.ndarray, k: int) -> list[SweepPlan]:
    """Derive the full sweep schedule from the presence mask alone.

    Mask evolution is value-independent: an axis with >= k present cells
    becomes fully present after its decode. Axes below k are carried in
    the batch (static shapes) but masked out of the write."""
    from celestia_tpu.da.repair import UnrepairableError

    w = 2 * k
    mask = present.copy()
    _log, exp = gf256._tables()
    plans: list[SweepPlan] = []
    while not mask.all():
        progress = False
        for transpose in (False, True):
            m = mask.T if transpose else mask
            counts = m.sum(axis=1)
            decodable = (counts >= k) & ~m.all(axis=1)
            if not decodable.any():
                continue
            # erasure indicators in codeword order [parity | data]
            erased = np.concatenate([~m[:, k:], ~m[:, :k]], axis=1).astype(
                np.int64
            )
            loc = gf256._error_locator_logs_batch(erased)[:, : 2 * k]
            scale_logs = np.where(erased == 0, loc, gf256.K_MODULUS)
            unscale_logs = np.where(
                erased == 1,
                (gf256.K_MODULUS - loc) % gf256.K_MODULUS,
                gf256.K_MODULUS,
            )
            to_bytes = lambda logs: np.where(  # noqa: E731
                logs == gf256.K_MODULUS, 0, exp[logs]
            ).astype(np.uint8)
            write = ~m & decodable[:, None]
            plans.append(
                SweepPlan(
                    transpose=transpose,
                    scale_bytes=to_bytes(scale_logs),
                    unscale_bytes=to_bytes(unscale_logs),
                    write=write,
                )
            )
            if transpose:
                mask.T[decodable] = True
            else:
                mask[decodable] = True
            progress = True
        if not progress:
            raise UnrepairableError(
                f"impossible to recover: {int((~mask).sum())} cells still missing"
            )
    return plans


def _sweep_device(eds, scale_bytes, unscale_bytes, write, t2, bitmul, k: int,
                  chunks: int):
    """One decode sweep over ALL w axes of the current orientation.

    eds: (w, w, B) uint8 (axes along dim 0); scale/unscale constants as
    (w, n) uint8; write (w, n) bool; t2 (8n, 8n) int8; bitmul the
    resident (256, 8, 8) constant-multiply bit-matrix table. Returns eds
    with the written cells replaced by recovered bytes.
    """
    import jax
    import jax.numpy as jnp

    w = eds.shape[0]
    n = w
    b = eds.shape[2]
    k_ = k

    # expand scale constants to 8×8 bit matrices on device (tiny gather)
    scale = jnp.take(bitmul, scale_bytes, axis=0).astype(jnp.int8)
    unscale = jnp.take(bitmul, unscale_bytes, axis=0).astype(jnp.int8)

    # codeword order [parity | data]
    codeword = jnp.concatenate([eds[:, k_:], eds[:, :k_]], axis=1)

    def run_chunk(args):
        cells, s_mats, u_mats = args
        bits = unpack_bits(cells).reshape(-1, n, 8, b)  # (a, n, 8c, B)
        # per-position 8×8 locator scale (VPU): out_r = Σ_c S[r,c]·bit_c
        scaled = (
            jax.lax.dot_general(
                s_mats,
                bits,
                dimension_numbers=(((3,), (2,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.int8)
        # the shared decode core: ONE (8n, 8n) GF(2) contraction (MXU)
        y = (
            jax.lax.dot_general(
                t2,
                scaled.reshape(-1, 8 * n, b),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.int8)
        y = jnp.moveaxis(y, 0, 1).reshape(-1, n, 8, b)
        out = (
            jax.lax.dot_general(
                u_mats,
                y,
                dimension_numbers=(((3,), (2,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.int32,
            )
            & 1
        )
        return pack_bits(out.reshape(-1, 8 * n, b))

    if chunks > 1:
        shape = (chunks, w // chunks)
        recovered = jax.lax.map(
            run_chunk,
            (
                codeword.reshape(shape[0], shape[1], n, b),
                scale.reshape(shape[0], shape[1], n, 8, 8),
                unscale.reshape(shape[0], shape[1], n, 8, 8),
            ),
        ).reshape(w, n, b)
    else:
        recovered = run_chunk((codeword, scale, unscale))

    # back to cell order [data | parity]
    recovered = jnp.concatenate([recovered[:, k_:], recovered[:, :k_]], axis=1)
    return jnp.where(write[:, :, None], recovered, eds)


@functools.lru_cache(maxsize=4)
def _resident_constants(w: int):
    """The decode core matrix (8w × 8w int8, ~4 MB at w=256) and the
    constant-multiply bit table, uploaded ONCE and kept device-resident
    — re-uploading t2 per repair was most of the repair wall time
    over the remote device link of the earlier rounds."""
    import jax.numpy as jnp

    return (
        jnp.asarray(decode_bit_matrix(w).astype(np.int8)),
        jnp.asarray(_bitmul_table()),
    )


@functools.lru_cache(maxsize=1)
def _jitted_clear():
    import jax
    import jax.numpy as jnp

    # jax.jit specializes per input shape on its own; one wrapper serves
    # every square size
    return jax.jit(lambda eds, present: jnp.where(present[..., None], eds, 0))


@functools.lru_cache(maxsize=8)
def _jitted_sweep(k: int, b: int, chunks: int):
    import jax

    def fn(eds, scale_bytes, unscale_bytes, write, t2, bitmul, transpose):
        if transpose:
            eds = jax.numpy.swapaxes(eds, 0, 1)
        out = _sweep_device(
            eds, scale_bytes, unscale_bytes, write, t2, bitmul, k, chunks
        )
        if transpose:
            out = jax.numpy.swapaxes(out, 0, 1)
        return out

    return jax.jit(fn, static_argnames=("transpose",))


def stage_resident_repair(
    eds, present: np.ndarray, device=None
):
    """Plan a repair and stage everything on the device.

    `eds` may be a host numpy array (uploaded once here) or an already
    device-resident buffer — e.g. the EDS handle the extend pipeline just
    produced (extend_tpu.extend_roots_device_resident): the node's
    repair-after-extend flow passes the handle straight through and no
    share byte crosses the interconnect.

    Returns (run, n_sweeps): run() dispatches the planned sweep chain on
    the resident buffers and returns the repaired square as a device
    array (sweeps are idempotent on repaired data, so run() may be
    re-invoked — bench.py slope-fits exactly this, the shipped path).
    """
    import jax
    import jax.numpy as jnp

    from celestia_tpu.ops import transfers

    w = eds.shape[0]
    k = w // 2
    if isinstance(eds, np.ndarray):
        # Dispatch the upload BEFORE planning: the async row-block DMAs
        # (transfers.device_put_chunked) stream the raw square while the
        # host derives the sweep schedule from the mask — transfer
        # overlaps planning instead of serializing after it. Erased
        # cells are zeroed on DEVICE (same jnp.where the resident path
        # uses), which also drops the former host-side 32 MB np.where
        # pass from the critical path. Byte-identical either way.
        with tracing.span("repair.upload", backend="tpu", k=k):
            dev_raw = transfers.device_put_chunked(
                eds, device, site="repair.stage"
            )
    else:
        dev_raw = eds
    with tracing.span("repair.plan", backend="host", k=k,
                      missing=int((~present).sum())) as _plan_span:
        plans = plan_sweeps(present, k)
        _plan_span.set(sweeps=len(plans))

    # Chunk the axis batch so the int32 matmul accumulator stays bounded
    # (w × 8w × B int32 at k=128 is ~2 GB; 4 chunks keep peaks ~0.5 GB).
    chunks = 4 if w >= 256 else 1
    t2, bitmul = _resident_constants(w)
    dev = _jitted_clear()(dev_raw, jnp.asarray(present))
    step = _jitted_sweep(k, eds.shape[2], chunks)
    staged = [
        (
            jnp.asarray(p.scale_bytes),
            jnp.asarray(p.unscale_bytes),
            jnp.asarray(p.write),
            p.transpose,
        )
        for p in plans
    ]

    def run():
        with tracing.span("repair.sweep", backend="tpu", k=k,
                          n_sweeps=len(staged)):
            out = dev
            for sb, ub, wr, tr in staged:
                out = step(out, sb, ub, wr, t2, bitmul, transpose=tr)
            return out

    return run, len(plans)


def repair_resident_verified(
    eds,
    present: np.ndarray,
    row_roots: list[bytes] | None = None,
    col_roots: list[bytes] | None = None,
    device=None,
):
    """Repair + verify wholly on device; only roots cross to host.

    `eds` is ideally the device buffer the extend pipeline just produced
    (the rsmt2d.Repair flow in a node starts from an EDS it just
    extended — BASELINE config 4's real-world shape). The sweeps run on
    the resident buffers, the NMT axis roots of the repaired square are
    recomputed on device (extend_tpu.eds_roots_device) and compared to
    the DAH roots host-side (2·2k·90 bytes fetched, not (2k)²·512).
    Returns the repaired square as a DEVICE buffer; fetching bytes is
    the caller's lazy decision. Raises ValueError on root mismatch."""
    from celestia_tpu.telemetry import metrics

    k = int(eds.shape[0]) // 2
    with tracing.span("repair.device", backend="tpu", k=k,
                      entry="repair_resident_verified",
                      missing=int((~present).sum())), \
            metrics.measure("repair", backend="tpu"):
        faults.fire("device.repair", entry="repair_resident_verified")
        from celestia_tpu.ops import extend_tpu

        run, _ = stage_resident_repair(eds, present, device)
        fixed = run()
        fixed = _postprocess_repair(fixed, k,
                                    entry="repair_resident_verified")
        if row_roots is not None or col_roots is not None:
            with tracing.span("repair.verify", backend="tpu", k=k):
                rows, cols = extend_tpu.eds_roots_device(fixed)
                if row_roots is not None and [
                    r.tobytes() for r in rows
                ] != list(row_roots):
                    raise ValueError("repaired row roots do not match DAH")
                if col_roots is not None and [
                    c.tobytes() for c in cols
                ] != list(col_roots):
                    raise ValueError("repaired column roots do not match DAH")
        return fixed


def repair_tpu(
    eds: np.ndarray, present: np.ndarray, device=None
) -> np.ndarray:
    """Repair a (2k, 2k, B) EDS on the accelerator.

    Host plans the sweeps from the mask; the device runs them
    back-to-back with no host round-trip in between; the repaired square
    is fetched once at the end. Bit-exact vs da.repair (tests pin all
    three implementations together).
    """
    from celestia_tpu.telemetry import metrics

    k = int(eds.shape[0]) // 2
    with tracing.span("repair.device", backend="tpu", k=k,
                      entry="repair_tpu", missing=int((~present).sum())), \
            metrics.measure("repair", backend="tpu"):
        faults.fire("device.repair", entry="repair_tpu")
        from celestia_tpu.ops import transfers

        run, _ = stage_resident_repair(eds, present, device)
        out = _postprocess_repair(run(), k, entry="repair_tpu")
        # overlapped row-block download (all D2H DMAs in flight at once)
        # instead of one monolithic blocking device_get
        return transfers.device_get_chunked(out, site="repair.fetch")


def _postprocess_repair(fixed, k: int, *, entry: str):
    """The device.repair.output fault site + the integrity audit over
    the repaired square (ADR-015): a seeded bitflip damages the result
    in flight, and the syndrome audit must raise IntegrityError before
    any caller trusts the bytes. Audits off = one boolean check."""
    flip = faults.fire("device.repair.output", entry=entry)
    if flip is not None:
        import jax.numpy as jnp

        fixed = jnp.asarray(flip(fixed))
    eng = integrity.get()
    if eng.enabled:
        integrity.audit_or_raise(eng, fixed, k,
                                 site="device.repair.output",
                                 where="device.repair")
    return fixed
