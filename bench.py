#!/usr/bin/env python
"""BASELINE benchmark suite: the five configs of BASELINE.md over the TPU
pipeline (celestia_tpu.ops.extend_tpu) vs the host CPU path (the native
C++ runtime when built — this repo's stand-in for the reference's
rsmt2d/Leopard SIMD path — else numpy/hashlib).

Headline (BASELINE config 3): ExtendBlock at the mainnet-max 128x128
square (8 MB) -> 256x256 EDS + NMT row/col roots, DAH byte-parity
asserted against the CPU path before timing counts.

Device times use a SLOPE fit: run N1 and N2 back-to-back dispatches,
fetch results to force completion, and report (t2-t1)/(N2-N1) — the
serialized per-call device time with the constant dispatch and fetch
overhead cancelled. The raw single-dispatch number (result fetch
included) is reported alongside as `tpu_single_dispatch_with_fetch_ms`,
with the measured fetch floor. None of these numbers has been measured
on the current tree (see PERF.md).

The run needs the accelerator: on the CPU backend, when the device
cannot be reached, or when any config throws, it exits non-zero. No
path replays numbers from an earlier run.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
vs_baseline = CPU_ms / value (speedup; target >= 10).
"""

import json
import pathlib
import sys
import time

import numpy as np


def build_square(k: int, seed: int = 42) -> np.ndarray:
    rng = np.random.default_rng(seed)
    import celestia_tpu.namespace as ns

    flat = rng.integers(0, 256, size=(k * k, 512), dtype=np.uint8)
    subs = sorted(rng.integers(0, 200, size=(k * k, 10), dtype=np.uint8).tolist())
    for i, sub in enumerate(subs):
        flat[i, :29] = np.frombuffer(ns.new_v0(bytes(sub)).bytes, dtype=np.uint8)
    return flat.reshape(k, k, 512)


def time_host_extend(sq: np.ndarray, repeats: int):
    """CPU baseline for extend+roots; native C++ when available."""
    from celestia_tpu import da, native

    use_native = native.available()
    best = float("inf")
    dah = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        if use_native:
            _eds, _rows, _cols, dah = native.extend_and_root_native(sq)
        else:
            eds = da.extend_shares(sq)
            dah = da.new_data_availability_header(eds).hash()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3, dah, ("native-cc" if use_native else "host-numpy")


def _slope(dispatch, fetch, n1=8, n2=48, tries=3):
    """True serialized per-call device time via two-point fit.

    `dispatch(i)` is called with a rotating index so callers can cycle
    distinct input buffers — back-to-back identical dispatches measure
    faster than real traffic (result caching / HBM locality)."""
    fetch(dispatch(0))  # warm
    slopes = []
    for _ in range(tries):
        t0 = time.perf_counter()
        r = None
        for i in range(n1):
            r = dispatch(i)
        fetch(r)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(n2):
            r = dispatch(i)
        fetch(r)
        t2 = time.perf_counter() - t0
        slopes.append((t2 - t1) / (n2 - n1))
    # median, not min: one jitter-induced negative slope must not win and
    # then get clamped into a fabricated speedup
    slopes.sort()
    return slopes[len(slopes) // 2] * 1e3


def _single_with_fetch(dispatch, fetch, repeats=5):
    fetch(dispatch())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fetch(dispatch())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def bench_extend_config(k: int):
    """Configs 1-3: full extend+roots at square size k."""
    import jax
    import jax.numpy as jnp

    from celestia_tpu import da
    from celestia_tpu.ops import extend_tpu, rs_tpu

    sq = build_square(k)
    cpu_ms, dah_cpu, cpu_backend = time_host_extend(sq, repeats=3)

    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
    fn = jax.jit(lambda s: extend_tpu.extend_and_roots_only(s, m2))
    devs = [jax.device_put(build_square(k, seed=42 + i)) for i in range(4)]
    dev = devs[0]

    def fetch_roots(r):
        return np.asarray(r[1]), np.asarray(r[2])

    rows, cols = fetch_roots(fn(dev))
    dah_tpu = da.DataAvailabilityHeader(
        [r.tobytes() for r in rows], [c.tobytes() for c in cols]
    ).hash()
    parity = dah_tpu == dah_cpu

    # scale repeat counts so small squares aren't drowned by dispatch noise
    if k <= 4:
        n1, n2 = (64, 768)
    elif k <= 32:
        n1, n2 = (32, 192)
    else:
        n1, n2 = (8, 48)
    tpu_ms = _slope(lambda i: fn(devs[i % 4]), fetch_roots, n1=n1, n2=n2)
    noise_limited = tpu_ms <= 0  # device time below measurement noise
    single_ms = _single_with_fetch(lambda: fn(dev), fetch_roots)
    return {
        "cpu_ms": round(cpu_ms, 3),
        "cpu_backend": cpu_backend,
        "tpu_ms": None if noise_limited else round(tpu_ms, 3),
        "tpu_single_dispatch_with_fetch_ms": round(single_ms, 3),
        "speedup": None if noise_limited else round(cpu_ms / tpu_ms, 2),
        "parity": bool(parity),
        "dah": dah_tpu.hex(),
    }


def bench_nmt_only(k: int):
    """Config 5: NMT row/col roots over an existing 2k x 2k EDS."""
    import jax
    import jax.numpy as jnp

    from celestia_tpu import da, native
    from celestia_tpu.appconsts import NAMESPACE_SIZE
    from celestia_tpu.ops import extend_tpu, rs_tpu

    sq = build_square(k)
    eds_np = da.extend_shares(sq).data

    use_native = native.available()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        if use_native:
            native.eds_nmt_roots(eds_np)
        else:
            e = da.ExtendedDataSquare(eds_np, k)
            e.row_roots(), e.col_roots()
        best = min(best, time.perf_counter() - t0)
    cpu_ms = best * 1e3

    leaf_ns = extend_tpu._leaf_namespaces(
        jnp.asarray(sq)[..., :NAMESPACE_SIZE], k
    )

    @jax.jit
    def roots(eds):
        return extend_tpu.nmt_roots_of_eds(eds, leaf_ns)

    dev = jax.device_put(eds_np)

    def fetch(r):
        return np.asarray(r[0]), np.asarray(r[1])

    tpu_ms = _slope(lambda i: roots(dev), fetch)
    noise_limited = tpu_ms <= 0
    return {
        "cpu_ms": round(cpu_ms, 3),
        "cpu_backend": "native-cc" if use_native else "host-numpy",
        "tpu_ms": None if noise_limited else round(tpu_ms, 3),
        "speedup": None if noise_limited else round(cpu_ms / tpu_ms, 2),
    }


def bench_repair(k: int, erase_frac: float = 0.25):
    """Config 4: Repair of a 2k x 2k EDS with 25% random erasures,
    CPU vs TPU (BASELINE.md config 4, rsmt2d.Repair).

    CPU baseline: the native C++ Leopard O(n log n) erasure decode
    (native/leopard.cc eds_repair) — this build's stand-in for the
    reference's klauspost SIMD decode. The numpy host path is reported
    alongside for continuity with earlier rounds.

    Accelerated path: ops/repair_tpu — the host plans the sweep schedule
    from the presence mask alone (mask evolution is value-independent),
    then the MXU runs the shared pattern-independent decode core as one
    (8n x 8n) GF(2) bit-matmul batched over all axes; only the tiny
    locator constants travel per sweep. tpu_ms = plan_host_ms + slope-fit
    device sweep time (same slope methodology as configs 1-3); the raw
    wall time with the 32 MB EDS up+down is reported separately as
    tpu_wall_with_transfers_ms."""
    from celestia_tpu import da, native
    from celestia_tpu.da import repair as repair_mod
    from celestia_tpu.ops import repair_tpu

    sq = build_square(k)
    eds = da.extend_shares(sq).data
    width = 2 * k
    masks, srcs = [], []
    for i in range(4):
        rng = np.random.default_rng(7 + i)
        present = np.ones((width, width), dtype=bool)
        flat = rng.choice(
            width * width, size=int(erase_frac * width * width), replace=False
        )
        present.reshape(-1)[flat] = False
        masks.append(present)
        srcs.append(np.where(present[..., None], eds, 0))

    # --- CPU baseline (native C++; numpy fallback) ---
    use_native = native.available()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        if use_native:
            fixed = native.eds_repair(srcs[0], masks[0])
        else:
            fixed = repair_mod.repair(srcs[0], masks[0].copy())
        best = min(best, time.perf_counter() - t0)
    cpu_ms = best * 1e3
    ok_cpu = np.array_equal(fixed, eds)

    t0 = time.perf_counter()
    fixed_np = repair_mod.repair(srcs[0], masks[0].copy())
    host_numpy_ms = (time.perf_counter() - t0) * 1e3
    ok_np = np.array_equal(fixed_np, eds)

    # --- accelerated ---
    t0 = time.perf_counter()
    fixed_tpu = repair_tpu.repair_tpu(srcs[0], masks[0])
    wall_cold = (time.perf_counter() - t0) * 1e3
    ok_tpu = np.array_equal(fixed_tpu, eds)
    # ONE warm repetition of this transfer-bound documentation number
    t0 = time.perf_counter()
    repair_tpu.repair_tpu(srcs[0], masks[0])
    wall_ms = (time.perf_counter() - t0) * 1e3

    # --- repair-after-extend: the node's real flow (VERDICT r3 item 2).
    # The EDS the node just extended is already in HBM
    # (extend_roots_device_resident); repair consumes the device handle,
    # verifies the repaired roots on device, and only the axis roots
    # (2·2k·90 B) ever cross back. Measured as the full cycle a catching-
    # up node runs per block: plan (host, from the mask) + sweeps
    # (device) + root recompute (device) + root fetch/compare (host).
    from celestia_tpu import da as da_pkg
    from celestia_tpu.ops import extend_tpu

    dah_ref = da_pkg.new_data_availability_header(da_pkg.ExtendedDataSquare(eds, k))
    eds_dev, _rr, _cc = extend_tpu.extend_roots_device_resident(sq)

    def resident_cycle(i):
        m = masks[i % 4]
        fixed = repair_tpu.repair_resident_verified(
            eds_dev, m, dah_ref.row_roots, dah_ref.column_roots
        )
        return fixed

    # warm/compile; correctness is asserted ON DEVICE — the cycle
    # recomputes the NMT roots of the repaired square and compares them
    # to the true DAH (raises on mismatch), so no 32 MB fetch is needed
    try:
        resident_cycle(0)
        ok_resident = True
    except ValueError:
        ok_resident = False
    best = float("inf")
    for i in range(3):
        t0 = time.perf_counter()
        resident_cycle(i)
        best = min(best, time.perf_counter() - t0)
    wall_after_extend_single = best * 1e3
    # streaming: per-repair wall when repairs run back-to-back (the
    # catching-up-node shape); fetch is inside each cycle so the slope
    # charges the per-call root fetch honestly
    stream_ms = _slope(resident_cycle, lambda r: r, n1=4, n2=16, tries=3)

    plan_ms = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        plans = repair_tpu.plan_sweeps(masks[0], k)
        plan_ms = min(plan_ms, (time.perf_counter() - t0) * 1e3)

    # slope-fit the shipped resident sweep chain (re-dispatch is sound:
    # sweeps are idempotent on repaired data)
    chains = [
        repair_tpu.stage_resident_repair(src, mask)[0]
        for src, mask in zip(srcs, masks)
    ]

    def fetch(r):
        return np.asarray(r[0, 0])

    sweep_ms = _slope(lambda i: chains[i % 4](), fetch, n1=4, n2=24)
    noise_limited = sweep_ms <= 0
    tpu_ms = None if noise_limited else plan_ms + sweep_ms
    return {
        "cpu_ms": round(cpu_ms, 3),
        "cpu_backend": "native-cc" if use_native else "host-numpy",
        "host_numpy_ms": round(host_numpy_ms, 3),
        "tpu_ms": None if tpu_ms is None else round(tpu_ms, 3),
        "tpu_plan_host_ms": round(plan_ms, 3),
        "tpu_sweep_device_ms": None if noise_limited else round(sweep_ms, 3),
        "tpu_wall_with_transfers_ms": round(wall_ms, 3),
        "tpu_wall_cold_ms": round(wall_cold, 3),
        "tpu_wall_after_extend_ms": round(wall_after_extend_single, 3),
        "tpu_wall_after_extend_stream_ms": (
            round(stream_ms, 3) if stream_ms > 0 else None
        ),
        "sweeps": len(plans),
        "speedup": None if tpu_ms is None else round(cpu_ms / tpu_ms, 2),
        "recovered": bool(ok_cpu and ok_np and ok_tpu and ok_resident),
    }


def bench_batched_throughput(k: int, batch: int = 8):
    """Supplementary: multi-square throughput (state sync / replay / many
    proposals) on one chip. The HEADLINE stays the unbatched single-call
    number. tpu_ms_per_batch is the historical full-vmap extend (EDS
    outputs materialized); roots_only is the shipped path — ONE dispatch
    whose lax.map/vmap chunking (ops/extend_tpu._batch_chunk) bounds the
    HBM working set, which is what removed the round-3 k=128 regression
    (7.99 vs 5.03 ms/square). The node's replay verifier now uses this
    single code path at every size (node.py
    _batch_verify_data_availability)."""
    import jax
    import jax.numpy as jnp

    from celestia_tpu.ops import extend_tpu, rs_tpu

    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))

    @jax.jit
    def run(batched):
        return extend_tpu.extend_and_root_batched(batched, m2)

    import numpy as _np

    devs = [
        jax.device_put(
            _np.stack([build_square(k, seed=100 + 17 * b + i) for i in range(batch)])
        )
        for b in range(4)
    ]

    def fetch(r):
        return _np.asarray(r[3])

    per_batch_ms = _slope(lambda i: run(devs[i % 4]), fetch, n1=4, n2=24)
    if per_batch_ms <= 0:
        return {"batch": batch, "note": "below measurement noise"}

    # roots-only: no B x EDS output buffers — the replay verifier's path
    # (ops/extend_tpu.batched_roots_device): one vmapped dispatch for
    # small squares; large squares pipeline vmappable CHUNKS (pairs) of
    # the cached chunk program, which bounds the HBM working set at
    # chunk x single while still amortizing dispatch — the fix for the
    # round-5 "pipelined-singles" degradation at k=128. chunk == 1 only
    # survives as a last-resort spelling (batch == 1).
    roots_map_fn = extend_tpu._jitted_batched_roots(k)
    single_fn = extend_tpu._jitted_roots_noeds(k)
    chunk = extend_tpu._batch_chunk(k, batch)

    def fetch_roots(r):
        return _np.asarray(r[0])

    if chunk >= batch:
        spelling = "vmapped"
        roots_ms = _slope(
            lambda i: roots_map_fn(devs[i % 4]), fetch_roots, n1=4, n2=24
        )
    elif chunk > 1:
        spelling = f"pipelined-chunks({chunk})"
        chunk_fn = extend_tpu._jitted_chunk_roots(k, chunk)

        def dispatch(i):
            return [
                chunk_fn(devs[i % 4][g : g + chunk])
                for g in range(0, batch, chunk)
            ][-1]

        roots_ms = _slope(dispatch, fetch_roots, n1=4, n2=24)
    else:
        spelling = "pipelined-singles"

        def dispatch(i):
            return [single_fn(devs[i % 4][j]) for j in range(batch)][-1]

        roots_ms = _slope(dispatch, fetch_roots, n1=4, n2=24)
    return {
        "batch": batch,
        "roots_only_ms_per_square": (
            round(roots_ms / batch, 3) if roots_ms > 0 else None
        ),
        "roots_only_spelling": spelling,
        "tpu_ms_per_batch": round(per_batch_ms, 3),
        "tpu_ms_per_square": round(per_batch_ms / batch, 3),
    }


def bench_square_construct(tx_count: int, blob_size: int):
    """The reference's own square-construction benchmark shape
    (pkg/square/square_benchmark_test.go:16-56: Build over txCount PFB
    txs of blobSize bytes). Host-only in both builds — square packing
    is orchestration, not codec work — recorded so the harness parity
    with the reference's bench surface is complete."""
    from celestia_tpu import blob as blob_pkg
    from celestia_tpu import namespace as ns
    from celestia_tpu import square as square_pkg
    from celestia_tpu.appconsts import square_size_upper_bound
    from celestia_tpu.crypto import PrivateKey
    from celestia_tpu.tx import Fee, sign_tx
    from celestia_tpu.x.blob.types import estimate_gas, new_msg_pay_for_blobs

    key = PrivateKey.from_secret(b"bench-square")
    signer_addr = key.bech32_address()
    txs = []
    for i in range(tx_count):
        b = blob_pkg.new_blob(
            ns.new_v0(b"bench" + i.to_bytes(5, "big")), bytes([i & 0xFF]) * blob_size, 0
        )
        msg = new_msg_pay_for_blobs(signer_addr, b)
        gas = estimate_gas([blob_size])
        tx = sign_tx(key, [msg], "bench", 0, i, Fee(amount=gas, gas_limit=gas))
        txs.append(blob_pkg.marshal_blob_tx(tx.marshal(), [b]))

    best = float("inf")
    kept = 0
    # 8 repeats: the first warms the parse/layout memos the node's own
    # Prepare/Process/Deliver re-builds share, the rest sample the warm
    # path (the reference's Go benchmark auto-scales iterations the
    # same way); best-of filters scheduler noise
    for _ in range(8):
        t0 = time.perf_counter()
        square, kept_txs = square_pkg.build(txs, 1, square_size_upper_bound(1))
        best = min(best, time.perf_counter() - t0)
        kept = len(kept_txs)
    return {
        "tx_count": tx_count,
        "blob_size": blob_size,
        "build_ms": round(best * 1e3, 3),
        "txs_kept": kept,
        "square_size": square_pkg.square_size(len(square)),
    }


def bench_sha256_kernels(n: int = 65536, length: int = 571):
    """Supplementary: the two SHA-256 spellings head-to-head on the
    k=128 leaf workload, HBM-resident input (where the Pallas kernel
    wins; inside the fused pipeline XLA's leaf-construction fusion wins
    instead — see ops/sha256_pallas.py's docstring for both numbers)."""
    import jax

    if jax.default_backend() == "cpu":
        # Mosaic kernels don't lower on the CPU backend. Unreachable
        # via main() (the probe refuses the cpu backend outright) but
        # kept for direct callers of this function
        return {"skipped": "no TPU device (pallas kernels need Mosaic)"}
    import jax.numpy as jnp

    from celestia_tpu.ops import sha256_jax, sha256_pallas

    rng = np.random.default_rng(9)
    devs = [
        jax.device_put(
            jnp.asarray(
                rng.integers(0, 256, size=(n, length), dtype=np.uint8)
            )
        )
        for _ in range(4)
    ]
    jit_x = jax.jit(sha256_jax.sha256_fixed)
    jit_p = jax.jit(sha256_pallas.sha256_fixed)

    def fetch(r):
        return np.asarray(r)

    xla_ms = _slope(lambda i: jit_x(devs[i % 4]), fetch, n1=8, n2=48)
    pallas_ms = _slope(lambda i: jit_p(devs[i % 4]), fetch, n1=8, n2=48)
    ok = np.asarray(jit_p(devs[0])).tobytes() == np.asarray(
        jit_x(devs[0])
    ).tobytes()
    return {
        "messages": n,
        "length": length,
        "xla_ms": round(xla_ms, 3) if xla_ms > 0 else None,
        "pallas_ms": round(pallas_ms, 3) if pallas_ms > 0 else None,
        "parity": bool(ok),
    }


def bench_fused_kernels(k: int):
    """Config 12 (ADR-019): the fused Pallas extend+hash ROOTS-ONLY
    pipeline vs the XLA roots path vs the native-CPU baseline at one k.
    The fused spelling keeps parity planes + leaf messages in VMEM and
    returns 90-byte NMT axis roots — HBM never sees the unpacked
    message tensor. Parity is gated against the host DAH (byte compare of
    every row/col root)."""
    import jax

    if jax.default_backend() == "cpu":
        # Mosaic kernels don't lower on the CPU backend; the eager
        # reference spelling is covered by tests, not benched
        return {"skipped": "no TPU device (fused pallas pipeline needs Mosaic)"}
    from celestia_tpu import da, native
    from celestia_tpu.ops import extend_tpu, rs_pallas

    if not rs_pallas.fused_supported(k, k * 512):
        return {"skipped": f"fused kernel unsupported at k={k}"}

    sq = build_square(k)
    devs = [jax.device_put(build_square(k, seed=100 + i)) for i in range(4)]
    fused_fn = extend_tpu._jitted_roots_noeds(k, True)
    xla_fn = extend_tpu._jitted_roots_noeds(k, False)

    def fetch(r):
        return np.asarray(r[0])

    fused_ms = _slope(lambda i: fused_fn(devs[i % 4]), fetch, n1=4, n2=24)
    xla_ms = _slope(lambda i: xla_fn(devs[i % 4]), fetch, n1=4, n2=24)

    rows_f, cols_f = (np.asarray(a) for a in fused_fn(jax.device_put(sq)))
    eds_ref = da.extend_shares(sq.reshape(k * k, 512))
    dah_ref = da.new_data_availability_header(eds_ref)
    parity = (
        [bytes(r) for r in rows_f] == dah_ref.row_roots
        and [bytes(c) for c in cols_f] == dah_ref.column_roots
    )

    native_ms = None
    if native.available():
        native.extend_and_root_native(sq)  # warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            native.extend_and_root_native(sq)
            best = min(best, time.perf_counter() - t0)
        native_ms = best * 1e3
    return {
        "square_size": k,
        "fused_ms_per_square": round(fused_ms, 3) if fused_ms > 0 else None,
        "xla_roots_ms_per_square": round(xla_ms, 3) if xla_ms > 0 else None,
        "native_ms_per_square": (
            round(native_ms, 3) if native_ms is not None else None
        ),
        "fused_vs_xla_speedup": (
            round(xla_ms / fused_ms, 2) if fused_ms > 0 and xla_ms > 0 else None
        ),
        "fused_vs_native_speedup": (
            round(native_ms / fused_ms, 2)
            if fused_ms > 0 and native_ms is not None
            else None
        ),
        "parity": bool(parity),
    }


def bench_node_path(k: int):
    """Node-path proposal flow: square -> DAH through App._proposal_dah —
    the code Prepare/ProcessProposal and `cli start` actually run
    (backend resolution, share-bytes assembly, roots-only device
    dispatch, host DAH merkle). On the TPU backend the EDS never leaves
    the device (ops/extend_tpu.roots_device): the wall includes the
    upload of the 8 MB square but fetches only
    2·2k·90 B of roots — the round-3 number that fetched (and discarded)
    the 32 MB EDS is kept as tpu_wall_with_eds_fetch_ms for comparison.
    Asserts all backends produce the same DAH through the node path."""
    from celestia_tpu.app.app import App
    from celestia_tpu.shares import Share

    sq = build_square(k)
    data_square = [Share(bytes(s)) for s in sq.reshape(k * k, 512)]

    out = {}
    hashes = {}
    for backend in ("native", "tpu"):
        app = App(extend_backend=backend)
        try:
            dah = app._proposal_dah(data_square)  # warm/compile
        except Exception as e:  # noqa: BLE001 — e.g. device init failure
            out[f"{backend}_error"] = str(e)[:120]
            continue
        if app._active_backend != backend:
            # e.g. native toolchain missing: resolve fell back to numpy —
            # don't record a timing under a label that didn't run
            out[f"{backend}_error"] = f"degraded to {app._active_backend}"
            continue
        hashes[backend] = dah.hash()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            app._proposal_dah(data_square)
            best = min(best, time.perf_counter() - t0)
        key = "tpu_wall_roots_only_ms" if backend == "tpu" else f"{backend}_ms"
        out[key] = round(best * 1e3, 3)
        if backend == "tpu":
            # streaming: back-to-back proposal verifications (the busy /
            # catching-up node shape) — the dispatch round trip
            # amortizes across the async dispatch queue
            stream_ms = _slope(
                lambda i: app._proposal_dah(data_square),
                lambda r: r, n1=2, n2=8, tries=3,
            )
            out["tpu_wall_roots_only_stream_ms"] = (
                round(stream_ms, 3) if stream_ms > 0 else None
            )
            # the ExtendBlock path: EDS produced but device-resident
            # (lazy ExtendedDataSquare — nothing fetched)
            app._extend_and_hash(data_square)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                app._extend_and_hash(data_square)
                best = min(best, time.perf_counter() - t0)
            out["tpu_wall_extend_lazy_ms"] = round(best * 1e3, 3)
            # round-3 semantics: force the full 32 MB EDS fetch (ONE
            # run — transfer-bound documentation number)
            t0 = time.perf_counter()
            eds_sq, _d = app._extend_and_hash(data_square)
            _ = eds_sq.data  # materialize on host
            out["tpu_wall_with_eds_fetch_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 3
            )
    # parity is only meaningful when at least two backends really ran;
    # main() asserts every "parity" key, so omit it otherwise
    if len(hashes) >= 2:
        out["parity"] = len(set(hashes.values())) == 1
    else:
        out["parity_note"] = "fewer than two backends ran; nothing to compare"
    out["live_backend_at_k"] = App(extend_backend="auto").resolve_extend_backend(k)
    return out


def bench_node_path_arena(k: int = 128):
    """Config 8b: the proposal wall with the device blob arena
    (ops/blob_pool.py) — the shape `cli start --extend-backend tpu`
    runs once the mempool has staged the block's blobs in HBM at
    CheckTx time. The square is assembled ON DEVICE: per proposal only
    share metadata (~300 KB at k=128) crosses the interconnect instead
    of the 8 MB square, so the wall is round-trip-bound, not
    bandwidth-bound."""
    from celestia_tpu import blob as blob_pkg
    from celestia_tpu import namespace as ns_pkg
    from celestia_tpu import square as square_pkg
    from celestia_tpu.app.app import App
    from celestia_tpu.crypto import PrivateKey
    from celestia_tpu.tx import Fee, sign_tx
    from celestia_tpu.x.blob.types import estimate_gas, new_msg_pay_for_blobs

    # blob-heavy block: ~60 x 120 KB blobs fills a k=128 square
    key = PrivateKey.from_secret(b"bench-arena")
    addr = key.bech32_address()
    rng = np.random.default_rng(11)
    txs = []
    blob_size = 120_000
    for i in range(60):
        data = rng.integers(0, 256, blob_size, dtype=np.uint8).tobytes()
        b = blob_pkg.new_blob(
            ns_pkg.new_v0(b"arena" + i.to_bytes(5, "big")), data, 0
        )
        gas = estimate_gas([blob_size])
        tx = sign_tx(key, [new_msg_pay_for_blobs(addr, b)], "bench", 0, i,
                     Fee(amount=gas, gas_limit=gas))
        txs.append(blob_pkg.marshal_blob_tx(tx.marshal(), [b]))
    square, _kept, builder = square_pkg.build_ex(txs, 1, k)
    got_k = square_pkg.square_size(len(square))

    from celestia_tpu import native

    use_native = native.available()
    arr = np.frombuffer(
        b"".join(s.data for s in square), dtype=np.uint8
    ).reshape(got_k, got_k, 512)
    best = float("inf")
    dah_native = None
    for _ in range(3):
        t0 = time.perf_counter()
        if use_native:
            _e, _r, _c, dah_native = native.extend_and_root_native(arr)
        best = min(best, time.perf_counter() - t0)
    native_ms = best * 1e3 if use_native else None

    app = App(extend_backend="tpu")
    arena = app.enable_blob_pool()
    # CheckTx-time staging cost, off-path: put_many dispatches every
    # blob's H2D DMA before the donated inserts consume them — uploads
    # overlap instead of the per-blob upload→insert lockstep (the 854 ms
    # round-5 number was the sequential loop)
    t0 = time.perf_counter()
    arena.put_many([blob.data for _start, blob in builder.blob_layout()])
    staging_ms = (time.perf_counter() - t0) * 1e3

    dah = app._assembled_proposal_dah(square, builder, got_k)  # warm/compile
    if dah is None:
        return {"error": "arena path declined (residency)"}
    if dah_native is None:
        # no native runtime: check against the independent host python
        # path instead — a parity key must never be vacuous
        from celestia_tpu import da as da_pkg
        from celestia_tpu.shares import to_bytes as _to_bytes

        dah_native = da_pkg.new_data_availability_header(
            da_pkg.extend_shares(_to_bytes(square))
        ).hash()
    parity = dah.hash() == dah_native
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        app._assembled_proposal_dah(square, builder, got_k)
        best = min(best, time.perf_counter() - t0)
    stream = _slope(
        lambda i: app._assembled_proposal_dah(square, builder, got_k),
        lambda r: r, n1=2, n2=8, tries=3,
    )
    # churn regime: a working set ~2x the arena forces eviction (half
    # flips) between proposals — the busy-node oscillation (VERDICT r4
    # weak 5).
    # Report the measured hit rate and the wall under churn.
    churn_app = App(extend_backend="tpu")
    churn_arena = churn_app.enable_blob_pool(
        capacity_bytes=30 * 1024 * 1024  # < the ~7.2 MB x 8 working sets
    )
    churn_walls = []
    for i in range(8):
        c_txs = []
        rng_i = np.random.default_rng(100 + i)
        for j in range(60):
            data = rng_i.integers(0, 256, blob_size, dtype=np.uint8).tobytes()
            b = blob_pkg.new_blob(
                ns_pkg.new_v0(b"chrn" + bytes([i, j]) * 3), data, 0
            )
            gas = estimate_gas([blob_size])
            tx = sign_tx(key, [new_msg_pay_for_blobs(addr, b)], "bench", 0,
                         60 + i * 60 + j, Fee(amount=gas, gas_limit=gas))
            c_txs.append(blob_pkg.marshal_blob_tx(tx.marshal(), [b]))
        c_square, _k2, c_builder = square_pkg.build_ex(c_txs, 1, k)
        churn_arena.put_many(
            [blob.data for _start, blob in c_builder.blob_layout()]
        )
        t0 = time.perf_counter()
        churn_app._proposal_dah(c_square, c_builder)
        churn_walls.append((time.perf_counter() - t0) * 1e3)
    stats = churn_app.arena_stats
    total_props = stats["assembled"] + stats["fallback"]
    return {
        "square_size": got_k,
        "blob_bytes": 60 * blob_size,
        "native_ms": round(native_ms, 3) if native_ms else None,
        "tpu_wall_arena_ms": round(best * 1e3, 3),
        "tpu_wall_arena_stream_ms": round(stream, 3) if stream > 0 else None,
        "staging_ms_offpath": round(staging_ms, 3),
        "parity": bool(parity),
        "churn_hit_rate": (
            round(stats["assembled"] / total_props, 3) if total_props else None
        ),
        "churn_proposals": total_props,
        "churn_wall_ms_best": round(min(churn_walls), 3),
        "churn_wall_ms_median": round(sorted(churn_walls)[len(churn_walls) // 2], 3),
    }


def bench_sliced_sample(k: int = 128, samples: int = 16):
    """Config 11: DAS serving cost from a DEVICE-RESIDENT EDS — the
    round-5 pain point where serving ONE sample forced the full 32 MB
    fetch (da/__init__.py's lazy `.data`). Compares the legacy
    full-fetch path against the transfer-aware sliced accessors
    (ops/transfers): `samples` random share reads plus one full row (the
    /sample proof-serving unit). Bytes moved are read back from the
    transfer_bytes telemetry, so the numbers are the counters operators
    see, not a separate estimate. parity: every sliced byte equals the
    full-fetch byte."""
    from celestia_tpu import da
    from celestia_tpu.ops import extend_tpu
    from celestia_tpu.telemetry import metrics

    sq = build_square(k)
    eds_dev, _rows, _cols = extend_tpu.extend_roots_device_resident(sq)
    w = 2 * k
    rng = np.random.default_rng(7)
    coords = [(int(r), int(c)) for r, c in rng.integers(0, w, size=(samples, 2))]

    def _counters():
        return sum(
            metrics.get_counter("transfer_bytes", site=s, direction="d2h")
            for s in ("eds.row", "eds.col", "eds.share")
        )

    # legacy semantics: materialize the whole square to serve anything
    # (fresh handle per run so `.data` genuinely re-fetches)
    best_full = float("inf")
    for _ in range(2):
        handle = da.ExtendedDataSquare.from_device(eds_dev, k)
        t0 = time.perf_counter()
        arr = handle.data
        full_vals = [arr[r, c].tobytes() for r, c in coords]
        best_full = min(best_full, time.perf_counter() - t0)
    full_bytes = int(arr.nbytes)

    # sliced path (warm once: the dynamic-slice programs compile here)
    da.ExtendedDataSquare.from_device(eds_dev, k).share(0, 0)
    best_sliced = float("inf")
    for _ in range(3):
        handle = da.ExtendedDataSquare.from_device(eds_dev, k)
        b0 = _counters()
        t0 = time.perf_counter()
        sliced_vals = [handle.share(r, c) for r, c in coords]
        best_sliced = min(best_sliced, time.perf_counter() - t0)
        sliced_bytes = int(_counters() - b0)
    handle = da.ExtendedDataSquare.from_device(eds_dev, k)
    b0 = _counters()
    t0 = time.perf_counter()
    row_cells = handle.row(coords[0][0])
    row_ms = (time.perf_counter() - t0) * 1e3
    row_bytes = int(_counters() - b0)

    parity = sliced_vals == full_vals and row_cells == [
        arr[coords[0][0], c].tobytes() for c in range(w)
    ]
    return {
        "square_size": k,
        "samples": samples,
        "full_fetch_ms": round(best_full * 1e3, 3),
        "full_fetch_bytes": full_bytes,
        "sliced_shares_ms": round(best_sliced * 1e3, 3),
        "sliced_shares_bytes": sliced_bytes,
        "sliced_row_ms": round(row_ms, 3),
        "sliced_row_bytes": row_bytes,
        "parity": bool(parity),
    }


def bench_native_parallel(k: int = 128, threads: int | None = None):
    """Config 3b: MULTI-threaded native baseline (VERDICT round-5: the
    91.75x headline compares against a single-threaded native run;
    ctypes releases the GIL during the foreign call, so the honest CPU
    ceiling is T concurrent extend_and_root calls on T squares). The
    per-square number under full thread occupancy is the baseline the
    headline speedup should be read against."""
    import concurrent.futures
    import os

    from celestia_tpu import native

    if not native.available():
        return {"error": "native toolchain unavailable"}
    t_count = threads or min(8, os.cpu_count() or 1)
    squares = [build_square(k, seed=100 + i) for i in range(t_count)]
    native.extend_and_root_native(squares[0])  # warm (library init)
    single = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        native.extend_and_root_native(squares[0])
        single = min(single, time.perf_counter() - t0)
    best_wall = float("inf")
    with concurrent.futures.ThreadPoolExecutor(t_count) as pool:
        for _ in range(3):
            t0 = time.perf_counter()
            list(pool.map(native.extend_and_root_native, squares))
            best_wall = min(best_wall, time.perf_counter() - t0)
    per_square = best_wall / t_count
    return {
        "square_size": k,
        "threads": t_count,
        "native_single_thread_ms": round(single * 1e3, 3),
        "native_parallel_wall_ms": round(best_wall * 1e3, 3),
        "native_parallel_ms_per_square": round(per_square * 1e3, 3),
        # single_wall / parallel_wall: 1.0 = perfect scaling (T squares
        # in the time of one). The honest-baseline divisor for the
        # headline is native_parallel_ms_per_square.
        "scaling_efficiency": round(single / best_wall, 3) if best_wall else None,
    }


def bench_codec_service(k: int = 32):
    """Codec service boundary (SURVEY P2): round-trip overhead of the
    gRPC sidecar vs the same backend called in-process, measured on
    ExtendAndRoot (roots-only reply keeps the response small the way a
    production boundary would)."""
    from celestia_tpu import da
    from celestia_tpu.service import CodecClient, CodecServer

    sq = build_square(k)
    server = CodecServer(port=0, use_tpu=False)
    server.start()
    client = CodecClient(f"127.0.0.1:{server.port}")
    try:
        rows, _cols, dah = client.extend_and_root(sq)  # warm + parity
        eds_ref = da.extend_shares(sq.reshape(k * k, 512))
        dah_ref = da.new_data_availability_header(eds_ref)
        parity = dah == dah_ref.hash() and rows == dah_ref.row_roots

        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            client.extend_and_root(sq)
            best = min(best, time.perf_counter() - t0)
        service_ms = best * 1e3

        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            server.backend.extend_and_root(k, 512, sq.tobytes())
            best = min(best, time.perf_counter() - t0)
        inproc_ms = best * 1e3
    finally:
        client.close()
        server.stop()
    # best-of-3 timers on two code paths can invert by scheduler noise,
    # producing a nonsense NEGATIVE "overhead". Report the signed delta
    # as-is, but clamp the overhead claim at a noise floor: deltas whose
    # magnitude is under 5% of the in-process time (or 50 µs absolute)
    # are indistinguishable from zero on this harness.
    delta_ms = service_ms - inproc_ms
    noise_floor_ms = max(0.05, inproc_ms * 0.05)
    return {
        "service_ms": round(service_ms, 3),
        "inprocess_ms": round(inproc_ms, 3),
        "boundary_delta_ms": round(delta_ms, 3),
        "boundary_overhead_ms": (
            round(delta_ms, 3) if delta_ms > noise_floor_ms else 0.0
        ),
        "noise_floor_ms": round(noise_floor_ms, 3),
        "parity": bool(parity),
    }


def fetch_floor_ms():
    import jax
    import jax.numpy as jnp

    x = jax.device_put(np.ones((8, 128), np.uint8))
    f = jax.jit(lambda a: a.astype(jnp.int32).sum())
    np.asarray(f(x))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(f(x))
        best = min(best, time.perf_counter() - t0)
    return round(best * 1e3, 3)


def h2d_d2h_bandwidth_mb_s():
    """Measured host<->device bandwidth (4 MB each way), recorded so
    every wall-clock number in this file's output is self-describing."""
    import jax

    x = np.ones((4 * 1024 * 1024,), np.uint8)
    t0 = time.perf_counter()
    d = jax.device_put(x)
    d.block_until_ready()
    up = 4 / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    np.asarray(d)
    down = 4 / (time.perf_counter() - t0)
    return {"up": round(up, 1), "down": round(down, 1)}


_NO_RETRY = "[no-retry] "


def _probe_device(timeout_s: float = 120.0):
    """(reachable, why) — whether the accelerator answers a tiny round
    trip within the timeout, and the failure reason otherwise. A reason
    prefixed with _NO_RETRY cannot change within this process."""
    import threading

    ok: list = []
    err: list = []

    def attempt():
        try:
            import jax

            # a cpu round trip would "succeed" and the run would record
            # cpu-vs-cpu numbers as tpu. Refuse: cpu IS unreachable.
            if jax.default_backend() == "cpu":
                # backend selection is cached for the process lifetime,
                # so retrying this is futile
                err.append(
                    _NO_RETRY
                    + "jax initialized on the cpu backend (no accelerator) "
                    "— refusing to measure 'tpu' numbers on cpu"
                )
                return
            x = jax.device_put(np.ones((8,), np.uint8))
            np.asarray(x)
            ok.append(True)
        except Exception as e:  # noqa: BLE001 — surfaced in the JSON
            err.append(f"{type(e).__name__}: {e}")

    t = threading.Thread(target=attempt, daemon=True)
    t.start()
    t.join(timeout_s)
    if ok:
        return True, None
    if err:
        return False, err[0]
    return False, f"device round trip timed out after {timeout_s:.0f}s"


def _probe_with_retries(attempts: int = 3, timeout_s: float = 60.0,
                        backoff_s: float = 15.0):
    """Bounded retry on the device probe, so one failed round trip (a
    device still initialising, a transient runtime error) does not
    condemn the whole run. Worst case: attempts*timeout + backoffs
    (~4 min). A _NO_RETRY failure gives up at once."""
    last = None
    for i in range(attempts):
        ok, why = _probe_device(timeout_s)
        if ok:
            return True, None
        last = why
        if why and why.startswith(_NO_RETRY):
            return False, why[len(_NO_RETRY):]
        if i < attempts - 1:
            time.sleep(backoff_s * (i + 1))
    return False, last


CONFIG_TIMEOUT_S = 600


class _ConfigTimeout(Exception):
    pass


def _run_config(configs: dict, provenance: dict, name: str, fn,
                *args, **kwargs) -> None:
    """Run one bench config and record its result, or its error with
    provenance "failed" (the caller's run then exits non-zero).

    A SIGALRM watchdog bounds each config: a device call that never
    returns must not hang the harness. The alarm raises at the next
    Python bytecode after the blocked call returns/aborts; the outer
    watcher's process-level timeout is the backstop when even that
    never happens."""
    import signal

    def _on_alarm(_sig, _frm):
        raise _ConfigTimeout(f"config exceeded {CONFIG_TIMEOUT_S}s")

    # `disarmed` also gates the HANDLER: alarm(0) cancels the timer but
    # not a signal already delivered and pending — the handler must
    # become a no-op the instant the guarded region ends, or a pending
    # alarm could fire during bookkeeping and clobber a measured result
    disarmed = [False]

    def _on_alarm_guarded(_sig, _frm):
        if disarmed[0]:
            return
        _on_alarm(_sig, _frm)

    armed = False
    old_handler = None
    try:
        old_handler = signal.signal(signal.SIGALRM, _on_alarm_guarded)
        signal.alarm(CONFIG_TIMEOUT_S)
        armed = True
    except ValueError:  # not the main thread: run unguarded
        pass
    try:
        try:
            result = fn(*args, **kwargs)
            _stamp_host(result)
            configs[name] = result
            if isinstance(result, dict) and result.get("parity") is False:
                provenance[name] = "parity-failed"
            else:
                provenance[name] = "measured"
        finally:
            # neutralize FIRST, then cancel the timer: anything pending
            # after this point is ignored by the guarded handler
            disarmed[0] = True
            if armed:
                signal.alarm(0)
    except Exception as e:  # noqa: BLE001 — recorded; the run fails
        configs[name] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
        provenance[name] = "failed"
    finally:
        if armed:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old_handler)


def _safe(fn, default=None):
    try:
        return fn()
    except Exception:  # noqa: BLE001
        return default


def _stamp_host(result) -> None:
    """Stamp the measuring host's shape (device count + cpus) into one
    bench result dict, so every number names the box that produced it."""
    if not isinstance(result, dict):
        return
    import os as _os

    result.setdefault("cpus", _os.cpu_count())
    result.setdefault("n_devices", _safe(
        lambda: len(__import__("jax").devices())))
    # full runtime provenance (ADR-025): jax/jaxlib versions, backend,
    # device kind, and the ADR-011 host fingerprint
    prov = _safe(lambda: __import__(
        "celestia_tpu.devledger", fromlist=["runtime_provenance"]
    ).runtime_provenance(), {}) or {}
    for key, value in prov.items():
        result.setdefault(key, value)


def main():
    headline_k = int(sys.argv[1]) if len(sys.argv) > 1 else 128

    # persistent XLA compile cache: keeps the repair/extend cold starts
    # at disk-load cost on every process start (VERDICT r3 item 2)
    from celestia_tpu.ops import enable_compile_cache

    enable_compile_cache()

    head_name = f"3_headline_k{headline_k}"
    reachable, why = _probe_with_retries()
    if not reachable:
        print(json.dumps({
            "metric": f"extend_block_k{headline_k}_tpu_ms_per_square",
            "value": None,
            "unit": "ms",
            "vs_baseline": None,
            "error": f"accelerator unreachable: {why}",
        }))
        sys.exit(1)

    configs: dict = {}
    prov: dict = {}
    _run_config(configs, prov, "1_smoke_k2", bench_extend_config, 2)
    _run_config(configs, prov, "2_k32", bench_extend_config, 32)
    _run_config(configs, prov, head_name, bench_extend_config, headline_k)
    _run_config(configs, prov, "3b_native_parallel_k128",
                bench_native_parallel, 128)
    _run_config(configs, prov, "4_repair_k128_25pct", bench_repair, 128)
    _run_config(configs, prov, "5_nmt_only_k128", bench_nmt_only, 128)
    _run_config(configs, prov, "6_codec_service_k32", bench_codec_service, 32)
    _run_config(configs, prov, "7a_batched_throughput_k32",
                bench_batched_throughput, 32)
    _run_config(configs, prov, f"7b_batched_throughput_k{headline_k}",
                bench_batched_throughput, headline_k)
    _run_config(configs, prov, f"8_node_path_k{headline_k}",
                bench_node_path, headline_k)
    _run_config(configs, prov, "8b_node_path_arena_k128",
                bench_node_path_arena, 128)
    _run_config(configs, prov, "8c_node_path_k64", bench_node_path, 64)
    _run_config(
        configs, prov, "9_square_construct",
        lambda: {
            f"tx{n}_blob{s}": bench_square_construct(n, s)
            for n, s in ((10, 10_000), (100, 1_000), (1_000, 100))
        },
    )
    _run_config(configs, prov, "10_sha256_kernels", bench_sha256_kernels)
    _run_config(configs, prov, "11_sliced_sample_k128",
                bench_sliced_sample, 128)
    _run_config(configs, prov, "12_fused_kernels_k64",
                bench_fused_kernels, 64)
    _run_config(configs, prov, "12b_fused_kernels_k32",
                bench_fused_kernels, 32)

    head = configs.get(head_name) or {}
    headline = {
        "metric": f"extend_block_k{headline_k}_tpu_ms_per_square",
        "value": head.get("tpu_ms"),
        "unit": "ms",
        "vs_baseline": head.get("speedup"),
        "cpu_baseline_ms": head.get("cpu_ms"),
        "cpu_backend": head.get("cpu_backend"),
        # slope-fit serialized per-call device time (unbatched); the
        # raw latency with the result fetch is the _with_fetch_ number
        "tpu_single_call_ms": head.get("tpu_ms"),
        "tpu_single_call_note": "slope-fit per-call device time, unbatched; dispatch+fetch overhead excluded (see tpu_single_dispatch_with_fetch_ms and fetch_floor_ms)",
        "tpu_single_dispatch_with_fetch_ms": head.get(
            "tpu_single_dispatch_with_fetch_ms"
        ),
        "fetch_floor_ms": _safe(fetch_floor_ms),
        "h2d_d2h_bandwidth_mb_s": _safe(h2d_d2h_bandwidth_mb_s),
        "dah": head.get("dah"),
        "parity": head.get("parity"),
    }
    _finish(headline, configs, prov, "DAH mismatch between CPU and TPU paths")


def _finish(headline: dict, configs: dict, prov: dict,
            parity_msg: str) -> None:
    """Print the run's one JSON line, then exit non-zero (an explicit
    raise, not assert — python -O must not silence a DAH mismatch) when
    a config failed its parity check or threw."""
    out = dict(headline)
    out["configs"] = configs
    bad = {k: v for k, v in prov.items() if v != "measured"}
    if bad:
        out["failed_configs"] = bad
    print(json.dumps(out))
    parity = [n for n, v in bad.items() if v == "parity-failed"]
    if parity:
        raise SystemExit(f"{parity_msg}: {parity}")
    if bad:
        raise SystemExit(f"bench configs failed: {sorted(bad)}")


def _percentile(sorted_vals: list, q: float):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


def main_das_storm_lite(seconds: float = 3.0, threads: int = 8,
                        queue_capacity: int = 4, deadline_ms: int = 500,
                        stall_ms: float = 5.0, k: int = 8):
    """`python bench.py --das-storm-lite`: a saturating DAS load storm
    through the REAL serving stack — node/rpc.py handler + device
    dispatcher + admission queue + the synthetic DAS prober — reporting
    samples/sec, shed rate, and accepted-request p99 against the SLO
    objectives (specs/serving.md).

    The node behind the handler is the crypto-free chaosnet facade (the
    same harness `make obs-smoke` boots), so the storm runs in stripped
    environments and on CPU-only hosts; per-job device cost is emulated
    with a deterministic `delay` rule at the documented `dispatch.run`
    fault site (specs/faults.md) so the storm actually saturates the
    bounded queue instead of measuring how fast chaosnet can answer.
    Blocks are produced WHILE the storm runs (resident-cache churn).

    Storm numbers measure degradation behavior under an armed injector,
    not device performance. Exit is nonzero on any HTTP 500,
    on a malformed shed reply, or on an accepted sample that fails
    cryptographic verification."""
    from celestia_tpu import faults
    from celestia_tpu.da import DataAvailabilityHeader
    from celestia_tpu.node.prober import Prober
    from celestia_tpu.node.rpc import RpcServer
    from celestia_tpu.slo import SloEngine, default_objectives
    from celestia_tpu.telemetry import metrics
    from celestia_tpu.testutil.chaosnet import RpcChaosNode

    import json as _json
    import random as _random
    import threading as _threading
    import urllib.error
    import urllib.request

    node = RpcChaosNode(heights=1, k=k)
    server = RpcServer(node, port=0, queue_capacity=queue_capacity,
                       default_deadline_s=deadline_ms / 1000.0)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    w = 2 * k

    engine = SloEngine(default_objectives(), registry=metrics)
    engine.evaluate()  # baseline snapshot for the burn-rate windows

    counts = {"200": 0, "503": 0, "504": 0, "other": 0, "500": 0}
    accepted_lat_ms: list = []
    accepted_samples: list = []  # (height, i, j, body)
    malformed: list = []
    lock = _threading.Lock()
    stop = _threading.Event()

    def fetch(path, headers=None):
        req = urllib.request.Request(base + path, headers=headers or {})
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, _json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, _json.loads(e.read())

    def producer():
        while not stop.wait(0.2):
            node.grow()

    def client(seed):
        rng = _random.Random(seed)
        while not stop.is_set():
            h = rng.randint(1, node.latest_height())
            i, j = rng.randrange(w), rng.randrange(w)
            t0 = time.perf_counter()
            try:
                status, body = fetch(f"/sample/{h}/{i}/{j}")
            except Exception:  # noqa: BLE001 — socket teardown at stop
                continue
            lat_ms = (time.perf_counter() - t0) * 1e3
            with lock:
                if status == 200:
                    counts["200"] += 1
                    accepted_lat_ms.append(lat_ms)
                    accepted_samples.append((h, i, j, body))
                elif status in (503, 504):
                    counts[str(status)] += 1
                    if status == 503 and (
                        body.get("error") != "overloaded"
                        or body.get("reason")
                        not in ("queue_full", "draining")
                    ):
                        malformed.append(body)
                elif status == 500:
                    counts["500"] += 1
                else:
                    counts["other"] += 1

    prober = Prober(base, samples_per_cycle=4, share_proofs=False,
                    rng=_random.Random(1), registry=metrics)

    def probe_loop():
        while not stop.wait(0.25):
            prober.probe_cycle()

    storm_threads = (
        [_threading.Thread(target=producer, daemon=True),
         _threading.Thread(target=probe_loop, daemon=True)]
        + [_threading.Thread(target=client, args=(s,), daemon=True)
           for s in range(threads)]
    )
    t_start = time.perf_counter()
    with faults.inject(
        faults.rule("dispatch.run", "delay", delay_s=stall_ms / 1000.0),
        seed=1337,
    ):
        for t in storm_threads:
            t.start()
        time.sleep(seconds)
        # graceful drain MID-STORM is part of what this mode exercises
        server.stop()
        stop.set()
        for t in storm_threads:
            t.join(10.0)
    elapsed = time.perf_counter() - t_start

    # every accepted sample must still proof-verify (degradation must
    # never corrupt acceptance) — DAHs come from the node's own store
    # since the server is now down
    from celestia_tpu.da import erasured_leaf_namespace
    from celestia_tpu.proof import NmtRangeProof

    verify_failures = 0
    for h, i, j, body in accepted_samples:
        try:
            dah = node.dah(h)
            share = bytes.fromhex(body["share"])
            p = body["proof"]
            proof = NmtRangeProof(
                start=int(p["start"]), end=int(p["end"]),
                nodes=[bytes.fromhex(x) for x in p["nodes"]],
                tree_size=int(p["tree_size"]),
            )
            ns = erasured_leaf_namespace(i, j, share, k)
            proof.verify_inclusion(dah.row_roots[i], [ns], [share])
        except Exception:  # noqa: BLE001 — counted, reported, fatal
            verify_failures += 1

    slo = engine.evaluate()
    slo_by_name = {o["name"]: o["ok"] for o in slo["objectives"]}
    total = sum(counts.values())
    shed = counts["503"] + counts["504"]
    accepted_lat_ms.sort()
    dispatcher_dead = not server.dispatcher.alive
    out = {
        "mode": "das-storm-lite",
        "seconds": round(elapsed, 2),
        "threads": threads,
        "queue_capacity": queue_capacity,
        "deadline_ms": deadline_ms,
        "stall_ms": stall_ms,
        "heights_produced": node.latest_height(),
        "requests_total": total,
        "counts": counts,
        "samples_per_sec": round(counts["200"] / elapsed, 1),
        "shed_rate": round(shed / total, 3) if total else None,
        "accepted_p50_ms": (
            round(_percentile(accepted_lat_ms, 0.50), 2)
            if accepted_lat_ms else None
        ),
        "accepted_p99_ms": (
            round(_percentile(accepted_lat_ms, 0.99), 2)
            if accepted_lat_ms else None
        ),
        "accepted_verified": len(accepted_samples) - verify_failures,
        "verify_failures": verify_failures,
        "malformed_sheds": len(malformed),
        "probe_availability_ratio": metrics.gauges.get(
            "probe_availability_ratio"
        ),
        "drain_clean": dispatcher_dead,
        "slo": {
            "sample_availability_ok": slo_by_name.get(
                "sample_availability"
            ),
            "rpc_admission_ok": slo_by_name.get("rpc_admission"),
        },
    }
    print(_json.dumps(out))
    failures = []
    if counts["500"]:
        failures.append(f"{counts['500']} HTTP 500s")
    if malformed:
        failures.append(f"{len(malformed)} malformed shed replies")
    if verify_failures:
        failures.append(f"{verify_failures} accepted samples failed "
                        "verification")
    if not dispatcher_dead:
        failures.append("dispatcher thread survived drain")
    if failures:
        raise SystemExit("das-storm-lite failed: " + "; ".join(failures))


def _das_storm_phase(label: str, *, seconds: float, threads: int, k: int,
                     heights: int, queue_capacity: int, deadline_ms: int,
                     batch_window_ms: float, max_batch: int,
                     paged_budget: int | None, stall_ms: float,
                     crowd: int | None = None, ragged: bool = True):
    """One measured storm phase behind a FRESH node + server: `threads`
    closed-loop light clients hammer `/sample` through the real RPC
    stack while a producer grows the chain and the synthetic prober
    runs its cycles. Returns the phase report dict; every accepted
    sample is NMT-verified post-hoc against the node's own DAH.

    `stall_ms` emulates the fixed per-DEVICE-DISPATCH launch cost
    (kernel launch + host round-trip) that the chaosnet facade
    doesn't pay, via the same documented delay-rule technique
    storm-lite uses: one `delay` at `dispatch.run`, which fires once
    per device dispatch — per job unbatched, per micro-batch batched —
    so both phases pay the same fixed overhead per dispatch and the
    measured win is exactly what batching amortizes.

    `crowd=N` switches the clients to the multi-height flash-crowd
    pattern (ISSUE 14): uniform over the LAST N heights instead of
    head-clustered — the workload that fragments a per-height batch
    key into N tiny groups. `ragged=False` builds the server with the
    per-height key (`ragged_batching=False`), the control arm the
    ragged gather is measured against on the identical workload."""
    from celestia_tpu import faults
    from celestia_tpu.node.prober import Prober
    from celestia_tpu.node.rpc import RpcServer
    from celestia_tpu.telemetry import metrics
    from celestia_tpu.testutil.chaosnet import RpcChaosNode

    import json as _json
    import random as _random
    import threading as _threading
    import urllib.error
    import urllib.request

    node = RpcChaosNode(heights=heights, k=k, seed=7,
                        paged_budget_bytes=paged_budget)
    server = RpcServer(node, port=0, queue_capacity=queue_capacity,
                       default_deadline_s=deadline_ms / 1000.0,
                       batch_window_s=batch_window_ms / 1000.0,
                       max_batch=max_batch, ragged_batching=ragged)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    w = 2 * k

    if crowd:
        # compile warmup: the ragged gather (like the same-height batch
        # slicer) traces one XLA program per pow2 occupancy bucket, and
        # each trace costs ~0.3 s on CPU. The head-clustered phases run
        # first and warm the control arm's shapes, so a cold crowd
        # phase would charge its compiles to the measured window.
        # Warm both arms identically: the window then measures
        # steady-state serving, which is what the gate compares.
        top = node.latest_height()
        hs = list(range(max(1, top - crowd + 1), top + 1))
        n = 2
        while n <= max(2, 2 * max_batch):
            payloads = [(hs[t % len(hs)], (3 * t) % w, (5 * t) % w)
                        for t in range(n)]
            if ragged and hasattr(node, "sample_batch_ragged"):
                node.sample_batch_ragged(payloads)
            else:
                by_h: dict[int, list] = {}
                for h, i, j in payloads:
                    by_h.setdefault(h, []).append((i, j))
                for h, coords in by_h.items():
                    node.sample_batch(h, coords)
            n *= 2

    # metric deltas, so back-to-back phases in one process stay honest
    batches0 = metrics.get_counter("dispatch_batch_total")
    bjobs0 = metrics.get_counter("dispatch_batched_jobs_total")

    counts = {"200": 0, "503": 0, "504": 0, "500": 0, "other": 0}
    accepted_lat_ms: list = []
    accepted_samples: list = []
    lock = _threading.Lock()
    stop = _threading.Event()

    def fetch(path):
        req = urllib.request.Request(base + path)
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, _json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, _json.loads(e.read())

    def producer():
        while not stop.wait(0.5):
            node.grow()

    def client(seed):
        rng = _random.Random(seed)
        while not stop.is_set():
            if crowd:
                # multi-height flash crowd (ISSUE 14): uniform over the
                # last `crowd` heights — the realistic light-client
                # pattern a per-height batch key fragments into `crowd`
                # tiny groups and the ragged key answers in one
                top = node.latest_height()
                h = rng.randint(max(1, top - crowd + 1), top)
            else:
                # cluster on the chain head (the DAS access pattern:
                # light clients sample the newest block) — that density
                # is what same-height micro-batching feeds on; 10%
                # stragglers keep the paged cache churning across
                # heights without diluting the batch key space into
                # singleton groups
                h = (node.latest_height() if rng.random() < 0.9
                     else rng.randint(1, node.latest_height()))
            i, j = rng.randrange(w), rng.randrange(w)
            t0 = time.perf_counter()
            try:
                status, body = fetch(f"/sample/{h}/{i}/{j}")
            except Exception:  # noqa: BLE001 — socket teardown at stop
                continue
            lat_ms = (time.perf_counter() - t0) * 1e3
            with lock:
                if status == 200:
                    counts["200"] += 1
                    accepted_lat_ms.append(lat_ms)
                    accepted_samples.append((h, i, j, body))
                elif status in (503, 504):
                    counts[str(status)] += 1
                elif status == 500:
                    counts["500"] += 1
                else:
                    counts["other"] += 1

    prober = Prober(base, samples_per_cycle=4, share_proofs=False,
                    rng=_random.Random(1), registry=metrics)

    def probe_loop():
        while not stop.wait(0.25):
            prober.probe_cycle()

    storm_threads = (
        [_threading.Thread(target=producer, daemon=True),
         _threading.Thread(target=probe_loop, daemon=True)]
        + [_threading.Thread(target=client, args=(s,), daemon=True)
           for s in range(threads)]
    )
    t_start = time.perf_counter()
    with faults.inject(
        faults.rule("dispatch.run", "delay", delay_s=stall_ms / 1000.0),
        seed=1337,
    ):
        for t in storm_threads:
            t.start()
        time.sleep(seconds)
        server.stop()  # graceful mid-storm drain, same as storm-lite
        stop.set()
        for t in storm_threads:
            t.join(10.0)
    elapsed = time.perf_counter() - t_start

    from celestia_tpu.da import erasured_leaf_namespace
    from celestia_tpu.proof import NmtRangeProof

    verify_failures = 0
    for h, i, j, body in accepted_samples:
        try:
            dah = node.dah(h)
            share = bytes.fromhex(body["share"])
            p = body["proof"]
            proof = NmtRangeProof(
                start=int(p["start"]), end=int(p["end"]),
                nodes=[bytes.fromhex(x) for x in p["nodes"]],
                tree_size=int(p["tree_size"]),
            )
            ns = erasured_leaf_namespace(i, j, share, k)
            proof.verify_inclusion(dah.row_roots[i], [ns], [share])
        except Exception:  # noqa: BLE001 — counted, reported, fatal
            verify_failures += 1

    batches = metrics.get_counter("dispatch_batch_total") - batches0
    bjobs = metrics.get_counter("dispatch_batched_jobs_total") - bjobs0
    cache = getattr(node, "_eds_cache", None)
    cache_stats = cache.stats() if hasattr(cache, "stats") else None
    page_rates = None
    if cache_stats:
        looked = cache_stats["page_hits"] + cache_stats["page_misses"]
        page_rates = {
            "hit_rate": (round(cache_stats["page_hits"] / looked, 3)
                         if looked else None),
            "hits": cache_stats["page_hits"],
            "misses": cache_stats["page_misses"],
            "demotes": cache_stats["page_demotes"],
            "faultins": cache_stats["page_faultins"],
            "corrupt": cache_stats["page_corrupt"],
            "pages_resident": cache_stats["pages_resident"],
            "device_bytes": cache_stats["device_bytes"],
        }
    accepted_lat_ms.sort()
    total = sum(counts.values())
    return {
        "label": label,
        "seconds": round(elapsed, 2),
        # config attribution (ISSUE 14 satellite): every storm entry
        # names the batching shape it measured, like cpus/n_devices
        # name the host shape
        "batch_window_s": batch_window_ms / 1000.0,
        "max_batch": max_batch,
        "crowd": crowd,
        "ragged": ragged,
        "heights_produced": node.latest_height(),
        "requests_total": total,
        "counts": counts,
        "samples_per_sec": round(counts["200"] / elapsed, 1),
        "accepted_p50_ms": (round(_percentile(accepted_lat_ms, 0.50), 2)
                            if accepted_lat_ms else None),
        "accepted_p99_ms": (round(_percentile(accepted_lat_ms, 0.99), 2)
                            if accepted_lat_ms else None),
        "accepted_verified": len(accepted_samples) - verify_failures,
        "verify_failures": verify_failures,
        "batches": int(batches),
        "batched_jobs": int(bjobs),
        "mean_batch_occupancy": (round(bjobs / batches, 2)
                                 if batches else None),
        "paged_cache": page_rates,
        "drain_clean": not server.dispatcher.alive,
    }


def main_das_storm(seconds: float = 4.0, threads: int = 32, k: int = 8,
                   heights: int = 2, queue_capacity: int = 128,
                   deadline_ms: int = 2000, batch_window_ms: float = 2.0,
                   max_batch: int = 32, paged_budget: int | None = None,
                   stall_ms: float = 5.0, ledger: str | None = None,
                   require_speedup: float | None = None):
    """`python bench.py --das-storm` / `make storm-bench`: the full-fat
    successor to --das-storm-lite (ADR-017). Two back-to-back storm
    phases on IDENTICAL config — continuous batching disabled
    (max_batch=1, the pre-ADR-017 serving path) then enabled — each
    driving `threads` concurrent light clients through the real RPC
    stack + prober, reporting samples/sec, batch-occupancy, paged-cache
    hit/demote rates (when --paged-budget arms the paged device cache),
    and accepted p50/p99 vs the SLO objectives.

    The fault injector arms ONE rule: a `stall_ms` delay at
    `dispatch.run`, which fires once per DEVICE DISPATCH (per job
    unbatched, per micro-batch batched) — emulating the fixed launch
    overhead the crypto-free chaosnet facade doesn't pay, the cost
    continuous batching exists to amortize. Both phases pay the same
    per-dispatch price; the speedup is dedup + hash-once NMT proving +
    that fixed cost spread over the group. Exit is nonzero on any
    accepted sample that fails NMT verification, on an unclean drain,
    or — with --require-speedup X — when batched samples/sec fails to
    reach X times the unbatched phase.

    Two further phases run the multi-height crowd workload (clients
    uniform over the last 8 heights) against the per-height batch key
    and the ragged ``("sample",)`` key (ISSUE 14): identical load,
    identical per-dispatch stall — exit is nonzero unless ragged
    samples/sec ≥ the same-height-only batcher.

    --ledger PATH appends the batched phase to the storm ledger (JSON,
    capped history) that `tools/perf_ledger.py` folds into `make
    bench-gate` as the lower-is-better `storm_ms_per_accepted_sample`
    series — plus `ragged_ms_per_accepted_sample` from the crowd-ragged
    phase, with `batch_window_s`/`max_batch` stamped for config
    attribution."""
    from celestia_tpu.slo import SloEngine, default_objectives
    from celestia_tpu.telemetry import metrics

    import json as _json
    import os as _os

    engine = SloEngine(default_objectives(), registry=metrics)
    engine.evaluate()  # baseline snapshot for the burn-rate windows

    common = dict(seconds=seconds, threads=threads, k=k, heights=heights,
                  queue_capacity=queue_capacity, deadline_ms=deadline_ms,
                  batch_window_ms=batch_window_ms,
                  paged_budget=paged_budget, stall_ms=stall_ms)
    unbatched = _das_storm_phase("unbatched", max_batch=1, **common)
    batched = _das_storm_phase("batched", max_batch=max_batch, **common)

    # multi-height crowd phases (ISSUE 14): the same mixed workload —
    # clients uniform over the last N=8 heights — against the
    # per-height batch key (control) and the ragged ("sample",) key.
    # The per-dispatch stall is identical; the ragged win is one
    # dispatch per group instead of one per height represented in it.
    # The paged budget is floored at 2× the hot-window working set: a
    # node serving a flash crowd provisions its device cache for the
    # hot heights (the churn drill is the head-clustered phases
    # above), and a budget smaller than ONE group's page span would
    # measure fault-in thrash, not the batch-key shape under test.
    crowd_n = 8
    crowd_budget = paged_budget
    if paged_budget is not None:
        hot_set = crowd_n * (2 * k) * (2 * k) * 512
        crowd_budget = max(paged_budget, 2 * hot_set)
    crowd_common = dict(common, heights=max(heights, crowd_n),
                        paged_budget=crowd_budget)
    crowd_same = _das_storm_phase("crowd-same-height",
                                  max_batch=max_batch, crowd=crowd_n,
                                  ragged=False, **crowd_common)
    crowd_ragged = _das_storm_phase("crowd-ragged",
                                    max_batch=max_batch, crowd=crowd_n,
                                    ragged=True, **crowd_common)

    slo = engine.evaluate()
    slo_by_name = {o["name"]: o["ok"] for o in slo["objectives"]}
    occ_hist = metrics.get_timing("dispatch_batch_occupancy")
    speedup = (
        round(batched["samples_per_sec"] / unbatched["samples_per_sec"], 2)
        if unbatched["samples_per_sec"] else None
    )
    crowd_speedup = (
        round(crowd_ragged["samples_per_sec"]
              / crowd_same["samples_per_sec"], 2)
        if crowd_same["samples_per_sec"] else None
    )
    out = {
        "mode": "das-storm",
        "threads": threads,
        "k": k,
        "batch_window_ms": batch_window_ms,
        "batch_window_s": batch_window_ms / 1000.0,
        "max_batch": max_batch,
        "paged_budget": paged_budget,
        "stall_ms": stall_ms,
        "unbatched": unbatched,
        "batched": batched,
        "crowd_same_height": crowd_same,
        "crowd_ragged": crowd_ragged,
        "speedup": speedup,
        "crowd_speedup": crowd_speedup,
        "batch_occupancy_p50": (round(occ_hist.quantile(0.50), 1)
                                if occ_hist else None),
        "batch_occupancy_p90": (round(occ_hist.quantile(0.90), 1)
                                if occ_hist else None),
        "slo": {
            "sample_availability_ok": slo_by_name.get(
                "sample_availability"
            ),
            "rpc_admission_ok": slo_by_name.get("rpc_admission"),
        },
    }
    print(_json.dumps(out))

    if ledger:
        doc = {"runs": []}
        if _os.path.exists(ledger):
            try:
                with open(ledger) as f:
                    loaded = _json.load(f)
                if isinstance(loaded, dict) and isinstance(
                        loaded.get("runs"), list):
                    doc = loaded
            except (OSError, ValueError):
                pass  # unreadable ledger: start fresh rather than crash
        sps = batched["samples_per_sec"]
        ragged_sps = crowd_ragged["samples_per_sec"]
        doc["runs"].append({
            "ts": time.time(),
            "threads": threads, "k": k, "seconds": seconds,
            "batch_window_s": batch_window_ms / 1000.0,
            "max_batch": max_batch, "paged_budget": paged_budget,
            "stall_ms": stall_ms,
            "samples_per_sec": sps,
            "ms_per_accepted_sample": (round(1000.0 / sps, 4)
                                       if sps else None),
            "speedup_vs_unbatched": speedup,
            "ragged_samples_per_sec": ragged_sps,
            "ragged_ms_per_accepted_sample": (round(1000.0 / ragged_sps, 4)
                                              if ragged_sps else None),
            "crowd_speedup": crowd_speedup,
        })
        doc["runs"] = doc["runs"][-40:]  # capped history
        with open(ledger, "w") as f:
            _json.dump(doc, f, indent=1)
        print(f"storm ledger updated: {ledger} "
              f"({len(doc['runs'])} runs)", file=sys.stderr)

    failures = []
    for phase in (unbatched, batched, crowd_same, crowd_ragged):
        if phase["counts"]["500"]:
            failures.append(
                f"{phase['counts']['500']} HTTP 500s ({phase['label']})")
        if phase["verify_failures"]:
            failures.append(
                f"{phase['verify_failures']} accepted samples failed "
                f"verification ({phase['label']})")
        if not phase["drain_clean"]:
            failures.append(
                f"dispatcher survived drain ({phase['label']})")
    if require_speedup is not None and (
            speedup is None or speedup < require_speedup):
        failures.append(
            f"batched speedup {speedup} < required {require_speedup}")
    if (crowd_same["samples_per_sec"]
            and crowd_ragged["samples_per_sec"]
            < crowd_same["samples_per_sec"]):
        failures.append(
            f"ragged crowd {crowd_ragged['samples_per_sec']} samples/s "
            f"< same-height batcher {crowd_same['samples_per_sec']}")
    if failures:
        raise SystemExit("das-storm failed: " + "; ".join(failures))


def _gateway_fleet_phase(label: str, n: int, *, seconds: float,
                         threads: int, k: int, heights: int,
                         queue_capacity: int, deadline_ms: int,
                         trace_out: str | None = None):
    """One gateway-fleet phase: n chaosnet backends (byte-identical
    replicas — same k/seed/chain) behind node/gateway.Gateway, with
    `threads` closed-loop light clients sampling random cells THROUGH
    the gateway and NMT-verifying every accepted share against the
    canonical DAH. Returns the phase counters + samples/sec.
    `trace_out` writes the phase's Chrome trace (gateway route/hedge
    spans + every backend's handler/dispatch spans, one trace id per
    request) to `<trace_out>.<label>.json` — merge multi-process runs
    with tools/trace_merge."""
    import json as _json
    import random as _random
    import threading as _threading
    import urllib.error
    import urllib.request

    from celestia_tpu import tracing
    from celestia_tpu.node.gateway import Gateway
    from celestia_tpu.node.rpc import RpcServer
    from celestia_tpu.scenarios.world import _verify_sample
    from celestia_tpu.telemetry import metrics
    from celestia_tpu.testutil.chaosnet import RpcChaosNode

    nodes = [RpcChaosNode(heights=heights, k=k, seed=7,
                          chain_id="gateway-bench") for _ in range(n)]
    servers = [RpcServer(nd, port=0, queue_capacity=queue_capacity)
               for nd in nodes]
    for s in servers:
        s.start()
    gw = Gateway([f"http://127.0.0.1:{s.port}" for s in servers])
    gw.start()
    base = gw.url
    # the replicas are byte-identical, so one node's DAHs are THE
    # verification oracle no matter which backend the ring picked
    dahs = {h: nodes[0].block_dah(h) for h in range(1, heights + 1)}
    w = 2 * k
    counts = {"ok": 0, "shed": 0, "deadline": 0, "not_found": 0,
              "error": 0}
    verify_failures = 0
    lock = _threading.Lock()
    stop = _threading.Event()
    hedges0 = metrics.get_counter("gateway_hedge_total")

    def client(seed: int) -> None:
        nonlocal verify_failures
        rng = _random.Random(seed)
        while not stop.is_set():
            h = rng.randint(1, heights)
            i, j = rng.randrange(w), rng.randrange(w)
            req = urllib.request.Request(
                f"{base}/sample/{h}/{i}/{j}",
                headers={"X-Deadline-Ms": str(deadline_ms)})
            try:
                with urllib.request.urlopen(req, timeout=5.0) as resp:
                    body = _json.loads(resp.read())
                ok = _verify_sample(dahs[h], k, i, j, body)
                with lock:
                    counts["ok"] += 1
                    if not ok:
                        verify_failures += 1
            except urllib.error.HTTPError as e:
                key = {503: "shed", 504: "deadline",
                       404: "not_found"}.get(e.code, "error")
                with lock:
                    counts[key] += 1
            except Exception:  # noqa: BLE001 — transport-level failure
                with lock:
                    counts["error"] += 1

    rec = tracing.record().start() if trace_out else None
    t0 = time.perf_counter()
    workers = [_threading.Thread(target=client, args=(1000 + ci,),
                                 daemon=True) for ci in range(threads)]
    for t in workers:
        t.start()
    stop.wait(seconds)
    stop.set()
    for t in workers:
        t.join(timeout=10)
    wall = time.perf_counter() - t0
    if rec is not None:
        rec.stop()
        path = f"{trace_out}.{label}.json"
        rec.write(path)
        print(f"trace written: {path} ({len(rec.spans)} spans)",
              file=sys.stderr)
    gw.stop()
    for s in servers:
        s.stop(drain_timeout=2.0)
    sps = round(counts["ok"] / wall, 1) if wall > 0 else 0.0
    return {
        "label": label,
        "backends": n,
        "wall_s": round(wall, 2),
        "counts": counts,
        "verify_failures": verify_failures,
        "samples_per_sec": sps,
        "hedges": metrics.get_counter("gateway_hedge_total") - hedges0,
    }


def main_gateway_fleet(seconds: float = 3.0, threads: int = 16, k: int = 8,
                       heights: int = 4, queue_capacity: int = 128,
                       deadline_ms: int = 2000, fleet: int = 3,
                       ledger: str | None = None,
                       require_scaling: float | None = None,
                       trace_out: str | None = None,
                       processes: int = 0):
    """`python bench.py --gateway-fleet` / `make gateway-bench`: the
    ADR-021 horizontal-scaling config. Two phases on identical client
    load — ONE backend behind the gateway, then `fleet` backends — each
    phase driving `threads` closed-loop light clients through the
    consistent-hash (height, row) ring with every accepted sample
    NMT-verified against the canonical DAH. Reports samples/sec per
    phase and the fleet/single scaling ratio.

    The backends are in-process Python servers sharing one GIL, so the
    expected scaling is MODEST (the win is real: N dispatcher queues +
    N sha256 proving paths that release the GIL) — --require-scaling
    gates on a floor when set. Exit is nonzero on any accepted sample
    that fails NMT verification or any HTTP-level error.

    --ledger PATH appends the fleet phase to the storm ledger as the
    lower-is-better `gateway_ms_per_accepted_sample` series that
    `make bench-gate` (tools/perf_ledger.py) judges.

    --processes N switches to the OS-process fleet (ADR-023): real
    supervised backend subprocesses under node/fleet.FleetSupervisor
    instead of in-process servers — see main_gateway_fleet_processes."""
    import json as _json
    import os as _os

    if processes:
        return main_gateway_fleet_processes(
            processes, seconds=seconds, threads=threads, k=k,
            heights=heights, deadline_ms=deadline_ms, ledger=ledger,
            require_scaling=require_scaling, trace_out=trace_out)

    common = dict(seconds=seconds, threads=threads, k=k, heights=heights,
                  queue_capacity=queue_capacity, deadline_ms=deadline_ms,
                  trace_out=trace_out)
    single = _gateway_fleet_phase("single", 1, **common)
    fleet_phase = _gateway_fleet_phase(f"fleet-{fleet}", fleet, **common)
    scaling = (
        round(fleet_phase["samples_per_sec"] / single["samples_per_sec"], 2)
        if single["samples_per_sec"] else None
    )
    out = {
        "mode": "gateway-fleet",
        "threads": threads,
        "k": k,
        "heights": heights,
        "fleet": fleet,
        # scaling is cpu-bound: on a 1-core box the phases tie (the
        # gate below should only assert no collapse); real headroom
        # needs cores for the N dispatcher/proving paths to land on
        "cpus": _os.cpu_count(),
        "single": single,
        "fleet_phase": fleet_phase,
        "scaling_vs_single": scaling,
    }
    print(_json.dumps(out))

    if ledger:
        doc = {"runs": []}
        if _os.path.exists(ledger):
            try:
                with open(ledger) as f:
                    loaded = _json.load(f)
                if isinstance(loaded, dict) and isinstance(
                        loaded.get("runs"), list):
                    doc = loaded
            except (OSError, ValueError):
                pass  # unreadable ledger: start fresh rather than crash
        sps = fleet_phase["samples_per_sec"]
        doc["runs"].append({
            "ts": time.time(),
            "mode": "gateway-fleet",
            "threads": threads, "k": k, "seconds": seconds,
            "fleet": fleet,
            "samples_per_sec": sps,
            "gateway_ms_per_accepted_sample": (round(1000.0 / sps, 4)
                                               if sps else None),
            "scaling_vs_single": scaling,
        })
        doc["runs"] = doc["runs"][-40:]  # capped history
        with open(ledger, "w") as f:
            _json.dump(doc, f, indent=1)
        print(f"storm ledger updated: {ledger} "
              f"({len(doc['runs'])} runs)", file=sys.stderr)

    failures = []
    for phase in (single, fleet_phase):
        if phase["verify_failures"]:
            failures.append(
                f"{phase['verify_failures']} accepted samples failed "
                f"NMT verification ({phase['label']})")
        if phase["counts"]["error"]:
            failures.append(
                f"{phase['counts']['error']} HTTP-level errors "
                f"({phase['label']})")
    if require_scaling is not None and (
            scaling is None or scaling < require_scaling):
        failures.append(
            f"fleet scaling {scaling} < required {require_scaling}")
    if failures:
        raise SystemExit("gateway-fleet failed: " + "; ".join(failures))


def _fleet_process_phase(label: str, n: int, *, seconds: float,
                         threads: int, k: int, heights: int,
                         deadline_ms: int, store_root, trace_dir,
                         scale_to: int | None = None,
                         kill_index: int | None = None):
    """One OS-process fleet phase: a FleetSupervisor launches `n` real
    backend subprocesses (own port + own store dir), attaches them to a
    node/gateway.Gateway ring, and `threads` closed-loop clients sample
    random cells THROUGH the gateway while a producer thread streams new
    blocks into the whole fleet via supervisor.advance(). Every accepted
    share is NMT-verified against an in-process oracle node that grows
    the same deterministic chain (chain_shares is seed-pure, so replica
    DAHs are byte-identical to the oracle's).

    `scale_to` grows the fleet mid-storm (at ~30% of the window);
    `kill_index` SIGKILLs that member at ~60% and gates on the
    supervisor restarting + re-warming it. Returns phase counters plus
    blocks/sec from the producer stream and the merged-trace pid count
    (gateway pid + one pid per backend process)."""
    import json as _json
    import pathlib as _pathlib
    import random as _random
    import threading as _threading
    import urllib.error
    import urllib.request

    from celestia_tpu import tracing
    from celestia_tpu.node.fleet import FleetSupervisor
    from celestia_tpu.node.gateway import Gateway
    from celestia_tpu.scenarios.world import _verify_sample
    from celestia_tpu.telemetry import metrics
    from celestia_tpu.testutil.chaosnet import RpcChaosNode
    from celestia_tpu.tools import trace_merge

    phase_dir = _pathlib.Path(trace_dir) / label
    phase_dir.mkdir(parents=True, exist_ok=True)
    oracle = RpcChaosNode(heights=heights, k=k, seed=7,
                          chain_id="fleet-bench")
    gw = Gateway([])
    gw.start()
    sup = FleetSupervisor(
        n, _pathlib.Path(store_root) / label, gateway=gw, k=k,
        heights=heights, seed=7, chain_id="fleet-bench",
        trace_dir=str(phase_dir))
    rec = tracing.record().start()
    sup.start()
    base = gw.url
    w = 2 * k
    dahs = {h: oracle.block_dah(h) for h in range(1, heights + 1)}
    shared = {"head": heights, "blocks": 0}
    counts = {"ok": 0, "shed": 0, "deadline": 0, "not_found": 0,
              "error": 0}
    verify_failures = 0
    lock = _threading.Lock()
    stop = _threading.Event()
    hedges0 = metrics.get_counter("gateway_hedge_total")

    def producer() -> None:
        # block stream: grow the oracle, fan the height out to every
        # ready process — this segment IS the blocks/sec measurement
        while not stop.is_set():
            oracle.grow()
            h = oracle.latest_height()
            dah = oracle.block_dah(h)
            sup.advance(h)
            with lock:
                dahs[h] = dah
                shared["head"] = h
                shared["blocks"] += 1

    def chaos() -> None:
        # the scale-out and the kill are part of the phase's CONTRACT,
        # not best-effort load: they run even if the storm window
        # already lapsed (a 1-core box can spend most of it warming)
        if scale_to is not None and scale_to > n:
            stop.wait(seconds * 0.3)
            sup.scale_to(scale_to)
        if kill_index is not None:
            stop.wait(seconds * 0.3)
            victim = sup.members()[kill_index]
            gen0 = victim.generation
            if victim.proc is not None:
                victim.proc.kill()
            sup.wait_ready(kill_index, timeout=60.0,
                           min_generation=gen0 + 1)

    def client(seed: int) -> None:
        nonlocal verify_failures
        rng = _random.Random(seed)
        while not stop.is_set():
            with lock:
                head = shared["head"]
            h = rng.randint(1, head)
            i, j = rng.randrange(w), rng.randrange(w)
            req = urllib.request.Request(
                f"{base}/sample/{h}/{i}/{j}",
                headers={"X-Deadline-Ms": str(deadline_ms)})
            try:
                with urllib.request.urlopen(req, timeout=5.0) as resp:
                    body = _json.loads(resp.read())
                with lock:
                    dah = dahs[h]
                ok = _verify_sample(dah, k, i, j, body)
                with lock:
                    counts["ok"] += 1
                    if not ok:
                        verify_failures += 1
            except urllib.error.HTTPError as e:
                key = {503: "shed", 504: "deadline",
                       404: "not_found"}.get(e.code, "error")
                with lock:
                    counts[key] += 1
            except Exception:  # noqa: BLE001 — transport-level failure
                with lock:
                    counts["error"] += 1

    t0 = time.perf_counter()
    workers = [_threading.Thread(target=client, args=(1000 + ci,),
                                 daemon=True) for ci in range(threads)]
    aux = [_threading.Thread(target=producer, daemon=True),
           _threading.Thread(target=chaos, daemon=True)]
    for t in workers + aux:
        t.start()
    stop.wait(seconds)
    stop.set()
    for t in workers + aux:
        t.join(timeout=60)
    wall = time.perf_counter() - t0
    report = sup.report()
    sup.stop()  # graceful stop makes every backend write its trace
    gw.stop()
    rec.stop()
    gateway_trace = str(phase_dir / "gateway.json")
    rec.write(gateway_trace)
    merged_path = str(phase_dir / "merged.json")
    merged_pids: int = 0
    backend_traces = sup.trace_files()
    if backend_traces:
        merged = trace_merge.merge_files(
            merged_path, [gateway_trace, *backend_traces])
        merged_pids = len({
            ev.get("pid") for ev in merged.get("traceEvents", [])
            if ev.get("ph") == "X" and isinstance(ev.get("pid"), int)
        })
        print(f"merged fleet trace: {merged_path} "
              f"({merged_pids} pids)", file=sys.stderr)
    sps = round(counts["ok"] / wall, 1) if wall > 0 else 0.0
    bps = round(shared["blocks"] / wall, 1) if wall > 0 else 0.0
    return {
        "label": label,
        "processes": n if scale_to is None else scale_to,
        "wall_s": round(wall, 2),
        "counts": counts,
        "verify_failures": verify_failures,
        "samples_per_sec": sps,
        "blocks_per_sec": bps,
        "blocks_produced": shared["blocks"],
        "hedges": metrics.get_counter("gateway_hedge_total") - hedges0,
        "restarts": report["restarts"],
        "crashloops": report["crashloops"],
        "events": report["events"],
        "merged_trace": merged_path if backend_traces else None,
        "merged_pids": merged_pids,
    }


def main_gateway_fleet_processes(processes: int = 3,
                                 seconds: float = 6.0, threads: int = 16,
                                 k: int = 8, heights: int = 2,
                                 deadline_ms: int = 2000,
                                 ledger: str | None = None,
                                 require_scaling: float | None = None,
                                 trace_out: str | None = None):
    """`python bench.py --gateway-fleet --processes N`: the ADR-023
    OS-process fleet config. Three phases, all against real supervised
    backend subprocesses with a live block stream:

      single   — 1 process behind the gateway
      fleet-N  — N processes, same client load (the no-collapse gate
                 compares its samples/sec and blocks/sec to single)
      elastic  — starts at 1 process, scales out to N mid-storm, then
                 SIGKILLs member 0 and gates on the supervisor
                 restarting + re-warming it; zero NMT verification
                 failures are required across the whole window

    Each phase merges the gateway's trace with every backend process's
    trace (tools/trace_merge) into ONE Chrome trace spanning gateway +
    N real PIDs. --ledger appends `fleet_blocks_per_sec` (higher is
    better) and `fleet_ms_per_accepted_sample` (lower is better) for
    tools/perf_ledger.py / `make bench-gate` to judge."""
    import json as _json
    import os as _os
    import tempfile as _tempfile

    root = _tempfile.mkdtemp(prefix="fleet-bench-")
    trace_dir = trace_out if trace_out else _os.path.join(root, "traces")
    common = dict(seconds=seconds, threads=threads, k=k, heights=heights,
                  deadline_ms=deadline_ms, store_root=root,
                  trace_dir=trace_dir)
    single = _fleet_process_phase("single", 1, **common)
    fleet_phase = _fleet_process_phase(f"fleet-{processes}", processes,
                                       **common)
    elastic = _fleet_process_phase("elastic", 1, scale_to=processes,
                                   kill_index=0, **common)
    scaling = (
        round(fleet_phase["samples_per_sec"] / single["samples_per_sec"], 2)
        if single["samples_per_sec"] else None
    )
    block_scaling = (
        round(fleet_phase["blocks_per_sec"] / single["blocks_per_sec"], 2)
        if single["blocks_per_sec"] else None
    )
    out = {
        "mode": "gateway-fleet-processes",
        "threads": threads,
        "k": k,
        "heights": heights,
        "processes": processes,
        "cpus": _os.cpu_count(),
        "single": single,
        "fleet_phase": fleet_phase,
        "elastic": elastic,
        "scaling_vs_single": scaling,
        "block_scaling_vs_single": block_scaling,
    }
    print(_json.dumps(out))

    if ledger:
        doc = {"runs": []}
        if _os.path.exists(ledger):
            try:
                with open(ledger) as f:
                    loaded = _json.load(f)
                if isinstance(loaded, dict) and isinstance(
                        loaded.get("runs"), list):
                    doc = loaded
            except (OSError, ValueError):
                pass  # unreadable ledger: start fresh rather than crash
        sps = fleet_phase["samples_per_sec"]
        doc["runs"].append({
            "ts": time.time(),
            "mode": "gateway-fleet-processes",
            "threads": threads, "k": k, "seconds": seconds,
            "processes": processes,
            "samples_per_sec": sps,
            "fleet_blocks_per_sec": fleet_phase["blocks_per_sec"],
            "fleet_ms_per_accepted_sample": (round(1000.0 / sps, 4)
                                             if sps else None),
            "scaling_vs_single": scaling,
        })
        doc["runs"] = doc["runs"][-40:]  # capped history
        with open(ledger, "w") as f:
            _json.dump(doc, f, indent=1)
        print(f"storm ledger updated: {ledger} "
              f"({len(doc['runs'])} runs)", file=sys.stderr)

    failures = []
    for phase in (single, fleet_phase, elastic):
        if phase["verify_failures"]:
            failures.append(
                f"{phase['verify_failures']} accepted samples failed "
                f"NMT verification ({phase['label']})")
        if phase["counts"]["error"]:
            failures.append(
                f"{phase['counts']['error']} HTTP-level errors "
                f"({phase['label']})")
        if phase["crashloops"]:
            failures.append(
                f"{phase['crashloops']} crash-looped members "
                f"({phase['label']})")
        want_pids = phase["processes"] + 1  # every backend + gateway
        if phase["merged_pids"] < want_pids:
            failures.append(
                f"merged trace spans {phase['merged_pids']} pids "
                f"< {want_pids} ({phase['label']})")
    if not elastic["restarts"]:
        failures.append("supervisor never restarted the killed member")
    join_events = [e for e in elastic["events"]
                   if e.get("event") == "join"]
    if len(join_events) < processes:
        failures.append(
            f"elastic phase saw {len(join_events)} joins "
            f"< {processes} (scale-out did not complete)")
    if require_scaling is not None and (
            scaling is None or scaling < require_scaling):
        failures.append(
            f"fleet scaling {scaling} < required {require_scaling}")
    if failures:
        raise SystemExit("gateway-fleet --processes failed: "
                         + "; ".join(failures))


def main_multichip_child(devices: int = 8, blocks: int = 24, k: int = 8,
                         depth: int = 3):
    """One phase of --multichip-pipeline, run in its own process so the
    device count is a launch-time property (`XLA_FLAGS=
    --xla_force_host_platform_device_count=N` must precede the jax
    import — the parent sets it, this child just measures). Streams
    `blocks` distinct squares through a BlockPipeline — row-sharded over
    a (1, devices) mesh when devices > 1, the single-chip path otherwise
    — and prints ONE JSON line with blocks/sec plus the parity evidence
    the parent gates on: every retired DAH (hex) and a digest over the
    device-computed level stacks and one end-to-end prover proof."""
    import hashlib
    import os as _os

    from celestia_tpu.ops import enable_compile_cache

    enable_compile_cache()
    import jax

    from celestia_tpu import parallel
    from celestia_tpu.node.pipeline import BlockPipeline
    from celestia_tpu.proof import NmtRowProver

    n_dev = len(jax.devices())
    mesh_shape = None
    if devices > 1:
        if n_dev < devices:
            raise SystemExit(
                f"multichip child wants {devices} devices, jax sees "
                f"{n_dev} — launch under XLA_FLAGS="
                "--xla_force_host_platform_device_count=N")
        parallel.configure_mesh(parallel.make_mesh(1, devices))
        mesh_shape = {"dp": 1, "sp": devices}
    squares = [build_square(k, seed=100 + h) for h in range(blocks)]

    def stream(pipe, heights):
        out = []
        for h in heights:
            r = pipe.feed(h, squares[h])
            if r is not None:
                out.append(r)
        out.extend(pipe.drain())
        return out

    # warm pass compiles the (sharded) extend + levels programs so the
    # timed pass measures the pipeline, not XLA
    stream(BlockPipeline(k, depth=depth), range(min(depth, blocks)))
    pipe = BlockPipeline(k, depth=depth)
    t0 = time.perf_counter()
    retired = stream(pipe, range(blocks))
    wall = time.perf_counter() - t0
    retired.sort(key=lambda b: b.height)

    digest = hashlib.sha256()
    for b in retired:
        digest.update(b.dah.tobytes())
        for lvl in b.levels:
            digest.update(np.ascontiguousarray(lvl).tobytes())
    # one proof served off the device-seeded prover rides the digest:
    # levels -> memo -> serialized range proof, the exact serving path
    first = retired[0]
    prover = NmtRowProver.from_node_levels([lvl[0] for lvl in first.levels])
    digest.update(prover.root())
    for node in prover.prove_range(0, 1).nodes:
        digest.update(node)

    bps = round(blocks / wall, 2) if wall > 0 else 0.0
    print(json.dumps({
        "mode": "multichip-child",
        "n_devices": n_dev,
        "devices_used": devices,
        "mesh": mesh_shape,
        "cpus": _os.cpu_count(),
        "k": k, "blocks": blocks, "depth": depth,
        "wall_s": round(wall, 3),
        "blocks_per_sec": bps,
        "dahs": [b.dah.tobytes().hex() for b in retired],
        "digest": digest.hexdigest(),
        "stage_wall_s": {s: round(v, 3) for s, v in
                         pipe.stats()["stage_wall_s"].items()},
    }))


def main_multichip_pipeline(devices: int = 8, blocks: int = 24, k: int = 8,
                            depth: int = 3, ledger: str | None = None,
                            require_scaling: float | None = None):
    """`python bench.py --multichip-pipeline` / `make multichip-bench`:
    the scale-out config. Two child processes stream the SAME block
    sequence through the 3-deep pipeline — one device, then a virtual
    (1, devices) host mesh — and the parent gates byte-identical DAHs,
    identical prover digests (device-seeded levels + one served proof),
    and aggregate blocks/sec not collapsing under sharding.

    The CI box is CPU-only, so the dp·sp "devices" share one socket and
    the expected scaling is ~1× (XLA threads the unsharded program too)
    — --require-scaling gates a collapse floor (0.7 in CI), not a
    speedup claim; real scale-out headroom needs real chips. --ledger
    PATH appends the mesh phase as the higher-is-better
    `multichip_blocks_per_sec` series that `make bench-gate`
    (tools/perf_ledger.py) judges."""
    import json as _json
    import os as _os
    import subprocess

    def run_child(n: int) -> dict:
        env = dict(_os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        cmd = [sys.executable, str(pathlib.Path(__file__).resolve()),
               "--multichip-child", "--devices", str(n),
               "--blocks", str(blocks), "--k", str(k),
               "--depth", str(depth)]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=900)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-2000:])
            raise SystemExit(
                f"multichip child (devices={n}) failed rc={proc.returncode}")
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith("{"):
                return _json.loads(line)
        raise SystemExit(f"multichip child (devices={n}) printed no JSON")

    single = run_child(1)
    mesh = run_child(devices)
    scaling = (round(mesh["blocks_per_sec"] / single["blocks_per_sec"], 2)
               if single["blocks_per_sec"] else None)
    out = {
        "mode": "multichip-pipeline",
        "k": k, "blocks": blocks, "depth": depth, "devices": devices,
        "cpus": _os.cpu_count(),
        "single": single,
        "mesh_phase": mesh,
        "scaling_vs_single": scaling,
        "dah_parity": single["dahs"] == mesh["dahs"],
        "prover_parity": single["digest"] == mesh["digest"],
    }
    # the per-block DAH lists are parity evidence, not report content
    for phase in (out["single"], out["mesh_phase"]):
        phase.pop("dahs", None)
    print(_json.dumps(out))

    if ledger:
        doc = {"runs": []}
        if _os.path.exists(ledger):
            try:
                with open(ledger) as f:
                    loaded = _json.load(f)
                if isinstance(loaded, dict) and isinstance(
                        loaded.get("runs"), list):
                    doc = loaded
            except (OSError, ValueError):
                pass  # unreadable ledger: start fresh rather than crash
        doc["runs"].append({
            "ts": time.time(),
            "mode": "multichip-pipeline",
            "k": k, "blocks": blocks, "devices": devices,
            "multichip_blocks_per_sec": mesh["blocks_per_sec"],
            "single_blocks_per_sec": single["blocks_per_sec"],
            "scaling_vs_single": scaling,
        })
        doc["runs"] = doc["runs"][-40:]  # capped history
        with open(ledger, "w") as f:
            _json.dump(doc, f, indent=1)
        print(f"storm ledger updated: {ledger} "
              f"({len(doc['runs'])} runs)", file=sys.stderr)

    failures = []
    if not out["dah_parity"]:
        failures.append("sharded DAHs diverge from single-chip")
    if not out["prover_parity"]:
        failures.append("device-seeded prover digest diverges")
    if require_scaling is not None and (
            scaling is None or scaling < require_scaling):
        failures.append(
            f"mesh scaling {scaling} < required {require_scaling}")
    if failures:
        raise SystemExit("multichip-pipeline failed: " + "; ".join(failures))


def main_fused_kernels():
    """`python bench.py --fused-kernels`: the ADR-019 configs alone —
    fused Pallas extend+hash roots-only vs the XLA roots path vs native
    at k ∈ {64, 32}. Exits non-zero when the device cannot be reached,
    on a parity failure, or when a config throws."""
    from celestia_tpu.ops import enable_compile_cache

    enable_compile_cache()
    name = "12_fused_kernels_k64"
    metric = "fused_ms_per_square_k64"
    reachable, why = _probe_with_retries()
    if not reachable:
        print(json.dumps({
            "metric": metric,
            "value": None,
            "unit": "ms",
            "error": f"accelerator unreachable: {why}",
        }))
        sys.exit(1)

    configs: dict = {}
    prov: dict = {}
    _run_config(configs, prov, name, bench_fused_kernels, 64)
    _run_config(configs, prov, "12b_fused_kernels_k32",
                bench_fused_kernels, 32)
    head = configs.get(name) or {}
    headline = {
        "metric": metric,
        "value": head.get("fused_ms_per_square"),
        "unit": "ms",
        "vs_baseline": head.get("fused_vs_xla_speedup"),
        "native_baseline_ms": head.get("native_ms_per_square"),
        "xla_roots_ms": head.get("xla_roots_ms_per_square"),
        "parity": head.get("parity"),
    }
    _finish(headline, configs, prov, "fused-path DAH mismatch vs host")


def main_transfers():
    """`make bench-transfers` / `python bench.py --transfers`: the
    sliced-read and k=64 node-path configs with the fault injector ARMED
    at the device boundaries (delay faults at device.extend and
    device.repair) — pins that the new async/overlapped transfer paths
    still yield byte-identical DAH and share bytes under degradation.

    Unlike main(), any jax backend is accepted: parity is what this mode
    gates on, and parity is backend-independent. Timings are labelled
    with the backend that produced them. Exits non-zero on any parity
    failure."""
    from celestia_tpu import faults
    from celestia_tpu.ops import enable_compile_cache

    enable_compile_cache()
    import jax

    out: dict = {
        "mode": "transfers-under-faults",
        "jax_backend": jax.devices()[0].platform,
        "faults": "delay@device.extend + delay@device.repair (seed 1337)",
    }
    with faults.inject(
        faults.rule("device.extend", "delay", delay_s=0.002),
        faults.rule("device.repair", "delay", delay_s=0.002),
        seed=1337,
    ):
        out["11_sliced_sample_k64"] = bench_sliced_sample(64)
        out["8c_node_path_k64"] = bench_node_path(64)
        out["4t_repair_k64_25pct"] = bench_repair(64)
    failures = [
        name
        for name, cfg in out.items()
        # the repair config reports its byte check as "recovered"
        if isinstance(cfg, dict)
        and (cfg.get("parity") is False or cfg.get("recovered") is False)
    ]
    print(json.dumps(out))
    if failures:
        raise SystemExit(
            f"parity failure under armed fault injector: {failures}"
        )


if __name__ == "__main__":
    # --audit-level LEVEL rides along with any bench mode: strip it
    # BEFORE dispatch (main() parses sys.argv[1] positionally as the
    # headline k; perf_ledger's parser would reject it), install the
    # global integrity engine so every benched extend/repair pays (and
    # reports) the audit cost (ADR-015)
    if "--audit-level" in sys.argv:
        _i = sys.argv.index("--audit-level")
        if _i + 1 >= len(sys.argv):
            raise SystemExit("--audit-level requires off|sampled|full")
        _audit_level = sys.argv[_i + 1]
        del sys.argv[_i:_i + 2]
        from celestia_tpu import integrity as _integrity

        try:
            _integrity.configure(_audit_level)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        print(f"audit-level {_audit_level}", file=sys.stderr)
    # --check-regressions never touches the accelerator: it gates the
    # BENCH_r*.json records under --root and exits with the sentinel's
    # verdict (`make bench-gate`, specs/slo.md)
    if "--check-regressions" in sys.argv:
        from celestia_tpu.tools import perf_ledger

        sys.exit(perf_ledger.main(
            [a for a in sys.argv[1:] if a != "--check-regressions"]
        ))
    # --san rides along with any bench mode: wrap the run in a
    # celestia-san Session (specs/analysis.md, "Runtime sanitizer") and
    # fail the bench on any new T-finding observed under real load —
    # the storm/pipeline arms are the heaviest concurrent exercise the
    # repo has, exactly where a latent inversion would surface
    _san = None
    if "--san" in sys.argv:
        sys.argv.remove("--san")
        from celestia_tpu.tools import sanitizer as _sanitizer

        _san = _sanitizer.Session()
        _sanitizer.activate(_san)
    # --trace-out PATH rides along the same way
    _trace_path = None
    if "--trace-out" in sys.argv:
        _i = sys.argv.index("--trace-out")
        if _i + 1 >= len(sys.argv):
            raise SystemExit("--trace-out requires a PATH argument")
        _trace_path = sys.argv[_i + 1]
        del sys.argv[_i:_i + 2]
    _rec = None
    # --gateway-fleet writes PER-PHASE traces inside the phases (so
    # the single/fleet recordings don't bleed into one file) — the
    # global recording only wraps the other modes
    if _trace_path is not None and "--gateway-fleet" not in sys.argv:
        from celestia_tpu import tracing as _tracing

        _rec = _tracing.start_recording()
    try:
        if "--das-storm" in sys.argv and "--das-storm-lite" not in sys.argv:
            _kw = {}
            for _flag, _key, _cast in (
                ("--seconds", "seconds", float),
                ("--threads", "threads", int),
                ("--k", "k", int),
                ("--heights", "heights", int),
                ("--queue-capacity", "queue_capacity", int),
                ("--deadline-ms", "deadline_ms", int),
                ("--batch-window-ms", "batch_window_ms", float),
                ("--max-batch", "max_batch", int),
                ("--paged-budget", "paged_budget", int),
                ("--stall-ms", "stall_ms", float),
                ("--ledger", "ledger", str),
                ("--require-speedup", "require_speedup", float),
            ):
                if _flag in sys.argv:
                    _i = sys.argv.index(_flag)
                    if _i + 1 >= len(sys.argv):
                        raise SystemExit(f"{_flag} requires a value")
                    _kw[_key] = _cast(sys.argv[_i + 1])
            main_das_storm(**_kw)
        elif "--das-storm-lite" in sys.argv:
            _kw = {}
            for _flag, _key, _cast in (
                ("--seconds", "seconds", float),
                ("--threads", "threads", int),
                ("--queue-capacity", "queue_capacity", int),
                ("--deadline-ms", "deadline_ms", int),
                ("--stall-ms", "stall_ms", float),
                ("--k", "k", int),
            ):
                if _flag in sys.argv:
                    _i = sys.argv.index(_flag)
                    if _i + 1 >= len(sys.argv):
                        raise SystemExit(f"{_flag} requires a value")
                    _kw[_key] = _cast(sys.argv[_i + 1])
            main_das_storm_lite(**_kw)
        elif "--gateway-fleet" in sys.argv:
            _kw = {}
            for _flag, _key, _cast in (
                ("--seconds", "seconds", float),
                ("--threads", "threads", int),
                ("--k", "k", int),
                ("--heights", "heights", int),
                ("--queue-capacity", "queue_capacity", int),
                ("--deadline-ms", "deadline_ms", int),
                ("--fleet", "fleet", int),
                ("--processes", "processes", int),
                ("--ledger", "ledger", str),
                ("--require-scaling", "require_scaling", float),
            ):
                if _flag in sys.argv:
                    _i = sys.argv.index(_flag)
                    if _i + 1 >= len(sys.argv):
                        raise SystemExit(f"{_flag} requires a value")
                    _kw[_key] = _cast(sys.argv[_i + 1])
            if _trace_path is not None:
                _kw["trace_out"] = _trace_path
            main_gateway_fleet(**_kw)
        elif "--multichip-child" in sys.argv:
            _kw = {}
            for _flag, _key, _cast in (
                ("--devices", "devices", int),
                ("--blocks", "blocks", int),
                ("--k", "k", int),
                ("--depth", "depth", int),
            ):
                if _flag in sys.argv:
                    _i = sys.argv.index(_flag)
                    if _i + 1 >= len(sys.argv):
                        raise SystemExit(f"{_flag} requires a value")
                    _kw[_key] = _cast(sys.argv[_i + 1])
            main_multichip_child(**_kw)
        elif "--multichip-pipeline" in sys.argv:
            _kw = {}
            for _flag, _key, _cast in (
                ("--devices", "devices", int),
                ("--blocks", "blocks", int),
                ("--k", "k", int),
                ("--depth", "depth", int),
                ("--ledger", "ledger", str),
                ("--require-scaling", "require_scaling", float),
            ):
                if _flag in sys.argv:
                    _i = sys.argv.index(_flag)
                    if _i + 1 >= len(sys.argv):
                        raise SystemExit(f"{_flag} requires a value")
                    _kw[_key] = _cast(sys.argv[_i + 1])
            main_multichip_pipeline(**_kw)
        elif "--transfers" in sys.argv:
            main_transfers()
        elif "--fused-kernels" in sys.argv:
            main_fused_kernels()
        else:
            main()
    finally:
        if _san is not None:
            _sanitizer.deactivate(_san)
        if _rec is not None:
            _rec.stop()
            _rec.write(_trace_path)
            print(
                f"trace written: {_trace_path} ({len(_rec.spans)} spans)",
                file=sys.stderr,
            )
    if _san is not None:
        import pathlib as _pathlib

        _srep = _sanitizer.finalize(
            _san, _pathlib.Path(__file__).resolve().parent,
            coverage=False)
        if _srep.new_findings:
            print(
                f"celestia-san: {len(_srep.new_findings)} new runtime "
                "finding(s) under bench load:", file=sys.stderr)
            for _f in _srep.new_findings:
                print(f"  {_f.render()}", file=sys.stderr)
            sys.exit(1)
        print(
            f"celestia-san: clean ({len(_srep.tokens)} tokens, "
            f"{len(_srep.edges)} edges observed)", file=sys.stderr)
