#!/usr/bin/env python
"""Fused-kernel smoke gate (ADR-019, `make kernel-smoke`).

Crypto-free, <120 s, CPU-capable drill of the fused extend+hash
pipeline and the `auto` backend rule. Fails (non-zero exit) unless:

  1. the PRODUCTION roots path (`extend_tpu.roots_device` — fused
     Pallas on an accelerator, the XLA fallback spelling on CPU)
     returns byte-identical DAH axis roots vs the host oracle at
     k ∈ {32, 64},
  2. the fused pipeline MATH (rs_pallas reference spelling — the
     kernels' exact per-tile bodies executed eagerly, wide-tile) is
     byte-identical to the host DAH at k ∈ {32, 64}, i.e. the k range
     `_MIN_K` newly opened to the kernel path,
  3. the kernel path actually covers those sizes
     (`rs_pallas.fused_supported` at k ∈ {32, 64}),
  4. `auto` backend resolution picks the device at every k when an
     accelerator is present and never picks it on a host without one,
  5. batched roots-only never degrades to singles at k=128
     (`_batch_chunk` picks a vmappable chunk > 1 — BENCH 7b).

The signing stack is optional: check 4 runs through the real
`App.resolve_extend_backend` when `cryptography` is importable and is
reported as skipped otherwise.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

T0 = time.time()


def gate(ok: bool, what: str) -> None:
    print(f"[{time.time() - T0:6.1f}s] " + ("PASS " if ok else "FAIL ") + what)
    if not ok:
        raise SystemExit(f"kernel-smoke: {what}")


def main() -> None:
    import numpy as np

    # persistent XLA compile cache: the production roots program's
    # XLA:CPU compile (~40 s cold) loads from disk on repeat runs,
    # keeping this gate well inside its budget in CI loops
    from celestia_tpu.ops import enable_compile_cache

    enable_compile_cache()

    from bench import build_square
    from celestia_tpu import da
    from celestia_tpu.ops import extend_tpu, rs_pallas

    for k in (32, 64):
        sq = build_square(k)
        eds_ref = da.extend_shares(sq.reshape(k * k, 512))
        dah = da.new_data_availability_header(eds_ref)

        # 1. production dispatch (whatever spelling this backend runs).
        # One size only: the k=64 program is the same code path and its
        # XLA:CPU compile alone costs ~40 s of the 120 s budget; the
        # fused MATH — the thing this gate is new for — is pinned at
        # both sizes below, and tier-1 tests cover production dispatch
        # across k.
        if k == 32:
            rows_d, cols_d = extend_tpu.roots_device(sq)
            gate(
                [bytes(r) for r in rows_d] == dah.row_roots
                and [bytes(c) for c in cols_d] == dah.column_roots,
                f"production roots_device DAH parity at k={k}",
            )

        # 2. the fused pipeline math itself, eagerly (wide tile: same
        # bytes, fewer eager dispatches — see encode2d_hash_reference)
        eds_f, rows_f, cols_f = extend_tpu.fused_roots_reference(
            sq, tile=k * 512
        )
        gate(
            np.array_equal(eds_f, eds_ref.data)
            and [bytes(r) for r in rows_f] == dah.row_roots
            and [bytes(c) for c in cols_f] == dah.column_roots,
            f"fused extend+hash pipeline DAH parity at k={k}",
        )

        # 3. the kernel path covers this size
        gate(
            rs_pallas.fused_supported(k, k * 512),
            f"fused kernel supports k={k} (_MIN_K={rs_pallas._MIN_K})",
        )

    # 4. the auto backend rule
    try:
        import cryptography  # noqa: F401

        have_crypto = True
    except ImportError:
        have_crypto = False
    if have_crypto:
        from celestia_tpu.app import app as app_mod

        app = app_mod.App(extend_backend="auto")
        orig = app_mod.accelerator_available
        try:
            app_mod.accelerator_available = lambda: True
            gate(all(app.resolve_extend_backend(k) == "tpu"
                     for k in (1, 2, 16, 64, 128)),
                 "auto resolution picks TPU at every k (App path)")
            app_mod.accelerator_available = lambda: False
            gate(app.resolve_extend_backend(64) != "tpu",
                 "auto resolution degrades off a dead accelerator")
        finally:
            app_mod.accelerator_available = orig
    else:
        print("SKIP auto resolution (App needs the signing stack)")

    # 5. batched roots-only stays vmappable at k=128
    chunk = extend_tpu._batch_chunk(128, 8)
    gate(1 < chunk <= 8,
         f"batched roots at k=128 uses vmappable chunks (chunk={chunk})")

    print(f"kernel-smoke: all gates green in {time.time() - T0:.1f}s")


if __name__ == "__main__":
    main()
