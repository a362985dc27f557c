"""Fused extend+hash pipeline (ADR-019): byte-exactness and safety net.

The fused Pallas pipeline computes RS parity, NMT leaf digests, and the
axis roots in one device pass — HBM never sees the unpacked leaf
messages. On CPU the Mosaic kernels cannot lower, so these tests drive
the kernels' EXACT per-tile math through the eager reference spellings
(`rs_pallas.encode2d_hash_reference` et al. — see
ops/sha256_pallas.py on why interpret-mode jit is unusable for the
unrolled SHA graph on CPU) and pin, against the host NMT oracle:

  * DAH byte-parity (EDS bytes + every row/col root) across
    k ∈ {2, 4, 16} tier-1 and k ∈ {32, 64, 128} in the slow tier —
    spanning the `_MIN_K` boundary the kernel path newly covers;
  * the tail-padding edge (a square whose content doesn't fill k², so
    Q0 carries TAIL_PADDING namespaces next to real ones);
  * device-computed NMT node levels seeding `NmtRowProver`
    byte-identically (zero host hashing), including the single-leaf
    tree edge and the malformed-levels rejections;
  * the ADR-015 audit catching an armed `device.extend.output` bitflip
    when the EDS came from the FUSED math;
  * vmappable chunking for batched roots at large k (BENCH 7b).
"""

from __future__ import annotations

import numpy as np
import pytest

from celestia_tpu import da, faults, integrity
from celestia_tpu import namespace as ns
from celestia_tpu.ops import extend_tpu, rs_pallas
from celestia_tpu.proof import NmtRowProver, das_sample_docs

CHAOS_SEED = 1337


def _square(k: int, seed: int = 42, pad_tail: int = 0) -> np.ndarray:
    """Valid k×k Q0: sorted v0 namespaces; the last `pad_tail` shares
    carry TAIL_PADDING_NAMESPACE (the non-pow2-content padding case —
    real squares pad up to k² with these, and the namespace-range logic
    must keep them below PARITY in every tree)."""
    rng = np.random.default_rng(seed)
    flat = rng.integers(0, 256, size=(k * k, 512), dtype=np.uint8)
    body = k * k - pad_tail
    subs = sorted(
        rng.integers(0, 200, size=(body, 10), dtype=np.uint8).tolist()
    )
    for i, sub in enumerate(subs):
        flat[i, :29] = np.frombuffer(
            ns.new_v0(bytes(sub)).bytes, dtype=np.uint8
        )
    for i in range(body, k * k):
        flat[i, :29] = np.frombuffer(
            ns.TAIL_PADDING_NAMESPACE.bytes, dtype=np.uint8
        )
    return flat.reshape(k, k, 512)


def _host_oracle(sq: np.ndarray):
    k = sq.shape[0]
    eds = da.extend_shares(sq.reshape(k * k, 512))
    dah = da.new_data_availability_header(eds)
    return eds, dah


def _assert_fused_parity(sq: np.ndarray, tile: int | None = None):
    k = sq.shape[0]
    eds_ref, dah = _host_oracle(sq)
    eds_f, rows_f, cols_f = extend_tpu.fused_roots_reference(sq, tile=tile)
    assert np.array_equal(eds_f, eds_ref.data)
    assert [bytes(r) for r in rows_f] == dah.row_roots
    assert [bytes(c) for c in cols_f] == dah.column_roots


class TestFusedDahParity:
    @pytest.mark.parametrize("k", [2, 4, 16])
    def test_parity_small_k(self, k):
        _assert_fused_parity(_square(k), tile=k * 512)

    @pytest.mark.slow
    @pytest.mark.parametrize("k", [32, 64, 128])
    def test_parity_large_k(self, k):
        _assert_fused_parity(_square(k), tile=k * 512)

    def test_parity_tail_padding(self):
        # non-pow2 content: 11 real shares padded to 16 with the tail
        # namespace — the min/max namespace walk crosses the boundary
        _assert_fused_parity(_square(4, pad_tail=5), tile=4 * 512)

    def test_reference_tiling_invariant(self):
        # the tile override trades dispatch count for width only: the
        # kernel-exact tiling and the wide spelling must agree on bytes
        sq = _square(2, seed=9)
        a = extend_tpu.fused_roots_reference(sq)
        b = extend_tpu.fused_roots_reference(sq, tile=2 * 512)
        for x, y in zip(a, b):
            assert np.array_equal(x, y)

    def test_kernel_coverage_floor(self):
        # _MIN_K now admits the governance-default sizes
        assert rs_pallas.supported(32, 32 * 512)
        assert rs_pallas.supported(64, 64 * 512)
        assert rs_pallas.fused_supported(32, 32 * 512)
        assert rs_pallas.fused_supported(64, 64 * 512)
        # below the Mosaic tile floor the kernels refuse (XLA fallback)
        assert not rs_pallas.supported(8, 8 * 512)

    def test_fused_inactive_on_cpu_backend(self):
        # the CPU backend keeps the XLA spelling — Mosaic kernels can't
        # lower there
        import jax

        if jax.default_backend() == "cpu":
            assert not extend_tpu._fused_active(64)

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    @pytest.mark.parametrize("k", [4, 8, 16, 64, 128])
    def test_fused_active_rule(self, monkeypatch, backend, k):
        """Fused exactly when the backend is an accelerator AND the
        kernels tile k (the `_MIN_K` floor sits between 8 and 16) —
        decided from what the process sees, with no environment read."""
        import os

        import jax

        class _NoReads(dict):
            def __getitem__(self, key):
                raise AssertionError(f"environment read: {key}")

            get = __contains__ = __getitem__

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(os, "environ", _NoReads(os.environ))
        want = backend == "tpu" and k >= rs_pallas._MIN_K
        assert want == (backend == "tpu"
                        and rs_pallas.fused_supported(k, k * 512))
        assert extend_tpu._fused_active(k) is want


class TestDeviceProverSeeding:
    def _levels(self, k: int, seed: int = 3):
        eds, dah = _host_oracle(_square(k, seed=seed))
        levels = extend_tpu.eds_row_levels_device(eds.data)
        return eds, dah, levels

    @pytest.mark.parametrize("k", [2, 8])
    def test_levels_seed_byte_identical_provers(self, k):
        eds, dah, levels = self._levels(k)
        w = 2 * k
        assert [lv.shape for lv in levels] == [
            (w, w >> i, 90) for i in range(w.bit_length())
        ]
        for r in range(w):
            leaves = da.erasured_axis_leaves(
                [bytes(eds.data[r, c]) for c in range(w)], r, k
            )
            host = NmtRowProver(leaves)
            seeded = NmtRowProver.from_node_levels(
                [levels[L][r] for L in range(len(levels))]
            )
            assert seeded.root() == host.root() == dah.row_roots[r]
            for j in (0, w - 1, w // 2):
                ph = host.prove_range(j, j + 1)
                ps = seeded.prove_range(j, j + 1)
                assert ph.nodes == ps.nodes
                assert ph.tree_size == ps.tree_size

    def test_sample_docs_with_seeded_provers_identical(self):
        k = 4
        eds, _dah, levels = self._levels(k)
        rows = {
            r: [bytes(eds.data[r, c]) for c in range(2 * k)] for r in (0, 5)
        }
        coords = [(0, 0), (5, 3), (0, 7), (5, 5)]
        pre = {
            r: NmtRowProver.from_node_levels(
                [levels[L][r] for L in range(len(levels))]
            )
            for r in rows
        }
        assert das_sample_docs(rows, coords, k) == das_sample_docs(
            rows, coords, k, provers=pre
        )

    def test_single_leaf_tree(self):
        # n=1: one level, one node — the degenerate tree must still
        # serve root() and reject out-of-range proofs
        from celestia_tpu.ops.nmt_host import hash_leaf

        leaf = ns.new_v0(b"a" * 10).bytes + b"\x01" * 16
        node = hash_leaf(leaf)
        prover = NmtRowProver.from_node_levels(
            [np.frombuffer(node, np.uint8).reshape(1, 90)]
        )
        assert prover.tree_size == 1
        assert prover.root() == node
        assert prover.prove_range(0, 1).nodes == []
        with pytest.raises(ValueError):
            prover.prove_range(1, 2)

    def test_malformed_levels_rejected(self):
        good = [np.zeros((4, 90), np.uint8), np.zeros((2, 90), np.uint8),
                np.zeros((1, 90), np.uint8)]
        NmtRowProver.from_node_levels(good)  # shape is acceptable
        with pytest.raises(ValueError, match="pow2"):
            NmtRowProver.from_node_levels([np.zeros((3, 90), np.uint8)])
        with pytest.raises(ValueError, match="complete binary tree"):
            NmtRowProver.from_node_levels(good[:2])


class TestFusedPathAudited:
    def test_bitflip_in_fused_eds_detected(self, monkeypatch):
        """ADR-015 safety net around the NEW math: corrupt the EDS the
        fused pipeline produced (the `device.extend.output` SDC model —
        HBM upset / bad D2H after compute) and the audit must raise
        before any DAH is committed. The audit recomputes GF syndromes
        on the tensor itself, so it is spelling-independent — this pins
        that the fused outputs feed it unchanged."""
        k = 4
        sq = _square(k)

        def fused_run(dev):
            eds, rows, cols = extend_tpu.fused_roots_reference(
                np.asarray(dev), tile=k * 512
            )
            import jax.numpy as jnp

            return jnp.asarray(eds), jnp.asarray(rows), jnp.asarray(cols)

        monkeypatch.setattr(
            extend_tpu, "_jitted_roots_for_k", lambda _k: fused_run
        )
        integrity.configure("full")
        try:
            with faults.inject(
                faults.rule("device.extend.output", "bitflip"),
                seed=CHAOS_SEED,
            ):
                with pytest.raises(integrity.IntegrityError) as ei:
                    extend_tpu.extend_roots_device(sq)
            assert ei.value.site == "device.extend.output"
            assert ei.value.mismatches > 0
            # clean fused output passes the same audit
            eds, rows, cols = extend_tpu.extend_roots_device(sq)
            _eds_ref, dah = _host_oracle(sq)
            assert [bytes(r) for r in rows] == dah.row_roots
        finally:
            integrity.configure("off")


class TestBatchedChunking:
    def test_large_k_chunk_is_vmappable(self):
        # BENCH 7b regression: batched roots at k=128 must not degrade
        # to pipelined singles — pairs bound HBM at 2x a single square
        # while halving dispatches
        assert extend_tpu._batch_chunk(128, 8) == 2
        assert extend_tpu._batch_chunk(128, 1) == 1
        assert extend_tpu._batch_chunk(64, 8) == 8
        assert extend_tpu._batch_chunk(16, 4) == 4

    def test_chunked_dispatch_byte_identical(self, monkeypatch):
        squares = [_square(4, seed=50 + i) for i in range(5)]
        singles = [extend_tpu.roots_device(s) for s in squares]
        monkeypatch.setattr(extend_tpu, "_batch_chunk", lambda k, b: 2)
        rows_b, cols_b = extend_tpu.batched_roots_device(squares)
        for i, (rows_s, cols_s) in enumerate(singles):
            assert np.array_equal(rows_b[i], rows_s)
            assert np.array_equal(cols_b[i], cols_s)
