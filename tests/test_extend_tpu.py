"""Byte-parity of the device pipeline (ops.rs_tpu / ops.sha256_jax /
ops.extend_tpu) against the host reference path (celestia_tpu.da), which is
itself oracle-verified against the reference DAH vectors
(tests/test_dah_oracle.py)."""

import hashlib

import numpy as np
import pytest

import celestia_tpu.namespace as ns
from celestia_tpu import da
from celestia_tpu.ops import extend_tpu, gf256, rs_tpu, sha256_jax


def rand_square(rng, k):
    sh = rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)
    flat = sh.reshape(k * k, 512)
    subs = sorted(rng.integers(0, 200, size=(k * k, 10), dtype=np.uint8).tolist())
    for i, sub in enumerate(subs):
        flat[i, :29] = np.frombuffer(ns.new_v0(bytes(sub)).bytes, dtype=np.uint8)
    return flat.reshape(k, k, 512)


class TestSha256Jax:
    @pytest.mark.parametrize("length", [1, 55, 56, 64, 91, 181, 542])
    def test_matches_hashlib(self, length):
        rng = np.random.default_rng(length)
        msgs = rng.integers(0, 256, size=(4, length), dtype=np.uint8)
        got = sha256_jax.sha256(msgs)
        for i in range(4):
            assert got[i].tobytes() == hashlib.sha256(msgs[i].tobytes()).digest()

    def test_multidim_batch(self):
        rng = np.random.default_rng(7)
        msgs = rng.integers(0, 256, size=(2, 3, 90), dtype=np.uint8)
        got = sha256_jax.sha256(msgs)
        assert got.shape == (2, 3, 32)
        assert got[1, 2].tobytes() == hashlib.sha256(msgs[1, 2].tobytes()).digest()


class TestRsBitMatmul:
    @pytest.mark.parametrize("k", [2, 4, 16])
    def test_matches_leopard(self, k):
        import jax.numpy as jnp

        rng = np.random.default_rng(k)
        data = rng.integers(0, 256, size=(k, 64), dtype=np.uint8)
        ref = gf256.leopard_encode(data)
        m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
        got = np.asarray(rs_tpu.rs_encode_rows(jnp.asarray(data), m2))
        assert np.array_equal(ref, got)

    def test_batched(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        k = 4
        batch = rng.integers(0, 256, size=(3, k, 32), dtype=np.uint8)
        ref = np.stack([gf256.leopard_encode(b) for b in batch])
        m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
        got = np.asarray(rs_tpu.rs_encode_rows(jnp.asarray(batch), m2))
        assert np.array_equal(ref, got)


POW2_KS = [2, 4, 8, 16, 32, 64, 128]


def _assert_matrix_matches_leopard(k: int) -> None:
    """The expanded (8k,8k) GF(2) matrix must spell exactly the Leopard
    encode: parity bytes via unpack -> m2-contraction -> pack equal
    gf256.leopard_encode on random shards."""
    rng = np.random.default_rng(1000 + k)
    data = rng.integers(0, 256, size=(k, 48), dtype=np.uint8)
    ref = gf256.leopard_encode(data)
    # pure-numpy spelling of the bit contraction (LSB-first planes,
    # contraction index q = 8*shard + bit — the rs_tpu layout contract)
    bits = ((data[:, None, :] >> np.arange(8)[None, :, None]) & 1).reshape(
        8 * k, -1
    )
    m2 = rs_tpu.encode_bit_matrix(k)
    pbits = ((m2.astype(np.int64) @ bits) & 1).reshape(k, 8, -1)
    got = (pbits << np.arange(8)[None, :, None]).sum(axis=1).astype(np.uint8)
    assert np.array_equal(ref, got)


class TestEncodeMatrixVsLeopard:
    """Every committed power-of-two k (2..128); k >= 32 rides the slow
    tier."""

    @pytest.mark.parametrize("k", [k for k in POW2_KS if k < 32])
    def test_matrix_matches_leopard(self, k):
        _assert_matrix_matches_leopard(k)

    @pytest.mark.slow
    @pytest.mark.parametrize("k", [k for k in POW2_KS if k >= 32])
    def test_matrix_matches_leopard_large(self, k):
        _assert_matrix_matches_leopard(k)


class TestExtendAndRoot:
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_byte_parity_vs_host(self, k):
        rng = np.random.default_rng(100 + k)
        sq = rand_square(rng, k)
        eds_h = da.extend_shares(sq)
        dah_h = da.new_data_availability_header(eds_h).hash()
        eds_t, rows_t, cols_t, dah_t = extend_tpu.extend_and_root_device(sq)
        assert np.array_equal(eds_h.data, eds_t)
        assert [r.tobytes() for r in rows_t] == eds_h.row_roots()
        assert [c.tobytes() for c in cols_t] == eds_h.col_roots()
        assert dah_t.tobytes() == dah_h

    @pytest.mark.slow
    def test_byte_parity_k16(self):
        rng = np.random.default_rng(116)
        sq = rand_square(rng, 16)
        eds_h = da.extend_shares(sq)
        dah_h = da.new_data_availability_header(eds_h).hash()
        _, _, _, dah_t = extend_tpu.extend_and_root_device(sq)
        assert dah_t.tobytes() == dah_h


class TestPallasKernel:
    """The all-VMEM Pallas encode (ops.rs_pallas) must be bit-exact vs the
    XLA spelling; interpret mode exercises it on the CPU test platform."""

    @pytest.mark.parametrize("k", [32, 64])
    def test_pallas_extend_matches_xla(self, k):
        import jax.numpy as jnp

        from celestia_tpu.ops import rs_pallas, rs_tpu

        rng = np.random.default_rng(200 + k)
        q0 = rand_square(rng, k)
        m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
        # reference: the pure-XLA quadrant chain (extend_square is XLA-only)
        ref = np.asarray(rs_tpu.extend_square(jnp.asarray(q0), m2))
        pal = np.asarray(rs_pallas.extend_square(jnp.asarray(q0), m2, interpret=True))
        assert np.array_equal(ref, pal)

    @pytest.mark.slow  # pallas interpret mode: compile-bound on 1 CPU core;
    # the XLA roots-only path stays covered fast by test_device_resident
    def test_roots_only_matches_full(self):
        import jax.numpy as jnp

        from celestia_tpu.ops import rs_tpu

        k = 4
        rng = np.random.default_rng(77)
        sq = rand_square(rng, k)
        m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
        eds_f, rows_f, cols_f, _dah = extend_tpu.extend_and_root(jnp.asarray(sq), m2)
        eds_r, rows_r, cols_r = extend_tpu.extend_and_roots_only(jnp.asarray(sq), m2)
        assert np.array_equal(np.asarray(eds_f), np.asarray(eds_r))
        assert np.array_equal(np.asarray(rows_f), np.asarray(rows_r))
        assert np.array_equal(np.asarray(cols_f), np.asarray(cols_r))


class TestSha256Pallas:
    """The all-VMEM unrolled Pallas SHA-256 (ops.sha256_pallas): the
    kernel MATH (sha_core_reference — the exact function body the
    device kernel runs on its VMEM tile) must be bit-exact vs hashlib
    and the XLA spelling at both NMT message shapes. The pallas grid
    glue itself needs a real TPU (interpret mode jits internally and
    XLA:CPU takes minutes on the unrolled graph) — covered by the
    tpu-marked test below and the device microbench in the module
    docstring."""

    @pytest.mark.slow  # supplementary: the production XLA spelling is
    # covered fast by TestSha256Jax; this pins the Pallas kernel MATH
    def test_kernel_math_matches_hashlib(self):
        import hashlib

        import jax.numpy as jnp

        from celestia_tpu.ops import sha256_jax, sha256_pallas

        rng = np.random.default_rng(77)
        for n, length in ((7, 90), (5, 181), (3, 571)):
            msgs = rng.integers(0, 256, size=(n, length), dtype=np.uint8)
            words = sha256_pallas.message_words(jnp.asarray(msgs))
            digests = np.asarray(
                sha256_pallas.sha_core_reference(words)
            ).T  # (n, 8) words
            got = np.asarray(
                sha256_jax.words_to_bytes(np.ascontiguousarray(digests))
            )
            ref = np.asarray(sha256_jax.sha256_fixed(msgs))
            assert got.tobytes() == ref.tobytes()
            for i in range(n):
                assert (
                    got[i].tobytes()
                    == hashlib.sha256(msgs[i].tobytes()).digest()
                )

    @pytest.mark.tpu
    def test_pallas_call_on_device(self):
        """The grid/BlockSpec glue on a real TPU, incl. lane padding."""
        import hashlib

        import jax
        import jax.numpy as jnp

        from celestia_tpu.ops import sha256_pallas

        if jax.default_backend() == "cpu":
            pytest.skip("needs a TPU device")
        rng = np.random.default_rng(78)
        msgs = rng.integers(0, 256, size=(700, 571), dtype=np.uint8)
        got = np.asarray(sha256_pallas.sha256_fixed(jnp.asarray(msgs)))
        for i in (0, 1, 511, 512, 699):  # crosses the tile boundary
            assert got[i].tobytes() == hashlib.sha256(
                msgs[i].tobytes()
            ).digest()
