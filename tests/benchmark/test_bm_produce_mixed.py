"""The produce-mixed cell at its own size: the mix's cycle of block
shapes at k=64, signed as the benchmark signs them, laid out by the
program and by the plain reference alike, and every assembled-roots
program the blocks reach predicted and warmed by set-up."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import gen, harness, reference, shapes  # noqa: E402

K = 64
SEED = 2**31 + 2202
CYCLE = 8  # the mix's cycle_blocks
LRU = 16  # extend_tpu._jitted_assembled_roots keeps this many programs


@pytest.fixture(scope="module")
def mix():
    return harness.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                          "produce-mixed.json"))


@pytest.fixture(scope="module")
def blocks(mix):
    """(planned block, the program's square, its builder, the block's
    txs in square order) for each block of the mix's cycle."""
    from celestia_tpu import square as square_pkg

    signer = gen.Signer(mix["accounts"], list(range(mix["accounts"])))
    out = []
    for i, block in enumerate(gen.plan(mix, K, SEED, mix["cycle_blocks"])):
        txs = signer.sign_block(block, gen.block_bytes(SEED, i, block))
        square, block_txs, builder = square_pkg.build_ex(txs, 2, K)
        assert len(block_txs) == len(txs)  # every PFB lands
        out.append((block, square, builder, block_txs))
    return out


def _array(square):
    return np.frombuffer(b"".join(s.data for s in square), np.uint8).reshape(K, K, 512)


def test_cycle_is_the_whole_square_of_many_small_pfbs(mix, blocks):
    assert len(blocks) == mix["cycle_blocks"] == CYCLE
    for block, square, _, _ in blocks:
        assert len(square) == K * K
        assert max(len(pfb) for pfb in block) <= 4
    assert max(len(b[0]) for b in blocks) > 4 * min(len(b[0]) for b in blocks)


@pytest.mark.parametrize("i", range(CYCLE))
def test_reference_layout_equals_the_program_square(blocks, i):
    from celestia_tpu import square as square_pkg

    _, square, _, txs = blocks[i]
    program = _array(square)
    assert np.array_equal(program, _array(square_pkg.construct(txs, 2, K)))
    assert np.array_equal(reference.construct(txs), program)


@pytest.mark.parametrize("i", range(CYCLE))
def test_reference_checks_the_program_dah_and_eds(blocks, i):
    from celestia_tpu import da

    _, square, _, txs = blocks[i]
    eds = da.extend_shares(_array(square).reshape(-1, 512))
    data_hash = da.new_data_availability_header(eds).hash()
    res = reference.check_block(txs, data_hash, K, np.asarray(eds.data))
    assert res == {"layout_error": None, "size_ok": True, "dah_ok": True,
                   "eds_ok": True}


class _ResidentArena:
    """Every blob resident, as CheckTx leaves them in the benchmark."""

    def __init__(self, lengths: dict):
        self.lengths = lengths
        self.n = 0

    def offset_of(self, key):
        self.n += 1
        return self.n * 4096, self.lengths[key]


def _program_bucket(square, builder) -> tuple[int, int, int]:
    """(h_pad, b_pad, hc_pad) of the assembled-roots program the proposal
    dispatches: the program's own packing, padded as the entry pads."""
    from celestia_tpu.app import App
    from celestia_tpu.ops import blob_pool, extend_tpu

    arena = _ResidentArena({blob_pool.blob_key(b.data): len(b.data)
                            for _, b in builder.blob_layout()})
    packed = App._assemble_pack(SimpleNamespace(blob_pool=arena), square,
                                builder, K)
    host_shares, host_pos, _, _, _, _, _, ns_table = packed
    return (extend_tpu._pow2_at_least(max(len(host_shares), 1), 16),
            extend_tpu._pow2_at_least(max(len(ns_table), 1), 8),
            extend_tpu._pow2_at_least(max(len(host_pos), 1), 16))


def test_set_up_warms_every_proposal_program_the_cycle_reaches(blocks):
    reached = set()
    for block, square, builder, _ in blocks:
        bucket = _program_bucket(square, builder)
        assert bucket in shapes.proposal_buckets(K, block)
        reached.add(bucket)
    assert 1 < len(reached) <= LRU
