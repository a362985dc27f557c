"""Share commitments computed once per parsed blob (x/blob/types
validate_blob_tx): CheckTx computes each blob's commitment, the
proposer's filter and ProcessProposal read it from the parsed blob that
blob.unmarshal_blob_tx's parse cache hands them; a wrong declared
commitment is rejected whether the commitment was computed before or
not; blobs of the same data under another namespace or share version are
computed anew; a node that parses a proposal afresh reaches the
proposer's verdict and DAH; the memo goes with its parse-cache entry;
threads validating shared parses see only true commitments."""

import threading

import pytest

from celestia_tpu import blob as blob_pkg
from celestia_tpu import da
from celestia_tpu import inclusion
from celestia_tpu import namespace as ns
from celestia_tpu import square as square_pkg
from celestia_tpu.app import App
from celestia_tpu.app.app import ProposalBlockData
from celestia_tpu.crypto import PrivateKey
from celestia_tpu.node import Node
from celestia_tpu.shares import to_bytes
from celestia_tpu.telemetry import metrics
from celestia_tpu.tx import Fee, sign_tx
from celestia_tpu.x.blob import validate_blob_tx
from celestia_tpu.x.blob.types import (
    _share_commitment,
    estimate_gas,
    new_msg_pay_for_blobs,
)
from test_app import ALICE, BOB, fresh_app, make_pfb_tx

PARSE_BUDGET = 192 * 1024 * 1024


@pytest.fixture(autouse=True)
def parse_cache(monkeypatch):
    """A parse cache of this test's own, so that no parse (and no
    commitment on it) from another test is found."""
    cache = blob_pkg._ByteBudgetLRU(budget_bytes=PARSE_BUDGET, overhead_factor=1)
    monkeypatch.setattr(blob_pkg, "_PARSE_CACHE", cache)
    return cache


def _lookups() -> tuple[float, float]:
    return (metrics.get_counter("blob_commitment_lookups_total", result="miss"),
            metrics.get_counter("blob_commitment_lookups_total", result="hit"))


def _delta(before) -> tuple[float, float]:
    after = _lookups()
    return after[0] - before[0], after[1] - before[1]


def _blob(data: bytes, sub_id: bytes = b"cc1", share_version: int = 0):
    return blob_pkg.Blob(namespace_id=ns.new_v0(sub_id).id, data=data,
                         share_version=share_version, namespace_version=0)


def _wrong_commitment_tx(app, key: PrivateKey, b) -> bytes:
    """A PFB for blob `b` that declares another commitment."""
    acc = app.accounts.get_account(key.bech32_address())
    msg = new_msg_pay_for_blobs(key.bech32_address(), b)
    msg.share_commitments[0] = bytes(32)
    gas = estimate_gas([len(b.data)])
    tx = sign_tx(key, [msg], app.chain_id, acc.account_number, acc.sequence,
                 Fee(amount=gas, gas_limit=gas))
    return blob_pkg.marshal_blob_tx(tx.marshal(), [b])


def _proposal(app, txs: list[bytes]) -> ProposalBlockData:
    """A proposal of exactly `txs` with its true DAH, built outside the
    app so that nothing it holds is consulted."""
    data_square, kept = square_pkg.build(txs, app.app_version,
                                         app.gov_square_size_upper_bound())
    dah = da.new_data_availability_header(da.extend_shares(to_bytes(data_square)))
    return ProposalBlockData(txs=kept,
                             square_size=square_pkg.square_size(len(data_square)),
                             hash=dah.hash())


def test_admit_filter_process_is_one_miss_then_two_hits():
    app = fresh_app()
    raw = make_pfb_tx(app, ALICE, b"\x11" * 3000)
    before = _lookups()
    assert app.check_tx(raw).code == 0
    assert _delta(before) == (1, 0)
    block = app.prepare_proposal([raw])
    assert block.txs == [raw]
    assert _delta(before) == (1, 1)
    assert app.process_proposal(block)
    assert _delta(before) == (1, 2)
    (b,) = blob_pkg.unmarshal_blob_tx(raw)[0].blobs
    assert b._commitment == inclusion.create_commitment(b)


@pytest.mark.parametrize("phase", ["check_tx", "process_proposal"])
@pytest.mark.parametrize("seen", ["same_tx", "same_blob"])
def test_wrong_declared_commitment_is_rejected_after_a_computed_one(phase, seen):
    """`same_tx`: CheckTx computed the commitment of this very tx's blob
    and rejected it; the next pass reads it and rejects again.
    `same_blob`: another tx carried the same blob with its true
    commitment and was admitted; this tx's blob is parsed anew."""
    app = fresh_app()
    b = blob_pkg.new_blob(ns.new_v0(b"cc2"), b"\x22" * 900, 0)
    bad = _wrong_commitment_tx(app, ALICE, b)
    if seen == "same_tx":
        res = app.check_tx(bad)
        assert res.code != 0 and "commitment" in res.log
        want = (0, 1)
    else:
        assert app.check_tx(make_pfb_tx(app, BOB, b.data, sub_id=b"cc2")).code == 0
        want = (1, 0)
    proposal = _proposal(app, [bad]) if phase == "process_proposal" else None
    before = _lookups()
    if phase == "check_tx":
        res = app.check_tx(bad)
        assert res.code != 0 and "commitment" in res.log
    else:
        assert not app.process_proposal(proposal)
    assert _delta(before) == want


@pytest.mark.parametrize("change", ["namespace", "share_version"])
def test_same_data_under_another_namespace_or_version_is_computed(change):
    """Share version 1 has no splitter here: a lookup that served the
    version-0 commitment would hide that; computing it raises."""
    data = bytes(range(256)) * 200
    first = _blob(data)
    _share_commitment(first)
    other = (_blob(data, sub_id=b"cc3") if change == "namespace"
             else _blob(data, share_version=1))
    before = _lookups()
    if change == "namespace":
        got, hit = _share_commitment(other)
        assert not hit
        assert got == inclusion.create_commitment(other)
        assert got != first._commitment
    else:
        with pytest.raises(ValueError, match="share version"):
            _share_commitment(other)
    assert _delta(before) == (1, 0)


def test_fresh_parse_reaches_the_proposers_verdict_and_dah(monkeypatch):
    proposer, follower = fresh_app(), fresh_app()
    txs = [make_pfb_tx(proposer, ALICE, bytes([7]) * 5000),
           make_pfb_tx(proposer, BOB, bytes([9]) * 700, sub_id=b"ns2")]
    for raw in txs:
        assert proposer.check_tx(raw).code == 0
    block = proposer.prepare_proposal(txs)
    assert len(block.txs) == 2
    assert proposer.process_proposal(block)
    # the follower's process parses the txs itself, as a node that
    # never admitted them does
    monkeypatch.setattr(blob_pkg, "_PARSE_CACHE", blob_pkg._ByteBudgetLRU(
        budget_bytes=PARSE_BUDGET, overhead_factor=1))
    before = _lookups()
    assert follower.process_proposal(block)  # checks the DAH hash too
    assert _delta(before) == (2, 0)
    assert _proposal(follower, block.txs).hash == block.hash


def test_commitment_goes_with_its_parse_cache_entry(parse_cache):
    """The memo is bounded by the parse cache's byte budget: once a tx's
    parse is evicted, its blobs' commitments are computed again."""
    app = fresh_app()
    bound, extra = 4, 2
    raws = [make_pfb_tx(app, ALICE, bytes([i]) * 2000) for i in range(bound + extra)]
    blob_pkg.unmarshal_blob_tx(raws[0])
    cost = parse_cache.used
    parse_cache.budget = cost * bound + cost // 2
    before = _lookups()
    for raw in raws:
        validate_blob_tx(blob_pkg.unmarshal_blob_tx(raw)[0])
        assert len(parse_cache._data) <= bound
    assert _delta(before) == (bound + extra, 0)
    assert list(parse_cache._data) == raws[extra:]
    validate_blob_tx(blob_pkg.unmarshal_blob_tx(raws[-1])[0])
    assert _delta(before) == (bound + extra, 1)
    validate_blob_tx(blob_pkg.unmarshal_blob_tx(raws[0])[0])
    assert _delta(before) == (bound + extra + 1, 1)


def test_concurrent_admission_stays_correct():
    """RPC threads admit through Node.broadcast_tx at once, each with its
    own signer; each also sends another thread's blob under a wrong
    commitment, which must be rejected."""
    keys = [PrivateKey.from_secret(b"cc-thread-%d" % i) for i in range(4)]
    app = App()
    app.init_chain({key.bech32_address(): 10**10 for key in keys}, genesis_time=0.0)
    node = Node(app)
    node.produce_block(15.0)
    per_thread = 3
    blobs = [[blob_pkg.new_blob(ns.new_v0(b"cc%d" % t), bytes([t, j]) * (300 + j), 0)
              for j in range(per_thread)] for t in range(len(keys))]
    signed = {}
    for t, key in enumerate(keys):
        acc = app.accounts.get_account(key.bech32_address())
        signed[t] = []
        for j, b in enumerate(blobs[t]):
            gas = estimate_gas([len(b.data)])
            tx = sign_tx(key, [new_msg_pay_for_blobs(key.bech32_address(), b)],
                         app.chain_id, acc.account_number, acc.sequence + j,
                         Fee(amount=gas, gas_limit=gas))
            signed[t].append(blob_pkg.marshal_blob_tx(tx.marshal(), [b]))
    results: dict = {}

    def admit(t):
        out = [node.broadcast_tx(raw).code for raw in signed[t]]
        other = blobs[(t + 1) % len(keys)][0]
        res = node.broadcast_tx(_wrong_commitment_tx(app, keys[t], other))
        results[t] = (out, res.code, res.log)

    before = _lookups()
    threads = [threading.Thread(target=admit, args=(t,)) for t in range(len(keys))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for t in range(len(keys)):
        codes, bad_code, bad_log = results[t]
        assert codes == [0] * per_thread
        assert bad_code != 0 and "commitment" in bad_log
    n = len(keys) * per_thread
    assert _delta(before) == (n + len(keys), 0)
    block = node.produce_block(30.0)
    assert sorted(block.txs) == sorted(raw for t in signed for raw in signed[t])
    assert _delta(before) == (n + len(keys), 2 * n)


def test_threads_validating_shared_parses_see_only_true_commitments():
    """Threads validate the same parsed txs at once, good ones and ones
    that pair the same blob data with a wrong commitment: every good tx
    passes, every wrong one is rejected, and each parsed blob keeps its
    own commitment."""
    app = fresh_app()
    blobs = [_blob(bytes([i]) * (4000 + i), sub_id=b"cc%d" % (i % 3)) for i in range(6)]
    goods = [make_pfb_tx(app, ALICE, b.data, sub_id=b"cc%d" % (i % 3))
             for i, b in enumerate(blobs)]
    bads = [_wrong_commitment_tx(app, BOB, b) for b in blobs]
    wrong = []

    def look(offset):
        for r in range(12):
            i = (offset + r) % len(blobs)
            try:
                validate_blob_tx(blob_pkg.unmarshal_blob_tx(goods[i])[0])
            except ValueError:
                wrong.append(("rejected", i))
            try:
                validate_blob_tx(blob_pkg.unmarshal_blob_tx(bads[i])[0])
                wrong.append(("admitted", i))
            except ValueError:
                pass

    threads = [threading.Thread(target=look, args=(t,)) for t in range(6)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert wrong == []
    for raw in goods + bads:
        (b,) = blob_pkg.unmarshal_blob_tx(raw)[0].blobs
        assert b._commitment == inclusion.create_commitment(b)
