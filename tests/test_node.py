"""Node shell tests: mempool, block production, RPC, signer, txsim,
checkpoint/resume (reference model: test/util/testnode usage in
app/test/*_test.go)."""

import json
import urllib.request

import pytest

from celestia_tpu import blob as blob_pkg
from celestia_tpu import namespace as ns
from celestia_tpu.app import App
from celestia_tpu.crypto import PrivateKey
from celestia_tpu.node import Node
from celestia_tpu.node.node import tx_hash
from celestia_tpu.node.rpc import RpcServer
from celestia_tpu.txsim import BlobSequence, SendSequence, run as txsim_run
from celestia_tpu.user import Signer

VALIDATOR = PrivateKey.from_secret(b"validator")
ALICE = PrivateKey.from_secret(b"alice")


def new_node(tmp_path=None, **app_kwargs) -> Node:
    app = App(**app_kwargs)
    app.init_chain(
        {
            VALIDATOR.bech32_address(): 1_000_000_000_000,
            ALICE.bech32_address(): 50_000_000_000,
        },
        genesis_time=0.0,
    )
    node = Node(app, home=str(tmp_path) if tmp_path else None)
    node.produce_block(15.0)  # empty first block
    return node


class TestNode:
    def test_blob_lifecycle(self):
        node = new_node()
        signer = Signer.setup_single(ALICE, node)
        b = blob_pkg.new_blob(ns.new_v0(b"node-test"), b"\x11" * 2000, 0)
        res = signer.submit_pay_for_blob([b])
        assert res.code == 0, res.log
        assert len(node.mempool) == 1

        block = node.produce_block()
        assert len(block.txs) == 1
        assert len(node.mempool) == 0
        assert block.tx_results[0].code == 0

        # confirm + deconstruct round-trip
        found = node.get_tx(tx_hash(block.txs[0]))
        assert found is not None

        square = node.app.extend_block(block.txs)
        assert square.width >= 2

    def test_mempool_priority_order(self):
        node = new_node()
        s_val = Signer.setup_single(VALIDATOR, node)
        s_alice = Signer.setup_single(ALICE, node)
        from celestia_tpu.tx import Fee
        from celestia_tpu.x.bank import MsgSend

        # alice pays a higher gas price -> higher priority
        r1 = s_val.submit_tx(
            [MsgSend(s_val.address(), s_alice.address(), 1)],
            Fee(amount=100_000, gas_limit=200_000),
        )
        r2 = s_alice.submit_tx(
            [MsgSend(s_alice.address(), s_val.address(), 1)],
            Fee(amount=400_000, gas_limit=200_000),
        )
        assert r1.code == 0 and r2.code == 0
        reaped = node.mempool.reap()
        assert len(reaped) == 2
        from celestia_tpu.tx import Tx

        first = Tx.unmarshal(reaped[0])
        assert first.fee.amount == 400_000  # higher priority first

    def test_mempool_ttl_eviction(self):
        node = new_node()
        node.mempool.add(b"some-unprocessable-tx", priority=0, height=node.app.height)
        # mempool txs that never make it into a block expire after TTL blocks
        for _ in range(5):
            node.produce_block()
        assert len(node.mempool) == 0

    def test_txsim(self):
        from celestia_tpu.txsim import StakeSequence
        from celestia_tpu.x.staking import MsgDelegate

        node = new_node()
        val = VALIDATOR.bech32_address()
        vs = Signer.setup_single(VALIDATOR, node)
        vs.submit_tx([MsgDelegate(val, val, 5_000_000)])
        node.produce_block()
        stats = txsim_run(
            node,
            VALIDATOR,
            [BlobSequence(size_min=100, size_max=2000), SendSequence(amount=5),
             StakeSequence(validator=val)],
            rounds=3,
        )
        assert stats["accepted"] == 9
        assert stats["rejected"] == 0
        assert node.latest_height() >= 5
        # the stake churn reached the validator set
        assert node.app.staking.get_validator(val).tokens > 5_000_000

    def test_checkpoint_resume(self, tmp_path):
        node = new_node(tmp_path)
        signer = Signer.setup_single(ALICE, node)
        b = blob_pkg.new_blob(ns.new_v0(b"persist"), b"\x22" * 500, 0)
        assert signer.submit_pay_for_blob([b]).code == 0
        block = node.produce_block()
        node.save_snapshot()

        resumed = Node.load(str(tmp_path))
        assert resumed.latest_height() == node.latest_height()
        assert (
            resumed.app.store.app_hashes[resumed.app.store.version]
            == node.app.store.app_hashes[node.app.store.version]
        )
        assert resumed.get_block(block.height).data_hash == block.data_hash
        # the resumed chain keeps producing blocks
        resumed.produce_block()
        assert resumed.latest_height() == node.latest_height() + 1


class TestRpcClient:
    """The remote transport: the full Signer stack (tx options, nonce
    recovery) over HTTP instead of an in-process Node."""

    def test_signer_over_rpc_client(self):
        from celestia_tpu.node.client import RpcClient

        node = new_node()
        srv = RpcServer(node, port=0)
        srv.start()
        try:
            client = RpcClient(f"http://127.0.0.1:{srv.port}")
            assert client.status()["chain_id"] == node.app.chain_id
            signer = Signer.setup_single(ALICE, client)
            b = blob_pkg.new_blob(ns.new_v0(b"remote"), b"\x21" * 400, 0)
            res = signer.submit_pay_for_blob([b])
            assert res.code == 0, res.log
            node.produce_block(30.0)
            found = client.get_tx(tx_hash(res.raw))
            assert found is not None and found["result"]["code"] == 0
            assert client.balance(ALICE.bech32_address()) > 0
            assert client.params("blob")["gas_per_blob_byte"] == 8
        finally:
            srv.stop()

    def test_nonce_recovery_over_rpc(self):
        """Two remote signers racing one account: the stale one recovers
        from the CheckTx error text through the HTTP boundary."""
        from celestia_tpu.node.client import RpcClient
        from celestia_tpu.x.bank import MsgSend

        node = new_node()
        srv = RpcServer(node, port=0)
        srv.start()
        try:
            client = RpcClient(f"http://127.0.0.1:{srv.port}")
            s1 = Signer.setup_single(ALICE, client)
            s2 = Signer.setup_single(ALICE, client)  # same sequence
            assert s1.submit_tx(
                [MsgSend(ALICE.bech32_address(), VALIDATOR.bech32_address(), 5)]
            ).code == 0
            res = s2.submit_tx(
                [MsgSend(ALICE.bech32_address(), VALIDATOR.bech32_address(), 7)]
            )
            assert res.code == 0, res.log  # auto re-signed at expected seq
            block = node.produce_block(30.0)
            assert [r.code for r in block.tx_results] == [0, 0]
            assert s2.resync_sequence() == 2
        finally:
            srv.stop()


class TestStateSync:
    def test_bootstrap_from_live_peer(self):
        """A fresh node state-syncs over the live RPC snapshot endpoint
        and then produces the same app hash as the peer."""
        node = new_node()
        signer = Signer.setup_single(ALICE, node)
        b = blob_pkg.new_blob(ns.new_v0(b"sync-test"), b"\x44" * 500, 0)
        signer.submit_pay_for_blob([b])
        node.produce_block(30.0)

        server = RpcServer(node, port=0)
        server.start()
        try:
            payload = json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/snapshot"
                ).read()
            )
        finally:
            server.stop()

        synced = Node.state_sync_from(payload)
        assert synced.app.height == node.app.height
        assert synced.app.bank.get_balance(ALICE.bech32_address()) == \
            node.app.bank.get_balance(ALICE.bech32_address())
        b1 = node.produce_block(45.0)
        b2 = synced.produce_block(45.0)
        assert b1.app_hash == b2.app_hash

    def test_tampered_snapshot_rejected(self):
        node = new_node()
        payload = node.snapshot_payload()
        payload["app_hash"] = "00" * 32
        with pytest.raises(ValueError, match="app hash mismatch"):
            Node.state_sync_from(payload)

    def test_trusted_hash_authenticates_against_malicious_peer(self):
        """A peer controls both state and app_hash in its payload; only a
        caller-supplied trusted hash catches consistent tampering."""
        node = new_node()
        victim_trusts = node.snapshot_payload()["app_hash"]

        evil = new_node()
        evil.app.bank.mint(ALICE.bech32_address(), 10**15)  # forged riches
        evil.app.store.commit_hash_refresh()
        payload = evil.snapshot_payload()
        # self-consistent payload passes the integrity-only check...
        Node.state_sync_from(payload)
        # ...but not the authenticated one
        with pytest.raises(ValueError, match="app hash mismatch"):
            Node.state_sync_from(payload, trusted_app_hash=victim_trusts)

    def test_crash_replay_from_stale_snapshot(self, tmp_path):
        """Blocks persisted after the last disk snapshot are replayed
        through the app on load, and each replayed commit is verified
        against the stored app hash."""
        node = new_node(tmp_path)
        node.save_snapshot()  # snapshot at height 1
        signer = Signer.setup_single(ALICE, node)
        b = blob_pkg.new_blob(ns.new_v0(b"replaytest"), b"\x55" * 300, 0)
        signer.submit_pay_for_blob([b])
        node.produce_block(30.0)  # height 2: NOT snapshotted
        node.produce_block(45.0)  # height 3: NOT snapshotted
        final_balance = node.app.bank.get_balance(ALICE.bech32_address())

        recovered = Node.load(str(tmp_path))
        assert recovered.app.height == 3
        assert recovered.app.bank.get_balance(ALICE.bech32_address()) == \
            final_balance
        b1 = node.produce_block(60.0)
        b2 = recovered.produce_block(60.0)
        assert b1.app_hash == b2.app_hash

    def test_corrupt_replay_detected(self, tmp_path):
        node = new_node(tmp_path)
        node.save_snapshot()
        node.produce_block(30.0)
        # corrupt the stored block's app hash
        import pathlib

        path = pathlib.Path(tmp_path) / "blocks" / "2.json"
        data = json.loads(path.read_text())
        data["app_hash"] = "00" * 32
        path.write_text(json.dumps(data))
        with pytest.raises(ValueError, match="state corruption"):
            Node.load(str(tmp_path))

    def test_corrupt_data_hash_detected_on_replay(self, tmp_path):
        """Replay re-verifies data availability: a stored block whose
        data_hash doesn't match its txs is rejected."""
        node = new_node(tmp_path)
        node.save_snapshot()
        node.produce_block(30.0)
        import pathlib

        path = pathlib.Path(tmp_path) / "blocks" / "2.json"
        data = json.loads(path.read_text())
        data["data_hash"] = "11" * 32
        path.write_text(json.dumps(data))
        with pytest.raises(ValueError, match="data hash mismatch"):
            Node.load(str(tmp_path))

    def test_batched_da_verification_on_replay(self, tmp_path):
        """A catching-up node with several queued blocks of equal square
        size verifies their data roots in ONE batched device dispatch
        (extend_and_root_batched) when the device backend is live."""
        node = new_node(tmp_path, extend_backend="tpu")
        node.save_snapshot()  # snapshot at height 1
        signer = Signer.setup_single(ALICE, node)
        for i in range(3):
            b = blob_pkg.new_blob(ns.new_v0(b"batchsync!"), bytes([i]) * 400, 0)
            signer.submit_pay_for_blob([b])
            node.produce_block(30.0 + 15.0 * i)

        pending = [node.blocks[h] for h in (2, 3, 4)]
        app2 = Node._restore_app(
            json.loads((tmp_path / "meta.json").read_text()),
            (tmp_path / "state.json").read_bytes(),
            extend_backend="tpu",
        )
        verified = Node._batch_verify_data_availability(app2, pending)
        assert verified == {2, 3, 4}

        recovered = Node.load(str(tmp_path), extend_backend="tpu")
        assert recovered.app.height == 4
        assert recovered.produce_block(90.0).app_hash == \
            node.produce_block(90.0).app_hash


class TestExtendBackend:
    """Backend selection for the ExtendBlock hot path (config flag +
    crossover auto rule) — the operator-facing TPU wiring."""

    def test_invalid_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown extend backend"):
            App(extend_backend="cuda")

    def test_auto_rules(self, monkeypatch):
        import celestia_tpu.app.app as app_mod
        from celestia_tpu import native

        app = App(extend_backend="auto")
        # fresh Apps carry the repo-committed default table (ADR-019);
        # detach it here to pin the STATIC-gate fallback rules
        app.crossover = None
        # accelerator present: device above the crossover, native below
        monkeypatch.setattr(app_mod, "_accel_probe", True)
        monkeypatch.setattr(native, "available", lambda: True)
        assert app.resolve_extend_backend(128) == "tpu"
        assert app.resolve_extend_backend(app_mod.TPU_MIN_SQUARE) == "tpu"
        assert app.resolve_extend_backend(2) == "native"
        # no accelerator: native everywhere, numpy as last resort
        monkeypatch.setattr(app_mod, "_accel_probe", False)
        assert app.resolve_extend_backend(128) == "native"
        monkeypatch.setattr(native, "available", lambda: False)
        assert app.resolve_extend_backend(128) == "numpy"

    def test_accelerator_init_failure_is_logged(self, monkeypatch):
        """A device that fails to initialize reads as "no accelerator",
        but never silently: the probe logs the exception's type and
        text at warning level."""
        import logging

        import jax

        import celestia_tpu.app.app as app_mod

        records = []

        class Grab(logging.Handler):
            def emit(self, record):
                records.append(record)

        def broken():
            raise RuntimeError("TPU initialization failed: busy")

        monkeypatch.setattr(app_mod, "_accel_probe", None)
        monkeypatch.setattr(jax, "devices", broken)
        handler = Grab()
        logger = logging.getLogger("celestia_tpu.app")
        logger.addHandler(handler)
        try:
            assert app_mod.accelerator_available() is False
        finally:
            logger.removeHandler(handler)
        warn = [r for r in records if r.levelno == logging.WARNING]
        assert warn and "accelerator init failed" in warn[0].getMessage()
        assert "RuntimeError: TPU initialization failed: busy" in str(
            warn[0].kv["error"])

    @pytest.mark.parametrize("backend,device", [("numpy", False),
                                                ("tpu", True)])
    def test_boot_extend_backend(self, backend, device):
        """`cli start` boots through Node.boot_extend_backend: only a
        device backend turns on the blob arena and EDS retention."""
        node = new_node(extend_backend=backend)
        assert node.boot_extend_backend() == backend
        assert (node.app.blob_pool is not None) is device
        assert node.extend_blocks is device

    def test_cross_backend_proposal_acceptance(self):
        """A proposal produced on the device path must be accepted by a
        validator running numpy (and vice versa): process_proposal
        recomputes the DAH on its own backend and compares hashes, so
        this pins the backends byte-identical through the full node
        path. (Tx bytes themselves are signature-nonced, so two
        independently-signed chains can't be compared directly.)"""
        from celestia_tpu.app.app import ProposalBlockData

        a = new_node(extend_backend="tpu")
        b = new_node(extend_backend="numpy")
        signer = Signer.setup_single(ALICE, a)
        blob = blob_pkg.new_blob(ns.new_v0(b"backendtst"), b"\x42" * 600, 0)
        signer.submit_pay_for_blob([blob])
        proposal = a.app.prepare_proposal(a.mempool.reap())
        assert a.app._active_backend == "tpu"
        assert b.app.process_proposal(proposal)  # numpy validates tpu
        assert b.app._active_backend == "numpy"
        # and the reverse direction
        proposal_b = b.app.prepare_proposal(proposal.txs)
        assert proposal_b.hash == proposal.hash
        assert a.app.process_proposal(proposal_b)

    def test_config_layer_carries_backend(self, tmp_path):
        from celestia_tpu.config import load_config

        cfg = load_config(tmp_path, {"app.extend_backend": "native"})
        assert cfg.app.extend_backend == "native"


class TestRpc:
    def test_http_api(self):
        node = new_node()
        server = RpcServer(node, port=0)
        server.start()
        base = f"http://127.0.0.1:{server.port}"
        try:
            status = json.loads(urllib.request.urlopen(f"{base}/status").read())
            assert status["height"] == 1

            acc = json.loads(
                urllib.request.urlopen(f"{base}/account/{ALICE.bech32_address()}").read()
            )
            assert acc["balance"] == 50_000_000_000

            # broadcast a pfb over HTTP
            signer = Signer.setup_single(ALICE, node)
            b = blob_pkg.new_blob(ns.new_v0(b"rpc-test"), b"\x33" * 100, 0)
            from celestia_tpu.x.blob.types import estimate_gas, new_msg_pay_for_blobs
            from celestia_tpu.tx import Fee, sign_tx

            msg = new_msg_pay_for_blobs(signer.address(), b)
            gas = estimate_gas([100])
            tx = sign_tx(ALICE, [msg], node.app.chain_id, signer.account_number,
                         signer.sequence, Fee(amount=gas, gas_limit=gas))
            raw = blob_pkg.marshal_blob_tx(tx.marshal(), [b])
            req = urllib.request.Request(
                f"{base}/broadcast_tx",
                data=json.dumps({"tx": raw.hex()}).encode(),
                method="POST",
            )
            res = json.loads(urllib.request.urlopen(req).read())
            assert res["code"] == 0, res

            req = urllib.request.Request(f"{base}/produce_block", data=b"{}",
                                         method="POST")
            block = json.loads(urllib.request.urlopen(req).read())
            assert len(block["txs"]) == 1

            # telemetry exported in prometheus format
            metrics_text = urllib.request.urlopen(f"{base}/metrics").read().decode()
            assert "prepare_proposal_seconds_count" in metrics_text
            assert "process_proposal_seconds_count" in metrics_text

            # tx inclusion proof over RPC (validated server-side)
            proof = json.loads(
                urllib.request.urlopen(f"{base}/proof/tx/{block['height']}:0").read()
            )
            assert proof["row_proof"]["row_roots"]
            assert proof["share_proofs"]

            # namespace data query: the blob comes back with its range
            # and a server-validated inclusion proof
            nshex = ns.new_v0(b"rpc-test").bytes.hex()
            nd = json.loads(
                urllib.request.urlopen(
                    f"{base}/namespace_data/{block['height']}/{nshex}"
                ).read()
            )
            assert nd["namespace"] == nshex
            assert len(nd["ranges"]) == 1
            assert bytes.fromhex(nd["ranges"][0]["blobs"][0]) == b"\x33" * 100
            assert nd["ranges"][0]["proof"]["share_proofs"]
            # absent namespace -> empty ranges
            other = ns.new_v0(b"absent-ns").bytes.hex()
            nd2 = json.loads(
                urllib.request.urlopen(
                    f"{base}/namespace_data/{block['height']}/{other}"
                ).read()
            )
            assert nd2["ranges"] == []

            # absent namespace: verifiable nmt absence proofs per
            # covering row (or pure root-range absence)
            from celestia_tpu.proof import (
                NmtAbsenceProof,
                verify_namespace_absent,
            )

            # "absent-ns" sorts into the GAP between row 0's max (the
            # blob) and row 1's min (tail padding): no row covers it, so
            # absence follows from the ordered row-root ranges alone
            nd3 = json.loads(
                urllib.request.urlopen(
                    f"{base}/namespace_data/{block['height']}/{other}"
                ).read()
            )
            assert nd3["ranges"] == [] and nd3["absence"] == []
            # "absent" sorts BETWEEN the PFB and blob namespaces inside
            # row 0's range: a witness-leaf absence proof is served and
            # verifies against the row root
            inside = ns.new_v0(b"absent").bytes.hex()
            nd4 = json.loads(
                urllib.request.urlopen(
                    f"{base}/namespace_data/{block['height']}/{inside}"
                ).read()
            )
            assert nd4["ranges"] == []
            assert nd4["absence"], nd4
            from celestia_tpu.proof import MerkleProof

            for item in nd4["absence"]:
                root = bytes.fromhex(item["row_root"])
                proof = NmtAbsenceProof.from_json(item["proof"])
                verify_namespace_absent(root, bytes.fromhex(inside), proof)
                # the row root itself authenticates to the block data root
                rp = item["root_proof"]
                MerkleProof(
                    total=rp["total"], index=rp["index"],
                    leaf_hash=bytes.fromhex(rp["leaf_hash"]),
                    aunts=[bytes.fromhex(a) for a in rp["aunts"]],
                ).verify(bytes.fromhex(block["data_hash"]), root)

            # padding/parity namespaces are rejected as meaningless queries
            tailpad = ns.TAIL_PADDING_NAMESPACE.bytes.hex()
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"{base}/namespace_data/{block['height']}/{tailpad}"
                )

            # module param queries
            bp = json.loads(urllib.request.urlopen(f"{base}/params/blob").read())
            assert bp["gas_per_blob_byte"] == 8
            assert bp["gov_max_square_size"] == 64
            sp = json.loads(urllib.request.urlopen(f"{base}/params/staking").read())
            assert sp["bond_denom"] == "utia"
            assert sp["unbonding_time_seconds"] == 3 * 7 * 24 * 3600
            gp = json.loads(urllib.request.urlopen(f"{base}/params/gov").read())
            assert gp["voting_period_seconds"] == 7 * 24 * 3600
            bsp = json.loads(
                urllib.request.urlopen(f"{base}/params/blobstream").read()
            )
            assert bsp["data_commitment_window"] == 400
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{base}/params/nope")
        finally:
            server.stop()


class TestCli:
    def test_init_and_keys(self, tmp_path):
        from celestia_tpu.cli import main

        main(["--home", str(tmp_path), "init"])
        assert (tmp_path / "genesis.json").exists()
        main(["--home", str(tmp_path), "keys", "add", "test-key"])
        keys = json.loads((tmp_path / "keys.json").read_text())
        assert "validator" in keys and "test-key" in keys
