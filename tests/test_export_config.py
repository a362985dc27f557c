"""app/errors parsing, Signer recovery, genesis export/import, and the
layered config system (VERDICT r1 item 8; ref: app/errors/,
app/export.go, app/default_overrides.go:198-271)."""

import json
import os

import pytest

from celestia_tpu import blob as blob_pkg
from celestia_tpu import namespace as ns
from celestia_tpu.app import App
from celestia_tpu.app import errors as apperrors
from celestia_tpu.app.export import export_app_state_and_validators, import_genesis
from celestia_tpu.crypto import PrivateKey
from celestia_tpu.node import Node
from celestia_tpu.tx import Fee
from celestia_tpu.user import Signer, TxOptions
from celestia_tpu.x.bank import MsgSend
from celestia_tpu.x.staking import MsgDelegate

VALIDATOR = PrivateKey.from_secret(b"validator")
ALICE = PrivateKey.from_secret(b"alice")
BOB = PrivateKey.from_secret(b"bob")


def new_node(tmp_path=None, **app_kwargs) -> Node:
    app = App(**app_kwargs)
    app.init_chain(
        {
            VALIDATOR.bech32_address(): 1_000_000_000_000,
            ALICE.bech32_address(): 50_000_000_000,
            BOB.bech32_address(): 50_000_000_000,
        },
        genesis_time=0.0,
    )
    node = Node(app, home=str(tmp_path) if tmp_path else None)
    node.produce_block(15.0)
    return node


class TestAppErrors:
    """ref: app/errors/nonce_mismatch_test.go + insufficient_gas_price_test.go"""

    def test_nonce_mismatch_detection_and_parse(self):
        log = "account sequence mismatch: expected 5, got 3"
        assert apperrors.is_nonce_mismatch(log)
        assert apperrors.parse_nonce_mismatch(log) == 5

    def test_non_nonce_error(self):
        assert not apperrors.is_nonce_mismatch("insufficient funds")
        with pytest.raises(ValueError):
            apperrors.parse_nonce_mismatch("insufficient funds")

    def test_min_gas_price_parse(self):
        # required/got ratio scales the old gas price (reference math)
        log = "insufficient fees; got: 10utia required: 100utia"
        assert apperrors.is_insufficient_min_gas_price(log)
        price = apperrors.parse_insufficient_min_gas_price(log, 0.01, 1000)
        assert price == pytest.approx(0.1)

    def test_min_gas_price_parse_zero_price(self):
        log = "insufficient fees; got: 0utia required: 100utia"
        price = apperrors.parse_insufficient_min_gas_price(log, 0.0, 1000)
        assert price == pytest.approx(0.1)

    def test_min_gas_price_unrelated_error(self):
        assert apperrors.parse_insufficient_min_gas_price("boom", 1.0, 10) == 0.0
        assert not apperrors.is_insufficient_min_gas_price("boom")

    def test_real_ante_messages_parse(self):
        """The regexes must match what app/ante.py actually raises."""
        node = new_node()
        signer = Signer.setup_single(ALICE, node)
        # force a stale sequence → CheckTx nonce mismatch, no recovery
        stale = Signer(ALICE, node, node.app.chain_id, signer.account_number, 0)
        ok = signer.submit_tx([MsgSend(ALICE.bech32_address(),
                                       BOB.bech32_address(), 100)])
        assert ok.code == 0
        res = stale._broadcast_with_recovery(
            [MsgSend(ALICE.bech32_address(), BOB.bech32_address(), 100)],
            Fee(amount=200_000, gas_limit=200_000), retries=0,
        )
        assert res.code != 0
        assert apperrors.is_nonce_mismatch(res.log)
        assert apperrors.parse_nonce_mismatch(res.log) == 1

        node.app.min_gas_price = 0.1
        cheap = Signer.setup_single(BOB, node)
        res = cheap._broadcast_with_recovery(
            [MsgSend(BOB.bech32_address(), ALICE.bech32_address(), 100)],
            Fee(amount=1, gas_limit=200_000), retries=0,
        )
        assert res.code != 0
        assert apperrors.is_insufficient_min_gas_price(res.log)


class TestSignerRecovery:
    def test_sequence_race_auto_recovery(self):
        """Two Signer instances over one account: the second starts stale
        and must recover from the node's expected-sequence error."""
        node = new_node()
        s1 = Signer.setup_single(ALICE, node)
        s2 = Signer.setup_single(ALICE, node)
        assert s1.submit_tx(
            [MsgSend(ALICE.bech32_address(), BOB.bech32_address(), 10)]
        ).code == 0
        # s2's local sequence (0) is now stale; recovery re-signs at 1
        res = s2.submit_tx(
            [MsgSend(ALICE.bech32_address(), BOB.bech32_address(), 20)]
        )
        assert res.code == 0, res.log
        assert s2.sequence == 2
        block = node.produce_block()
        assert [r.code for r in block.tx_results] == [0, 0]

    def test_min_gas_price_auto_bump(self):
        node = new_node()
        node.app.min_gas_price = 0.25
        signer = Signer.setup_single(ALICE, node)
        res = signer.submit_tx(
            [MsgSend(ALICE.bech32_address(), BOB.bech32_address(), 10)],
            fee=Fee(amount=1, gas_limit=200_000),
        )
        assert res.code == 0, res.log  # bumped to the implied min price
        block = node.produce_block()
        assert block.tx_results[0].code == 0

    def test_pfb_with_tx_options(self):
        node = new_node()
        signer = Signer.setup_single(ALICE, node)
        b = blob_pkg.new_blob(ns.new_v0(b"opts-test"), b"\x42" * 1000, 0)
        res = signer.submit_pay_for_blob(
            [b], opts=TxOptions(gas_limit=120_000, gas_price=0.5)
        )
        assert res.code == 0, res.log


class TestExport:
    def _populated_node(self):
        node = new_node()
        signer = Signer.setup_single(ALICE, node)
        signer.submit_tx([MsgSend(ALICE.bech32_address(), BOB.bech32_address(), 777)])
        b = blob_pkg.new_blob(ns.new_v0(b"exporttest"), b"\x07" * 600, 0)
        signer.submit_pay_for_blob([b])
        vs = Signer.setup_single(VALIDATOR, node)
        vs.submit_tx(
            [MsgDelegate(VALIDATOR.bech32_address(),
                         VALIDATOR.bech32_address(), 5_000_000)]
        )
        node.produce_block(30.0)
        # the PFB reorders ahead of the lower-sequence send (priority) and
        # defers one block via FilterTxs; drain it so export sees a
        # quiesced chain
        node.produce_block(31.0)
        assert len(node.mempool) == 0
        return node

    def test_export_shape(self):
        node = self._populated_node()
        g = export_app_state_and_validators(node.app)
        assert g["height"] == node.app.height + 1  # InitChain resume height
        assert g["chain_id"] == node.app.chain_id
        state = g["app_state"]
        addrs = {a["address"] for a in state["auth"]["accounts"]}
        assert ALICE.bech32_address() in addrs
        assert state["bank"]["balances"][BOB.bech32_address()]["utia"] >= 777
        assert any(
            v["operator"] == VALIDATOR.bech32_address()
            for v in state["staking"]["validators"]
        )
        assert g["validators"][0]["power"] == 5  # 5_000_000 utia / 1e6
        # the export is JSON-serializable as-is
        json.dumps(g)

    def test_import_restores_state_and_continues(self):
        node = self._populated_node()
        g = export_app_state_and_validators(node.app)

        app2 = import_genesis(g)
        assert app2.height == node.app.height
        assert app2.bank.get_balance(BOB.bech32_address()) == \
            node.app.bank.get_balance(BOB.bech32_address())
        assert app2.accounts.get_account(ALICE.bech32_address()).sequence == \
            node.app.accounts.get_account(ALICE.bech32_address()).sequence
        # every keeper must see the imported store (rebind_store), not the
        # discarded one from App.__init__
        assert app2.staking.get_validator(VALIDATOR.bech32_address()) is not None
        assert app2.staking.total_power() == node.app.staking.total_power() > 0

        # restart-compatibility: producing the same next (empty) block on the
        # original and the restored chain commits the SAME app hash
        node2 = Node(app2)
        b_orig = node.produce_block(99.0)
        b_restored = node2.produce_block(99.0)
        assert b_restored.height == b_orig.height
        assert b_restored.app_hash == b_orig.app_hash

    def test_import_accepts_new_txs(self):
        node = self._populated_node()
        node2 = Node(import_genesis(export_app_state_and_validators(node.app)))
        signer = Signer.setup_single(BOB, node2)
        res = signer.submit_tx(
            [MsgSend(BOB.bech32_address(), ALICE.bech32_address(), 5)]
        )
        assert res.code == 0, res.log
        block = node2.produce_block()
        assert block.tx_results[0].code == 0

    def test_zero_height_export(self):
        node = self._populated_node()
        g = export_app_state_and_validators(node.app, for_zero_height=True)
        assert g["height"] == 0
        app2 = import_genesis(g)
        assert app2.height == 0
        # block time continues past the exported chain's last block time
        # (mint's previous-block-time record survives the export)
        Node(app2).produce_block(45.0)


class TestConfig:
    def test_defaults_match_reference_overrides(self):
        from celestia_tpu.config import NodeConfig

        cfg = NodeConfig()
        # app/default_overrides.go values
        assert cfg.app.min_gas_price == pytest.approx(0.1)
        assert cfg.consensus.mempool.ttl_num_blocks == 5
        assert cfg.consensus.mempool.version == "v1"
        assert cfg.consensus.rpc.max_body_bytes == 8 * 1024 * 1024
        assert cfg.consensus.timeout_propose_seconds == 10
        assert cfg.consensus.timeout_commit_seconds == 11
        assert cfg.app.state_sync.snapshot_interval == 1500
        assert cfg.consensus.mempool.max_txs_bytes == \
            cfg.consensus.mempool.max_tx_bytes * 5

    def test_write_and_load_round_trip(self, tmp_path):
        from celestia_tpu.config import load_config, write_default_configs

        write_default_configs(tmp_path)
        assert (tmp_path / "config" / "config.toml").exists()
        assert (tmp_path / "config" / "app.toml").exists()
        cfg = load_config(tmp_path)
        assert cfg.app.min_gas_price == pytest.approx(0.1)
        assert cfg.consensus.goal_block_time_seconds == 15

    def test_file_layer_overrides_defaults(self, tmp_path):
        from celestia_tpu.config import load_config, write_default_configs

        write_default_configs(tmp_path)
        app_toml = tmp_path / "config" / "app.toml"
        app_toml.write_text(app_toml.read_text().replace(
            "min_gas_price = 0.1", "min_gas_price = 0.75"))
        cfg = load_config(tmp_path)
        assert cfg.app.min_gas_price == pytest.approx(0.75)

    def test_file_layer_ignores_retired_keys(self, tmp_path):
        """An app.toml written by an older release may carry fields this
        one no longer has: they are ignored, and the node still boots."""
        from celestia_tpu.config import load_config, write_default_configs

        write_default_configs(tmp_path)
        app_toml = tmp_path / "config" / "app.toml"
        text = app_toml.read_text()
        assert "[app]\n" in text
        app_toml.write_text(text.replace(
            "[app]\n", "[app]\nretired_switch = true\n", 1))
        cfg = load_config(tmp_path)
        assert not hasattr(cfg.app, "retired_switch")
        assert cfg.app.extend_backend == "auto"

    def test_env_layer_overrides_file(self, tmp_path, monkeypatch):
        from celestia_tpu.config import load_config, write_default_configs

        write_default_configs(tmp_path)
        monkeypatch.setenv("CELESTIA_APP_MIN_GAS_PRICE", "1.5")
        monkeypatch.setenv("CELESTIA_CONSENSUS_MEMPOOL_TTL_NUM_BLOCKS", "9")
        cfg = load_config(tmp_path)
        assert cfg.app.min_gas_price == pytest.approx(1.5)
        assert cfg.consensus.mempool.ttl_num_blocks == 9

    def test_flag_layer_wins(self, tmp_path, monkeypatch):
        from celestia_tpu.config import load_config, write_default_configs

        write_default_configs(tmp_path)
        monkeypatch.setenv("CELESTIA_APP_MIN_GAS_PRICE", "1.5")
        cfg = load_config(tmp_path, {"app.min_gas_price": 2.0})
        assert cfg.app.min_gas_price == pytest.approx(2.0)

    def test_cli_init_writes_configs_and_export_restarts(self, tmp_path):
        """End-to-end: init → (in-process) blocks → export → fresh home
        restarts from the exported genesis (kill/restart-from-export)."""
        from celestia_tpu import cli

        home = tmp_path / "node1"
        cli.main(["--home", str(home), "init"])
        assert (home / "config" / "app.toml").exists()

        node = cli._build_node(home)
        node.produce_block(1.0)
        node.produce_block(2.0)
        g = export_app_state_and_validators(node.app)
        exported = tmp_path / "exported.json"
        exported.write_text(json.dumps(g))

        home2 = tmp_path / "node2"
        home2.mkdir()
        (home2 / "genesis.json").write_text(exported.read_text())
        node2 = cli._build_node(home2)
        assert node2.app.height == node.app.height
        block = node2.produce_block(3.0)
        assert block.height == node.app.height + 1
