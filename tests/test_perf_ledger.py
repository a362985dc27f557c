"""Perf-regression sentinel tests (ADR-014, `make bench-gate`).

The fixtures mirror the real heterogeneity of the committed BENCH
history: clean parsed rounds, rounds whose JSON line survived only in
the tail, head-truncated tails that need balanced-brace salvage, and
error rounds that must be skipped — plus the gate semantics (median ±
MAD double gate, min-history, exit codes)."""

import json
import os

import pytest

from celestia_tpu.tools import perf_ledger

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_round(path, *, rc=0, parsed=None, tail=""):
    with open(path, "w") as f:
        json.dump({"rc": rc, "parsed": parsed, "tail": tail}, f)


def configs_doc(tpu_ms, transfers_ms=None):
    cfg = {
        "3_headline_k128": {"tpu_ms": tpu_ms},
        "4_repair_k128_25pct": {"tpu_ms": tpu_ms * 1.8},
    }
    if transfers_ms is not None:
        cfg["4_repair_k128_25pct"]["tpu_wall_with_transfers_ms"] = transfers_ms
    return {"value": tpu_ms, "configs": cfg}


def write_history(root, walls, latest=None):
    """One BENCH_r<i>.json per wall value, mixing all three parse
    tiers so every loader path is on the hook in every test; `latest`
    appends one more clean round as the newest point."""
    if latest is not None:
        walls = [*walls, latest]
    for i, w in enumerate(walls, start=1):
        path = os.path.join(root, f"BENCH_r{i}.json")
        doc = configs_doc(w, transfers_ms=w * 100)
        if i == len(walls) and latest is not None:
            bench_round(path, parsed=doc)
        elif i % 3 == 1:  # tier 1: clean parsed dict
            bench_round(path, parsed=doc)
        elif i % 3 == 2:  # tier 2: JSON line in the tail only
            bench_round(path, tail="noise\n" + json.dumps(doc) + "\n")
        else:  # tier 3: decapitated tail, config objects salvageable
            line = json.dumps(doc)
            bench_round(path, tail=line[line.index('"3_headline'):])


class TestSalvage:
    def test_recovers_complete_config_objects(self):
        tail = ('_k64": {"tpu_ms": 1.5}, '
                '"4_repair_k128_25pct": {"tpu_ms": 9.0, '
                '"tpu_wall_with_transfers_ms": 2360.0}, '
                '"8_node_path_k128": {"tpu_wall_roots_only_ms": 390.7}}')
        out = perf_ledger.salvage_configs(tail)
        assert out["4_repair_k128_25pct"]["tpu_ms"] == 9.0
        assert out["8_node_path_k128"]["tpu_wall_roots_only_ms"] == 390.7
        # the decapitated leading fragment is not a config name match
        assert "_k64" not in out

    def test_truncated_object_is_dropped_not_garbage(self):
        tail = '"4_repair_k128_25pct": {"tpu_ms": 9.0, "tpu_wall'
        assert perf_ledger.salvage_configs(tail) == {}

    def test_nested_braces_balance(self):
        tail = '"9_cfg_x": {"inner": {"a": 1}, "tpu_ms": 2.0}'
        out = perf_ledger.salvage_configs(tail)
        assert out["9_cfg_x"]["inner"] == {"a": 1}


class TestParseRound:
    def test_error_rounds_are_skipped(self):
        assert perf_ledger.parse_round({"rc": 1, "parsed": None,
                                        "tail": ""}) is None
        assert perf_ledger.parse_round(
            {"rc": 0, "parsed": {"error": "no TPU"}, "tail": ""}
        ) is None

    def test_tiers_agree(self):
        doc = configs_doc(5.0)
        t1 = perf_ledger.parse_round({"rc": 0, "parsed": doc, "tail": ""})
        t2 = perf_ledger.parse_round(
            {"rc": 0, "parsed": None, "tail": json.dumps(doc)}
        )
        line = json.dumps(doc)
        t3 = perf_ledger.parse_round(
            {"rc": 0, "parsed": None,
             "tail": line[line.index('"3_headline'):]}
        )
        for t in (t1, t2, t3):
            assert t["configs"]["3_headline_k128"]["tpu_ms"] == 5.0


class TestLedger:
    def test_rounds_sort_numerically_newest_last(self, tmp_path):
        root = str(tmp_path)
        walls = [5.0, 5.1, 4.9, 5.0, 5.2, 4.8, 5.0, 5.1, 4.9]
        write_history(root, walls, latest=5.05)
        ledger = perf_ledger.load_ledger(root)
        series = ledger["extend_k128_tpu_ms"]
        # r10 sorts after r9, not after r1
        assert [label for label, _ in series] == [
            f"BENCH_r{i}.json" for i in range(1, 11)]
        assert series[-1][1] == 5.05

    def test_cache_file_is_not_read(self, tmp_path):
        """No cached numbers enter the ledger: a stray bench_cache.json
        is ignored."""
        root = str(tmp_path)
        write_history(root, [5.0, 5.1])
        with open(os.path.join(root, "bench_cache.json"), "w") as f:
            json.dump({"headlines": {"k128": {"value": 99.0}},
                       "configs": configs_doc(99.0)["configs"]}, f)
        series = perf_ledger.load_ledger(root)["extend_k128_tpu_ms"]
        assert [v for _, v in series] == [5.0, 5.1]

    def test_error_round_leaves_a_gap(self, tmp_path):
        root = str(tmp_path)
        write_history(root, [5.0, 5.1])
        bench_round(os.path.join(root, "BENCH_r03.json"), rc=1,
                    tail="accelerator unreachable")
        ledger = perf_ledger.load_ledger(root)
        assert len(ledger["extend_k128_tpu_ms"]) == 2


class TestGate:
    def test_flat_history_passes(self, tmp_path):
        root = str(tmp_path)
        write_history(root, [5.0, 5.1, 4.9, 5.0], latest=5.02)
        result = perf_ledger.check(root)
        assert result["ok"]
        r = result["metrics"]["extend_k128_tpu_ms"]
        assert r["gating"] and not r["regressed"]

    def test_2x_regression_fails(self, tmp_path):
        root = str(tmp_path)
        write_history(root, [5.0, 5.1, 4.9, 5.0], latest=10.0)
        result = perf_ledger.check(root)
        assert not result["ok"]
        r = result["metrics"]["extend_k128_tpu_ms"]
        assert r["regressed"] and r["ratio"] == pytest.approx(2.0)

    def test_double_gate_needs_ratio_and_band(self, tmp_path):
        # 1.3x is inside the 1.5x threshold: noisy but not a regression
        root = str(tmp_path)
        write_history(root, [5.0, 5.1, 4.9, 5.0], latest=6.5)
        assert perf_ledger.check(root)["ok"]
        # zero-MAD series (identical best-of values): the 5% floor
        # still tolerates a wiggle, but not 1.6x
        root2 = str(tmp_path / "b")
        os.mkdir(root2)
        write_history(root2, [5.0, 5.0, 5.0], latest=5.2)
        assert perf_ledger.check(root2)["ok"]
        write_history(root2, [5.0, 5.0, 5.0], latest=8.0)
        assert not perf_ledger.check(root2)["ok"]

    def test_short_history_is_informational(self, tmp_path):
        root = str(tmp_path)
        write_history(root, [5.0], latest=50.0)  # 10x but n=2
        result = perf_ledger.check(root)
        assert result["ok"]
        r = result["metrics"]["extend_k128_tpu_ms"]
        assert not r["gating"] and "informational" in r["note"]

    def test_committed_tree_has_no_records(self):
        """The repo carries no bench rounds (the chip record is the
        driver's PERF_LEDGER.jsonl): the bench-wall series are inert
        until the benchmark PR feeds them, so nothing gates."""
        result = perf_ledger.check(REPO_ROOT)
        assert result["ok"], perf_ledger.render_table(result)
        assert not [m for m, r in result["metrics"].items() if r["gating"]]


class TestCli:
    def test_exit_codes_and_table(self, tmp_path, capsys):
        root = str(tmp_path)
        write_history(root, [5.0, 5.1, 4.9], latest=5.0)
        assert perf_ledger.main(["--root", root]) == 0
        out = capsys.readouterr().out
        assert "PASS" in out and "extend_k128_tpu_ms" in out
        write_history(root, [5.0, 5.1, 4.9], latest=11.0)
        assert perf_ledger.main(["--root", root]) == 1
        assert "REGRESSED" in capsys.readouterr().out

    def test_json_report(self, tmp_path, capsys):
        root = str(tmp_path)
        write_history(root, [5.0, 5.1, 4.9], latest=5.0)
        assert perf_ledger.main(["--root", root, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["ok"] and "metrics" in doc
