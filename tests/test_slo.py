"""SLO engine + readiness + endpoint tests (specs/slo.md).

Pure-Python state machine checks run against a private Registry with an
injected clock (burn-rate windows are exercised by moving time, not by
sleeping). The endpoint contract — /healthz, /readyz 503↔200,
/debug/slo, the /status enrichment, the consistent JSON 404 — is pinned
over the REAL node/rpc.py handler serving the crypto-free RpcChaosNode
facade, so the suite runs in stripped environments."""

import json
import urllib.error
import urllib.request

import pytest

from celestia_tpu.slo import (
    Objective,
    SloEngine,
    default_objectives,
    readiness,
)
from celestia_tpu.telemetry import Registry
from celestia_tpu.testutil.chaosnet import RpcChaosNode


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def ratio_engine(registry):
    clock = FakeClock()
    obj = Objective(name="avail", kind="ratio", good="good_total",
                    total="all_total", target=0.999)
    return SloEngine([obj], registry=registry, clock=clock), clock


class TestRatioBurnRate:
    def test_no_traffic_is_ok(self):
        r = Registry()
        eng, clock = ratio_engine(r)
        res = eng.evaluate()
        obj = res["objectives"][0]
        assert res["ok"] and obj["ok"]
        assert obj["total"] == 0.0 and obj["ratio_overall"] is None
        # no traffic in any window: burn rates are unknowable, not fired
        for w in obj["windows"]:
            assert w["burn_long"] is None and not w["breaching"]

    def test_total_errors_breach_both_windows(self):
        r = Registry()
        eng, clock = ratio_engine(r)
        eng.evaluate()  # baseline snapshot at t=0
        r.incr_counter("all_total", 100.0)  # 100 samples, zero good
        clock.t = 30.0
        res = eng.evaluate()
        obj = res["objectives"][0]
        # err=1.0 against a 0.001 budget => burn 1000 in every window
        # (short history falls back to the oldest snapshot)
        assert not obj["ok"]
        assert any(w["breaching"] for w in obj["windows"])
        fast = obj["windows"][0]
        assert fast["burn_long"] == pytest.approx(1000.0)
        assert fast["burn_short"] == pytest.approx(1000.0)

    def test_recovery_clears_when_errors_stop(self):
        r = Registry()
        eng, clock = ratio_engine(r)
        eng.evaluate()
        r.incr_counter("all_total", 100.0)
        clock.t = 30.0
        assert not eng.evaluate()["ok"]
        # error burst ends; healthy traffic resumes
        r.incr_counter("all_total", 5000.0)
        r.incr_counter("good_total", 5000.0)
        clock.t = 400.0  # both windows now diff against the t=30 snapshot
        res = eng.evaluate()
        assert res["ok"], res

    def test_below_threshold_burn_does_not_fire(self):
        r = Registry()
        eng, clock = ratio_engine(r)
        eng.evaluate()
        # 1% errors: burn 10 — above the slow-burn 6 ceiling? Use a
        # volume where burn lands between the two thresholds (6..14.4):
        # only the SLOW window pair may fire, and it needs BOTH windows.
        r.incr_counter("all_total", 10000.0)
        r.incr_counter("good_total", 9990.0)  # 0.1% err => burn 1.0
        clock.t = 30.0
        res = eng.evaluate()
        obj = res["objectives"][0]
        assert obj["ok"]
        for w in obj["windows"]:
            assert not w["breaching"]

    def test_breach_counter_fires_once_per_transition(self):
        r = Registry()
        eng, clock = ratio_engine(r)
        eng.evaluate()
        r.incr_counter("all_total", 100.0)
        clock.t = 30.0
        eng.evaluate()
        clock.t = 35.0
        eng.evaluate()  # still breaching: no second transition
        assert r.get_counter("slo_breach_total", objective="avail") == 1.0


class TestQuantileObjective:
    def engine(self, registry, limit_s=0.5):
        obj = Objective(name="p99", kind="quantile",
                        metric="extend_block", q=0.99, limit_s=limit_s)
        return SloEngine([obj], registry=registry)

    def test_no_observations_is_ok(self):
        r = Registry()
        res = self.engine(r).evaluate()
        obj = res["objectives"][0]
        assert obj["ok"] and obj["value_s"] is None and obj["count"] == 0

    def test_merges_label_sets_and_judges_p99(self):
        r = Registry()
        for _ in range(50):
            r.observe("extend_block", 0.01, backend="tpu")
            r.observe("extend_block", 0.02, backend="numpy")
        res = self.engine(r).evaluate()
        obj = res["objectives"][0]
        assert obj["ok"]
        assert obj["count"] == 100  # family-wide merge, both label sets

    def test_slow_tail_breaches(self):
        r = Registry()
        for _ in range(100):
            r.observe("extend_block", 10.0, backend="numpy")
        res = self.engine(r).evaluate()
        obj = res["objectives"][0]
        assert not obj["ok"] and obj["value_s"] > 0.5


class TestCounterMaxObjective:
    def test_sticky_disable_is_a_breach(self):
        r = Registry()
        obj = Objective(name="no_disable", kind="counter_max",
                        counter="extend_tpu_disabled_total", limit=0.0)
        eng = SloEngine([obj], registry=r)
        assert eng.evaluate()["ok"]
        r.incr_counter("extend_tpu_disabled_total")
        res = eng.evaluate()
        assert not res["ok"]
        assert r.get_counter("slo_breach_total",
                             objective="no_disable") == 1.0


class TestObjectiveDeclaration:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            Objective(name="x", kind="nope")

    def test_default_set_names(self):
        names = {o.name for o in default_objectives()}
        assert names == {"sample_availability", "extend_block_p99",
                         "tpu_not_sticky_disabled", "sdc_detected",
                         "rpc_admission", "store_integrity",
                         "store_writable"}


# ---------------------------------------------------------------------- #
# readiness (serving-fit) against the chaosnet facade


def check_map(checks):
    return {c["name"]: c["ok"] for c in checks}


class TestReadiness:
    def test_no_blocks_means_not_ready(self):
        node = RpcChaosNode(heights=0)
        ready, checks = readiness(node)
        m = check_map(checks)
        assert not ready and not m["has_blocks"]
        assert m["not_sticky_degraded"] and m["backend_resolved"]

    def test_ready_after_first_block(self):
        node = RpcChaosNode(heights=0)
        node.grow()
        ready, checks = readiness(node)
        assert ready and all(check_map(checks).values())

    def test_sticky_degradation_is_unfit(self):
        node = RpcChaosNode(heights=1)
        node.app._tpu_disabled = True
        node.app._tpu_strikes = 3
        ready, checks = readiness(node)
        m = check_map(checks)
        assert not ready and not m["not_sticky_degraded"]
        detail = next(c["detail"] for c in checks
                      if c["name"] == "not_sticky_degraded")
        assert "3 strikes" in detail

    def test_exhausted_arena_is_unfit(self):
        node = RpcChaosNode(heights=1)
        node.app.blob_pool = object()
        node.app.arena_stats = {"assembled": 0, "fallback": 5}
        ready, checks = readiness(node)
        assert not ready and not check_map(checks)["arena_not_exhausted"]
        node.app.arena_stats = {"assembled": 100, "fallback": 3}
        ready, _checks = readiness(node)
        assert ready

    def test_unresolvable_backend_is_unfit(self):
        node = RpcChaosNode(heights=1)

        def boom(_k):
            raise RuntimeError("no backend for k")

        node.app.resolve_extend_backend = boom
        ready, checks = readiness(node)
        assert not ready and not check_map(checks)["backend_resolved"]


# ---------------------------------------------------------------------- #
# endpoint contract over the real rpc.py handler


def fetch(base: str, path: str):
    try:
        with urllib.request.urlopen(base + path, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture()
def served_node():
    from celestia_tpu.node.rpc import RpcServer

    node = RpcChaosNode(heights=0, k=2)
    server = RpcServer(node, port=0)
    server.start()
    try:
        yield node, f"http://127.0.0.1:{server.port}"
    finally:
        server.stop()


class TestEndpoints:
    def test_healthz_always_200(self, served_node):
        node, base = served_node
        status, body = fetch(base, "/healthz")
        assert status == 200 and body["ok"] is True
        assert body["uptime_s"] >= 0.0
        # liveness is unconditional: a degraded node is still alive
        node.app._tpu_disabled = True
        status, body = fetch(base, "/healthz")
        assert status == 200 and body["ok"] is True

    def test_readyz_flips_503_to_200_across_startup(self, served_node):
        node, base = served_node
        status, body = fetch(base, "/readyz")
        assert status == 503 and body["ready"] is False
        assert not check_map(body["checks"])["has_blocks"]
        node.grow()
        status, body = fetch(base, "/readyz")
        assert status == 200 and body["ready"] is True

    def test_readyz_503_when_sticky_disabled(self, served_node):
        node, base = served_node
        node.grow()
        node.app._tpu_disabled = True
        status, body = fetch(base, "/readyz")
        assert status == 503
        assert not check_map(body["checks"])["not_sticky_degraded"]

    def test_status_enrichment(self, served_node):
        node, base = served_node
        node.app._tpu_strikes = 2
        status, body = fetch(base, "/status")
        assert status == 200
        assert body["uptime_s"] >= 0.0
        assert body["tpu_strikes"] == 2
        assert body["tpu_disabled"] is False
        assert body["mempool_size"] == 0

    def test_debug_slo_shape(self, served_node):
        node, base = served_node
        node.grow()
        status, body = fetch(base, "/debug/slo")
        assert status == 200
        names = {o["name"] for o in body["slo"]["objectives"]}
        assert "sample_availability" in names
        assert body["ready"] is True
        assert body["probe_last"] is None  # no prober attached
        # the engine is a per-node singleton: snapshots accumulate
        first = body["slo"]["snapshots"]
        _status, body = fetch(base, "/debug/slo")
        assert body["slo"]["snapshots"] == first + 1

    def test_unknown_routes_are_consistent_json_404(self, served_node):
        _node, base = served_node
        for path in ("/", "/no/such/route", "/cosmos/nope"):
            status, body = fetch(base, path)
            assert status == 404, path
            assert body["error"] == "unknown route"
            assert body["status"] == 404
            assert body["path"] == path
