#!/usr/bin/env python
"""Observability smoke gate (specs/slo.md acceptance, `make obs-smoke`).

Boots a devnet node with its HTTP RPC server — the full App/Node stack
when the signing dependency is importable, otherwise the crypto-free
RpcChaosNode facade (testutil/chaosnet.py) behind the SAME real
node/rpc.py handler — and fails (non-zero exit) unless:

  1. /healthz answers 200 immediately (liveness is unconditional),
  2. /readyz answers 503 BEFORE the first block and 200 AFTER it —
     the startup flip a load balancer needs,
  3. the synthetic DAS prober completes several cycles against the
     node's real /sample (+ /proof/share) path with every NMT proof
     verified, and /debug/slo then shows the availability objective
     healthy with nonzero probe traffic,
  4. forcing sticky TPU degradation flips /readyz back to 503 with the
     offending check named,
  5. unknown GET routes (including "/") return the consistent JSON 404
     body,
  6. the perf-regression sentinel passes on the repo tree.

CPU-only, seconds warm. The node runs the numpy extend backend so the
gate needs no accelerator and no native build.
"""

from __future__ import annotations

import json
import os
import sys
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PROBE_CYCLES = 3


def fetch(base: str, path: str) -> tuple[int, dict]:
    try:
        with urllib.request.urlopen(base + path, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def gate(ok: bool, what: str) -> None:
    print(("PASS " if ok else "FAIL ") + what)
    if not ok:
        raise SystemExit(f"obs-smoke: {what}")


def boot_node():
    """(node, produce_block_fn, share_proofs) — real devnet node when
    the signing stack imports, else the chaosnet facade (no block
    bodies, so the /proof/share prober leg is skipped there)."""
    try:
        from celestia_tpu.app import App
        from celestia_tpu.node import Node
    except ImportError:
        from celestia_tpu.testutil.chaosnet import RpcChaosNode

        node = RpcChaosNode(heights=0, k=4, chain_id="obs-smoke")
        print("note: signing stack unavailable, using RpcChaosNode facade")
        return node, node.grow, False
    app = App(chain_id="obs-smoke", extend_backend="numpy")
    app.init_chain({}, genesis_time=0.0)
    node = Node(app)
    return node, lambda: node.produce_block(1.0), True


def check_node() -> None:
    from celestia_tpu.node.prober import Prober
    from celestia_tpu.node.rpc import RpcServer

    node, produce_block, share_proofs = boot_node()
    app = node.app
    server = RpcServer(node, port=0)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        status, health = fetch(base, "/healthz")
        gate(status == 200 and health.get("ok") is True,
             "/healthz 200 at boot")

        status, ready = fetch(base, "/readyz")
        failing = [c["name"] for c in ready["checks"] if not c["ok"]]
        gate(status == 503 and "has_blocks" in failing,
             f"/readyz 503 before first block (failing: {failing})")

        produce_block()
        status, ready = fetch(base, "/readyz")
        gate(status == 200 and ready["ready"] is True,
             "/readyz 200 after first block")

        # a few verified prober cycles through the real serve path
        prober = Prober(base, samples_per_cycle=4,
                        share_proofs=share_proofs)
        node.prober = prober
        for _ in range(PROBE_CYCLES):
            summary = prober.probe_cycle()
            if not summary["ok"]:
                gate(False, f"probe cycle failed: {summary}")
        gate(True, f"{PROBE_CYCLES} probe cycles verified "
                   f"(last: {prober.last['sample_ok']}/"
                   f"{prober.last['samples']} samples ok)")

        status, debug = fetch(base, "/debug/slo")
        avail = next(o for o in debug["slo"]["objectives"]
                     if o["name"] == "sample_availability")
        gate(status == 200 and debug["slo"]["ok"]
             and avail["total"] > 0 and avail["ok"],
             f"/debug/slo healthy with probe traffic "
             f"(availability {avail['good']:.0f}/{avail['total']:.0f})")

        # sticky degradation must flip readiness off, with the check named
        app._tpu_disabled = True
        app._tpu_strikes = app.TPU_STRIKE_LIMIT
        status, ready = fetch(base, "/readyz")
        failing = [c["name"] for c in ready["checks"] if not c["ok"]]
        gate(status == 503 and "not_sticky_degraded" in failing,
             "/readyz 503 when sticky-degraded")
        app._tpu_disabled = False
        app._tpu_strikes = 0

        for path in ("/", "/no/such/route"):
            status, body = fetch(base, path)
            gate(status == 404 and body.get("error") == "unknown route"
                 and body.get("status") == 404,
                 f"GET {path} -> consistent JSON 404")
    finally:
        server.stop()


def check_bench_gate() -> None:
    from celestia_tpu.tools import perf_ledger

    # the repo carries no BENCH records since PR 21 (the driver's
    # PERF_LEDGER.jsonl holds the chip record); regression detection
    # itself is pinned by tests/test_perf_ledger.py
    result = perf_ledger.check(REPO)
    gate(result["ok"], "bench gate passes on the repo tree")


def main() -> int:
    check_node()
    check_bench_gate()
    print("obs-smoke: all gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
