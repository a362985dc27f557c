#!/usr/bin/env python
"""Device-runtime-ledger smoke gate (`make devledger-smoke`).

Exercises the ADR-025 device runtime ledger end-to-end in under two
minutes, crypto-free (no signing stack; jax on CPU only for real
live-array accounting). Fails (non-zero exit) unless:

  1. the compile watchdog counts warmup builds as compiles (not
     retraces), flags a post-warmup fresh key on a known entry as a
     retrace, and under strict mode raises RetraceError BEFORE the
     builder body runs (the lru cache never adopts the churned key);
  2. an lru-evicted key that gets REBUILT is a compile, not a retrace —
     the per-entry seen-key set outlives the builder's lru cache;
  3. the byte ledger's owner registration/unattribution flip works:
     an unregistered device hoard shows up as unattributed bytes,
     registering an owner over it moves the bytes into
     `device_ledger_bytes{owner}`, unregistering flips them back;
  4. the busy timeline integrates exec durations over its window and
     clamps oversubscription at 1.0;
  5. the `/debug/device` RPC route serves the watchdog + ledger +
     provenance document over the real node/rpc.py handler, and
     `publish()` lands every `device_ledger_*` / `dispatch_busy_ratio` /
     `xla_*` gauge family in prometheus exposition text.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def gate(ok: bool, what: str) -> None:
    print(("PASS " if ok else "FAIL ") + what)
    if not ok:
        raise SystemExit(f"devledger-smoke: {what}")


def main() -> int:
    t_start = time.monotonic()
    from celestia_tpu import devledger, telemetry

    # -- 1. watchdog: warmup compiles, steady-state retrace, strict --- #
    led = devledger.DeviceLedger()
    built = []

    @functools.lru_cache(maxsize=None)
    @led.instrument_builder("smoke.entry")
    def build(k: int):
        built.append(k)
        return lambda: ("compiled", k)

    build(2)()
    build(4)()
    gate(led.retrace_count() == 0 and built == [2, 4],
         "warmup builds are compiles, not retraces")
    led.end_warmup()
    build(4)  # lru hit: the watchdog never even fires
    gate(led.retrace_count() == 0, "known key after warmup is not a retrace")
    build(8)
    gate(led.retrace_count() == 1,
         "fresh key on a known entry after warmup IS a retrace")
    with led.strict_retraces():
        try:
            build(16)
            gate(False, "strict mode raises RetraceError")
        except devledger.RetraceError as e:
            gate("smoke.entry" in str(e),
                 f"strict mode raises RetraceError naming the entry ({e})")
    gate(built == [2, 4, 8],
         "strict raise fired BEFORE the build (key 16 never built)")

    # -- 2. lru eviction is not geometry churn ------------------------- #
    led2 = devledger.DeviceLedger()
    rebuilt = []

    @functools.lru_cache(maxsize=1)
    @led2.instrument_builder("smoke.evict")
    def build2(k: int):
        rebuilt.append(k)
        return lambda: k

    build2(1)
    build2(2)  # evicts key 1 from the lru
    led2.end_warmup()
    build2(1)  # lru miss -> builder reruns, but the key is KNOWN
    gate(rebuilt == [1, 2, 1] and led2.retrace_count() == 0,
         "lru-evicted key rebuilt is a compile, NOT a retrace")

    # -- 3. owner registration / unattribution flip -------------------- #
    import jax.numpy as jnp

    hoard = [jnp.ones((1024 * 1024,), jnp.uint8)]
    hoard_bytes = sum(int(a.nbytes) for a in hoard)
    before = devledger.ledger.snapshot()
    gate(before["unattributed_bytes"] >= hoard_bytes,
         f"unregistered hoard is unattributed "
         f"({before['unattributed_bytes']} >= {hoard_bytes})")
    devledger.register_owner(
        "smoke.hoard", lambda: sum(int(a.nbytes) for a in hoard))
    owned = devledger.ledger.snapshot()
    gate(owned["owners"].get("smoke.hoard") == hoard_bytes,
         f"registered owner attributes its bytes "
         f"({owned['owners'].get('smoke.hoard')})")
    gate(owned["unattributed_bytes"] <= before["unattributed_bytes"]
         - hoard_bytes + 1024,
         "attribution moved the hoard out of the unattributed remainder")
    devledger.unregister_owner("smoke.hoard")
    back = devledger.ledger.snapshot()
    gate("smoke.hoard" not in back["owners"]
         and back["unattributed_bytes"] >= hoard_bytes,
         "unregistering flips the bytes back to unattributed")

    # -- 4. busy-ratio sanity ------------------------------------------ #
    led3 = devledger.DeviceLedger(busy_window_s=10.0)
    gate(led3.busy_ratio() == 0.0, "idle device lane reads 0.0")
    led3.note_busy(2.5)
    led3.note_busy(2.5)
    ratio = led3.busy_ratio()
    gate(abs(ratio - 0.5) < 0.05,
         f"busy ratio integrates exec durations ({ratio:.3f} ~ 0.5)")
    led3.note_busy(50.0)
    gate(led3.busy_ratio() == 1.0,
         "oversubscribed lane clamps at 1.0")

    # -- 5. /debug/device + publish over the real RPC handler ---------- #
    from celestia_tpu.node.rpc import RpcServer
    from celestia_tpu.testutil.chaosnet import RpcChaosNode

    devledger.note_busy(0.01)
    # the route serves the PROCESS singleton — make it hold a known entry
    devledger.ledger.note_build("smoke.rpc", "(k=2)")
    node = RpcChaosNode(k=2, seed=7)
    server = RpcServer(node, port=0)
    server.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/debug/device",
                timeout=10) as resp:
            doc = json.loads(resp.read())
        gate(set(doc) >= {"compile", "ledger", "busy_ratio", "provenance"},
             f"/debug/device serves the full document ({sorted(doc)})")
        gate(doc["compile"]["entries"].get("smoke.rpc", {}).get("keys") == 1,
             "watchdog entries visible over RPC")
        gate(isinstance(doc["ledger"].get("unattributed_bytes"), int)
             and isinstance(doc["ledger"].get("owners"), dict),
             "byte-ledger audit visible over RPC")
        gate(doc["provenance"].get("python") and
             doc["provenance"].get("host_fingerprint"),
             "runtime provenance stamped into the document")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics",
                timeout=10) as resp:
            text = resp.read().decode()
        for family in ("device_ledger_unattributed_bytes",
                       "device_ledger_live_bytes", "dispatch_busy_ratio"):
            gate(f"\n{family}" in text or text.startswith(family),
                 f"/metrics exports {family}")
    finally:
        server.stop()

    wall = time.monotonic() - t_start
    gate(wall < 120, f"devledger-smoke finished in {wall:.1f}s (< 120s)")
    print("devledger-smoke: all gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
