#!/usr/bin/env python
"""Trace smoke gate (specs/observability.md acceptance).

Phase 1 (device): one k=32 extend+root through the device entry under
a tracing recording, written as Chrome trace-event JSON. Fails
(non-zero exit) unless:

  1. the file round-trips through json.load and passes
     tracing.validate_chrome_trace with zero problems,
  2. the expected extend-stage spans are present
     (extend.device > extend.stage / extend.rs_nmt), and
  3. root spans cover >= 90% of the measured wall time of the traced
     region (the "spans explain the block" acceptance bar).

Phase 2 (fleet, ADR-022): two REAL backend processes (this script
re-exec'd with --backend: RpcChaosNode behind node/rpc.py, each
recording its own trace file) behind an in-process gateway. The
sample key's primary backend is told to drain, the ``gateway.route``
fault site is armed, and one /sample is fired through the gateway —
forcing a real hedge: attempt 0 sheds (503) on the drained primary,
attempt 1 serves from the secondary. The three per-process traces are
merged by tools/trace_merge and the gate fails unless the merged
document validates, ONE trace id spans the gateway's route+hedge
spans and BOTH backends' rpc.request (plus the serving backend's
dispatch) spans, every traced handler's wire parent resolves to a
gateway hedge span, per-request ``rpc_stage_ms`` stage sums agree
with the handler span's end-to-end duration within 10%, and the
``rpc_stage_ms`` exemplar trace ids resolve to real spans in the
merged trace.

Runs fine on CPU — JAX_PLATFORMS defaults to cpu here so `make
trace-smoke` needs no accelerator. The compile happens in a warm-up
pass OUTSIDE the recording so the traced run reflects steady-state
dispatch, same convention as bench.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

REQUIRED_SPANS = ("extend.device", "extend.stage", "extend.rs_nmt")
COVERAGE_FLOOR = 0.90


def build_square(k: int, seed: int = 42) -> np.ndarray:
    """Same construction as bench.py: random payloads, sorted v0
    namespaces so the NMT ordering invariant holds."""
    import celestia_tpu.namespace as ns

    rng = np.random.default_rng(seed)
    flat = rng.integers(0, 256, size=(k * k, 512), dtype=np.uint8)
    subs = sorted(
        rng.integers(0, 200, size=(k * k, 10), dtype=np.uint8).tolist()
    )
    for i, sub in enumerate(subs):
        flat[i, :29] = np.frombuffer(
            ns.new_v0(bytes(sub)).bytes, dtype=np.uint8
        )
    return flat.reshape(k, k, 512)


def run(k: int, trace_out: str) -> list[str]:
    """Execute the smoke run; returns a list of problems (empty = pass)."""
    from celestia_tpu import tracing
    from celestia_tpu.ops import extend_tpu

    sq = build_square(k)
    extend_tpu.extend_and_root_device(sq)  # warm-up: compile outside the trace

    with tracing.record() as rec:
        t0 = time.perf_counter()
        extend_tpu.extend_and_root_device(sq)
        wall = time.perf_counter() - t0
    rec.write(trace_out)

    problems: list[str] = []
    with open(trace_out) as f:
        doc = json.load(f)
    problems += tracing.validate_chrome_trace(doc)

    names = {s.name for s in rec.spans}
    for want in REQUIRED_SPANS:
        if want not in names:
            problems.append(f"missing span {want!r}")

    root_dur = sum(s.duration for s in rec.spans if s.parent_id is None)
    coverage = root_dur / wall if wall > 0 else 0.0
    if coverage < COVERAGE_FLOOR:
        problems.append(
            f"root-span coverage {coverage:.1%} < {COVERAGE_FLOOR:.0%} "
            f"of {wall * 1e3:.2f}ms wall"
        )

    print(
        f"trace-smoke: k={k} spans={len(rec.spans)} "
        f"wall={wall * 1e3:.2f}ms coverage={coverage:.1%} -> {trace_out}"
    )
    return problems


def backend_main(k: int, trace_out: str) -> int:
    """--backend: one real RPC backend process for the fleet phase.

    RpcChaosNode (crypto-free DA chain, genuine NMT proofs) behind the
    REAL node/rpc.py server, recording every span to `trace_out`.
    Prints ``PORT <n>`` once serving, then obeys stdin commands:
    ``drain`` (dispatcher stops admitting → /sample sheds 503, the
    forced-hedge lever) and ``stop`` (graceful stop, write the trace,
    exit)."""
    from celestia_tpu import tracing
    from celestia_tpu.node.rpc import RpcServer
    from celestia_tpu.testutil.chaosnet import RpcChaosNode

    node = RpcChaosNode(heights=1, k=k, chain_id="trace-smoke")
    server = RpcServer(node, port=0)
    rec = tracing.record().start()
    server.start()
    print(f"PORT {server.port}", flush=True)
    try:
        for line in sys.stdin:
            cmd = line.strip()
            if cmd == "drain":
                server.dispatcher.begin_drain()
                print("OK drain", flush=True)
            elif cmd == "stop":
                break
    finally:
        server.stop(drain_timeout=2.0)
        rec.stop()
        rec.write(trace_out)
        print("OK stop", flush=True)
    return 0


def _gw_get(base: str, path: str):
    """(status, trace_id, body_bytes) for one gateway GET; HTTP errors
    are answers, not exceptions."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(base + path, timeout=10.0) as resp:
            return resp.status, resp.headers.get("X-Trace-Id"), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("X-Trace-Id"), e.read()


def _stage_sum_problems(doc: dict) -> list[str]:
    """Per-request attribution gate: for every handler span carrying
    stage attrs, the rpc_stage_ms stage sum must be within 10% of the
    span's own end-to-end duration (median over the workload, so one
    scheduler hiccup doesn't flake the gate)."""
    ratios = []
    for ev in doc.get("traceEvents", []):
        if ev.get("name") != "rpc.request" or ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        if "stage_queue_wait_ms" not in args:
            continue  # shed/error replies never traverse the dispatcher
        stage_ms = sum(v for a, v in args.items()
                       if a.startswith("stage_") and a.endswith("_ms")
                       and isinstance(v, (int, float)))
        dur_ms = float(ev.get("dur", 0.0)) / 1000.0
        if stage_ms > 0 and dur_ms > 0:
            ratios.append(stage_ms / dur_ms)
    if not ratios:
        return ["no rpc.request spans carry stage_*_ms attribution"]
    ratios.sort()
    median = ratios[len(ratios) // 2]
    if abs(median - 1.0) > 0.10:
        return [f"median stage-sum/e2e ratio {median:.2f} outside "
                f"1.0±0.10 ({len(ratios)} requests)"]
    return []


def _exemplar_problems(metrics_text: str, merged: dict) -> list[str]:
    """Every rpc_stage_ms exemplar trace id must resolve to a real
    span in the merged trace — an exemplar pointing nowhere is worse
    than none."""
    import re

    exemplar_tids = set(re.findall(
        r"^# EXEMPLAR rpc_stage_ms_seconds\S* trace_id=([0-9a-f]+)",
        metrics_text, re.MULTILINE))
    if not exemplar_tids:
        return ["no rpc_stage_ms exemplars in backend /metrics"]
    span_tids = {
        (ev.get("args") or {}).get("trace_id")
        for ev in merged.get("traceEvents", [])
    }
    missing = exemplar_tids - span_tids
    if missing:
        return [f"exemplar trace ids not found in merged trace: "
                f"{sorted(missing)[:3]}"]
    return []


def run_fleet(k: int, prefix: str, backends: int = 2) -> list[str]:
    """Fleet phase: spawn backend subprocesses, hedge one /sample
    through a gateway with the primary drained, merge the per-process
    traces, gate the merged document. Returns problems (empty = pass)."""
    import subprocess

    from celestia_tpu import faults, tracing
    from celestia_tpu.node.gateway import Gateway
    from celestia_tpu.tools import trace_merge

    problems: list[str] = []
    script = os.path.abspath(__file__)
    procs: list[subprocess.Popen] = []
    backend_paths = [f"{prefix}.backend{b}.json" for b in range(backends)]
    for path in backend_paths:
        procs.append(subprocess.Popen(
            [sys.executable, script, "--backend", "--k", str(k),
             "--trace-out", path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}))

    def cmd(p: subprocess.Popen, word: str) -> str:
        p.stdin.write(word + "\n")
        p.stdin.flush()
        return (p.stdout.readline() or "").strip()

    gw = None
    metrics_text = ""
    tid = None
    try:
        urls = []
        for p in procs:
            line = (p.stdout.readline() or "").strip()
            if not line.startswith("PORT "):
                return [f"backend did not start (got {line!r})"]
            urls.append(f"http://127.0.0.1:{int(line.split()[1])}")
        gw = Gateway(urls)
        gw.start()
        w = 2 * k
        sample = "/sample/1/0/0"
        primary = urls.index(gw.ring.owners(gw._route_key(sample))[0])
        serving = (primary + 1) % len(urls)
        if cmd(procs[primary], "drain") != "OK drain":
            return ["primary backend failed to drain"]
        with tracing.record() as rec:
            # the fault-armed route: a no-op delay rule keeps the
            # gateway.route site HOT (fired through the injector) while
            # the drained primary supplies the real shed that forces
            # the hedge
            with faults.inject(
                    faults.rule("gateway.route", "delay", delay_s=0.0),
                    seed=1):
                status, tid, _body = _gw_get(gw.url, sample)
                if status != 200:
                    problems.append(
                        f"hedged sample answered {status}, want 200")
                if not tid:
                    problems.append("hedged sample reply lacks X-Trace-Id")
                for r in range(8):  # steady stage-attribution workload
                    st, _t, _b = _gw_get(
                        gw.url, f"/sample/1/{r % w}/{(3 * r) % w}")
                    if st != 200:
                        problems.append(f"workload sample {r}: HTTP {st}")
            _st, _t, raw = _gw_get(urls[serving], "/metrics")
            metrics_text = raw.decode(errors="replace")
        rec.write(f"{prefix}.gateway.json")
    finally:
        if gw is not None:
            gw.stop()
        for p in procs:
            try:
                cmd(p, "stop")
            except (OSError, ValueError):
                pass
            p.wait(timeout=15)

    merged_path = f"{prefix}.merged.json"
    try:
        merged = trace_merge.merge_files(
            merged_path, [f"{prefix}.gateway.json", *backend_paths])
    except (OSError, ValueError) as e:
        return problems + [f"trace merge failed: {e}"]

    by_tid = [ev for ev in merged["traceEvents"]
              if (ev.get("args") or {}).get("trace_id") == tid]
    names = {ev["name"] for ev in by_tid}
    for want in ("gateway.route", "gateway.hedge", "rpc.request"):
        if want not in names:
            problems.append(f"trace {tid}: missing span {want!r}")
    if not any(n.startswith("dispatch.") for n in names):
        problems.append(f"trace {tid}: no dispatch span from the "
                        f"serving backend")
    hedges = [ev for ev in by_tid if ev["name"] == "gateway.hedge"]
    outcomes = {(ev.get("args") or {}).get("outcome") for ev in hedges}
    if len(hedges) < 2 or not {"shed", "served"} <= outcomes:
        problems.append(
            f"trace {tid}: want >=2 hedge attempts with shed+served, "
            f"got {len(hedges)} with outcomes {sorted(filter(None, outcomes))}")
    rpc_pids = {ev["pid"] for ev in by_tid if ev["name"] == "rpc.request"}
    if len(rpc_pids) < 2:
        problems.append(
            f"trace {tid}: rpc.request spans from {len(rpc_pids)} "
            f"process(es), want both backends")
    # parent-child well-formedness across the process boundary: every
    # traced handler's wire parent is a hedge span the gateway recorded
    hedge_wires = {(ev.get("args") or {}).get("wire_span_id")
                   for ev in merged["traceEvents"]
                   if ev.get("name") == "gateway.hedge"}
    for ev in merged["traceEvents"]:
        if ev.get("name") != "rpc.request":
            continue
        wire = (ev.get("args") or {}).get("wire_parent")
        if wire is not None and wire not in hedge_wires:
            problems.append(
                f"rpc.request wire_parent {wire} matches no gateway "
                f"hedge span")
    problems += _stage_sum_problems(merged)
    problems += _exemplar_problems(metrics_text, merged)
    traced = {(ev.get("args") or {}).get("trace_id")
              for ev in merged["traceEvents"]} - {None}
    print(f"trace-smoke[fleet]: backends={backends} "
          f"events={len(merged['traceEvents'])} traces={len(traced)} "
          f"hedges={len(hedges)} -> {merged_path}")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--trace-out", default="/tmp/trace_smoke.json",
                    metavar="PATH")
    ap.add_argument("--backend", action="store_true",
                    help="internal: run as one fleet-phase backend")
    ap.add_argument("--fleet-k", type=int, default=8,
                    help="square size for the fleet phase backends")
    ap.add_argument("--skip-fleet", action="store_true",
                    help="device phase only (no subprocesses)")
    args = ap.parse_args(argv)
    if args.backend:
        return backend_main(args.k, args.trace_out)
    problems = run(args.k, args.trace_out)
    if not args.skip_fleet:
        problems += run_fleet(args.fleet_k, args.trace_out)
    for p in problems:
        print(f"trace-smoke: FAIL: {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
