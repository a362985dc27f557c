"""celestia-tpu CLI — the celestia-appd analogue.

Reference semantics: cmd/celestia-appd/cmd/root.go:121-151 (init / start /
keys / tx / query command tree, env prefix CELESTIA, default home
~/.celestia-app). Run as `python -m celestia_tpu.cli <command>`.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

DEFAULT_HOME = os.environ.get(
    "CELESTIA_HOME", str(pathlib.Path.home() / ".celestia-tpu")
)


def _home(args) -> pathlib.Path:
    home = pathlib.Path(args.home)
    home.mkdir(parents=True, exist_ok=True)
    return home


def _load_keys(home: pathlib.Path) -> dict:
    path = home / "keys.json"
    return json.loads(path.read_text()) if path.exists() else {}


def _save_keys(home: pathlib.Path, keys: dict) -> None:
    (home / "keys.json").write_text(json.dumps(keys, indent=2))


def cmd_init(args):
    from celestia_tpu.config import write_default_configs
    from celestia_tpu.crypto import PrivateKey

    home = _home(args)
    keys = _load_keys(home)
    if "validator" not in keys:
        secret = os.urandom(32)
        keys["validator"] = secret.hex()
        _save_keys(home, keys)
    key = PrivateKey.from_secret(bytes.fromhex(keys["validator"]))
    chain_id = args.chain_id or "celestia-tpu-1"
    genesis = {
        "chain_id": chain_id,
        "genesis_time": time.time(),
        "accounts": {key.bech32_address(): 1_000_000_000_000},
        # the gentx flow: this node's key is a genesis validator with a
        # self-bond (genutil DeliverGenTxs analogue)
        "validators": {key.bech32_address(): 100_000_000_000},
    }
    (home / "genesis.json").write_text(json.dumps(genesis, indent=2))
    # layered config files (ref: app/default_overrides.go:230-271 written by
    # celestia-appd init; start layers defaults < files < env < flags)
    write_default_configs(home)
    print(f"initialized chain {chain_id} at {home}")
    print(f"validator address: {key.bech32_address()}")
    print(f"wrote {home}/config/config.toml and {home}/config/app.toml")


def _build_node(home: pathlib.Path, **app_kwargs):
    from celestia_tpu.app import App
    from celestia_tpu.node import Node

    genesis = json.loads((home / "genesis.json").read_text())
    if (home / "meta.json").exists():
        # app_kwargs reach the App BEFORE the startup replay so e.g. a
        # configured extend_backend governs the batched DA verification
        return Node.load(str(home), **app_kwargs)
    if (home / "blocks").exists() and any((home / "blocks").glob("*.json")):
        raise RuntimeError(
            f"{home} has persisted blocks but no state snapshot "
            "(meta.json) — refusing to re-initialize from genesis over an "
            "existing chain. Restore meta.json/state.json or clear blocks/."
        )
    if "app_state" in genesis:
        # genesis produced by `export` — rebuild the full module state
        from celestia_tpu.app.export import import_genesis

        app = import_genesis(genesis, **app_kwargs)
        return Node(app, home=str(home))
    app = App(chain_id=genesis["chain_id"], **app_kwargs)
    app.init_chain(
        genesis["accounts"],
        genesis_time=genesis["genesis_time"],
        genesis_validators=genesis.get("validators"),
    )
    return Node(app, home=str(home))


def cmd_start(args):
    from celestia_tpu import log as log_mod
    from celestia_tpu import tracing
    from celestia_tpu.config import load_config
    from celestia_tpu.node.rpc import RpcServer

    log_mod.configure(args.log_level)
    # flight recorder live for the whole run (/debug/flight next to
    # /metrics); --trace-out additionally collects EVERY span and writes
    # Chrome trace-event JSON (Perfetto-loadable) at shutdown
    tracing.enable()
    recording = None
    if getattr(args, "trace_out", None):
        recording = tracing.start_recording()
    home = _home(args)
    flag_overrides = {}
    if args.block_time is not None:
        flag_overrides["consensus.goal_block_time_seconds"] = args.block_time
    if getattr(args, "extend_backend", None) is not None:
        flag_overrides["app.extend_backend"] = args.extend_backend
    cfg = load_config(home, flag_overrides)
    # persistent XLA compile cache: a node restart pays disk-load, not a
    # recompile, for the extend/repair device programs
    from celestia_tpu.ops import enable_compile_cache

    enable_compile_cache()
    # SDC audit policy (ADR-015): installs the process-global integrity
    # engine BEFORE the node boots, so replay/startup extends are
    # audited too. Default off — the disabled path costs one boolean.
    if getattr(args, "audit_level", None):
        from celestia_tpu import integrity

        integrity.configure(args.audit_level)
    # App.__init__ validates the backend string, so a config/env typo
    # fails loudly here instead of silently degrading to numpy
    node = _build_node(home, extend_backend=cfg.app.extend_backend)
    node.app.min_gas_price = cfg.app.min_gas_price
    node.mempool.ttl_blocks = cfg.consensus.mempool.ttl_num_blocks
    node.mempool.max_tx_bytes = cfg.consensus.mempool.max_tx_bytes
    # calibrated auto crossover (app/calibration.py, ADR-012): load the
    # persisted per-k table when present; measure + persist a fresh one
    # when configured or asked (--calibrate-crossover refreshes a stale
    # table, e.g. after the hardware changed)
    from celestia_tpu.app.calibration import CrossoverTable, crossover_path

    cal_path = crossover_path(home)
    table = CrossoverTable.load(cal_path)
    if table is not None:
        node.app.crossover = table
    if cfg.app.calibrate_crossover or getattr(args, "calibrate_crossover",
                                              False):
        node.app.calibrate_crossover(persist_path=cal_path)
    # resolve + log the live backend up front so the operator sees what
    # this node will actually run on the hot path
    live = node.boot_extend_backend()
    server = RpcServer(node, port=args.port)
    server.start()
    # synthetic DAS prober (node/prober.py): black-box samples through
    # the node's OWN rpc surface, feeding the probe_* counters the SLO
    # availability objective reads. Off unless asked — the disabled
    # path must cost nothing.
    prober = None
    if getattr(args, "probe_interval", None):
        from celestia_tpu.node.prober import Prober

        prober = Prober(f"http://127.0.0.1:{server.port}",
                        interval=args.probe_interval)
        node.prober = prober
        prober.start()
    # the reference node serves gRPC alongside RPC (app/app.go:693-719);
    # enabled via app.toml grpc_enable or the --grpc-port flag
    grpc_server = None
    grpc_note = ""
    if cfg.app.grpc_enable or getattr(args, "grpc_port", None) is not None:
        from celestia_tpu.node.grpc_api import NodeGrpcServer

        grpc_server = NodeGrpcServer(
            node, port=getattr(args, "grpc_port", None) or 0
        )
        grpc_server.start()
        grpc_note = f"grpc 127.0.0.1:{grpc_server.port} "
    print(f"node started: chain {node.app.chain_id} height {node.latest_height()} "
          f"rpc http://127.0.0.1:{server.port} {grpc_note}"
          f"min-gas-price {cfg.app.min_gas_price} "
          f"extend-backend {cfg.app.extend_backend} (live: {live}) "
          f"audit-level {getattr(node.app, 'audit_level', 'off')}")
    # an initial snapshot so a hard crash before the first interval never
    # leaves blocks-without-meta (which _build_node refuses to re-init)
    node.save_snapshot()
    # SDK semantics: snapshot-interval 0 disables periodic snapshots
    # (crash recovery then replays the whole block store)
    snapshot_interval = cfg.app.state_sync.snapshot_interval
    try:
        while True:
            time.sleep(cfg.consensus.goal_block_time_seconds)
            block = node.produce_block()
            # disk snapshots on the configured StateSync cadence; the
            # block store itself is persisted per block by produce_block
            if snapshot_interval and block.height % snapshot_interval == 0:
                node.save_snapshot()
            print(f"height {block.height} txs {len(block.txs)} "
                  f"square {block.square_size} data {block.data_hash.hex()[:16]}")
    except KeyboardInterrupt:
        if prober is not None:
            prober.stop()
        server.stop()
        if grpc_server is not None:
            grpc_server.stop()
        node.save_snapshot()
        if recording is not None:
            recording.stop()
            path = recording.write(args.trace_out)
            print(f"trace written: {path} ({len(recording.spans)} spans)")
        print("node stopped")


def cmd_export(args):
    """ref: app/export.go via `celestia-appd export` — print (or write) a
    genesis document a fresh node can start from."""
    from celestia_tpu.app.export import export_app_state_and_validators

    home = _home(args)
    node = _build_node(home)
    genesis = export_app_state_and_validators(
        node.app, for_zero_height=args.for_zero_height
    )
    text = json.dumps(genesis, indent=2, sort_keys=True)
    if args.output:
        pathlib.Path(args.output).write_text(text)
        print(f"exported genesis (height {genesis['height']}) to {args.output}")
    else:
        print(text)


def cmd_download_genesis(args):
    """Fetch a chain's genesis from a live node and install it in the
    home directory (ref: cmd/celestia-appd/cmd/download-genesis.go,
    which fetches by chain id from a public URL; here the source is any
    node's /genesis RPC route)."""
    import urllib.request

    home = _home(args)
    with urllib.request.urlopen(
        args.node.rstrip("/") + "/genesis", timeout=15
    ) as resp:
        genesis = json.loads(resp.read())
    if args.chain_id and genesis.get("chain_id") != args.chain_id:
        print(
            f"refusing: node serves chain {genesis.get('chain_id')!r}, "
            f"expected {args.chain_id!r}",
            file=sys.stderr,
        )
        sys.exit(1)
    target = home / "genesis.json"
    if target.exists() and not args.force:
        print(f"{target} already exists (use --force to overwrite)",
              file=sys.stderr)
        sys.exit(1)
    target.write_text(json.dumps(genesis, indent=2, sort_keys=True))
    print(f"wrote genesis for chain {genesis.get('chain_id')} to {target}")


def cmd_addrbook(args):
    """Manage the peer address book (ref: cmd/celestia-appd/cmd/
    addrbook.go converts peer lists into the node's addrbook.json)."""
    home = _home(args)
    path = home / "addrbook.json"
    book = json.loads(path.read_text()) if path.exists() else {"peers": []}
    if args.book_cmd in ("add", "remove") and not args.peer:
        print(f"addrbook {args.book_cmd} needs a peer URL", file=sys.stderr)
        sys.exit(1)
    if args.book_cmd == "add":
        if args.peer in book["peers"]:
            print(f"{args.peer} already in addrbook")
        else:
            book["peers"].append(args.peer)
            path.write_text(json.dumps(book, indent=2))
            print(f"added {args.peer} ({len(book['peers'])} peers)")
    elif args.book_cmd == "remove":
        if args.peer not in book["peers"]:
            print(f"{args.peer} not in addrbook", file=sys.stderr)
            sys.exit(1)
        book["peers"].remove(args.peer)
        path.write_text(json.dumps(book, indent=2))
        print(f"removed {args.peer} ({len(book['peers'])} peers)")
    else:  # list
        for peer in book["peers"]:
            print(peer)


def cmd_rollback(args):
    """Roll the chain back one block (the CometBFT `rollback` analogue:
    recover from an app-hash mismatch by re-executing the last height).
    Works by deleting the newest persisted block and replaying from the
    last snapshot — so the snapshot must be at or below the target
    height."""
    home = _home(args)
    blocks_dir = home / "blocks"
    heights = sorted(
        int(p.stem) for p in blocks_dir.glob("*.json")
    ) if blocks_dir.exists() else []
    if not heights:
        print("no persisted blocks to roll back", file=sys.stderr)
        sys.exit(1)
    latest = heights[-1]
    if not (home / "meta.json").exists():
        # the blocks-without-meta crash state _build_node refuses to
        # re-init from — rollback can't help without a snapshot either
        print("no state snapshot (meta.json); cannot roll back — restore "
              "meta.json/state.json or clear blocks/", file=sys.stderr)
        sys.exit(1)
    meta = json.loads((home / "meta.json").read_text())
    if meta["height"] >= latest:
        print(
            f"snapshot is at height {meta['height']} >= latest block "
            f"{latest}: cannot roll back past the last snapshot (no "
            "older snapshot retained)",
            file=sys.stderr,
        )
        sys.exit(1)
    (blocks_dir / f"{latest}.json").unlink()
    # prove the store still replays cleanly to the new head
    node = _build_node(home)
    node.save_snapshot()
    print(f"rolled back block {latest}; chain head is now "
          f"{node.app.height} (app hash "
          f"{node.app.store.app_hashes[node.app.store.version].hex()[:16]}…)")


def cmd_compact(args):
    """Prune persisted blocks no longer needed for crash recovery
    (the store-compaction analogue): recovery replays from the last
    snapshot, so blocks strictly below the snapshot height are dead
    weight. `--keep-recent` retains extra history for serving peers."""
    home = _home(args)
    meta_path = home / "meta.json"
    if not meta_path.exists():
        print("no snapshot; refusing to prune (recovery would need "
              "every block)", file=sys.stderr)
        sys.exit(1)
    snapshot_height = json.loads(meta_path.read_text())["height"]
    floor = max(0, snapshot_height - args.keep_recent)
    removed = 0
    for path in sorted((home / "blocks").glob("*.json")):
        if int(path.stem) < floor:
            path.unlink()
            removed += 1
    print(f"pruned {removed} blocks below height {floor} "
          f"(snapshot at {snapshot_height}, keep-recent {args.keep_recent})")


def cmd_keys(args):
    from celestia_tpu.crypto import PrivateKey

    home = _home(args)
    keys = _load_keys(home)
    if args.keys_cmd == "add":
        if args.name in keys:
            print(f"key {args.name} already exists", file=sys.stderr)
            sys.exit(1)
        keys[args.name] = os.urandom(32).hex()
        _save_keys(home, keys)
    if args.keys_cmd in ("add", "show"):
        key = PrivateKey.from_secret(bytes.fromhex(keys[args.name]))
        print(f"{args.name}: {key.bech32_address()}")
    elif args.keys_cmd == "list":
        for name, secret in keys.items():
            key = PrivateKey.from_secret(bytes.fromhex(secret))
            print(f"{name}: {key.bech32_address()}")


def _rpc(args, method, path, body=None):
    import urllib.request

    url = f"http://127.0.0.1:{args.port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def cmd_tx(args):
    """Submit through the full Signer stack over the RPC client, so the
    CLI gets nonce-race recovery and min-gas-price bumping for free."""
    from celestia_tpu import blob as blob_pkg
    from celestia_tpu import namespace as ns
    from celestia_tpu.crypto import PrivateKey
    from celestia_tpu.node.client import RpcClient
    from celestia_tpu.user import Signer
    from celestia_tpu.x.bank import MsgSend

    home = _home(args)
    keys = _load_keys(home)
    key = PrivateKey.from_secret(bytes.fromhex(keys[args.from_key]))
    client = RpcClient(f"http://127.0.0.1:{args.port}")
    try:
        signer = Signer.setup_single(key, client)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        sys.exit(1)
    if args.chain_id is not None and args.chain_id != signer.chain_id:
        print(
            f"--chain-id {args.chain_id} disagrees with the node's chain "
            f"{signer.chain_id}",
            file=sys.stderr,
        )
        sys.exit(1)

    if args.tx_cmd == "pfb":
        data = pathlib.Path(args.file).read_bytes() if args.file else os.urandom(args.size)
        b = blob_pkg.new_blob(ns.new_v0(bytes.fromhex(args.namespace)), data, 0)
        res = signer.submit_pay_for_blob([b])
    elif args.tx_cmd == "send":
        res = signer.submit_tx(
            [MsgSend(key.bech32_address(), args.to, args.amount)]
        )
    from celestia_tpu.node.node import tx_hash

    print(json.dumps({"code": res.code, "log": res.log,
                      "hash": tx_hash(res.raw).hex()}))


def cmd_query(args):
    print(json.dumps(_rpc(args, "GET", args.path)))


def cmd_slo(args):
    """`celestia-tpu slo check`: one-shot health/readiness/SLO verdict
    against a running node. Exit codes: 0 fit, 1 not ready or an SLO
    objective breaching, 2 node unreachable — scriptable as a probe."""
    import urllib.error
    import urllib.request

    base = f"http://127.0.0.1:{args.port}"

    def fetch(path):
        req = urllib.request.Request(base + path, method="GET")
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            # /readyz answers 503 WITH a JSON body — that is a verdict,
            # not an unreachable node
            try:
                return e.code, json.loads(e.read())
            except ValueError:
                return e.code, {"error": f"HTTP {e.code}"}

    try:
        _, health = fetch("/healthz")
        ready_status, ready = fetch("/readyz")
        _, debug = fetch("/debug/slo")
    except (OSError, ValueError) as e:
        print(json.dumps({"error": f"node unreachable: {e}"}),
              file=sys.stderr)
        sys.exit(2)
    slo_ok = bool(debug.get("slo", {}).get("ok", False))
    verdict = {
        "healthy": bool(health.get("ok")),
        "ready": ready_status == 200,
        "checks": ready.get("checks", []),
        "slo_ok": slo_ok,
        "objectives": debug.get("slo", {}).get("objectives", []),
        "probe_last": debug.get("probe_last"),
    }
    print(json.dumps(verdict, indent=2))
    sys.exit(0 if (verdict["ready"] and slo_ok) else 1)


def cmd_ops(args):
    """`celestia-tpu ops audit <height>`: fetch a committed block's
    extended square from a running node and re-verify EVERY row and
    column against the GF(256) erasure code on the host — the offline
    full-strength SDC audit (ADR-015). Exit 0 clean, 1 when any parity
    cell mismatches the code, 2 when the block is unavailable."""
    import numpy as np

    from celestia_tpu import integrity

    try:
        doc = _rpc(args, "GET", f"/eds/{args.height}")
    except Exception as e:  # noqa: BLE001 — unreachable/missing: exit 2
        print(json.dumps({"error": f"cannot fetch eds: {e}"}),
              file=sys.stderr)
        sys.exit(2)
    w = int(doc["width"])
    eds = np.stack([
        np.frombuffer(bytes.fromhex(r), dtype=np.uint8).reshape(w, -1)
        for r in doc["rows"]
    ])
    mism = int(integrity.host_eds_mismatch(eds, w // 2))
    print(json.dumps({
        "height": args.height,
        "width": w,
        "mismatching_parity_cells": mism,
        "ok": mism == 0,
    }))
    sys.exit(0 if mism == 0 else 1)


def cmd_store(args):
    """`celestia-tpu store stat|verify|compact`: inspect, deep-verify
    or garbage-collect the CRC32C-guarded on-disk block store under
    --home (specs/store.md, ADR-021/ADR-023). `stat` re-indexes
    shallowly (header + size checks) and prints the index summary;
    `verify` additionally checks EVERY page record's CRC and exits 1
    when any file was quarantined — the offline bit-rot audit for a
    node's persisted chain. `compact --byte-budget N [--keep-recent R]`
    evicts whole cold heights (lowest first, newest R protected) until
    the store fits N bytes; retained files are untouched, so surviving
    DAH bytes are identical before and after."""
    from celestia_tpu.store import BlockStore

    home = _home(args)
    root = home / "store"
    if not root.is_dir():
        print(json.dumps({"error": f"no block store at {root}"}),
              file=sys.stderr)
        sys.exit(1)
    store = BlockStore(root)
    report = store.reindex(deep=(args.store_cmd == "verify"))
    doc = dict(store.stats())
    doc["cmd"] = args.store_cmd
    doc["skipped_files"] = report["skipped"]
    if args.store_cmd == "compact":
        if args.byte_budget is None:
            print(json.dumps({"error": "compact requires --byte-budget"}),
                  file=sys.stderr)
            sys.exit(2)
        doc["compaction"] = store.compact(args.byte_budget,
                                          keep_recent=args.keep_recent)
        doc.update(store.stats())
    print(json.dumps(doc, indent=2))
    if args.store_cmd == "verify" and report["skipped"]:
        sys.exit(1)
    if args.store_cmd == "compact" and doc["compaction"]["over_budget"]:
        sys.exit(1)


def cmd_light(args):
    """Fraud-aware light client (specs/fraud_proofs.md consumer role):
    follow headers from a primary full node, screen each against
    watchtower fraud proofs, print one JSON line per decision. Exits
    non-zero the moment a verified proof condemns a header."""
    from celestia_tpu.node.client import (
        FraudAwareLightClient,
        FraudDetected,
        RpcClient,
        Unavailable,
    )

    primary = RpcClient(args.primary)
    towers = [
        RpcClient(u.strip()) for u in args.watchtowers.split(",")
        if u.strip()
    ]
    lc = FraudAwareLightClient(primary, towers)
    height = args.from_height
    # idle timeout: reset on every accepted header — "stop waiting for
    # NEW headers", not an absolute run deadline
    idle_since = time.monotonic()
    polls = 0
    while True:
        try:
            hdr = lc.accept_header(height)
        except FraudDetected as e:
            print(json.dumps({"height": height, "accepted": False,
                              "fraud": str(e)}))
            raise SystemExit(2)
        if hdr is None:
            if args.once:
                # explicit record: exit 0 with silence would be
                # indistinguishable from "screened clean"
                print(json.dumps({"height": height, "accepted": None,
                                  "reason": "not yet produced"}))
                return
            if args.timeout and time.monotonic() - idle_since > args.timeout:
                return
            time.sleep(args.poll)
            polls += 1
            # rescreen for proofs that arrived after acceptance: a
            # cheap windowed pass each poll, a FULL pass periodically
            # (a proof can condemn a header far below the tip —
            # client.py requires windowed callers to do this)
            try:
                lc.rescreen(window=None if polls % 32 == 0 else 64)
            except FraudDetected as e:
                print(json.dumps(
                    {"height": getattr(e, "height", None),
                     "accepted": False, "fraud": str(e)}))
                raise SystemExit(2)
            # bound follower memory: headers far below the full-pass
            # horizon can no longer be condemned by a servable proof
            if len(lc.headers) > 16384:
                for h in sorted(lc.headers)[:-8192]:
                    del lc.headers[h]
            continue
        record = {"height": height, "accepted": True,
                  "data_hash": hdr["data_hash"]}
        if args.sample:
            try:
                record["das"] = lc.sample_availability(height, n=args.sample)
            except Unavailable as e:
                record.update(accepted=False, unavailable=str(e))
                print(json.dumps(record))
                raise SystemExit(3)
        print(json.dumps(record))
        idle_since = time.monotonic()
        height += 1
        if args.once:
            return


def main(argv=None):
    parser = argparse.ArgumentParser(prog="celestia-tpu")
    parser.add_argument("--home", default=DEFAULT_HOME)
    parser.add_argument("--port", type=int, default=26657)
    # None = not passed: init falls back to the default chain id; tx
    # verifies a passed value against the node's actual chain
    parser.add_argument("--chain-id", default=None)
    sub = parser.add_subparsers(dest="cmd", required=True)

    sub.add_parser("init")
    p_start = sub.add_parser("start")
    # None = "flag not passed" so config-file/env values aren't masked
    p_start.add_argument("--block-time", type=float, default=None)
    p_start.add_argument("--grpc-port", type=int, default=None,
                         help="also serve the gRPC API on this port "
                              "(0 = ephemeral; default: only when "
                              "app.toml grpc_enable)")
    p_start.add_argument("--extend-backend", default=None,
                         choices=["auto", "tpu", "native", "numpy"],
                         help="ExtendBlock backend (default: config "
                              "app.extend_backend, 'auto')")
    p_start.add_argument("--calibrate-crossover", action="store_true",
                         help="measure the per-k TPU/native latency "
                              "crossover now and persist it to "
                              "config/crossover.json ('auto' then picks "
                              "the measured winner per square size)")
    p_start.add_argument("--log-level", default="info",
                         choices=["debug", "info", "warning", "error"])
    p_start.add_argument("--trace-out", default=None, metavar="PATH",
                         help="write Chrome trace-event JSON of every "
                              "span to PATH at shutdown (the flight "
                              "recorder at /debug/flight is always on)")
    p_start.add_argument("--probe-interval", type=float, default=None,
                         metavar="SECONDS",
                         help="run the synthetic DAS prober against "
                              "this node every SECONDS (verified "
                              "/sample + /proof/share probes feeding "
                              "the availability SLO; default: off)")
    p_start.add_argument("--audit-level", default=None,
                         choices=["off", "sampled", "full"],
                         help="integrity audit of every device extend/"
                              "repair before the DAH commits (ADR-015): "
                              "off = zero overhead, sampled = q random "
                              "rows+cols device-side, full = sampled + "
                              "host recompute comparison")

    p_export = sub.add_parser("export")
    p_export.add_argument("--for-zero-height", action="store_true")
    p_export.add_argument("--output", default=None)

    p_keys = sub.add_parser("keys")
    p_keys.add_argument("keys_cmd", choices=["add", "list", "show"])
    p_keys.add_argument("name", nargs="?", default="validator")

    p_tx = sub.add_parser("tx")
    tx_sub = p_tx.add_subparsers(dest="tx_cmd", required=True)
    p_pfb = tx_sub.add_parser("pfb")
    p_pfb.add_argument("--from", dest="from_key", default="validator")
    # default: ascii "testing123" — all-zero-prefixed ids fall in the
    # primary-reserved range and are rejected for blobs
    p_pfb.add_argument("--namespace", default="74657374696e67313233",
                       help="up to 10 user bytes, hex")
    p_pfb.add_argument("--size", type=int, default=1000)
    p_pfb.add_argument("--file", default=None)
    p_send = tx_sub.add_parser("send")
    p_send.add_argument("--from", dest="from_key", default="validator")
    p_send.add_argument("to")
    p_send.add_argument("amount", type=int)

    p_query = sub.add_parser("query")
    p_query.add_argument("path")

    p_slo = sub.add_parser(
        "slo", help="SLO/readiness checks against a running node")
    p_slo.add_argument("slo_cmd", choices=["check"])

    p_ops = sub.add_parser(
        "ops", help="operator drills against a running node")
    ops_sub = p_ops.add_subparsers(dest="ops_cmd", required=True)
    p_audit = ops_sub.add_parser(
        "audit", help="host-recompute the erasure code over one "
        "committed block's extended square (exit 1 on any mismatch)")
    p_audit.add_argument("height", type=int)

    p_dl = sub.add_parser("download-genesis")
    p_dl.add_argument("--node", required=True,
                      help="RPC base URL of a live node to fetch from")
    p_dl.add_argument("--force", action="store_true")

    p_book = sub.add_parser("addrbook")
    p_book.add_argument("book_cmd", choices=["add", "remove", "list"])
    p_book.add_argument("peer", nargs="?", default=None)

    sub.add_parser("rollback")

    p_compact = sub.add_parser("compact")
    p_compact.add_argument("--keep-recent", type=int, default=100,
                           help="blocks to retain below the snapshot height")

    p_store = sub.add_parser(
        "store", help="inspect (stat), CRC-audit (verify) or GC "
        "(compact) the on-disk block store under --home; verify exits "
        "1 on any quarantined file, compact evicts cold heights to a "
        "byte budget (ADR-023)")
    p_store.add_argument("store_cmd", choices=["stat", "verify",
                                               "compact"])
    p_store.add_argument("--byte-budget", type=int, default=None,
                         help="compact: target on-disk byte budget "
                         "(required)")
    p_store.add_argument("--keep-recent", type=int, default=16,
                         help="compact: newest heights never evicted")

    p_light = sub.add_parser(
        "light", help="fraud-aware light client: follow headers from a "
        "primary node, reject on verified bad-encoding proofs")
    p_light.add_argument("--primary", required=True,
                         help="full node RPC base URL to follow")
    p_light.add_argument("--watchtowers", default="",
                         help="comma-separated RPC URLs serving "
                              "/fraud/befp")
    p_light.add_argument("--from-height", type=int, default=1)
    p_light.add_argument("--poll", type=float, default=1.0)
    p_light.add_argument("--timeout", type=float, default=0.0,
                         help="stop waiting for new headers after this "
                              "many seconds (0 = follow forever)")
    p_light.add_argument("--once", action="store_true",
                         help="screen exactly --from-height, then exit")
    def _nonneg(v):
        n = int(v)
        if n < 0:
            raise argparse.ArgumentTypeError("--sample must be >= 0")
        return n

    p_light.add_argument("--sample", type=_nonneg, default=0, metavar="N",
                         help="also data-availability-sample N random "
                              "shares per header (exit 3 on an "
                              "unavailable block)")

    args = parser.parse_args(argv)
    {
        "init": cmd_init,
        "start": cmd_start,
        "export": cmd_export,
        "keys": cmd_keys,
        "tx": cmd_tx,
        "query": cmd_query,
        "slo": cmd_slo,
        "ops": cmd_ops,
        "download-genesis": cmd_download_genesis,
        "addrbook": cmd_addrbook,
        "rollback": cmd_rollback,
        "compact": cmd_compact,
        "store": cmd_store,
        "light": cmd_light,
    }[args.cmd](args)


if __name__ == "__main__":
    main()
