#!/usr/bin/env python3
"""Bring-up smoke of the ExtendBlock -> DAH path on one TPU chip.

    python chip_smoke.py               # phases 1-3 on one chip
    python chip_smoke.py --four-chips  # the two mesh phases on four chips

One process drives the chip through the entry points a node uses:

1. DAH oracles: the reference's three DataAvailabilityHeader vectors
   (k = 1, 2, 128) through App(extend_backend="tpu"); the hashes must be
   byte-identical to pkg/da/data_availability_header_test.go's.
2. Node at the governance-default square (k = 64): a node booted as
   `cli start --extend-backend tpu` boots it (blob arena, retained
   device-resident squares, RpcServer on a local port) takes signed
   PFB transactions over RPC that fill >= 90% of the square, produces 3
   blocks, and answers /sample over HTTP. Each block's DAH is recomputed
   on the host path and each sample's proof is verified against it.
3. k = 128, the mainnet upper bound: App.extend_block keeps the EDS on
   the device, da.repair.repair_eds repairs 25% erasures on the device,
   and the result must equal the original EDS byte for byte.

After the phases, every extend must have run on the device: the App's
backend stayed "tpu" with no strikes, no *_tpu_fallback_total counter
moved and no "device prover seeding failed" or "eds retention failed"
event was logged.

--four-chips runs only the mesh spellings at k = 128: the row-sharded
extend_and_root_rowsharded on (dp=1, sp=4) and the batched
sharded_extend_and_root on (dp=4, sp=1); outputs must span four
devices and every DAH must equal the host path's.

Walls printed on the way are smoke walls of one cold run, not benchmark
numbers. The last line of standard output is one JSON object naming
the device; any failed check exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time

ORACLES = {
    # pkg/da/data_availability_header_test.go:28, :44, :50
    1: "3d96b7d238e7e0456f6af8e7cdf0a67bd6cf9c2089ecb559c659dcaa1f880353",
    2: "b56e4d251ac266f4b91cc5464b3fc7efcbdc888064647496d13133f0dc65ac25",
    128: "0bd3abeeacfbb0b92dfbdac4a154868e3c4e79666f7fcf6c620bb90dd3a0dcf0",
}
FALLBACK_COUNTERS = ("extend_tpu_fallback_total", "codec_tpu_fallback_total")
# host fallbacks that log instead of raising
FALLBACK_EVENTS = ("device prover seeding failed", "eds retention failed")
MAX_BLOB_SHARES = 48  # <= 64 shares: no subtree alignment padding


class SmokeError(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def say(msg: str) -> None:
    print(msg, flush=True)


class _Events(logging.Handler):
    """Every celestia_tpu log message of the run, for the post-checks."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.messages: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())


def counter_total(name: str) -> float:
    from celestia_tpu.telemetry import metrics

    return sum(v for key, v in list(metrics.counters.items())
               if key.split("{", 1)[0] == name)


def host_dah(shares):
    """The host path's DAH of (k, k, 512) share bytes: native C++ when it
    builds here, the numpy da/ reference otherwise."""
    from celestia_tpu import da, native

    if native.available():
        _eds, rows, cols, dah = native.extend_and_root_native(shares)
        return da.DataAvailabilityHeader(rows, cols, _hash=dah)
    eds = da.extend_shares([bytes(s) for s in shares.reshape(-1, 512)])
    return da.new_data_availability_header(eds)


def square_array(data_square):
    import numpy as np

    from celestia_tpu import square as square_pkg

    k = square_pkg.square_size(len(data_square))
    return np.frombuffer(b"".join(s.data for s in data_square),
                         dtype=np.uint8).reshape(k, k, 512)


def oracle_square(k: int):
    from celestia_tpu import namespace as ns
    from celestia_tpu.shares import Share, tail_padding_share

    if k == 1:
        return [tail_padding_share()]
    # data_availability_header_test.go:218-231 generateShares
    ns1 = ns.new_v0(b"\x01" * ns.NAMESPACE_VERSION_ZERO_ID_SIZE)
    share = Share(ns1.bytes + b"\xff" * (512 - len(ns1.bytes)))
    return [share] * (k * k)


def assert_device_only(app, events: _Events) -> None:
    check(app._active_backend == "tpu",
          f"app backend is {app._active_backend!r}, not 'tpu'")
    check(app._tpu_strikes == 0 and not app._tpu_disabled,
          f"device strikes {app._tpu_strikes}, disabled {app._tpu_disabled}")
    for name in FALLBACK_COUNTERS:
        check(counter_total(name) == 0, f"{name} = {counter_total(name)}")
    for event in FALLBACK_EVENTS:
        check(not any(event in m for m in events.messages),
              f"'{event}' was logged")


# ---------------------------------------------------------------------- #
# phases


def phase_oracles(ks=(1, 2, 128)) -> dict:
    from celestia_tpu.app import App

    app = App(extend_backend="tpu")
    out = {}
    for k in ks:
        t0 = time.perf_counter()
        eds, dah = app._extend_and_hash(oracle_square(k))
        check(eds.device_data is not None,
              f"k={k}: the EDS is not device-resident")
        check(dah.hash().hex() == ORACLES[k],
              f"k={k}: DAH {dah.hash().hex()} != oracle {ORACLES[k]}")
        out[k] = round(time.perf_counter() - t0, 3)
    return {"app": app, "wall_s": out}


def _blob_plan(k: int) -> tuple[int, int]:
    """(blobs, bytes per blob) for one block: blob shares plus about one
    compact share per PFB transaction land near 95% of a k x k square."""
    from celestia_tpu.appconsts import (
        CONTINUATION_SPARSE_SHARE_CONTENT_SIZE as CONT,
        FIRST_SPARSE_SHARE_CONTENT_SIZE as FIRST,
    )

    shares = min(MAX_BLOB_SHARES, k // 2)
    return int(0.95 * k * k) // (shares + 1), FIRST + (shares - 1) * CONT


def _random_blob(rng, blob_len: int):
    import numpy as np

    from celestia_tpu import blob as blob_pkg
    from celestia_tpu import namespace as ns

    data = rng.integers(0, 256, blob_len, dtype=np.uint8).tobytes()
    nid = rng.integers(0, 256, 10, dtype=np.uint8).tobytes()
    return blob_pkg.new_blob(ns.new_v0(nid), data, 0)


def phase_node(k: int, seed: int, n_blocks: int = 3,
               samples_per_block: int = 16) -> dict:
    import numpy as np

    from celestia_tpu.app import App
    from celestia_tpu.crypto import PrivateKey
    from celestia_tpu.da import DataAvailabilityHeader, erasured_leaf_namespace
    from celestia_tpu.node import Node
    from celestia_tpu.node.client import RpcClient
    from celestia_tpu.node.rpc import RpcServer
    from celestia_tpu.proof import NmtRangeProof
    from celestia_tpu.user import Signer

    rng = np.random.default_rng(seed)
    keys = [PrivateKey.from_secret(f"chip-smoke-{seed}-{i}".encode())
            for i in range(8)]
    app = App(chain_id="chip-smoke-1", extend_backend="tpu")
    app.init_chain({key.bech32_address(): 10**15 for key in keys},
                   genesis_time=time.time(),
                   genesis_validators={keys[0].bech32_address(): 10**11})
    node = Node(app)
    node.produce_block()  # the first block is empty by design
    check(node.boot_extend_backend() == "tpu",  # as `cli start` boots
          "the node's extend backend did not resolve to tpu")
    check(node.app.resolve_extend_backend(k) == "tpu",
          f"the extend backend does not resolve to tpu at k={k}")
    server = RpcServer(node, port=0)
    server.start()
    try:
        # the first /sample of a height compiles the row-levels program
        client = RpcClient(f"http://127.0.0.1:{server.port}", timeout=600)
        signers = [Signer.setup_single(key, client) for key in keys]
        n_blobs, blob_len = _blob_plan(k)
        walls, fills = [], []
        n_samples = 0
        for _ in range(n_blocks):
            for b in range(n_blobs):
                blob = _random_blob(rng, blob_len)
                res = signers[b % len(signers)].submit_pay_for_blob([blob])
                check(res.code == 0, f"PFB rejected: {res.log}")
            t0 = time.perf_counter()
            block = node.produce_block()
            walls.append(round(time.perf_counter() - t0, 3))
            check(len(node.mempool) == 0,
                  f"height {block.height}: {len(node.mempool)} txs left over")
            check(block.square_size == k,
                  f"height {block.height}: square {block.square_size} != {k}")
            data_square = Node._rebuild_square(node.app, block)
            fill = sum(not s.is_padding() for s in data_square) / (k * k)
            fills.append(round(fill, 4))
            check(fill >= 0.9, f"height {block.height}: square fill {fill}")
            dah = host_dah(square_array(data_square))
            check(dah.hash() == block.data_hash,
                  f"height {block.height}: device DAH != host DAH")
            served = DataAvailabilityHeader.from_json(client.dah(block.height))
            check(served.hash() == block.data_hash,
                  f"height {block.height}: /dah serves another DAH")
            w = 2 * k
            for i, j in rng.integers(0, w, size=(samples_per_block, 2)):
                i, j = int(i), int(j)
                body = client.sample(block.height, i, j)  # GET /sample
                share = bytes.fromhex(body["share"])
                p = body["proof"]
                proof = NmtRangeProof(
                    start=int(p["start"]), end=int(p["end"]),
                    nodes=[bytes.fromhex(x) for x in p["nodes"]],
                    tree_size=int(p["tree_size"]),
                )
                check((proof.start, proof.end, proof.tree_size) == (j, j + 1, w),
                      f"sample ({i},{j}): proof shape")
                proof.verify_inclusion(
                    dah.row_roots[i],
                    [erasured_leaf_namespace(i, j, share, k)], [share])
                n_samples += 1
    finally:
        server.stop()
    return {"app": node.app, "produce_block_wall_s": walls, "fill": fills,
            "samples_verified": n_samples,
            "arena_stats": dict(node.app.arena_stats)}


def _pfb_txs(k: int, seed: int) -> list[bytes]:
    """Signed PFB blob transactions filling about 95% of a k x k square."""
    import numpy as np

    from celestia_tpu import blob as blob_pkg
    from celestia_tpu.crypto import PrivateKey
    from celestia_tpu.tx import Fee, sign_tx
    from celestia_tpu.x.blob.types import estimate_gas, new_msg_pay_for_blobs

    rng = np.random.default_rng(seed)
    key = PrivateKey.from_secret(f"chip-smoke-repair-{seed}".encode())
    addr = key.bech32_address()
    n_blobs, blob_len = _blob_plan(k)
    txs = []
    for i in range(n_blobs):
        b = _random_blob(rng, blob_len)
        gas = estimate_gas([blob_len])
        tx = sign_tx(key, [new_msg_pay_for_blobs(addr, b)], "chip-smoke-1",
                     0, i, Fee(amount=gas, gas_limit=gas))
        txs.append(blob_pkg.marshal_blob_tx(tx.marshal(), [b]))
    return txs


def phase_repair(k: int, seed: int) -> dict:
    import numpy as np

    from celestia_tpu import square as square_pkg
    from celestia_tpu.app import App
    from celestia_tpu.da.repair import repair_eds

    app = App(chain_id="chip-smoke-1", extend_backend="tpu")
    txs = _pfb_txs(k, seed)
    data_square = square_pkg.construct(txs, app.app_version, k)
    check(square_pkg.square_size(len(data_square)) == k,
          f"repair square is not {k} x {k}")
    t0 = time.perf_counter()
    eds, _dah = app.extend_block(txs)
    dah = app._proposal_dah(data_square)
    extend_wall = time.perf_counter() - t0
    check(eds.device_data is not None, "extend_block EDS is not on device")
    want = host_dah(square_array(data_square))
    check(dah.hash() == want.hash(), "proposal DAH != host DAH")
    original = np.asarray(eds.device_data)
    w = 2 * k
    rng = np.random.default_rng(seed)
    present = np.ones(w * w, dtype=bool)
    present[rng.choice(w * w, size=w * w // 4, replace=False)] = False
    present = present.reshape(w, w)
    t0 = time.perf_counter()
    fixed = repair_eds(eds, present, want.row_roots, want.column_roots)
    check(fixed.device_data is not None, "repaired EDS is not on device")
    repaired = np.asarray(fixed.device_data)
    repair_wall = time.perf_counter() - t0
    check(np.array_equal(repaired, original),
          "repaired EDS differs from the original")
    return {"app": app, "extend_wall_s": round(extend_wall, 3),
            "repair_wall_s": round(repair_wall, 3),
            "erased": int((~present).sum())}


def phase_mesh(k: int, seed: int) -> dict:
    """The two sharded spellings on all four devices, each DAH against
    the host path's."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from celestia_tpu import parallel

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, has {len(devices)}")
    from celestia_tpu import square as square_pkg

    batch = np.stack([
        square_array(square_pkg.construct(_pfb_txs(k, seed + b), 1, k))
        for b in range(4)
    ])
    wants = [host_dah(sq).hash() for sq in batch]
    out = {}

    def spans_four(arrays) -> bool:
        return all(len({s.device for s in a.addressable_shards}) == 4
                   for a in arrays)

    mesh = parallel.make_mesh(1, 4)
    fn = parallel.extend_and_root_rowsharded(mesh, k)
    t0 = time.perf_counter()
    res = fn(jax.device_put(batch[0], NamedSharding(mesh, P("sp", None, None))))
    jax.block_until_ready(res)
    out["rowsharded_wall_s"] = round(time.perf_counter() - t0, 3)
    check(spans_four(res[:1]), "row-sharded EDS is not on four devices")
    check(np.asarray(res[3]).tobytes() == wants[0],
          "row-sharded DAH != host DAH")

    mesh = parallel.make_mesh(4, 1)
    fn = parallel.sharded_extend_and_root(mesh, k)
    t0 = time.perf_counter()
    res = fn(jax.device_put(batch, NamedSharding(mesh, P("dp", "sp", None, None))))
    jax.block_until_ready(res)
    out["batched_wall_s"] = round(time.perf_counter() - t0, 3)
    check(spans_four(res[:1]), "batched EDS is not on four devices")
    got = np.asarray(res[3])
    for b in range(4):
        check(got[b].tobytes() == wants[b],
              f"batched square {b}: DAH != host DAH")
    return out


# ---------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the two mesh phases on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    say(f"device: {dev.device_kind} x{len(devices)}")

    from celestia_tpu import devledger
    from celestia_tpu import log as log_mod
    from celestia_tpu.ops import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    devledger.install_monitoring()

    def cache_counts() -> dict:
        return {"hits": counter_total("xla_compile_cache_hit_total"),
                "misses": counter_total("xla_compile_cache_miss_total")}

    events = _Events()
    logging.getLogger(log_mod._ROOT).addHandler(events)
    logging.getLogger(log_mod._ROOT).setLevel(logging.INFO)

    def run(name, fn, *a):
        t0 = time.perf_counter()
        before = cache_counts()
        try:
            res = fn(*a)
        except SmokeError as e:
            say(f"{name}: FAIL {e}")
            raise
        res["phase_wall_s"] = round(time.perf_counter() - t0, 3)
        res["cache"] = {n: v - before[n] for n, v in cache_counts().items()}
        shown = {k: v for k, v in res.items() if k != "app"}
        say(f"{name}: ok (smoke walls, not benchmark numbers) {json.dumps(shown)}")
        return res

    try:
        if args.four_chips:
            run("mesh_k128", phase_mesh, 128, args.seed)
        else:
            apps = [
                run("oracles", phase_oracles)["app"],
                run("node_k64", phase_node, 64, args.seed)["app"],
                run("repair_k128", phase_repair, 128, args.seed)["app"],
            ]
            for app in apps:
                assert_device_only(app, events)
            say("device-only: backend tpu, 0 strikes, 0 fallbacks, "
                "no seeding failures")
    except SmokeError as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    from celestia_tpu.telemetry import metrics

    say("compile cache: " + json.dumps({
        **cache_counts(),
        "by_entry": {
            key: v for key, v in metrics.counters.items()
            if key.startswith(("xla_compile_cache_hit_total",
                               "xla_compile_cache_miss_total"))},
    }))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
