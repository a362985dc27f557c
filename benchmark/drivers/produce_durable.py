"""Block production on a validator with a home directory: the produce
driver's blocks, steps and checks, on a node booted as
`cli start --home <dir> --extend-backend tpu` boots one (Node(app,
home), then a state snapshot at boot), so that every committed block's
record and its EDS (pages, DAH, row levels) are made durable on the
block path. block_ms is the window over the blocks committed in it.

correct: everything the produce driver checks, and besides, for every
block of the window, its store file and block record equal the
reference's (benchmark/reference/durable.py), a restart from the home
reaches the window's last height and app hash and serves each stored
height's DAH, no persist failed, no square was hashed on the host, and
the store synced what the config's two durability guarantees promise
(store_sync_total, counted as each produce_block returns).

The home is a fresh directory on the checkout's disk, removed when the
run closes. Set-up refuses a page-cache filesystem (fsync there is
free) and a disk without room for the run.
"""

from __future__ import annotations

import multiprocessing
import os
import shutil
import time
from concurrent.futures import ProcessPoolExecutor

from benchmark import boot, gen, harness, shapes
from benchmark.drivers import produce
from benchmark.reference import durable as reference_durable

FAULTS = ("skip_persist", "flip_page", "stale_dah", "torn_record", "no_fsync")
SYNC_KINDS = ("fsync", "dirsync")
PAGE_CACHE_FS = ("tmpfs", "ramfs")
MIN_FREE_BYTES_K128 = 8e9  # ~130 blocks of ~61 MB at k=128

step, window = produce.step, produce.window


def home_dir(cell) -> str:
    """The cell's home: beside, never inside, the traced run's output
    directory (.bench_out/<cell>), which the harness empties after
    set-up. A config may name another (tests use a temporary one)."""
    return cell.config.get("home") or os.path.join(
        harness.ROOT, ".bench_out", cell.name + ".home")


def filesystem(path: str) -> str:
    """The type of the filesystem that holds `path`, from /proc/mounts
    (the longest mount point that contains it)."""
    path = os.path.realpath(path)
    best, fstype = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            parts = line.split()
            if len(parts) < 3:
                continue
            mount = parts[1].replace("\\040", " ")
            inside = path == mount or path.startswith(mount.rstrip("/") + "/")
            if inside and len(mount) >= len(best):
                best, fstype = mount, parts[2]
    return fstype


def host_roots_counted() -> bool:
    """Whether the program counts host NMT root hashing: hash one k=1
    square on the host and look at host_nmt_roots_total."""
    from celestia_tpu import da

    before = harness.counter_sum(harness.counters(), "host_nmt_roots_total")
    da.min_data_availability_header()
    return harness.counter_sum(harness.counters(), "host_nmt_roots_total") > before


def syncs() -> tuple:
    """The store's fsyncs and directory fsyncs so far."""
    from celestia_tpu.telemetry import metrics

    return tuple(metrics.get_counter("store_sync_total", kind=kind)
                 for kind in SYNC_KINDS)


def watch_syncs(st) -> None:
    """Note the sync counts as each produce_block of this node returns,
    from here on (the window's blocks)."""
    produce_block = st.node.produce_block
    st.syncs0, st.syncs = syncs(), []

    def counted(*args, **kw):
        block = produce_block(*args, **kw)
        st.syncs.append(syncs())
        return block
    st.node.produce_block = counted


def sync_shortfall(base: tuple, per_block: list, final: tuple) -> int:
    """The syncs the two durability guarantees need and the store did
    not make. When the i-th window block's produce_block returns, i
    block records (h's, before h returns) and i-1 EDS files (h's, before
    h+1 returns) must each have had an fsync and a dirsync; by the end
    of the window, all n of both. Each count is a delta from `base`."""
    n = len(per_block)
    needs = [2 * i - 1 for i in range(1, n + 1)] + [2 * n]
    return int(sum(max(0, need - (c - b))
                   for need, counts in zip(needs, per_block + [final])
                   for c, b in zip(counts, base)))


def boot_node(config: dict, n_accounts: int, home: str):
    """benchmark/boot.boot_node's validator, with a home: Node(app,
    home), whose BlockStore is durable, and the snapshot `cli start`
    takes at boot. Returns (node, account numbers)."""
    from celestia_tpu.app import App
    from celestia_tpu.node import Node
    from celestia_tpu.x.blob.keeper import Params

    keys = gen.keys(n_accounts)
    addresses = [key.bech32_address() for key in keys]
    app = App(chain_id=gen.CHAIN_ID, app_version=config["app_version"],
              extend_backend=config["extend_backend"])
    app.init_chain({a: 10**18 for a in addresses}, genesis_time=boot.GENESIS_TIME,
                   genesis_validators={addresses[0]: 10**11})
    app.blob.set_params(Params(gov_max_square_size=config["gov_max_square_size"]))
    app.store.commit()
    node = Node(app, home=home)
    node.produce_block(block_time=boot.block_time(node, config))  # empty
    app.enable_blob_pool(config["blob_arena_bytes"])
    live = node.boot_extend_backend()
    node.save_snapshot()
    cache = node._eds_cache
    booted = {"extend_backend": live, "extend_blocks": node.extend_blocks,
              "eds_cache": {"max_heights": cache.max_heights,
                            "device_byte_budget": cache.device_byte_budget,
                            "rows_per_page": cache.rows_per_page},
              "store_durable": node.store is not None and node.store.durable}
    for key, value in booted.items():
        if value != config[key]:  # the config file states what is run
            raise RuntimeError(f"booted {key}={value!r}, config says {config[key]!r}")
    numbers = [app.accounts.get_account(a).account_number for a in addresses]
    return node, numbers


def setup(cell, seed: int, fault: str | None):
    from celestia_tpu import tracing

    cfg, mix = cell.config, cell.mix
    if not host_roots_counted():
        raise RuntimeError("the program does not count host NMT root hashing "
                           "(host_nmt_roots_total): host_roots cannot be checked")
    st = produce.State()
    st.k = cfg["square_size"]
    st.config = cfg
    st.seed = seed
    st.fault = fault
    st.home = home_dir(cell)
    shutil.rmtree(st.home, ignore_errors=True)
    os.makedirs(st.home)
    st.fs = filesystem(st.home)
    if st.fs in PAGE_CACHE_FS:
        raise RuntimeError(f"the home {st.home} is on {st.fs}: fsync there is free")
    need = MIN_FREE_BYTES_K128 * (st.k / 128) ** 2
    free = shutil.disk_usage(st.home).free
    if free < need:
        raise RuntimeError(f"{free} bytes free under {st.home}, the run needs {need:.0f}")
    st.fallbacks = harness.FallbackLog()
    st.node, numbers = boot_node(cfg, mix["accounts"], st.home)
    st.stream = boot.TxStream(mix, st.k, seed, numbers)
    tracing.enable()  # flight recorder as `cli start` runs it
    t0 = time.perf_counter()
    planned = gen.cycle(mix, st.k)
    n_prop = shapes.warm_proposal(st.node.app, st.k, planned)
    n_pads = shapes.warm_arena(planned)
    st.blocks = []
    for _ in range(mix["warm_blocks"]):
        step(st)  # also warms the row-levels program and the persist
    st.blocks = []
    harness.say(f"set-up: home on {st.fs}, {free} bytes free; {n_prop} proposal "
                f"shapes, {n_pads} arena slot sizes, {mix['warm_blocks']} warm "
                f"blocks {time.perf_counter() - t0:.3f} s")
    if fault:
        plant(st, fault)
    watch_syncs(st)
    return st


def collect(st, run):
    """The produce driver's evidence, then, after the window, what only
    the home can tell: each window height's store file and block record
    against the reference (worker processes that never import JAX), and
    a restart from the home. Both read files close() removes."""
    ev = produce.collect(st, run)
    ev["sync_shortfall"] = sync_shortfall(st.syncs0, st.syncs, syncs())
    blocks = ev["blocks"]
    if blocks:
        paths = [os.path.join(st.home, d, f"{b['height']}{ext}") for b in blocks
                 for d, ext in (("store", ".ctps"), ("blocks", ".json"))]
        written = sum(os.path.getsize(p) for p in paths if os.path.exists(p))
        harness.say(f"home on {st.fs}: {written / len(blocks):.0f} bytes written "
                    "a block (store file and block record)")
    t0 = time.perf_counter()
    results = []
    if blocks:
        workers = min(8, os.cpu_count() or 1, len(blocks))
        with ProcessPoolExecutor(workers, mp_context=multiprocessing.get_context("spawn")) as pool:
            results = list(pool.map(reference_durable.check_height,
                                    [st.home] * len(blocks),
                                    [b["height"] for b in blocks],
                                    [b["block_txs"] for b in blocks],
                                    [b["data_hash"] for b in blocks],
                                    [st.k] * len(blocks)))
    errors = [r["error"] for r in results if r["error"]]
    if errors:
        harness.say(f"durable reference: {len(errors)} heights differ: {errors[0]}")
    ev["stored"] = results
    harness.say(f"durable reference: {len(results)} heights, "
                f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    ev["restart_mismatch"] = restart_mismatch(st, blocks)
    harness.say(f"restart from the home: {time.perf_counter() - t0:.3f} s")
    return ev


def restart_mismatch(st, blocks) -> int:
    """A node loaded from the home (Node.load: snapshot, replay of the
    stored blocks) must stand at the window's last height and app hash,
    and serve for every stored height a DAH that hashes to the block's
    data hash. Returns the count of what it gets wrong."""
    from celestia_tpu.node import Node

    if not blocks:
        return 0
    try:
        node = Node.load(st.home, extend_backend=st.config["extend_backend"])
    except Exception as e:  # noqa: BLE001 — a failed restart is the finding
        harness.say(f"restart failed: {type(e).__name__}: {e}")
        return 1
    last = st.node.blocks[blocks[-1]["height"]]
    wrong = int(node.app.height != last.height
                or node.app.store.app_hashes[node.app.store.version] != last.app_hash)
    for h in node.store.heights():
        dah = node.block_dah(h)
        wrong += int(dah is None or dah.hash() != node.blocks[h].data_hash)
    wrong += sum(b["height"] not in node.store for b in blocks)
    return wrong


def close(st) -> None:
    produce.close(st)
    shutil.rmtree(st.home, ignore_errors=True)


def check(ev, run) -> list:
    stored = ev["stored"]
    return produce.check(ev, run) + [
        ("stored_mismatch", sum(not r["store_ok"] for r in stored), 0),
        ("block_record_mismatch", sum(not r["record_ok"] for r in stored), 0),
        ("restart_mismatch", ev["restart_mismatch"], 0),
        ("persist_failures", run.counter_delta("eds_persist_failures_total"), 0),
        ("host_roots", run.counter_delta("host_nmt_roots_total"), 0),
        ("sync_shortfall", ev["sync_shortfall"], 0),
    ]


# ------------------------------------------------------------------ faults


def plant(st, fault: str) -> None:
    """Break what the home keeps, on this node only (control runs and
    tests): the first window height's persist skipped, a stored page
    byte flipped after its CRC, the previous height's DAH stored, a
    block record torn after it was acknowledged, or every fsync of the
    store made a no-op (the bytes are acknowledged before they reach
    the disk)."""
    from celestia_tpu import store as store_pkg
    from celestia_tpu.node import Node

    node = st.node
    first = node.app.height + 1
    if fault == "skip_persist":
        def persist(height, eds):
            if height != first:
                Node._persist_block_eds(node, height, eds)
        node._persist_block_eds = persist
    elif fault == "flip_page":
        def put_eds(height, *args, **kw):
            entry = store_pkg.BlockStore.put_eds(node.store, height, *args, **kw)
            if height == first:
                with open(entry.path, "r+b") as f:
                    f.seek(entry.page_offset(0) + store_pkg.RECORD_HEADER_SIZE + 100)
                    byte = f.read(1)
                    f.seek(-1, os.SEEK_CUR)
                    f.write(bytes([byte[0] ^ 0x01]))
            return entry
        node.store.put_eds = put_eds
    elif fault == "stale_dah":
        def block_dah(height):
            return Node.block_dah(node, height - 1 if height == first else height)
        node.block_dah = block_dah
    elif fault == "torn_record":
        def store_block(block):
            Node._store_block(node, block)
            if block.height == first:
                path = node.home / "blocks" / f"{block.height}.json"
                raw = path.read_bytes()
                path.write_bytes(raw[: len(raw) // 2])
        node._store_block = store_block
    elif fault == "no_fsync":
        class NoFsync(store_pkg.FsShim):
            def fsync(self, f, *, path, **ctx) -> None:
                pass
        node.store._fs = NoFsync()
    else:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
