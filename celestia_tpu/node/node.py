"""Node: mempool + block production + block store."""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import json
import pathlib
import threading
import time

from celestia_tpu import tracing
from celestia_tpu.app import App
from celestia_tpu.app.app import ProposalBlockData, TxResult
from celestia_tpu.log import logger
from celestia_tpu.node.eds_cache import PagedEdsCache

log = logger("node")

MEMPOOL_TTL_BLOCKS = 5  # ref: app/default_overrides.go:237-245 (v1 mempool TTL)
DEFAULT_MAX_TX_BYTES = 7_897_088  # max-square bytes, DefaultConsensusConfig


def tx_hash(raw: bytes) -> bytes:
    return hashlib.sha256(raw).digest()


@dataclasses.dataclass
class MempoolTx:
    raw: bytes
    priority: int
    height_added: int


class Mempool:
    """Priority-ordered mempool with block-TTL eviction (the capability
    surface of celestia-core's v1 prioritized mempool / CAT pool specs,
    specs/src/specs/cat_pool.md)."""

    def __init__(self, ttl_blocks: int = MEMPOOL_TTL_BLOCKS,
                 max_tx_bytes: int = DEFAULT_MAX_TX_BYTES):
        self.txs: dict[bytes, MempoolTx] = {}
        self.ttl_blocks = ttl_blocks
        self.max_tx_bytes = max_tx_bytes
        # every key this pool has ever admitted (height-bounded): the
        # CAT want/have answer — a peer offering a tx we hold OR already
        # processed gets "don't send" instead of the raw bytes
        # (specs/src/specs/cat_pool.md's SeenTx role)
        self._seen: dict[bytes, int] = {}

    def add(self, raw: bytes, priority: int, height: int) -> bytes:
        if len(raw) > self.max_tx_bytes:
            raise ValueError(f"tx exceeds max size {self.max_tx_bytes}")
        key = tx_hash(raw)
        if key not in self.txs:
            self.txs[key] = MempoolTx(raw=raw, priority=priority, height_added=height)
        self._seen[key] = height
        return key

    def remove(self, key: bytes) -> None:
        self.txs.pop(key, None)

    def has_seen(self, key: bytes) -> bool:
        """True when this pool holds or recently processed the tx — the
        want/have reply (want = NOT seen)."""
        return key in self.txs or key in self._seen

    def reap(self, max_bytes: int | None = None) -> list[bytes]:
        """Highest-priority txs first (stable within equal priority)."""
        ordered = sorted(
            self.txs.values(), key=lambda t: (-t.priority, t.height_added)
        )
        out: list[bytes] = []
        total = 0
        for t in ordered:
            if max_bytes is not None and total + len(t.raw) > max_bytes:
                continue
            out.append(t.raw)
            total += len(t.raw)
        return out

    def evict_expired(self, height: int) -> int:
        expired = [
            k for k, t in self.txs.items()
            if height - t.height_added >= self.ttl_blocks
        ]
        for k in expired:
            del self.txs[k]
            # a TTL-expired tx was never committed — forgetting it from
            # _seen lets a legitimate resubmission re-propagate through
            # the CAT want/have handshake instead of being refused by
            # every peer that saw the first attempt
            self._seen.pop(k, None)
        # seen records outlive the pool entry by one extra TTL window so
        # late duplicate offers are still deduplicated, then age out
        # (bounded memory in a long-running node)
        stale = [
            k for k, h in self._seen.items()
            if height - h >= 2 * self.ttl_blocks
        ]
        for k in stale:
            del self._seen[k]
        return len(expired)

    def __len__(self) -> int:
        return len(self.txs)


@dataclasses.dataclass
class Block:
    height: int
    time: float
    txs: list[bytes]
    square_size: int
    data_hash: bytes
    app_hash: bytes
    tx_results: list[TxResult] = dataclasses.field(default_factory=list)
    # slashing.Equivocation entries delivered with this block (ABCI
    # ByzantineValidators analogue). Evidence is state-affecting —
    # BeginBlock slashes/tombstones from it — so the block store MUST
    # carry it or crash-recovery replay recomputes a different app hash
    # (the reference's blocks persist ByzantineValidators the same way).
    evidence: list = dataclasses.field(default_factory=list)
    # app version the square was BUILT at (the reference's header
    # carries Version.App): reconstructing a historical square after an
    # upgrade must use the block's own rules. None = stored before this
    # field existed — reconstruct at current rules.
    version: int | None = None

    def to_json(self) -> dict:
        return {
            "height": self.height,
            "time": self.time,
            "txs": [t.hex() for t in self.txs],
            "square_size": self.square_size,
            "data_hash": self.data_hash.hex(),
            "app_hash": self.app_hash.hex(),
            "version": self.version,
            "tx_results": [
                {"code": r.code, "log": r.log, "gas_used": r.gas_used}
                for r in self.tx_results
            ],
            "evidence": [
                {"validator": e.validator, "height": e.height,
                 "power": e.power}
                for e in self.evidence
            ],
        }

    @classmethod
    def from_json(cls, d: dict) -> "Block":
        from celestia_tpu.x.slashing import Equivocation

        return cls(
            height=d["height"],
            time=d["time"],
            txs=[bytes.fromhex(t) for t in d["txs"]],
            square_size=d["square_size"],
            data_hash=bytes.fromhex(d["data_hash"]),
            app_hash=bytes.fromhex(d["app_hash"]),
            tx_results=[
                TxResult(code=r["code"], log=r["log"], gas_used=r["gas_used"])
                for r in d.get("tx_results", [])
            ],
            version=d.get("version"),
            evidence=[
                Equivocation(validator=e["validator"], height=e["height"],
                             power=e.get("power", 0))
                for e in d.get("evidence", [])
            ],
        )


class Node:
    """One-validator chain driver over an App."""

    def __init__(self, app: App, home: str | None = None,
                 extend_blocks: bool = False):
        self.app = app
        # ExtendBlock retention (ref: app/extend_block.go:14 — the
        # reference recomputes the EDS post-consensus for storage): when
        # on, each committed block's extended square HANDLE goes into
        # the serving cache. On the TPU backend that handle is
        # device-resident and lazy — share-serving routes then fetch
        # SLICES (one row per DAS sample) instead of reconstructing or
        # materializing the 32 MB square host-side.
        self.extend_blocks = extend_blocks
        self.mempool = Mempool()
        self.blocks: dict[int, Block] = {}
        self.tx_index: dict[bytes, tuple[int, int]] = {}  # hash -> (height, idx)
        # verified Bad Encoding Fraud Proofs: height -> dah_hash_hex ->
        # wire JSON ({"height", "dah": {row_roots, column_roots},
        # "proof"}), served on /fraud/befp/<height> so light clients can
        # reject the header without downloading the square
        # (specs/fraud_proofs.md role). Keyed by the DAH hash — dedup by
        # height alone would let an attacker SQUAT a height with a
        # self-made proof of some unrelated bad square and suppress the
        # real one. Capped per height against spam.
        self.fraud_proofs: dict[int, dict[str, dict]] = {}
        # O(1) "is this data hash proven fraudulent" for the consensus
        # hot path (validators refuse to endorse these)
        self.fraudulent_data_hashes: set[bytes] = set()
        # reconstruction memo for the share-serving routes: committed
        # blocks are immutable, so /dah answers come from a tiny
        # per-height cache and /eds from the PAGED device cache
        # (ADR-017): retained squares are split into row-group pages
        # under a device-byte budget — hot pages stay resident, cold
        # pages demote to checksummed host copies and fault back in on
        # access, and per-page pins keep eviction out of in-flight reads
        self._dah_cache: dict[int, object] = {}
        self.home = pathlib.Path(home) if home else None
        if self.home:
            (self.home / "blocks").mkdir(parents=True, exist_ok=True)
        # durable third tier (ADR-021): home-backed nodes persist
        # retained squares (pages + DAH + row-tree levels) to a
        # CRC-guarded BlockStore under home/store, re-indexed on
        # startup so a restarted node serves deep history from disk
        self.store = None
        if self.home:
            try:
                from celestia_tpu.store import BlockStore

                self.store = BlockStore(self.home / "store")
                self.store.reindex()
            except Exception as e:  # noqa: BLE001 — store is best-effort
                log.info("block store unavailable", error=str(e))
                self.store = None
        self._eds_cache = PagedEdsCache(store=self.store)
        # per-height NMT row-prover memo for the batched sample path
        # (ADR-019): device-resident squares seed every row's subtree
        # memo from ONE device reduce (`extend_tpu.eds_row_levels_device`
        # → `NmtRowProver.from_node_levels`, zero host hashing); host
        # squares fall back to hash-once host provers that still persist
        # across batches. Entry: (levels | None, {row: prover}).
        self._prover_cache: dict[int, tuple] = {}
        self._PROVER_CACHE_HEIGHTS = 4
        # The RPC server calls in from handler threads
        # (ThreadingHTTPServer) while the node thread produces blocks.
        # State-mutating entries (CheckTx speculation, the block pipeline)
        # serialize on this lock; read-only queries go lock-free (dict
        # reads are atomic, committed-store writes only happen under the
        # lock at Commit, and state proofs pair root+proof under the
        # store's own SMT lock).
        self._lock = threading.RLock()
        # observability attachments: /status uptime anchor, the lazily
        # built SLO engine (slo.engine_for), and the optional synthetic
        # DAS prober (cli --probe-interval)
        self.started_at = time.monotonic()
        self.slo = None
        self.prober = None
        # the device dispatcher (node/dispatch.py), attached by the
        # RpcServer that serves this node; None when embedded
        self.dispatcher = None

    MAX_FRAUD_PROOFS_PER_HEIGHT = 4
    # heights whose retention-computed DAH stays in the /dah memo
    _DAH_SEED_HEIGHTS = 64

    def boot_extend_backend(self) -> str:
        """Resolve the extend backend at the governance square cap and,
        when it is the device, turn on what a device node serves with:
        the blob arena (mempool blob bytes stage in HBM at CheckTx, so
        proposals assemble squares on device) and device-resident EDS
        retention (a DAS sample moves one row, not 32 MB). Returns the
        live backend. `cli start` boots through here."""
        live = self.app.resolve_extend_backend(
            self.app.gov_square_size_upper_bound())
        if live == "tpu":
            self.app.enable_blob_pool()
            self.extend_blocks = True
        return live

    def add_fraud_proof(self, height: int, dah_hash: bytes, wire: dict,
                        force: bool = False) -> bool:
        """Store a VERIFIED fraud proof. Returns False when already
        known or the per-height cap is hit (spam bound).

        force: the caller has bound dah_hash to a commit certificate or
        a committed block — the proof of record for the height. It
        bypasses (and if needed evicts a decoy from) the cap: an
        attacker pre-filling the height with valid proofs of unrelated
        junk squares must not be able to suppress it. Forced entries
        are bounded by the number of certified hashes per height, not
        attacker effort."""
        # RPC handler threads gossip concurrently while readers list
        # the height's proofs — same locking contract as every other
        # cross-thread Node mutation
        with self._lock:
            at_height = self.fraud_proofs.setdefault(height, {})
            key = dah_hash.hex()
            if key in at_height:
                return False
            if len(at_height) >= self.MAX_FRAUD_PROOFS_PER_HEIGHT:
                if not force:
                    return False
                # evict an unforced decoy to make room
                for k in list(at_height):
                    if not at_height[k].get("_certified"):
                        del at_height[k]
                        break
            # _certified is LOCAL provenance: never trust it from a
            # gossiped wire (an attacker would mark decoys eviction-
            # proof), always restamp from the caller's own verification
            wire = {k: v for k, v in wire.items() if k != "_certified"}
            if force:
                wire["_certified"] = True
            at_height[key] = wire
            self.fraudulent_data_hashes.add(dah_hash)
            return True

    def fraud_proofs_at(self, height: int) -> list[dict]:
        """Snapshot of the height's stored proofs (the /fraud/befp
        serving read) — copied under the lock so a concurrent gossip
        insert/eviction can never break the iteration. The local
        `_certified` provenance marker never goes on the wire (two
        towers serving the same proof must serve identical bytes)."""
        with self._lock:
            return [
                {k: v for k, v in wire.items() if k != "_certified"}
                for wire in self.fraud_proofs.get(height, {}).values()
            ]

    # --- mempool admission ---

    def broadcast_tx(self, raw: bytes) -> TxResult:
        with tracing.span("node.broadcast_tx") as sp:
            res = self._broadcast_tx_traced(raw, sp)
            sp.set(code=res.code, bytes=len(raw))
            return res

    def _broadcast_tx_traced(self, raw: bytes, sp) -> TxResult:
        with self._lock:
            res = self.app.check_tx(raw)
            if res.code == 0:
                self.mempool.add(raw, res.priority, self.app.height)
        if res.code == 0 and self.app.blob_pool is not None:
            # stage blob bytes in the device arena at ADMISSION time —
            # off the consensus hot path — so the proposal can assemble
            # the square on device without re-uploading them
            # (ops/blob_pool.py; every miss falls back safely)
            from celestia_tpu import blob as blob_pkg

            btx, is_blob = blob_pkg.unmarshal_blob_tx(raw)
            if is_blob:
                sp.set(blobs=len(btx.blobs))
                try:
                    # put_many dispatches every blob's upload before the
                    # arena inserts — the DMAs overlap instead of
                    # serializing per blob (ops/blob_pool.py). The
                    # uploads are device work, so when a device
                    # dispatcher is attached (RpcServer) they run on its
                    # thread — CheckTx admission itself stays on the
                    # request thread (specs/serving.md).
                    blob_bytes = [b.data for b in btx.blobs]
                    dispatcher = getattr(self, "dispatcher", None)
                    if dispatcher is not None:
                        dispatcher.run_device(
                            lambda: self.app.blob_pool.put_many(blob_bytes)
                        )
                    else:
                        self.app.blob_pool.put_many(blob_bytes)
                except Exception as e:  # noqa: BLE001 — cache only
                    log.info("blob staging failed", error=str(e))
        return res

    # --- block production (the proposer+validator round) ---

    def produce_block(self, block_time: float | None = None) -> Block:
        with self._lock:
            # lint: allow(C002,C003) reason=block application is atomic under the node RLock by design: the extend/commit runs inside the apply window so readers never see a half-applied height (same tradeoff the C005 baseline documents)
            return self._produce_block_locked(block_time)

    def _produce_block_locked(self, block_time: float | None) -> Block:
        block_time = block_time if block_time is not None else time.time()
        proposal = self.app.prepare_proposal(self.mempool.reap())
        return self._apply_block_locked(proposal, block_time, own=True)

    def apply_external_block(self, txs: list[bytes], square_size: int,
                             data_hash: bytes, block_time: float,
                             expected_height: int | None = None,
                             evidence: list | None = None) -> Block:
        """Apply a block decided elsewhere (a devnet peer's committed
        proposal): full ProcessProposal validation, then the normal
        deliver/commit pipeline. The caller (node/devnet.py) has already
        verified the commit certificate; `expected_height` re-binds the
        block to the height that certificate covers UNDER the node lock,
        so two concurrent commit deliveries can never stack (the second
        would otherwise land at height+1 with a cert for height)."""
        from celestia_tpu.app.app import ProposalBlockData

        with self._lock:
            if (
                expected_height is not None
                and self.app.height + 1 != expected_height
            ):
                raise ValueError(
                    f"block certified for height {expected_height}, node "
                    f"is at {self.app.height}"
                )
            proposal = ProposalBlockData(
                txs=list(txs), square_size=square_size, hash=data_hash
            )
            # lint: allow(C002,C003) reason=external block application is atomic under the node RLock by design (two concurrent commit deliveries must not stack); the extend runs inside the apply window
            return self._apply_block_locked(
                proposal, block_time, own=False, evidence=evidence
            )

    def _apply_block_locked(self, proposal, block_time: float,
                            own: bool, evidence: list | None = None) -> Block:
        with tracing.span("node.apply_block", height=self.app.height + 1,
                          txs=len(proposal.txs),
                          square_size=proposal.square_size):
            return self._apply_block_traced(
                proposal, block_time, own, evidence
            )

    def _apply_block_traced(self, proposal, block_time: float,
                            own: bool, evidence: list | None = None) -> Block:
        t0 = time.perf_counter()
        if not self.app.process_proposal(proposal):
            if own:
                log.error("own proposal rejected", height=self.app.height + 1)
                raise RuntimeError("node produced a proposal it cannot accept")
            raise ValueError(
                f"proposal for height {self.app.height + 1} fails "
                "ProcessProposal"
            )

        # the square was built/validated under the PRE-commit version
        # (commit may adopt a pending upgrade) — record that one
        build_version = self.app.app_version
        self.app.begin_block(block_time, evidence=evidence)
        results = [self.app.deliver_tx(t) for t in proposal.txs]
        self.app.end_block()
        app_hash = self.app.commit()
        log.info(
            "committed block",
            height=self.app.height,
            txs=len(proposal.txs),
            failed_txs=sum(1 for r in results if r.code != 0),
            square_size=proposal.square_size,
            data_hash=proposal.hash,
            app_hash=app_hash,
            elapsed_ms=round((time.perf_counter() - t0) * 1e3, 3),
        )

        block = Block(
            height=self.app.height,
            time=block_time,
            txs=proposal.txs,
            square_size=proposal.square_size,
            data_hash=proposal.hash,
            app_hash=app_hash,
            tx_results=results,
            evidence=list(evidence or []),
            version=build_version,
        )
        self.blocks[block.height] = block
        for i, raw in enumerate(proposal.txs):
            key = tx_hash(raw)
            self.mempool.remove(key)
            self.tx_index[key] = (block.height, i)
        self.mempool.evict_expired(self.app.height)
        # (skip retention across an upgrade boundary: extend_block runs
        # at the POST-commit version, the square was built at the
        # pre-commit one — block_eds's versioned reconstruction governs)
        retain = self.extend_blocks and build_version == self.app.app_version
        eds = self._retain_block_eds(block) if retain else None
        if self.home:
            with tracing.span("node.persist", height=block.height):
                self._store_block(block)
                if eds is not None:
                    self._persist_block_eds(block.height, eds)
                elif retain:  # the height's EDS file is not written
                    self._persist_failed(block.height, "retention")
        return block

    def _retain_block_eds(self, block: Block):
        """ExtendBlock retention: keep the committed square's EDS handle
        (device-resident + lazy on the TPU backend) so the serving
        routes answer DAS samples with SLICED reads instead of a
        pure-host re-extension, and keep the DAH the same extend
        computed, so neither the store nor /dah hashes the square again
        on the host. Returns the EDS, or None when retention failed:
        it is a cache, and the block falls back to block_eds
        reconstruction. A square whose roots disagree with the
        committed data_hash is an IntegrityError, counted in
        retention_dah_mismatch_total, and is neither retained nor
        stored."""
        from celestia_tpu.integrity import IntegrityError
        from celestia_tpu.telemetry import metrics

        try:
            with tracing.span("node.extend_retention", height=block.height):
                eds, dah = self.app.extend_block(block.txs)
                if dah.hash() != block.data_hash:
                    metrics.incr_counter("retention_dah_mismatch_total")
                    raise IntegrityError(
                        f"retention extend of height {block.height} hashes "
                        f"to {dah.hash().hex()}, the block commits to "
                        f"{block.data_hash.hex()}")
                self._eds_cache.put(block.height, eds)
        except Exception as e:  # noqa: BLE001 — retention is a cache
            log.warn("eds retention failed",
                     error=f"{type(e).__name__}: {e}")
            return None
        self._dah_cache[block.height] = dah
        # bounded: seeded heights age out (block_dah then reads the
        # store's copy, or recomputes)
        self._dah_cache.pop(block.height - self._DAH_SEED_HEIGHTS, None)
        return eds

    def _store_block(self, block: Block) -> None:
        """The block record under home/blocks, written durable (tmp,
        fsync, rename, directory fsync through the store's FsShim)
        before produce_block returns: Node.load replays these files, so
        an acknowledged height survives a power cut. Failures raise."""
        from celestia_tpu import store as store_pkg

        with tracing.span("node.persist.block_record",
                          height=block.height) as sp:
            raw = json.dumps(block.to_json()).encode()
            path = self.home / "blocks" / f"{block.height}.json"
            store = self.store
            store_pkg.write_durable(
                path, raw, height=block.height,
                fs=store_pkg._FS if store is None else store._fs,
                durable=True if store is None else store.durable)
            sp.set(bytes=len(raw))

    def _persist_block_eds(self, height: int, eds) -> None:
        """Durable retention: write the committed square's pages, its
        DAH (the one retention seeded) and, when the square is
        device-resident, its device row-tree levels to the BlockStore,
        so a restart serves this height from disk with byte-identical
        DAH and provers. Best effort — a failed put degrades to
        reconstruction and never fails the block — but every failure is
        counted in eds_persist_failures_total{reason} and logged."""
        if self.store is None:
            self._persist_failed(height, "no_store")
            return
        import numpy as np

        dah = self.block_dah(height)
        if dah is None:
            self._persist_failed(height, "no_dah")
            return
        try:
            with tracing.span("node.persist.fetch", height=height):
                levels = None
                if eds.device_data is not None:
                    from celestia_tpu.ops import extend_tpu

                    try:
                        levels = extend_tpu.eds_row_levels_device(
                            eds.device_data)
                    except Exception as e:  # noqa: BLE001 — stored without
                        self._persist_failed(height, "levels", e)
                data = np.asarray(eds.data)
        except Exception as e:  # noqa: BLE001 — persistence is a cache
            self._persist_failed(height, "fetch", e)
            return
        rpp = getattr(self._eds_cache, "rows_per_page", None) or 8
        try:
            entry = self.store.put_eds(height, data, eds.original_width,
                                       dah_doc=dah.to_json(),
                                       levels=levels, rows_per_page=rpp)
        except Exception as e:  # noqa: BLE001 — persistence is a cache
            self._persist_failed(height, "write", e)
            return
        if entry is None:
            self._persist_failed(height, "read_only")

    @staticmethod
    def _persist_failed(height: int, reason: str,
                        error: Exception | None = None) -> None:
        from celestia_tpu.telemetry import metrics

        metrics.incr_counter("eds_persist_failures_total", reason=reason)
        log.warn("eds persistence failed", height=height, reason=reason,
                 error=f"{type(error).__name__}: {error}" if error else None)

    # --- the multi-chip block pipeline (specs/parallel.md) ---

    def extend_pipeline(self, k: int, depth: int = 3):
        """A 3-deep H2D/compute/D2H block pipeline bound to this node
        (node/pipeline.py): feed consecutive (height, shares) squares —
        block replay, proposal bursts, catching-up streams — and each
        retired block lands exactly where the inline retention path
        puts it (paged serving cache, prover memo seeded from the
        device level stack, DAH memo, durable store), with the three
        legs of CONSECUTIVE blocks overlapped instead of serialized.
        Device work rides the attached dispatcher's internal lane, so
        the single-stream-owner rule (ADR-016) holds under load."""
        from celestia_tpu.node.pipeline import BlockPipeline

        def adopt(block):
            with self._lock:
                self._adopt_pipelined_block(block)

        return BlockPipeline(k, dispatcher=self.dispatcher, depth=depth,
                             on_block=adopt)

    def _adopt_pipelined_block(self, block) -> None:
        """Install one retired PipelinedBlock into the node's serving
        state — the pipeline's equivalent of extend-retention plus
        `_persist_block_eds`, sourced from the already-fetched outputs
        (no recompute, no second device pass). Called under `_lock`."""
        from celestia_tpu import da

        dah = da.DataAvailabilityHeader(
            [r.tobytes() for r in block.row_roots],
            [c.tobytes() for c in block.col_roots],
        )
        # lint: allow(C005) reason=_dah_cache is a lock-free memo of immutable DAHs: only single-key get/set/pop (atomic under the GIL), never iterated, so a reader outside the node lock sees a whole DAH or none
        self._dah_cache[block.height] = dah
        if block.eds is not None:
            try:
                self._eds_cache.put(block.height, block.eds)
            except Exception as e:  # noqa: BLE001 — retention is a cache
                log.info("pipelined eds retention failed",
                         height=block.height, error=str(e))
        if block.levels is not None:
            while len(self._prover_cache) >= self._PROVER_CACHE_HEIGHTS:
                self._prover_cache.pop(next(iter(self._prover_cache)))
            self._prover_cache[block.height] = (block.levels, {})
        if self.store is not None and block.eds is not None:
            try:
                rpp = getattr(self._eds_cache, "rows_per_page", None) or 8
                self.store.put_eds(
                    block.height, block.eds, block.eds.shape[0] // 2,
                    dah_doc=dah.to_json(), levels=block.levels,
                    rows_per_page=rpp)
            except Exception as e:  # noqa: BLE001 — persistence is a cache
                log.info("pipelined eds persistence failed",
                         height=block.height, error=str(e))

    # --- queries ---

    def status(self) -> dict:
        """Same shape as the RPC /status route — Node and RpcClient share
        the Signer transport surface."""
        return {
            "chain_id": self.app.chain_id,
            "height": self.latest_height(),
            "app_version": self.app.app_version,
            "mempool_size": len(self.mempool),
        }

    def account(self, address: str) -> dict | None:
        """Same shape as the RPC /account route."""
        acc = self.app.accounts.get_account(address)
        if acc is None:
            return None
        return {
            "address": acc.address,
            "account_number": acc.account_number,
            "sequence": acc.sequence,
            "balance": self.app.bank.get_balance(acc.address),
        }

    def get_block(self, height: int) -> Block | None:
        return self.blocks.get(height)

    def get_tx(self, key: bytes):
        """Returns (block, tx_index) or None."""
        loc = self.tx_index.get(key)
        if loc is None:
            return None
        return self.blocks[loc[0]], loc[1]

    def latest_height(self) -> int:
        return self.app.height

    def block_eds(self, height: int):
        """The (2w, 2w, 512) extended square of a committed block — the
        share-serving source for peers and fraud investigation. A
        MaliciousApp that committed a corrupted extension serves THAT
        square (its `published_eds`): under the DA assumption the data
        is available, the encoding is what's fraudulent.

        Returns either a host numpy array (reconstruction path) or a
        da.ExtendedDataSquare handle (published / ExtendBlock-retained
        squares — possibly device-resident and lazy). Serving routes
        should go through block_width/block_row/block_share, which
        normalize both and keep device-resident squares SLICED (one row
        per DAS sample crosses the interconnect, never the full EDS)."""
        published = getattr(self.app, "published_eds", None)
        if published and height in published:
            return published[height]
        cached = self._eds_cache.get(height)  # cache holds its own lock
        if cached is not None:
            return cached
        if (self.store is not None and height in self.store
                and hasattr(self._eds_cache, "load_from_store")):
            # restart path: adopt the persisted height page-by-page —
            # every page starts on disk and faults in on first read
            try:
                return self._eds_cache.load_from_store(height)
            except Exception as e:  # noqa: BLE001 — fall back to rebuild
                log.info("store load failed; reconstructing",
                         height=height, error=str(e))
        block = self.blocks.get(height)
        if block is None:
            return None
        # pure host reconstruction (NOT app.extend_block): this runs on
        # RPC handler threads, so it must not touch the app's device/
        # native backend state. The block's own build version governs
        # the layout rules — a post-upgrade node must still reproduce
        # pre-upgrade squares byte-exactly.
        from celestia_tpu import appconsts, da, square as square_pkg
        from celestia_tpu.shares import to_bytes

        v = block.version if block.version is not None else self.app.app_version
        sq = square_pkg.construct(
            block.txs, v, appconsts.square_size_upper_bound(v)
        )
        eds = da.extend_shares(to_bytes(sq)).data
        self._eds_cache.put(height, eds)
        return eds

    @contextlib.contextmanager
    def _borrow_eds(self, height: int):
        """Pin-guarded access to a block's EDS for sliced serving reads
        (/sample, /proof/share). While the context is open, the LRU
        cannot evict the borrowed square — the regression the plain
        OrderedDict allowed. Published squares (MaliciousApp) keep their
        precedence and are never evicted; a cache miss falls back to
        block_eds reconstruction (the returned object is then held by
        this frame, so it outlives the read regardless of the cache)."""
        published = getattr(self.app, "published_eds", None)
        if published and height in published:
            yield published[height]
            return
        with self._eds_cache.pinned(height) as pinned:
            if pinned is not None:
                yield pinned
                return
        yield self.block_eds(height)

    def block_width(self, height: int) -> int | None:
        """Extended-square width of a committed block, source-agnostic
        (numpy array or ExtendedDataSquare handle — no byte fetch)."""
        with self._borrow_eds(height) as eds:
            if eds is None:
                return None
            if hasattr(eds, "original_width"):
                return eds.width
            return int(eds.shape[0])

    def block_row(self, height: int, i: int) -> list[bytes] | None:
        """Row i of a block's extended square as share bytes — THE DAS
        serving read (/sample builds the row NMT proof from it). When
        the square is a device-resident handle only this row's w·512
        bytes cross the interconnect (ExtendedDataSquare.row sliced
        path); host sources slice in memory. Byte-identical either way.
        The borrow pins the cache entry for the read's whole duration."""
        with self._borrow_eds(height) as eds:
            if eds is None:
                return None
            if hasattr(eds, "original_width"):
                return eds.row(i)
            return [bytes(eds[i, c]) for c in range(eds.shape[0])]

    def block_share(self, height: int, r: int, c: int) -> bytes | None:
        """One cell of a block's extended square (512 bytes moved for a
        device-resident square, not 32 MB)."""
        with self._borrow_eds(height) as eds:
            if eds is None:
                return None
            if hasattr(eds, "original_width"):
                return eds.share(r, c)
            return bytes(eds[r, c])

    def sample_batch(self, height: int, coords) -> list:
        """Answer a micro-batch of DAS samples against ONE height — the
        `batch_exec` target of the continuous-batching dispatcher lane
        (ADR-017). Distinct rows are fetched as one vmapped sliced read
        (`rows_batch`) and each row's NMT leaf layer is hashed once
        (proof.NmtRowProver), so b samples over r distinct rows cost
        O(r·w) hashes instead of O(b·w); every returned document is
        byte-identical to the unbatched `/sample` route (pinned in
        tests). Returns one entry per coordinate, aligned: a response
        doc, the "range" sentinel, or None when the block is unknown.

        A paged-cache page whose fault-in checksum fails (IntegrityError)
        heals once: the height is invalidated — the cache is a cache —
        and the batch re-answers from reconstruction."""
        from celestia_tpu import integrity

        try:
            return self._sample_batch(height, coords)
        except integrity.IntegrityError:
            if not hasattr(self._eds_cache, "invalidate"):
                raise
            log.info("eds page corrupt; invalidating height",
                     height=height)
            self._eds_cache.invalidate(height)
            # seeded provers derive from the same (possibly corrupt)
            # square — drop them with it
            self._prover_cache.pop(height, None)
            return self._sample_batch(height, coords)

    def sample_batch_ragged(self, payloads) -> list:
        """Answer a micro-batch of DAS samples ACROSS heights — the
        `batch_exec` target of the widened ``("sample",)`` dispatcher
        lane (ISSUE 14). Jobs are grouped per height with per-height
        prover reuse (`_row_provers`); heights backed by the paged
        cache contribute their distinct rows to ONE ragged page-table
        gather (`PagedEdsCache.pages_batch`), so the whole mixed-height
        group costs one device dispatch per page geometry instead of
        one per height. Every returned document is byte-identical to
        the per-height `sample_batch` path, sentinel semantics
        included (None for an unknown block, "range" out of bounds).

        The IntegrityError heal contract is per-height: a poisoned
        fault-in invalidates only the attributed height (``err.height``,
        stamped by the paged cache) and the whole group re-answers; a
        second corruption of an already-healed height re-raises."""
        from celestia_tpu import integrity

        healed: set[int] = set()
        while True:
            try:
                return self._sample_batch_ragged(payloads)
            except integrity.IntegrityError as err:
                if not hasattr(self._eds_cache, "invalidate"):
                    raise
                height = getattr(err, "height", None)
                targets = [int(height)] if height is not None else \
                    sorted({int(h) for h, _i, _j in payloads})
                if any(h in healed for h in targets):
                    raise
                for h in targets:
                    log.info("eds page corrupt; invalidating height",
                             height=h)
                    self._eds_cache.invalidate(h)
                    self._prover_cache.pop(h, None)
                    healed.add(h)

    def _sample_batch_ragged(self, payloads) -> list:
        from celestia_tpu.node import eds_cache
        from celestia_tpu.ops import ragged
        from celestia_tpu.proof import das_sample_docs

        jobs = [(int(h), int(i), int(j)) for h, i, j in payloads]
        by_height: dict[int, list[int]] = {}
        for t, (h, _i, _j) in enumerate(jobs):
            by_height.setdefault(h, []).append(t)
        out: list = [None] * len(jobs)
        with ragged.ragged_span(len(by_height), len(jobs)), \
                contextlib.ExitStack() as borrows:
            # borrow every height up front: the pins outlive both the
            # gather and the prove stage, exactly like the per-height
            # path's single borrow
            plan: list = []            # (h, eds, w, valid, rows_needed)
            wants: list = []           # (PagedEds, row) ragged gather feed
            want_slot: dict = {}       # (h, row) -> index into wants
            for h, ts in by_height.items():
                eds = borrows.enter_context(self._borrow_eds(h))
                if eds is None:
                    continue  # out[t] stays None: unknown block
                if hasattr(eds, "original_width"):
                    w = eds.width
                else:
                    w = int(eds.shape[0])
                for t in ts:
                    out[t] = "range"
                valid = [t for t in ts
                         if 0 <= jobs[t][1] < w and 0 <= jobs[t][2] < w]
                if not valid:
                    continue
                rows_needed = sorted({jobs[t][1] for t in valid})
                plan.append((h, eds, w, valid, rows_needed))
                if (isinstance(eds, eds_cache.PagedEds)
                        and eds._cache is self._eds_cache
                        and hasattr(self._eds_cache, "pages_batch")):
                    for i in rows_needed:
                        want_slot[(h, i)] = len(wants)
                        wants.append((eds, i))
            with tracing.stage("device"):
                gathered = (self._eds_cache.pages_batch(wants)
                            if wants else [])
                rows_of: dict[int, dict] = {}
                for h, eds, w, valid, rows_needed in plan:
                    if (h, rows_needed[0]) in want_slot:
                        rows = {i: gathered[want_slot[(h, i)]]
                                for i in rows_needed}
                    elif hasattr(eds, "rows_batch"):
                        rows = dict(zip(rows_needed,
                                        eds.rows_batch(rows_needed)))
                    elif hasattr(eds, "original_width"):
                        rows = {i: eds.row(i) for i in rows_needed}
                    else:
                        rows = {i: [bytes(eds[i, c]) for c in range(w)]
                                for i in rows_needed}
                    rows_of[h] = rows
            with tracing.stage("prove"):
                for h, eds, w, valid, rows_needed in plan:
                    docs = das_sample_docs(
                        rows_of[h],
                        [(jobs[t][1], jobs[t][2]) for t in valid],
                        w // 2,
                        provers=self._row_provers(h, eds, rows_needed))
                    for t, doc in zip(valid, docs):
                        out[t] = doc
        return out

    def _row_provers(self, height: int, eds, rows_needed) -> dict:
        """Per-height prover memo for `das_sample_docs` (ADR-019).

        First touch of a height with a device-resident square runs ONE
        jitted NMT reduce over all rows (`eds_row_levels_device`) and
        keeps the node levels; each referenced row then gets its prover
        via `NmtRowProver.from_node_levels` — no host hashing at all.
        Host-resident squares (and any device failure, defensively)
        return a plain dict that `das_sample_docs` fills with host-built
        provers, which still persist across batches of the same height."""
        entry = self._prover_cache.get(height)
        if entry is None:
            levels = None
            try:
                arr = getattr(eds, "device_data", None)
                if arr is None and not hasattr(eds, "original_width"):
                    # raw host array: only worth a device round-trip when
                    # an accelerator actually backs the jit
                    import jax

                    if jax.default_backend() not in ("cpu",):
                        arr = eds
                if arr is not None:
                    from celestia_tpu.ops import extend_tpu

                    levels = extend_tpu.eds_row_levels_device(arr)
                elif self.store is not None and height in self.store:
                    # store-loaded square (no device buffer): the
                    # persisted row-tree levels seed provers that are
                    # byte-identical to the pre-restart ones — zero
                    # hashing on the restart path too
                    levels = self.store.read_levels(height)
            except Exception as exc:  # device trouble must not fail DAS
                log.warn("device prover seeding failed; host fallback",
                         height=height,
                         error=f"{type(exc).__name__}: {exc}")
                levels = None
            while len(self._prover_cache) >= self._PROVER_CACHE_HEIGHTS:
                self._prover_cache.pop(next(iter(self._prover_cache)))
            entry = (levels, {})
            self._prover_cache[height] = entry
        levels, provers = entry
        if levels is not None:
            from celestia_tpu.proof import NmtRowProver

            for i in rows_needed:
                if i not in provers:
                    provers[i] = NmtRowProver.from_node_levels(
                        [levels[L][i] for L in range(len(levels))]
                    )
        return provers

    def _sample_batch(self, height: int, coords) -> list:
        from celestia_tpu.proof import das_sample_docs

        coords = [(int(i), int(j)) for i, j in coords]
        with self._borrow_eds(height) as eds:
            if eds is None:
                return [None] * len(coords)
            if hasattr(eds, "original_width"):
                w = eds.width
            else:
                w = int(eds.shape[0])
            out: list = ["range"] * len(coords)
            valid = [t for t, (i, j) in enumerate(coords)
                     if 0 <= i < w and 0 <= j < w]
            if not valid:
                return out
            rows_needed = sorted({coords[t][0] for t in valid})
            # stage attribution (ADR-022): "device" covers the row
            # fetch (transfers records its d2h share separately and
            # stage() subtracts nested time, so the breakdown stays
            # disjoint); "prove" covers prover seeding + NMT proving.
            # Both are shared no-ops unless the dispatcher installed a
            # stage sink, i.e. tracing is enabled.
            with tracing.stage("device"):
                if hasattr(eds, "rows_batch"):
                    rows = dict(zip(rows_needed,
                                    eds.rows_batch(rows_needed)))
                elif hasattr(eds, "original_width"):
                    rows = {i: eds.row(i) for i in rows_needed}
                else:
                    rows = {i: [bytes(eds[i, c]) for c in range(w)]
                            for i in rows_needed}
            with tracing.stage("prove"):
                docs = das_sample_docs(rows, [coords[t] for t in valid],
                                       w // 2,
                                       provers=self._row_provers(
                                           height, eds, rows_needed))
        for t, doc in zip(valid, docs):
            out[t] = doc
        return out

    def block_dah(self, height: int):
        """The DataAvailabilityHeader a block's data_hash commits to —
        the O(w)-sized artifact light clients fetch instead of the
        square (row+column NMT roots; hash() == block.data_hash).
        Memoized per height: blocks are immutable and the roots are
        tiny, while recomputing them costs a full O(w^2) extension."""
        # single atomic dict get/set (no iteration/eviction): safe
        # lock-free under the Node's read contract; worst case two
        # threads compute the same immutable DAH once
        dah = self._dah_cache.get(height)
        if dah is not None:
            return dah
        from celestia_tpu import da

        if self.store is not None and height in self.store:
            # serve the STORED DAH: post-restart /dah bytes must equal
            # the pre-restart bytes exactly (the store wrote what this
            # node served), and no square materialization is needed
            try:
                dah = da.DataAvailabilityHeader.from_json(
                    self.store.read_dah(height))
                self._dah_cache[height] = dah
                return dah
            except Exception as e:  # noqa: BLE001 — recompute instead
                log.info("stored DAH unreadable; recomputing",
                         height=height, error=str(e))
        # root computation bulk-reads a device-resident square once:
        # borrow keeps the entry pinned across that fetch
        with self._borrow_eds(height) as eds:
            if eds is None:
                return None
            if not hasattr(eds, "original_width"):
                eds = da.ExtendedDataSquare(eds, eds.shape[0] // 2)
            dah = da.new_data_availability_header(eds)
        self._dah_cache[height] = dah
        return dah

    def ibc_light_client_header(self):
        """Unsigned light-client header material for this chain's latest
        committed state, read as ONE snapshot under the node lock (a
        racing commit must never pair height H with H+1's app hash —
        validators would sign a header no proof at H can satisfy).
        The single source for both transports' ibc-header routes, so
        the sign-bytes schema cannot drift between them."""
        from celestia_tpu.node.consensus import consensus_valset
        from celestia_tpu.x.lightclient import Header, ValidatorInfo

        with self._lock:
            height = self.app.height
            block = self.get_block(height)
            return Header(
                chain_id=self.app.chain_id,
                height=height,
                time=block.time if block else 0.0,
                app_hash=self.app.store.app_hashes[self.app.store.version],
                validators=[
                    ValidatorInfo(v.pubkey, v.power)
                    for v in consensus_valset(self.app.staking)
                ],
            )

    # --- state sync (serve + bootstrap) ---

    def snapshot_payload(self) -> dict:
        """The state-sync snapshot a peer can bootstrap from (SDK
        snapshot store analogue, served at GET /snapshot): committed
        state + the metadata needed to verify and resume."""
        with self._lock:
            # under the node lock no block can commit mid-assembly, so the
            # advertised app_hash and the state dump are one snapshot
            return {
                **self._meta(),
                "app_hash": self.app.store.app_hashes.get(
                    self.app.store.version, b""
                ).hex(),
                "state": self.app.store.snapshot().hex(),
            }

    def _meta(self) -> dict:
        return {
            "height": self.app.height,
            "chain_id": self.app.chain_id,
            "app_version": self.app.app_version,
            "block_time": self.app.block_time,
        }

    @staticmethod
    def _restore_app(meta: dict, state_bytes: bytes, **app_kwargs) -> App:
        """Shared restore path for disk resume and state sync: App +
        restored store + every keeper rebound + resume position."""
        from celestia_tpu.state import StateStore

        app = App(chain_id=meta["chain_id"], app_version=meta["app_version"],
                  **app_kwargs)
        app.rebind_store(StateStore.restore(state_bytes))
        app.height = meta["height"]
        app.block_time = meta["block_time"]
        return app

    @classmethod
    def _verified_restore(cls, payload: dict,
                          trusted_app_hash: bytes | str | None,
                          **app_kwargs) -> App:
        """Restore an App from a snapshot payload and verify its
        recomputed app hash — the single verification point for both
        state-sync spellings. Pass `trusted_app_hash` (from a source you
        already trust — a verified header, a corroborating peer set, a
        checkpoint) to authenticate; without it the payload's own
        app_hash is checked, which only detects transport corruption (a
        malicious peer controls both fields)."""
        app = cls._restore_app(payload, bytes.fromhex(payload["state"]),
                               **app_kwargs)
        computed = app.store.app_hashes[app.store.version]
        expected = trusted_app_hash if trusted_app_hash is not None \
            else payload["app_hash"]
        if isinstance(expected, bytes):
            expected = expected.hex()
        if computed.hex() != expected:
            raise ValueError(
                "snapshot app hash mismatch: expected "
                f"{expected}, state restores to {computed.hex()}"
            )
        return app

    def restore_from_snapshot(self, payload: dict,
                              trusted_app_hash: bytes | str | None = None,
                              **app_kwargs) -> None:
        """In-place state sync: swap this node's app for one restored
        from a peer snapshot (same verification as state_sync_from).
        For a live node catching up — the RPC server and consensus
        layer keep their references to this Node object."""
        app = self._verified_restore(payload, trusted_app_hash, **app_kwargs)
        with self._lock:
            self.app = app
            if self.home:
                self.save_snapshot()
        log.info("state synced in place", height=app.height,
                 app_hash=app.store.app_hashes[app.store.version],
                 authenticated=trusted_app_hash is not None)

    @classmethod
    def state_sync_from(cls, payload: dict, home: str | None = None,
                        trusted_app_hash: bytes | str | None = None,
                        **app_kwargs) -> "Node":
        """Bootstrap a fresh node from a peer's snapshot payload.

        Verification semantics live in `_verified_restore` (shared with
        the in-place `restore_from_snapshot`)."""
        app = cls._verified_restore(payload, trusted_app_hash, **app_kwargs)
        log.info("state synced", height=app.height,
                 app_hash=app.store.app_hashes[app.store.version],
                 authenticated=trusted_app_hash is not None)
        return cls(app, home=home)

    # --- checkpoint / resume ---

    def save_snapshot(self) -> None:
        if not self.home:
            raise ValueError("node has no home directory")
        with self._lock:
            (self.home / "state.json").write_bytes(self.app.store.snapshot())
            (self.home / "meta.json").write_text(json.dumps(self._meta()))

    @classmethod
    def load(cls, home: str, **app_kwargs) -> "Node":
        home_path = pathlib.Path(home)
        meta = json.loads((home_path / "meta.json").read_text())
        app = cls._restore_app(
            meta, (home_path / "state.json").read_bytes(), **app_kwargs
        )
        node = cls(app, home=home)
        for path in sorted((home_path / "blocks").glob("*.json"),
                           key=lambda p: int(p.stem)):
            block = Block.from_json(json.loads(path.read_text()))
            node.blocks[block.height] = block
            for i, raw in enumerate(block.txs):
                node.tx_index[tx_hash(raw)] = (block.height, i)
        # Crash recovery: snapshots are taken on the StateSync cadence,
        # so the persisted block store can be AHEAD of the state
        # snapshot — replay the newer blocks through the app (the WAL
        # replay the reference gets from cometbft), verifying each
        # replayed commit against the stored app hash.
        pending = [node.blocks[h]
                   for h in sorted(h for h in node.blocks if h > app.height)]
        da_verified = node._batch_verify_data_availability(app, pending)
        for block in pending:
            height = block.height
            app.begin_block(block.time, evidence=block.evidence)
            for raw in block.txs:
                app.deliver_tx(raw)
            app.end_block()
            app_hash = app.commit()
            if app_hash != block.app_hash:
                raise ValueError(
                    f"replayed block {height} commits app hash "
                    f"{app_hash.hex()}, stored block has "
                    f"{block.app_hash.hex()} — state corruption"
                )
            if height not in da_verified:
                # fallback (e.g. an app-version change inside the replay
                # window): verify solo at the now-current version
                node._verify_block_data_hash(app, block)
            log.info("replayed block", height=height, app_hash=app_hash,
                     da_verified=True)
        return node

    @staticmethod
    def _rebuild_square(app: App, block: "Block"):
        from celestia_tpu import square as square_pkg
        from celestia_tpu.appconsts import square_size_upper_bound

        return square_pkg.construct(
            block.txs, app.app_version, square_size_upper_bound(app.app_version)
        )

    @staticmethod
    def _verify_block_data_hash(app: App, block: "Block") -> None:
        square = Node._rebuild_square(app, block)
        dah = app._proposal_dah(square)
        if dah.hash() != block.data_hash:
            raise ValueError(
                f"replayed block {block.height} data hash mismatch — "
                "block store corruption"
            )

    @staticmethod
    def _batch_verify_data_availability(app: App, pending: list["Block"]):
        """Re-verify the data roots of queued replay blocks, batched.

        A catching-up node has many squares queued; equal sizes ride ONE
        batched device dispatch (ops/extend_tpu.extend_and_root_batched —
        the dp axis of the multichip design) instead of per-block calls.
        Returns the set of heights verified. This pre-pass rebuilds
        squares at the snapshot's app version, which can legitimately
        mismatch after an upgrade inside the window — so it never raises:
        any block it cannot positively verify is re-checked by the
        in-loop solo fallback at the then-current version, which IS
        authoritative."""
        import numpy as np

        from celestia_tpu import square as square_pkg
        from celestia_tpu.appconsts import SHARE_SIZE

        verified: set[int] = set()
        if not pending:
            return verified
        groups: dict[int, list] = {}  # k -> [(block, data_square), ...]
        for block in pending:
            try:
                sq = Node._rebuild_square(app, block)
            except Exception:  # noqa: BLE001 — solo fallback decides
                continue
            k = square_pkg.square_size(len(sq))
            if k != block.square_size:
                continue  # version drift — leave for the solo fallback
            groups.setdefault(k, []).append((block, sq))

        for k, items in groups.items():
            backend = app.resolve_extend_backend(k)
            if backend == "tpu" and len(items) > 1:
                from celestia_tpu import da as da_pkg
                from celestia_tpu.ops import extend_tpu

                squares = [
                    np.frombuffer(
                        b"".join(s.data for s in sq), dtype=np.uint8
                    ).reshape(k, k, SHARE_SIZE)
                    for _b, sq in items
                ]
                # jitted roots-only: the verifier never needs the EDS
                # bytes. One entry point at every size: small squares
                # ride one vmapped dispatch, large squares an async-
                # pipelined queue of single-square dispatches (the list
                # is passed as-is — no stacked copy at large k).
                rows, cols = extend_tpu.batched_roots_device(squares)
                for i, (block, _sq) in enumerate(items):
                    dah = da_pkg.DataAvailabilityHeader(
                        [r.tobytes() for r in rows[i]],
                        [c.tobytes() for c in cols[i]],
                    )
                    if dah.hash() == block.data_hash:
                        verified.add(block.height)
                log.info("batched DA verification", k=k, blocks=len(items),
                         backend=backend)
            else:
                for block, sq in items:
                    dah = app._proposal_dah(sq)
                    if dah.hash() == block.data_hash:
                        verified.add(block.height)
        return verified
