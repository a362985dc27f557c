"""Networked multi-process devnet: N validator Nodes on localhost.

The reference boots real in-process validator nodes with open ports
(test/util/testnode/full_node.go:70) and a k8s e2e testnet
(test/e2e/testnet.go:16). This module is the framework's localhost
equivalent: each validator is its own OS process running a Node +
RpcServer; they exchange proposals, stake-weighted votes, commit
certificates, and gossiped txs over the existing HTTP RPC transport,
and a crashed validator rejoins via the existing state-sync snapshot
path.

Protocol (node/consensus.py): leader-driven, one round per height.

1. The rotation leader (proposer_rotation over the bonded valset)
   reaps its mempool, runs PrepareProposal, signs the proposal hash,
   and POSTs /consensus/proposal to every peer.
2. Peers re-run ProcessProposal and return a signed stake vote. A
   validator votes at most once per height (tracked per height; a
   conflicting proposal at the same height is refused while the vote
   is fresh), so two certificates can never form at one height while
   > 1/3 of power is honest-and-live.
3. With > 2/3 of bonded power accepting, the leader applies the block,
   then POSTs /consensus/commit (proposal + certificate + its app
   hash). Peers verify the certificate against their OWN committed
   valset, apply the block, and cross-check the app hash — any
   divergence halts that peer loudly (the reference's app-hash
   mismatch panic).
4. broadcast_tx gossips: a tx accepted by any node's CheckTx is
   forwarded once to every peer, so it reaches the next leader's
   mempool.

Fault model: crash faults, not Byzantine. Within one liveness window
the vote-once rule makes two certificates at a height impossible while
> 1/3 of power is honest-and-live. The window is load-bearing: a
leader that STALLS longer than `liveness_timeout` mid-commit (rather
than dying) can leave one peer committed on its block while expired
votes let a takeover leader certify a different block — the stalled
leader then halts on the app-hash cross-check at the next height
instead of being prevented up front. CometBFT closes that hole with
locking/round machinery and slashable evidence; a devnet of
honest-but-crashable replicas accepts the window, and that divergence
is deliberate and documented here.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import threading
import time

from celestia_tpu.crypto import PrivateKey
from celestia_tpu.log import logger
from celestia_tpu.node.client import RpcClient
from celestia_tpu.node.consensus import (
    CommitCert,
    ConsensusValidator,
    VoteEvidence,
    consensus_valset,
    make_vote,
    meets_quorum,
    proposal_hash,
    proposer_rotation,
    tally,
    total_power,
    verify_commit_cert,
    verify_vote_evidence,
)
from celestia_tpu.node.node import Node

log = logger("devnet")


class PeerClient(RpcClient):
    """RpcClient + the consensus routes."""

    def consensus_proposal(self, body: dict) -> dict:
        return self._post("/consensus/proposal", body)

    def consensus_commit(self, body: dict) -> dict:
        return self._post("/consensus/commit", body)

    def consensus_evidence(self, body: dict) -> dict:
        return self._post("/consensus/evidence", body)

    def fraud_befp_submit(self, body: dict) -> dict:
        return self._post("/fraud/befp", body)

    def gossip_have(self, keys: list[bytes]) -> dict:
        return self._post("/gossip/have", {"keys": [k.hex() for k in keys]})

    def gossip_tx(self, raw: bytes) -> dict:
        return self._post("/broadcast_tx", {"tx": raw.hex(), "forward": False})


class ValidatorNode:
    """A Node + consensus key + peer set: one devnet validator.

    Attach to a Node before serving RPC (RpcServer routes the
    /consensus/* endpoints through `node.validator`)."""

    def __init__(self, node: Node, key: PrivateKey, peers: list[str],
                 liveness_timeout: float = 10.0):
        self.node = node
        self.key = key
        self.operator = key.bech32_address()
        self.peers = [PeerClient(p, timeout=5.0) for p in peers]
        self.liveness_timeout = liveness_timeout
        # vote-once bookkeeping: height -> (round, prop_hash, voted_at).
        # The round discipline is what keeps honest validators
        # slash-proof: NEVER sign two proposals at one (height, round);
        # the crash-fault re-vote path moves to a strictly higher round.
        self._voted: dict[int, tuple[int, bytes, float]] = {}
        # equivocation watch: every ACCEPT vote this validator has seen
        # (peer votes it collected as leader, certificate votes from
        # commits) — height -> (operator, round) -> (prop_hash, sig).
        # Two entries for one (height, round, operator) with different
        # proposal hashes ARE double-sign evidence.
        self._seen_votes: dict[int, dict[tuple[str, int], tuple[bytes, str]]] = {}
        # verified evidence awaiting inclusion in a block this node leads
        self._pending_evidence: dict[tuple[str, int, int], VoteEvidence] = {}
        # next round to propose with per height (bumped on failed rounds
        # so a takeover proposal eventually exceeds every peer's prior
        # vote round — the liveness ladder)
        self._round_attempt: dict[int, int] = {}
        # CAT gossip accounting: raw tx bytes actually sent vs bytes the
        # want/have handshake avoided sending (plus the tiny have keys)
        self.gossip_stats = {"raw_bytes": 0, "have_bytes": 0,
                             "deduped_bytes": 0}
        self._vote_lock = threading.Lock()
        self._last_commit = time.monotonic()
        # cached own proposal per height: a failed round (missing peer
        # vote) retries the IDENTICAL body next tick — regenerating with
        # a fresh timestamp would trip everyone's vote-once rule and
        # stall the height for a full liveness window
        self._my_proposal: tuple | None = None  # (height, body, ph, proposal, created)
        self.halted: str | None = None  # set on app-hash divergence
        node.validator = self

    # ---- helpers ----

    def _valset(self) -> list[ConsensusValidator]:
        return consensus_valset(self.node.app.staking)

    def _prop_hash(self, body: dict) -> bytes:
        import hashlib

        ph = proposal_hash(
            self.node.app.chain_id,
            int(body["height"]),
            float(body["time"]),
            body["proposer"],
            bytes.fromhex(body["data_hash"]),
            int(body["square_size"]),
            [bytes.fromhex(t) for t in body["txs"]],
        )
        ev = body.get("evidence") or []
        if ev:
            # evidence is state-affecting (BeginBlock slashing), so votes
            # must bind it — a leader cannot vary evidence post-vote
            # without producing a different proposal hash
            ev_digest = hashlib.sha256(
                json.dumps(ev, sort_keys=True, separators=(",", ":")).encode()
            ).digest()
            ph = hashlib.sha256(ph + ev_digest).digest()
        round_ = int(body.get("round", 0))
        if round_:
            # the round also binds the hash (round 0 keeps the legacy
            # bytes), so one proposal body cannot be replayed as a
            # different round
            ph = hashlib.sha256(ph + round_.to_bytes(8, "big")).digest()
        return ph

    # ---- equivocation detection / evidence pool ----

    def _body_evidence(self, body: dict) -> list:
        """Verify and convert a proposal body's evidence entries to
        slashing Equivocations. Deterministic given the committed valset
        — every replica converts identically, so state cannot fork.
        Raises on any invalid entry (an honest leader only includes
        verified evidence, so an invalid entry means a bad proposal)."""
        from celestia_tpu.x.slashing import Equivocation

        out = []
        for d in body.get("evidence") or []:
            ev = VoteEvidence.from_json(d)
            power = verify_vote_evidence(
                self._valset(), self.node.app.chain_id, ev
            )
            out.append(Equivocation(ev.operator, ev.height, power))
        return out

    def _record_accept_vote(
        self, height: int, round_: int, operator: str, ph: bytes,
        signature: str,
    ) -> None:
        """Watch every accept vote; a second vote by the same validator
        at the same (height, ROUND) for a DIFFERENT proposal becomes
        verified VoteEvidence, pooled for the next block and gossiped to
        peers (CometBFT's DuplicateVoteEvidence detection; the reference
        receives it as ABCI ByzantineValidators). Cross-round conflicts
        are NOT evidence — that is the honest crash-fault re-vote.

        The signature is verified BEFORE the vote is recorded: commit
        certificates can carry rider entries with garbage signatures
        (tally just skips them), and recording one unverified would
        poison the (height, round, operator) slot — the later REAL
        conflicting vote would pair with the garbage entry, fail
        evidence verification, and the actual double-sign would escape
        detection."""
        from celestia_tpu.node.consensus import (
            verify_signature,
            vote_sign_bytes,
        )

        pubkey = next(
            (v.pubkey for v in self._valset() if v.operator == operator), None
        )
        if pubkey is None:
            return
        try:
            ok = verify_signature(
                bytes.fromhex(pubkey),
                vote_sign_bytes(
                    self.node.app.chain_id, height, ph, True, round_
                ),
                bytes.fromhex(signature),
            )
        except ValueError:
            ok = False
        if not ok:
            return  # forged/garbage rider — never let it into the watch
        with self._vote_lock:
            seen = self._seen_votes.setdefault(height, {})
            prior = seen.get((operator, round_))
            if prior is None:
                seen[(operator, round_)] = (ph, signature)
                return
            if prior[0] == ph:
                return
            ev = VoteEvidence(
                operator=operator, height=height, round=round_,
                prop_hash_a=prior[0], sig_a=prior[1],
                prop_hash_b=ph, sig_b=signature,
            )
            try:
                verify_vote_evidence(
                    self._valset(), self.node.app.chain_id, ev
                )
            except ValueError as e:
                log.info("discarding unverifiable double-vote", error=str(e))
                return
            if ev.key() in self._pending_evidence:
                return
            self._pending_evidence[ev.key()] = ev
            log.info("EQUIVOCATION detected", operator=operator, height=height)
        for peer in self.peers:
            try:
                peer.consensus_evidence({"evidence": ev.to_json()})
            except Exception as e:  # noqa: BLE001 — a dead peer is fine
                log.info("evidence gossip skip", peer=peer.base_url,
                         error=str(e))

    def handle_evidence(self, body: dict) -> dict:
        """Accept gossiped double-sign evidence after independent
        verification (no trust in the reporter)."""
        ev = VoteEvidence.from_json(body["evidence"])
        verify_vote_evidence(self._valset(), self.node.app.chain_id, ev)
        with self._vote_lock:
            self._pending_evidence.setdefault(ev.key(), ev)
        return {"ok": True}

    def _prune_evidence(self, committed_height: int) -> None:
        """Drop vote records at committed heights and evidence already
        included (the equivocator is tombstoned — further evidence for
        it is redundant)."""
        with self._vote_lock:
            self._seen_votes = {
                h: v
                for h, v in self._seen_votes.items()
                if h > committed_height
            }

    # ---- peer-facing handlers (RPC threads) ----

    # ---- bad-encoding fraud proofs (specs/fraud_proofs.md) ----

    def _investigate_bad_encoding(self, height: int, body: dict) -> None:
        """A certificate-valid block failed our ProcessProposal. Fetch
        the proposer's published square from whichever peer serves it,
        and if the committed DAH's erasure coding is provably invalid,
        store + gossip a BEFP. Never raises: investigation is best-
        effort on top of the refusal that already happened."""
        import numpy as np

        from celestia_tpu.appconsts import SHARE_SIZE
        from celestia_tpu.da import DataAvailabilityHeader
        from celestia_tpu.da import fraud as fraud_mod

        announced = bytes.fromhex(body["data_hash"])
        if announced.hex() in self.node.fraud_proofs.get(height, {}):
            return
        for peer in self.peers:
            try:
                d = peer.dah(height)
                if d is None:
                    continue
                dah = DataAvailabilityHeader.from_json(d)
                if dah.hash() != announced:
                    continue  # this peer serves a different block
                e = peer.eds(height)
                if e is None:
                    continue
                w = int(e["width"])
                eds = np.stack(
                    [
                        np.frombuffer(
                            bytes.fromhex(row), dtype=np.uint8
                        ).reshape(w, SHARE_SIZE)
                        for row in e["rows"]
                    ]
                )
                proof = fraud_mod.find_befp(eds)
                if proof is None:
                    continue  # divergence was not a bad encoding
                if not fraud_mod.verify_befp(proof, dah):
                    continue  # served square is not the committed one
            except Exception as exc:  # noqa: BLE001 — best-effort per peer
                log.info("fraud investigation skip", peer=peer.base_url,
                         error=str(exc))
                continue
            wire = {"height": height, "dah": d, "proof": proof.to_json()}
            # force: `announced` came from a VERIFIED commit certificate
            # (handle_commit checked it before apply) — this is the
            # proof of record and must displace any cap-filling decoys
            if self.node.add_fraud_proof(height, announced, wire,
                                         force=True):
                log.error("bad encoding PROVEN", height=height,
                          axis=proof.axis, index=proof.index)
                self._gossip_fraud(wire)
            return

    def handle_fraud(self, body: dict) -> dict:
        """Accept a gossiped BEFP after INDEPENDENT verification — a
        forged proof must not let an attacker frame honest blocks —
        then re-gossip once (the store is the dedup)."""
        from celestia_tpu.da import DataAvailabilityHeader
        from celestia_tpu.da import fraud as fraud_mod

        height = int(body["height"])
        if height < 1 or height > self.node.app.height + 2:
            # no certificate can exist that far ahead — refusing keeps
            # an attacker from growing the store with proofs of junk
            # squares at heights 1..10^9 (each height is individually
            # capped, so the sum over fake heights was the exposure)
            raise ValueError(
                f"fraud proof height {height} is beyond the chain tip"
            )
        proof = fraud_mod.BadEncodingFraudProof.from_json(body["proof"])
        dah = DataAvailabilityHeader.from_json(body["dah"])
        dah_hash = dah.hash()
        if dah_hash.hex() in self.node.fraud_proofs.get(height, {}):
            return {"accepted": True, "duplicate": True}
        block = self.node.get_block(height)
        if block is not None and block.data_hash != dah_hash:
            raise ValueError("fraud proof DAH does not match the committed block")
        if not fraud_mod.verify_befp(proof, dah):
            raise ValueError("proof does not demonstrate a bad encoding")
        wire = {"height": height, "dah": body["dah"],
                "proof": body["proof"]}
        # a proof matching OUR committed block is the height's proof of
        # record — it bypasses the decoy cap
        force = block is not None and block.data_hash == dah_hash
        if not self.node.add_fraud_proof(height, dah_hash, wire, force=force):
            return {"accepted": False, "error": "per-height proof cap"}
        log.error("bad encoding fraud proof accepted", height=height,
                  axis=proof.axis, index=proof.index)
        self._gossip_fraud(wire)
        return {"accepted": True}

    def _known_fraudulent(self, data_hash: bytes) -> bool:
        # O(1) on the consensus hot path — maintained by add_fraud_proof
        return data_hash in self.node.fraudulent_data_hashes

    def _gossip_fraud(self, wire: dict) -> None:
        for peer in self.peers:
            try:
                peer.fraud_befp_submit(wire)
            except Exception as e:  # noqa: BLE001 — a dead peer is fine
                log.info("fraud gossip skip", peer=peer.base_url,
                         error=str(e))

    def handle_proposal(self, body: dict) -> dict:
        """ProcessProposal + stake vote (consensus step 2)."""
        if self.halted:
            raise ValueError(f"validator halted: {self.halted}")
        height = int(body["height"])
        if height != self.node.app.height + 1:
            raise ValueError(
                f"proposal height {height}, expected {self.node.app.height + 1}"
            )
        valset = self._valset()
        if body["proposer"] not in {v.operator for v in valset}:
            raise ValueError(f"proposer {body['proposer']} is not bonded")
        if self._known_fraudulent(bytes.fromhex(body["data_hash"])):
            # a verified BEFP proves this exact DAH commits a bad
            # encoding — never endorse it, whatever the round
            raise ValueError("proposal data hash has a verified fraud proof")
        ph = self._prop_hash(body)
        round_ = int(body.get("round", 0))

        with self._vote_lock:
            prior = self._voted.get(height)
            if prior is not None:
                p_round, p_ph, p_ts = prior
                if round_ == p_round and ph != p_ph:
                    # NEVER sign two proposals at one (height, round) —
                    # doing so is slashable equivocation by definition
                    raise ValueError(
                        f"already voted at height {height} round {round_} "
                        "for a different proposal"
                    )
                if round_ < p_round:
                    raise ValueError(
                        f"stale round {round_} at height {height} "
                        f"(already voted in round {p_round})"
                    )
                if round_ > p_round and (
                    time.monotonic() - p_ts < self.liveness_timeout
                ):
                    # the prior round's leader may still commit — only a
                    # stale vote frees us to endorse a later round
                    raise ValueError(
                        f"round {p_round} vote at height {height} is "
                        "still fresh"
                    )
            from celestia_tpu.app.app import ProposalBlockData

            proposal = ProposalBlockData(
                txs=[bytes.fromhex(t) for t in body["txs"]],
                square_size=int(body["square_size"]),
                hash=bytes.fromhex(body["data_hash"]),
            )
            with self.node._lock:
                accept = self.node.app.process_proposal(proposal)
            if accept and body.get("evidence"):
                # evidence is state-affecting: refuse to endorse a
                # proposal carrying entries we cannot verify
                try:
                    self._body_evidence(body)
                except ValueError as e:
                    log.info("rejecting proposal with bad evidence",
                             error=str(e))
                    accept = False
            vote = make_vote(
                self.key, self.operator, self.node.app.chain_id, height, ph,
                accept, round_,
            )
            if accept and (prior is None or (prior[0], prior[1]) != (round_, ph)):
                # stamp once per proposal, not per retry delivery — a
                # proposer re-POSTing its cached round must not keep our
                # vote record eternally fresh (see try_propose)
                self._voted[height] = (round_, ph, time.monotonic())
        return {"vote": vote.to_json()}

    def handle_commit(self, body: dict) -> dict:
        """Verify the certificate against our OWN valset, apply, and
        cross-check the app hash (consensus step 3)."""
        if self.halted:
            raise ValueError(f"validator halted: {self.halted}")
        height = int(body["height"])
        if height <= self.node.app.height:
            return {"app_hash": self._app_hash_hex(), "height": self.node.app.height}
        if height != self.node.app.height + 1:
            raise ValueError(
                f"commit height {height}, node at {self.node.app.height}: "
                "catch up via state sync"
            )
        cert = CommitCert.from_json(body["cert"])
        ph = self._prop_hash(body)
        if cert.prop_hash != ph:
            raise ValueError("certificate does not match the proposal")
        if cert.round != int(body.get("round", 0)):
            raise ValueError("certificate round does not match the proposal")
        verify_commit_cert(self._valset(), self.node.app.chain_id, cert)
        # certificate votes are publicly visible accept votes — feed the
        # equivocation watch (a validator that voted for a competing
        # proposal in the SAME round is caught right here)
        for v in cert.votes:
            if v.accept:
                self._record_accept_vote(
                    height, cert.round, v.operator, ph, v.signature
                )
        # expected_height re-checks under node._lock: two concurrent
        # commit handlers both passing the height gate above must not
        # stack — the second would apply a block its certificate does
        # not cover
        try:
            block = self.node.apply_external_block(
                [bytes.fromhex(t) for t in body["txs"]],
                int(body["square_size"]),
                bytes.fromhex(body["data_hash"]),
                float(body["time"]),
                expected_height=height,
                evidence=self._body_evidence(body),
            )
        except ValueError:
            if self.node.app.height + 1 == height:
                # a certificate-valid block WE reject: a >2/3-dishonest
                # committee may have committed a bad erasure coding —
                # fetch the published square and try to prove it before
                # refusing, so light clients get a warning they can
                # verify (specs/fraud_proofs.md's full-node role)
                self._investigate_bad_encoding(height, body)
            raise
        self._last_commit = time.monotonic()
        with self._vote_lock:
            # committed heights can never be voted again — drop their
            # records (unbounded growth in a long-running validator)
            self._voted = {h: v for h, v in self._voted.items() if h > height}
            self._round_attempt = {
                h: r for h, r in self._round_attempt.items() if h > height
            }
            for d in body.get("evidence") or []:
                self._pending_evidence.pop(
                    (d["operator"], int(d["height"]), int(d.get("round", 0))),
                    None,
                )
        self._prune_evidence(height)
        if block.app_hash.hex() != body["app_hash"]:
            # deterministic state machines diverged — halt loudly, never
            # keep signing on a forked state
            self.halted = (
                f"app hash divergence at height {height}: "
                f"{block.app_hash.hex()} != {body['app_hash']}"
            )
            log.error("HALT", reason=self.halted)
            raise ValueError(self.halted)
        return {"app_hash": block.app_hash.hex(), "height": block.height}

    def gossip_tx(self, raw: bytes) -> None:
        """Forward a freshly-admitted tx to every peer, CAT-style
        (specs/src/specs/cat_pool.md): offer the 32-byte tx KEY first
        (want/have); raw bytes travel only to peers that do not already
        hold or recently processed the tx. `gossip_stats` records the
        measured bytes-on-wire either way."""
        from celestia_tpu.node.node import tx_hash

        key = tx_hash(raw)
        for peer in self.peers:
            try:
                res = peer.gossip_have([key])
                self.gossip_stats["have_bytes"] += len(key)
                if key.hex() in res.get("want", []):
                    peer.gossip_tx(raw)
                    self.gossip_stats["raw_bytes"] += len(raw)
                else:
                    self.gossip_stats["deduped_bytes"] += len(raw)
            except Exception as e:  # noqa: BLE001 — a dead peer is fine
                log.info("gossip skip", peer=peer.base_url, error=str(e))

    # ---- catch-up (crash-fault rejoin, and recovery from a single
    # missed commit delivery) ----

    def maybe_catch_up(self) -> bool:
        """When no commit has landed for a liveness window and a peer is
        ahead, state-sync from it in place. This is what un-strands a
        validator that missed one commit POST (handle_commit refuses
        height gaps by design) and what lets a restarted process rejoin.

        Authentication: the snapshot's app hash must be corroborated by
        at least one OTHER ahead peer's stored block at the snapshot
        height whenever other ahead peers exist (a liar can always
        advertise the highest height, so "no one can check it" refuses
        rather than trusts); any explicit hash disagreement aborts. With
        a single configured peer the restore trusts it alone — the
        crash-fault devnet assumption, logged as authenticated=False.
        Returns True when a sync happened."""
        if self.halted:
            # a divergence halt preserves the forked local state for
            # forensics — never paper over it with a peer's state
            return False
        if time.monotonic() - self._last_commit < self.liveness_timeout:
            return False
        our_height = self.node.app.height
        ahead = []
        for peer in self.peers:
            try:
                if peer.status().get("height", 0) > our_height:
                    ahead.append(peer)
            except Exception:  # noqa: BLE001 — dead peer
                continue
        for peer in ahead:
            try:
                snap = peer.snapshot()
                if snap.get("height", 0) <= our_height:
                    continue  # peer is ahead but its snapshot is not
                others = [q for q in ahead if q is not peer]
                corroborations = 0
                for other in others:
                    blk = other.block(snap["height"])
                    if blk is None:
                        continue  # peer lacks that block (state-synced)
                    if blk.get("app_hash") != snap["app_hash"]:
                        log.error(
                            "catch-up abort: peers disagree on app hash",
                            height=snap["height"], peer=peer.base_url,
                            other=other.base_url,
                        )
                        return False
                    corroborations += 1
                if others and corroborations == 0:
                    # a liar can always ADVERTISE the highest height; it
                    # must not win by default just because no honest peer
                    # holds its fabricated block. Require at least one
                    # real corroboration whenever other ahead peers
                    # exist; maybe another candidate's snapshot (at a
                    # height others do hold) verifies instead.
                    log.info(
                        "catch-up skip: snapshot uncorroborated",
                        peer=peer.base_url, height=snap["height"],
                    )
                    continue
                self.node.restore_from_snapshot(
                    snap,
                    trusted_app_hash=(
                        snap["app_hash"] if corroborations else None
                    ),
                )
                with self._vote_lock:
                    self._voted = {
                        h: v for h, v in self._voted.items()
                        if h > self.node.app.height
                    }
                self._my_proposal = None
                self._last_commit = time.monotonic()
                log.info("caught up from peer", peer=peer.base_url,
                         height=self.node.app.height,
                         corroborated_by=corroborations)
                return True
            except Exception as e:  # noqa: BLE001 — try the next peer
                log.info("catch-up skip", peer=peer.base_url, error=str(e))
        return False

    # ---- leader drive ----

    def _app_hash_hex(self) -> str:
        store = self.node.app.store
        return store.app_hashes.get(store.version, b"").hex()

    def is_leader(self, height: int) -> bool:
        valset = self._valset()
        return bool(valset) and proposer_rotation(valset, height) == self.operator

    def try_propose(self, block_time: float | None = None) -> dict | None:
        """One consensus round, if it's our turn (or the leader looks
        dead). Returns the commit summary or None."""
        if self.halted:
            return None
        app = self.node.app
        height = app.height + 1
        leader = self.is_leader(height)
        if not leader and (
            time.monotonic() - self._last_commit < self.liveness_timeout
        ):
            return None  # the rotation leader is alive — let it drive

        cached = self._my_proposal
        if cached is not None and cached[0] == height:
            _h, body, ph, proposal, _created = cached  # retry identical round
        else:
            block_time = block_time if block_time is not None else time.time()
            with self.node._lock:
                proposal = app.prepare_proposal(self.node.mempool.reap())
            with self._vote_lock:
                # drop pooled evidence that no longer verifies (e.g. the
                # operator fully unbonded) — peers vote down proposals
                # carrying unverifiable entries, and an unprunable entry
                # would wedge every future proposal (liveness)
                for k, ev in list(self._pending_evidence.items()):
                    try:
                        verify_vote_evidence(
                            self._valset(), app.chain_id, ev
                        )
                    except ValueError as e:
                        log.info("dropping stale evidence", key=str(k),
                                 error=str(e))
                        del self._pending_evidence[k]
                pending_ev = sorted(
                    self._pending_evidence.values(), key=lambda e: e.key()
                )
                prior = self._voted.get(height)
                # round selection: strictly above our own prior vote
                # round (never re-sign a (height, round)), and above any
                # round we already burned in a failed attempt
                round_ = self._round_attempt.get(height, 0)
                if prior is not None and prior[0] >= round_:
                    round_ = prior[0] + 1
            body = {
                "height": height,
                "time": block_time,
                "round": round_,
                "proposer": self.operator,
                "square_size": proposal.square_size,
                "data_hash": proposal.hash.hex(),
                "txs": [t.hex() for t in proposal.txs],
            }
            if pending_ev:
                body["evidence"] = [e.to_json() for e in pending_ev]
            ph = self._prop_hash(body)
            self._my_proposal = (height, body, ph, proposal, time.monotonic())
        round_ = int(body.get("round", 0))
        valset = self._valset()

        with self._vote_lock:
            # the vote-once rule binds the proposer too: having voted
            # for another leader's fresh proposal at this height, we
            # must not sign a conflicting one of our own (same round),
            # nor abandon a fresh later-round vote
            prior = self._voted.get(height)
            if prior is not None and (prior[0], prior[1]) != (round_, ph):
                if prior[0] == round_ or prior[0] > round_:
                    # our cached round collided with a vote we since
                    # cast — regenerate at a higher round next tick
                    self._round_attempt[height] = prior[0] + 1
                    self._my_proposal = None
                    return None
                if time.monotonic() - prior[2] < self.liveness_timeout:
                    return None
            if prior is None or (prior[0], prior[1]) != (round_, ph):
                # stamp once per proposal, NOT per retry tick: refreshing
                # the timestamp on every retry would make our own vote
                # record never age out, permanently refusing a competing
                # proposal at this height (mutual refusal = liveness halt)
                self._voted[height] = (round_, ph, time.monotonic())
        votes = [
            make_vote(self.key, self.operator, app.chain_id, height, ph,
                      True, round_)
        ]
        for peer in self.peers:
            try:
                res = peer.consensus_proposal(body)
                if "vote" in res:
                    from celestia_tpu.node.consensus import Vote

                    v = Vote.from_json(res["vote"])
                    votes.append(v)
                    if v.accept:
                        # feed the equivocation watch with every peer
                        # accept vote this leader collects
                        self._record_accept_vote(
                            height, round_, v.operator, ph, v.signature
                        )
            except Exception as e:  # noqa: BLE001
                log.info("peer vote skip", peer=peer.base_url, error=str(e))

        accepted = tally(valset, app.chain_id, height, ph, votes, round_)
        total = total_power(valset)
        if not meets_quorum(accepted, total):
            log.info("round failed", height=height, round=round_,
                     power=f"{accepted}/{total}")
            # once this attempt has aged past the liveness window, burn
            # the round: peers that voted elsewhere only endorse a LATER
            # round, so retrying round_ forever would stall the height
            created = self._my_proposal[4] if self._my_proposal else 0.0
            if time.monotonic() - created > self.liveness_timeout:
                with self._vote_lock:
                    self._round_attempt[height] = round_ + 1
                self._my_proposal = None
            return None
        cert = CommitCert(height, ph, votes, round_)

        try:
            # evidence re-verification sits INSIDE the race guard: a
            # takeover commit landing between the tally and here can
            # change the valset (even unbond the equivocator), making
            # _body_evidence raise — that is the same benign race as the
            # expected_height guard below, not a fault
            block = self.node.apply_external_block(
                proposal.txs, proposal.square_size, proposal.hash,
                float(body["time"]),
                expected_height=height,
                evidence=self._body_evidence(body),
            )
        except ValueError as e:
            if self.node.app.height + 1 == height:
                raise  # deterministic rejection of our OWN block — halt
            # benign race: a takeover leader's commit landed between the
            # vote tally and our apply. Abandon the round and continue
            # at the new height — the validator process must survive.
            log.info("round overtaken", height=height, error=str(e))
            self._my_proposal = None
            return None
        self._my_proposal = None  # round closed
        self._last_commit = time.monotonic()
        with self._vote_lock:
            self._voted = {
                h: v for h, v in self._voted.items() if h > block.height
            }
            self._round_attempt = {
                h: r for h, r in self._round_attempt.items()
                if h > block.height
            }
            for d in body.get("evidence") or []:
                self._pending_evidence.pop(
                    (d["operator"], int(d["height"]), int(d.get("round", 0))),
                    None,
                )
        self._prune_evidence(block.height)
        commit_body = {**body, "cert": cert.to_json(),
                       "app_hash": block.app_hash.hex()}
        peer_hashes = {}
        for peer in self.peers:
            try:
                res = peer.consensus_commit(commit_body)
                peer_hashes[peer.base_url] = res.get("app_hash", res.get("error"))
            except Exception as e:  # noqa: BLE001
                log.info("peer commit skip", peer=peer.base_url, error=str(e))
        log.info("devnet block", height=block.height,
                 app_hash=block.app_hash.hex()[:16],
                 votes=f"{accepted}/{total}", peers=len(peer_hashes))
        return {
            "height": block.height,
            "app_hash": block.app_hash.hex(),
            "power": [accepted, total],
            "peer_hashes": peer_hashes,
        }


# ------------------------------------------------------------------ #
# process entry


def build_validator(genesis: dict, index: int, listen_port: int,
                    peer_ports: list[int], home: str | None = None,
                    liveness_timeout: float = 10.0):
    """Construct (Node, ValidatorNode, RpcServer) for validator `index`
    of a devnet genesis document:

        {"chain_id": ..., "accounts": {addr: amount},
         "validators": [{"secret": hex, "tokens": N}, ...],
         "malicious": {"index": i, "behavior": name}}  # optional
                                                       # fault injection

    The optional "malicious" key makes validator `index` run the
    rule-breaking app (testutil/malicious.py BehaviorConfig field
    names; adversarial devnet tests only).

    Every process derives the same genesis state, so height-0 app
    hashes agree by construction."""
    from celestia_tpu.app import App
    from celestia_tpu.node.rpc import RpcServer

    secrets = [bytes.fromhex(v["secret"]) for v in genesis["validators"]]
    keys = [PrivateKey.from_secret(s) for s in secrets]
    malicious = genesis.get("malicious") or {}
    if int(malicious.get("index", -1)) == index:
        # fault-injection for adversarial devnet tests: this PROCESS
        # runs the rule-breaking app (testutil/malicious.py) while the
        # honest processes defend (specs/fraud_proofs.md scenario)
        import dataclasses

        from celestia_tpu.testutil.malicious import (
            BehaviorConfig,
            MaliciousApp,
        )

        name = malicious.get("behavior", "corrupt_extension")
        valid = {f.name for f in dataclasses.fields(BehaviorConfig)}
        if name not in valid:
            # the child's stderr is usually discarded — a clear error
            # beats an opaque TypeError after a silent startup timeout
            raise ValueError(
                f"unknown malicious behavior {name!r}; expected one of "
                f"{sorted(valid)}"
            )
        behavior = BehaviorConfig(**{name: True})
        app = MaliciousApp(chain_id=genesis["chain_id"], behavior=behavior)
    else:
        app = App(chain_id=genesis["chain_id"])
    accounts = {k: int(v) for k, v in genesis.get("accounts", {}).items()}
    for key, v in zip(keys, genesis["validators"]):
        accounts.setdefault(key.bech32_address(), 0)
        accounts[key.bech32_address()] += int(v["tokens"])
    app.init_chain(
        accounts,
        genesis_time=float(genesis.get("genesis_time", 0.0)),
        genesis_validators={
            k.bech32_address(): int(v["tokens"])
            for k, v in zip(keys, genesis["validators"])
        },
    )
    # register consensus pubkeys (the gentx ConsensusPubkey field)
    for key in keys:
        val = app.staking.get_validator(key.bech32_address())
        val.pubkey = key.public_key().hex()
        app.staking.set_validator(val)
    app.store.commit_hash_refresh()

    node = Node(app, home=home)
    validator = ValidatorNode(
        node, keys[index],
        [f"http://127.0.0.1:{p}" for p in peer_ports],
        liveness_timeout=liveness_timeout,
    )
    server = RpcServer(node, port=listen_port)
    return node, validator, server


def write_genesis(path: str, n_validators: int = 3,
                  tokens: int = 10_000_000,
                  chain_id: str = "devnet-local") -> dict:
    """Write a throwaway devnet genesis: deterministic validator
    secrets (NEVER for anything but a local devnet) + a funded
    `devnet-faucet` account."""
    faucet = PrivateKey.from_secret(b"devnet-faucet")
    genesis = {
        "chain_id": chain_id,
        "accounts": {faucet.bech32_address(): 10**12},
        "validators": [
            {"secret": f"devnet-val-{i}".encode().hex(), "tokens": tokens}
            for i in range(n_validators)
        ],
    }
    pathlib.Path(path).write_text(json.dumps(genesis, indent=1))
    return genesis


def run_validator(args) -> None:
    genesis = json.loads(pathlib.Path(args.genesis).read_text())
    ports = [int(p) for p in args.ports.split(",")]
    listen = ports[args.index]
    peers = [p for i, p in enumerate(ports) if i != args.index]
    node, validator, server = build_validator(
        genesis, args.index, listen, peers, home=args.home or None,
        liveness_timeout=args.liveness_timeout,
    )
    server.start()
    log.info("validator up", index=args.index, port=listen,
             operator=validator.operator)
    try:
        while True:
            validator.maybe_catch_up()
            validator.try_propose()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()


def main(argv=None) -> int:
    # A devnet validator never needs the accelerator: honor a cpu
    # request at the config level too — N validator processes cannot
    # share one chip, and only one process at a time may hold it.
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        try:
            import jax

            jax.config.update("jax_platforms", "cpu")
        except Exception:  # noqa: BLE001 — no jax, nothing to pin
            pass
    parser = argparse.ArgumentParser(
        prog="python -m celestia_tpu.node.devnet",
        description="one validator process of a localhost devnet",
    )
    parser.add_argument("--genesis", required=True,
                        help="path to the shared genesis JSON")
    parser.add_argument("--index", type=int, required=True,
                        help="this validator's index in genesis.validators")
    parser.add_argument("--ports", required=True,
                        help="comma-separated RPC ports, one per validator")
    parser.add_argument("--home", default="",
                        help="block/snapshot persistence directory")
    parser.add_argument("--interval", type=float, default=1.0,
                        help="leader tick interval seconds")
    parser.add_argument("--liveness-timeout", type=float, default=10.0,
                        help="seconds before a peer takes over a dead leader")
    args = parser.parse_args(argv)
    run_validator(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
