"""Spans and counters inside the node (specs/observability.md): mempool
admission, blob validation, arena staging, square layout, DeliverTx and
Commit, the jit-rebuild counter, and the names of the extend programs
that a profiler trace charges device time to."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from celestia_tpu import blob as blob_pkg
from celestia_tpu import namespace as ns
from celestia_tpu import tracing
from celestia_tpu.app import App
from celestia_tpu.crypto import PrivateKey
from celestia_tpu.node import Node
from celestia_tpu.ops import blob_pool, extend_tpu
from celestia_tpu.telemetry import metrics
from celestia_tpu.tx import Fee, sign_tx
from celestia_tpu.x.blob.types import estimate_gas, new_msg_pay_for_blobs

CHAIN = "spans-1"
KEY = PrivateKey.from_secret(b"spans-signer")
K = 8


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.reset()
    yield
    tracing.reset()


def _blob_tx(seq: int, size: int) -> bytes:
    addr = KEY.bech32_address()
    data = np.random.default_rng(seq).integers(0, 256, size, np.uint8).tobytes()
    b = blob_pkg.new_blob(ns.new_v0(b"spans" + seq.to_bytes(4, "big")), data, 0)
    gas = estimate_gas([size])
    tx = sign_tx(KEY, [new_msg_pay_for_blobs(addr, b)], CHAIN, 0, seq,
                 Fee(amount=gas, gas_limit=gas))
    return blob_pkg.marshal_blob_tx(tx.marshal(), [b])


@pytest.fixture
def node():
    app = App(chain_id=CHAIN, extend_backend="tpu")
    app.init_chain({KEY.bech32_address(): 10**12}, genesis_time=0.0)
    app.enable_blob_pool(capacity_bytes=4 << 20)
    n = Node(app)
    n.produce_block(15.0)
    return n


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.span_id]


def _rebuilds() -> float:
    return metrics.get_counter("xla_rebuild_total", entry="blob_pool.insert")


def test_broadcast_tx_span_tree_and_insert_rebuild(node):
    """node.broadcast_tx > {app.check_tx > {blob.validate, ante.sig_verify},
    blob_pool.stage}; a rebuilt insert program is an xla.compile under
    blob_pool.stage and one xla_rebuild_total."""
    raw = _blob_tx(0, 5000)
    with tracing.record() as rec:
        assert node.broadcast_tx(raw).code == 0
    (root,) = [s for s in rec.spans if s.name == "node.broadcast_tx"]
    assert root.parent_id is None
    assert root.attrs["code"] == 0 and root.attrs["blobs"] == 1
    assert root.attrs["bytes"] == len(raw)
    kids = {s.name: s for s in _children(rec.spans, root)}
    assert set(kids) == {"app.check_tx", "blob_pool.stage"}
    assert kids["app.check_tx"].attrs == {"recheck": False, "code": 0}
    val, sig = _children(rec.spans, kids["app.check_tx"])
    assert val.name == "blob.validate"
    assert val.attrs == {"blobs": 1, "bytes": 5000}
    assert sig.name == "ante.sig_verify" and sig.attrs == {"sigs": 1}
    stage = kids["blob_pool.stage"].attrs
    assert stage == {"blobs": 1, "staged": 1, "resident_hits": 0}

    # the insert program for this slot size was built above: clear the
    # lru, and the next blob of the same slot size rebuilds a known key
    blob_pool._jitted_insert.cache_clear()
    before = _rebuilds()
    with tracing.record() as rec:
        assert node.broadcast_tx(_blob_tx(1, 5001)).code == 0
    assert _rebuilds() == before + 1
    (stage,) = [s for s in rec.spans if s.name == "blob_pool.stage"]
    compiles = [s for s in rec.spans if s.name == "xla.compile"]
    assert [c.attrs["entry"] for c in compiles] == ["blob_pool.insert"]
    assert compiles[0].parent_id == stage.span_id


def test_staging_resident_blob_is_a_hit(node):
    raw = _blob_tx(0, 3000)
    data = blob_pkg.unmarshal_blob_tx(raw)[0].blobs[0].data
    node.app.blob_pool.put(data)
    with tracing.record() as rec:
        assert node.broadcast_tx(raw).code == 0
    (stage,) = [s for s in rec.spans if s.name == "blob_pool.stage"]
    assert stage.attrs == {"blobs": 1, "staged": 0, "resident_hits": 1}


def test_stage_span_nests_under_dispatch_run(node):
    """With a dispatcher attached the staging runs on its thread; the
    dispatcher's own hand-off keeps the tree: xla.compile under
    blob_pool.stage under dispatch.run under node.broadcast_tx."""
    from celestia_tpu.node.dispatch import DeviceDispatcher
    from celestia_tpu.telemetry import Registry

    assert node.broadcast_tx(_blob_tx(0, 5000)).code == 0
    blob_pool._jitted_insert.cache_clear()
    node.dispatcher = DeviceDispatcher(registry=Registry()).start()
    try:
        with tracing.record() as rec:
            assert node.broadcast_tx(_blob_tx(1, 5001)).code == 0
    finally:
        assert node.dispatcher.drain()
        node.dispatcher = None
    by_id = {s.span_id: s for s in rec.spans}
    (comp,) = [s for s in rec.spans if s.name == "xla.compile"]
    chain = [comp]
    while chain[-1].parent_id is not None:
        chain.append(by_id[chain[-1].parent_id])
    assert [s.name for s in chain] == [
        "xla.compile", "blob_pool.stage", "dispatch.run", "node.broadcast_tx"]
    assert chain[1].tid == chain[2].tid != chain[3].tid


def test_produce_block_emits_layout_deliver_and_commit_spans(node):
    txs = [_blob_tx(i, 2000 + i) for i in range(3)]
    for raw in txs:
        assert node.broadcast_tx(raw).code == 0
    with tracing.record() as rec:
        block = node.produce_block(30.0)
    names = [s.name for s in rec.spans]
    assert names.count("square.build") == 1
    assert names.count("square.construct") == 1
    assert names.count("app.deliver_tx") == len(block.txs) == 3
    assert names.count("app.commit") == 1
    # CheckTx ran blob.validate before; the two proposal phases run it again
    assert names.count("blob.validate") == 2 * len(txs)
    for name in ("square.build", "square.construct"):
        (sp,) = [s for s in rec.spans if s.name == name]
        assert sp.attrs == {"txs": 3, "k": block.square_size}
    assert all(s.attrs == {"code": 0} for s in rec.spans
               if s.name == "app.deliver_tx")


def _module_name(builder, args, specs) -> str:
    # the builder body under its lru cache and devledger wrapper returns
    # the bare jax.jit, which lowers without running
    jitted = builder.__wrapped__.__wrapped__(*args)
    text = jitted.lower(*specs).as_text()
    return re.search(r"module @([\w.]+)", text).group(1)


def _square(k):
    return jax.ShapeDtypeStruct((k, k, 512), jnp.uint8)


def _eds(k):
    return jax.ShapeDtypeStruct((2 * k, 2 * k, 512), jnp.uint8)


@pytest.mark.parametrize("entry,builder,args,specs", [
    ("extend.for_k", extend_tpu._jitted_for_k, (K,), lambda: (_square(K),)),
    ("extend.roots_for_k", extend_tpu._jitted_roots_for_k, (K,),
     lambda: (_square(K),)),
    ("extend.eds_roots", extend_tpu._jitted_eds_roots, (K,),
     lambda: (_eds(K),)),
    ("extend.row_levels", extend_tpu._jitted_row_levels, (K,),
     lambda: (_eds(K),)),
    ("extend.assembled_roots", extend_tpu._jitted_assembled_roots,
     (K, 16, 8, 16, 1 << 16),
     lambda: (jax.ShapeDtypeStruct((1 << 16,), jnp.uint8),
              jax.ShapeDtypeStruct((16, 512), jnp.uint8),
              jax.ShapeDtypeStruct((4, 8), jnp.int32),
              jax.ShapeDtypeStruct((2, 16), jnp.int32),
              jax.ShapeDtypeStruct((8, 33), jnp.uint8))),
])
def test_extend_programs_are_named_after_their_entry(entry, builder, args,
                                                     specs):
    name = _module_name(builder, args, specs())
    assert name == f"jit_run.{entry}"
    # the benchmark's extend metrics match the family by this pattern
    assert re.search(r"^jit_run\b", name)
    assert re.search(rf"^jit_run\.{re.escape(entry)}\b", name + "(3)")


def _multi_blob_tx(seq: int, sizes: list[int]) -> bytes:
    addr = KEY.bech32_address()
    rng = np.random.default_rng(100 + seq)
    blobs = [blob_pkg.new_blob(ns.new_v0(b"multi" + bytes([seq, j])),
                               rng.integers(0, 256, s, np.uint8).tobytes(), 0)
             for j, s in enumerate(sizes)]
    gas = estimate_gas(sizes)
    tx = sign_tx(KEY, [new_msg_pay_for_blobs(addr, *blobs)], CHAIN, 0, seq,
                 Fee(amount=gas, gas_limit=gas))
    return blob_pkg.marshal_blob_tx(tx.marshal(), blobs)


def _ancestors(spans, span):
    by_id = {s.span_id: s for s in spans}
    names = []
    while span.parent_id is not None:
        span = by_id[span.parent_id]
        names.append(span.name)
    return names


def test_per_tx_and_per_blob_spans_and_insert_counter(node):
    """Two multi-blob PFBs through admission and one block: the ante's
    signature check under each of its four callers, the proposer's tx
    filter, the arena path's host packing, and one insert dispatch per
    blob staged."""
    txs = [_multi_blob_tx(0, [700, 1800, 3000]), _multi_blob_tx(1, [900, 2500])]
    before = metrics.get_counter("blob_arena_insert_calls_total")
    with tracing.record() as rec:
        for raw in txs:
            assert node.broadcast_tx(raw).code == 0
        block = node.produce_block(30.0)
    assert len(block.txs) == 2
    (stage_a, stage_b) = [s for s in rec.spans if s.name == "blob_pool.stage"]
    staged = stage_a.attrs["staged"] + stage_b.attrs["staged"]
    assert staged == 5
    assert metrics.get_counter("blob_arena_insert_calls_total") == before + staged

    sigs = [s for s in rec.spans if s.name == "ante.sig_verify"]
    assert all(s.attrs == {"sigs": 1} for s in sigs)
    callers = [next(a for a in _ancestors(rec.spans, s)
                    if a in ("app.check_tx", "app.filter_txs",
                             "app.process_proposal", "app.deliver_tx"))
               for s in sigs]
    assert sorted(callers) == sorted(["app.check_tx", "app.filter_txs",
                                      "app.process_proposal",
                                      "app.deliver_tx"] * 2)

    (filt,) = [s for s in rec.spans if s.name == "app.filter_txs"]
    assert filt.attrs == {"txs": 2, "kept": 2}
    assert _ancestors(rec.spans, filt)[0] == "app.prepare_proposal"

    packs = [s for s in rec.spans if s.name == "extend.assemble_pack"]
    assert len(packs) == 2  # Prepare and Process
    for sp in packs:
        assert _ancestors(rec.spans, sp)[0] == "extend.block"
        assert sp.attrs["blobs"] == 5
        assert 0 < sp.attrs["host_cells"] < block.square_size ** 2
