"""The per-layer metrics of per-transaction and per-blob work (the
ante's signature check, the proposer's tx filter, the arena path's host
packing, the arena insert dispatches) on a synthetic run: each reads its
number, and each reads nothing (None) from a program that lacks the
span or counter it reads."""

import os
import sys
from dataclasses import dataclass

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

MS = 1e-3
NAMES = ("sig_verify_ms", "filter_txs_ms", "assemble_pack_ms", "arena_insert_calls")
COUNTER = "blob_arena_insert_calls_total"


@dataclass
class Span:
    name: str
    span_id: int
    parent_id: int | None
    start: float
    duration: float


def _spans():
    """Two blocks' worth: per block one admitted tx (CheckTx with its
    validation and signature check), the proposal's filter (validation
    and signature check under it) and packing, ProcessProposal's and
    DeliverTx's signature checks."""
    out = []
    sid = 0

    def add(name, parent, start, dur):
        nonlocal sid
        sid += 1
        out.append(Span(name, sid, parent, start, dur))
        return sid

    for b in range(2):
        t = b * 0.5
        check = add("app.check_tx", None, t, 10 * MS)
        add("blob.validate", check, t, 6 * MS)
        add("ante.sig_verify", check, t + 6 * MS, 1 * MS)
        prep = add("app.prepare_proposal", None, t + 0.1, 50 * MS)
        filt = add("app.filter_txs", prep, t + 0.1, 12 * MS)
        add("blob.validate", filt, t + 0.1, 5 * MS)
        add("ante.sig_verify", filt, t + 0.106, 2 * MS)
        ext = add("extend.block", prep, t + 0.12, 20 * MS)
        add("extend.assemble_pack", ext, t + 0.12, 3 * MS)
        proc = add("app.process_proposal", None, t + 0.2, 50 * MS)
        add("ante.sig_verify", proc, t + 0.21, 1.5 * MS)
        ext = add("extend.block", proc, t + 0.22, 20 * MS)
        add("extend.assemble_pack", ext, t + 0.22, 4 * MS)
        dtx = add("app.deliver_tx", None, t + 0.3, 5 * MS)
        add("ante.sig_verify", dtx, t + 0.3, 0.5 * MS)
    return out


def _run(spans=None, counters0=None, counters1=None):
    run = harness.Run(cell=None, seed=1, seconds=1.0, trace=True)
    run.stats.update(blocks=2, clock_perf_ns=0)
    run.w0, run.w1 = 0.0, 1.0
    run.spans = _spans() if spans is None else spans
    run.counters0 = {} if counters0 is None else counters0
    run.counters1 = {} if counters1 is None else counters1
    return run


def read(name, run):
    return harness.metric_reader(name)(run)


@pytest.mark.parametrize("name,want", [
    ("sig_verify_ms", 5.0),        # 1 + 2 + 1.5 + 0.5, all four callers
    ("filter_txs_ms", 7.0),        # 12 ms less the 5 ms validation
    ("assemble_pack_ms", 7.0),     # 3 (Prepare) + 4 (Process)
])
def test_span_reader_on_a_synthetic_run(name, want):
    assert read(name, _run()) == pytest.approx(want)


def test_arena_insert_calls_reads_the_counter_over_the_window():
    run = _run(counters0={COUNTER: 500.0}, counters1={COUNTER: 774.0})
    assert read("arena_insert_calls", run) == pytest.approx(137.0)
    # kept but still: no insert is a reading, not a silence
    run = _run(counters0={COUNTER: 9.0}, counters1={COUNTER: 9.0})
    assert read("arena_insert_calls", run) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_silent_on_a_program_without_its_source(name):
    """What an older program gives: the spans the benchmark already
    read, none of these, and no insert counter."""
    old = [s for s in _spans() if s.name not in
           ("ante.sig_verify", "app.filter_txs", "extend.assemble_pack")]
    run = _run(spans=old,
               counters0={'xla_rebuild_total{entry="blob_pool.insert"}': 1.0},
               counters1={'xla_rebuild_total{entry="blob_pool.insert"}': 1.0})
    assert read(name, run) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_silent_without_blocks(name):
    run = _run(counters0={COUNTER: 1.0}, counters1={COUNTER: 5.0})
    run.stats["blocks"] = 0
    assert read(name, run) is None


def test_every_new_metric_is_reported_in_both_produce_cells():
    spec = harness.bench_spec(ROOT)
    produce = [w["name"] for w in spec["workloads"]
               if harness.find_cell(w["name"], spec).mix["driver"] == "produce"]
    for m in spec["per_layer"]:
        if m["name"] in NAMES:
            assert m["workloads"] == produce and m["moves"] == "block_ms"
