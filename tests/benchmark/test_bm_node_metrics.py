"""The per-layer metrics that read the node's own spans and counters and
the named extend programs, on a synthetic run: each reads its number,
and each reads nothing (None) from a program that lacks what it reads."""

import os
import sys
from dataclasses import dataclass

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, trace  # noqa: E402

TPU = "/device:TPU:0"
MS = 1e-3


@dataclass
class Span:
    name: str
    span_id: int
    parent_id: int | None
    start: float
    duration: float


def _spans():
    """Two blocks' worth: per block one admission with a nested CheckTx,
    validation and staging (with a rebuilt insert under it), and the
    proposal's layout and validation spans."""
    out = []
    sid = 0

    def add(name, parent, start, dur):
        nonlocal sid
        sid += 1
        out.append(Span(name, sid, parent, start, dur))
        return sid

    for b in range(2):
        t = b * 0.5
        root = add("node.broadcast_tx", None, t, 40 * MS)
        check = add("app.check_tx", root, t, 10 * MS)
        add("blob.validate", check, t, 6 * MS)
        stage = add("blob_pool.stage", root, t + 10 * MS, 30 * MS)
        add("xla.compile", stage, t + 12 * MS, 20 * MS)
        prep = add("app.prepare_proposal", None, t + 0.1, 50 * MS)
        add("blob.validate", prep, t + 0.1, 5 * MS)
        add("square.build", prep, t + 0.11, 8 * MS)
        proc = add("app.process_proposal", None, t + 0.2, 50 * MS)
        add("blob.validate", proc, t + 0.2, 4 * MS)
        add("square.construct", proc, t + 0.21, 7 * MS)
    add("xla.compile", None, 0.9, 2 * MS)  # a build outside admission
    return out


def _device_trace(names=("jit_run.extend.assembled_roots",
                         "jit_run.extend.roots_for_k")):
    """Per block two proposal programs of 3 ms and one retention extend
    of 1 ms (the first two names), and an insert program of 5 ms."""
    dt = trace.DeviceTrace(devices=[TPU])
    mods = []
    for b in range(2):
        t = b * 0.5e9
        mods.append((f"{names[0]}({b})", t + 1e8, t + 1e8 + 3e6))
        mods.append((f"{names[0]}({b})", t + 2e8, t + 2e8 + 3e6))
        mods.append((f"{names[1]}({b})", t + 3e8, t + 3e8 + 1e6))
        mods.append(("jit_insert(9)", t, t + 5e6))
    dt.modules = {TPU: mods}
    dt.ops = {TPU: []}
    dt.clock_ns = 0.0
    return dt


def _run(spans=None, counters0=None, counters1=None, dt=None):
    run = harness.Run(cell=None, seed=1, seconds=1.0, trace=True)
    run.stats.update(blocks=2, clock_perf_ns=0)
    run.w0, run.w1 = 0.0, 1.0
    run.spans = _spans() if spans is None else spans
    run.counters0 = counters0 or {}
    run.counters1 = counters1 or {}
    run.device_trace = _device_trace() if dt is None else dt
    return run


def read(name, run):
    return harness.metric_reader(name)(run)


@pytest.mark.parametrize("name,want", [
    ("mempool_admit_ms", 40.0),
    ("arena_stage_ms", 10.0),          # 30 ms less the 20 ms rebuild
    ("rejit_ms", 21.0),                # (2 x 20 + 2) / 2 blocks
    ("blob_validate_ms", 15.0),        # 6 + 5 + 4
    ("square_layout_ms", 15.0),        # 8 + 7
    ("proposal_roots_device_ms", 6.0),
    ("retention_extend_device_ms", 1.0),
    ("check_tx_ante_ms", 4.0),         # 10 ms less the 6 ms validation
])
def test_reader_on_a_synthetic_run(name, want):
    assert read(name, _run()) == pytest.approx(want)


def test_jit_rebuilds_reads_the_counter_over_the_window():
    key = 'xla_rebuild_total{entry="blob_pool.insert"}'
    other = 'xla_rebuild_total{entry="extend.for_k"}'
    run = _run(counters0={key: 10.0, other: 0.0},
               counters1={key: 70.0, other: 0.0})
    assert read("jit_rebuilds", run) == pytest.approx(30.0)
    # kept but still: zero rebuilds is a reading, not a silence
    run = _run(counters0={key: 5.0}, counters1={key: 5.0})
    assert read("jit_rebuilds", run) == 0.0


def test_device_readers_split_the_extend_programs():
    """The two named programs sum to what extend_device_ms reads, which
    still matches both by ^jit_run\\b; the insert program is in neither."""
    run = _run()
    total = read("extend_device_ms", run)
    assert total == pytest.approx(7.0)
    assert (read("proposal_roots_device_ms", run)
            + read("retention_extend_device_ms", run)) == pytest.approx(total)
    for name in ("jit_run.extend.assembled_roots(3)",
                 "jit_run.extend.roots_for_k(4)"):
        dt = trace.DeviceTrace(devices=[TPU], ops={TPU: []},
                               modules={TPU: [(name, 0, 1)]}, clock_ns=0.0)
        assert trace.program_ns(dt, [r"^jit_run\b"]) == (1, 1)


def test_readers_are_silent_on_a_program_without_the_spans():
    """What an older program gives: none of the new spans, no rebuild
    counter, every extend program named plain jit_run."""
    run = _run(spans=[Span("bench.checktx", 1, None, 0.0, 0.01)],
               counters0={'xla_compile_total{entry="blob_pool.insert"}': 1.0},
               counters1={'xla_compile_total{entry="blob_pool.insert"}': 9.0},
               dt=_device_trace(names=("jit_run", "jit_run")))
    for name in ("mempool_admit_ms", "arena_stage_ms", "blob_validate_ms",
                 "square_layout_ms", "jit_rebuilds",
                 "proposal_roots_device_ms", "retention_extend_device_ms",
                 "check_tx_ante_ms"):
        assert read(name, run) is None, name
    assert read("rejit_ms", run) == 0.0  # no build in the window
    assert read("extend_device_ms", run) == pytest.approx(7.0)
