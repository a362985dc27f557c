"""commitment_cache_hit_share on a synthetic run: hits over lookups of
the program's blob_commitment_lookups_total counter over the window, and
nothing (None) from a program that keeps no such counter or looked
nothing up."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "commitment_cache_hit_share"
HIT = 'blob_commitment_lookups_total{result="hit"}'
MISS = 'blob_commitment_lookups_total{result="miss"}'


def _run(counters0, counters1):
    run = harness.Run(cell=None, seed=1, seconds=1.0, trace=True)
    run.stats.update(blocks=16, clock_perf_ns=0)
    run.counters0, run.counters1 = counters0, counters1
    return run


def read(run):
    return harness.metric_reader(NAME)(run)


@pytest.mark.parametrize("c0,c1,want", [
    # 16 blocks of 39 blobs, three lookups a blob, the first a miss
    ({HIT: 100.0, MISS: 50.0}, {HIT: 100.0 + 2 * 624, MISS: 50.0 + 624}, 2 / 3),
    # the counter starts inside the window (no series at open)
    ({}, {HIT: 3.0, MISS: 1.0}, 0.75),
    # only misses: a node that validated none of the blobs before
    ({MISS: 9.0}, {MISS: 20.0}, 0.0),
])
def test_reads_hits_over_lookups_in_the_window(c0, c1, want):
    assert read(_run(c0, c1)) == pytest.approx(want)


@pytest.mark.parametrize("c0,c1", [
    # an older program: other counters, none of this one
    ({"blob_arena_insert_calls_total": 1.0}, {"blob_arena_insert_calls_total": 40.0}),
    # kept, but nothing looked up in the window: no share to read
    ({HIT: 6.0, MISS: 3.0}, {HIT: 6.0, MISS: 3.0}),
])
def test_is_silent_without_lookups(c0, c1):
    assert read(_run(c0, c1)) is None


def test_is_reported_in_both_produce_cells():
    spec = harness.bench_spec(ROOT)
    produce = [w["name"] for w in spec["workloads"]
               if harness.find_cell(w["name"], spec).mix["driver"] == "produce"]
    (m,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert m["workloads"] == produce and m["moves"] == "block_ms"
    assert m["source"] == "program_counter" and m["layer"] == "blob tx validation"
