"""arena_insert_calls: blob arena insert programs dispatched per block,
from the program's blob_arena_insert_calls_total counter over the
window (one per blob staged). None where the program does not keep
that counter."""

COUNTER = "blob_arena_insert_calls_total"


def read(run):
    blocks = run.stats.get("blocks")
    kept = any(key.partition("{")[0] == COUNTER for key in run.counters1)
    if not blocks or not kept:
        return None
    return run.counter_delta(COUNTER) / blocks
