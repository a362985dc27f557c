"""jit_rebuilds: jitted programs rebuilt per block for a key their entry
had already built (an lru eviction rebuilt), from the program's
xla_rebuild_total counter over the window. None where the program does
not keep that counter."""

COUNTER = "xla_rebuild_total"


def read(run):
    blocks = run.stats.get("blocks")
    kept = any(key.partition("{")[0] == COUNTER for key in run.counters1)
    if not blocks or not kept:
        return None
    return run.counter_delta(COUNTER) / blocks
