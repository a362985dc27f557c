"""rejit_ms: host time per block spent building jitted programs inside
the window, ms: every xla.compile span (trace, lower and a load from the
persistent cache, or a compile), summed and divided by the blocks. A
warm window builds nothing but what the lru caches evicted."""


def read(run):
    if not run.stats.get("blocks"):
        return None
    spans = run.spans_named("xla.compile")
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
