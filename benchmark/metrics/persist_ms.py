"""persist_ms: what a home directory adds to each block, ms: the
node.persist spans (the durable block record, the EDS fetch and its
row levels, the store put), summed and divided by the blocks. None
where the program has no such span."""


def read(run):
    spans = run.spans_named("node.persist")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
