"""block_record_ms: the durable block record per block, ms: the
node.persist.block_record spans (JSON encode, write, fsync, rename,
directory fsync), summed and divided by the blocks."""


def read(run):
    spans = run.spans_named("node.persist.block_record")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
