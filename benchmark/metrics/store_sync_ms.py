"""store_sync_ms: waiting for the disk per block, ms: the store.fsync and
store.dirsync spans of the FsShim (the block record's and the EDS
file's), summed and divided by the blocks."""


def read(run):
    spans = run.spans_named("store.fsync") + run.spans_named("store.dirsync")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
