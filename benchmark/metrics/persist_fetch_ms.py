"""persist_fetch_ms: the EDS leaving the device for the store per block,
ms: the node.persist.fetch spans (the row-levels program, its levels'
fetch and the EDS's fetch), summed and divided by the blocks."""


def read(run):
    spans = run.spans_named("node.persist.fetch")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
