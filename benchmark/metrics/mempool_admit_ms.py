"""mempool_admit_ms: mempool admission per block, ms, timed inside the
program: the node.broadcast_tx spans (CheckTx, mempool add, arena
staging) of the window, summed and divided by its blocks. The program's
own twin of checktx_ms, which times the same calls from outside."""


def read(run):
    spans = run.spans_named("node.broadcast_tx")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
