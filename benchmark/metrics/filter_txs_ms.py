"""filter_txs_ms: PrepareProposal's re-check of the mempool's txs per
block, ms: the app.filter_txs spans less their blob.validate children
(which blob_validate_ms counts), i.e. the tx decode and the ante chain
run again over every reaped tx."""


def read(run):
    if not run.stats.get("blocks") or not run.spans_named("app.filter_txs"):
        return None
    t = run.self_time(("app.filter_txs",), ("blob.validate",))
    return t * 1e3 / run.stats["blocks"]
