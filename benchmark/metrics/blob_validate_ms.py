"""blob_validate_ms: BlobTx validation per block, ms, over the three
phases that run it (CheckTx, PrepareProposal's filter, ProcessProposal):
the blob.validate spans, each a share-commitment recompute of every blob
of one tx, summed and divided by the blocks."""


def read(run):
    spans = run.spans_named("blob.validate")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
