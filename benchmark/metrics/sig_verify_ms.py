"""sig_verify_ms: the ante chain's signature check per block, ms: the
ante.sig_verify spans of all four phases that run the ante (CheckTx,
PrepareProposal's filter, ProcessProposal, DeliverTx), summed and
divided by the blocks."""


def read(run):
    spans = run.spans_named("ante.sig_verify")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
