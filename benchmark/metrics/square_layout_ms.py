"""square_layout_ms: square layout per block, ms: PrepareProposal's
square.build and ProcessProposal's square.construct spans, summed and
divided by the blocks."""


def read(run):
    spans = run.spans_named("square.build") + run.spans_named("square.construct")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
