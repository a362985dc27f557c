"""assemble_pack_ms: the arena path's host packing per block, ms: the
extend.assemble_pack spans (resident blob lookups, host-cell dedup),
which run inside extend.block before the assembled-roots program and
which neither proposal_host_ms nor the device metrics see."""


def read(run):
    spans = run.spans_named("extend.assemble_pack")
    if not spans or not run.stats.get("blocks"):
        return None
    return sum(s.duration for s in spans) * 1e3 / run.stats["blocks"]
