"""arena_stage_ms: blob staging into the device arena per block, ms: the
blob_pool.stage spans less their xla.compile descendants (the insert
programs rebuilt under them, which rejit_ms counts)."""


def read(run):
    if not run.stats.get("blocks") or not run.spans_named("blob_pool.stage"):
        return None
    t = run.self_time(("blob_pool.stage",), ("xla.compile",))
    return t * 1e3 / run.stats["blocks"]
