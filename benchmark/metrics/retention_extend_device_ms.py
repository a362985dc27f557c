"""retention_extend_device_ms: device time per block of the ExtendBlock
retention's extend (extend_roots_device_resident, whose program is the
one `_jitted_roots_for_k` makes), ms, from the profiler trace, matched
by the program's name."""

PROGRAMS = [r"^jit_run\.extend\.roots_for_k\b"]


def read(run):
    ns, runs = run.device_ns(PROGRAMS)
    if not runs or not run.stats.get("blocks"):
        return None
    return ns / 1e6 / run.stats["blocks"]
