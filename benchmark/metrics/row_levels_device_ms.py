"""row_levels_device_ms: device time per block of the row-levels program
the persist runs on the retained EDS (extend_tpu.eds_row_levels_device),
ms, from the profiler trace, matched by the program's name."""

PROGRAMS = [r"^jit_run\.extend\.row_levels\b"]


def read(run):
    ns, runs = run.device_ns(PROGRAMS)
    if not runs or not run.stats.get("blocks"):
        return None
    return ns / 1e6 / run.stats["blocks"]
