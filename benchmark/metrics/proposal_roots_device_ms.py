"""proposal_roots_device_ms: device time per block of the proposal's
arena-assembled roots program (PrepareProposal and ProcessProposal each
run it), ms, from the profiler trace, matched by the program's name."""

PROGRAMS = [r"^jit_run\.extend\.assembled_roots\b"]


def read(run):
    ns, runs = run.device_ns(PROGRAMS)
    if not runs or not run.stats.get("blocks"):
        return None
    return ns / 1e6 / run.stats["blocks"]
