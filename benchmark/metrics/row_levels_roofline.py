"""row_levels_roofline: share of the HBM roofline of the row-levels
program, %: its least HBM bytes per block (work_durable.py: the EDS in,
every level of every row's NMT out) over the chip's published
bandwidth is the least time, divided by the device time the trace gives
the program. v5e publishes no integer-VPU peak, so the bound is HBM
bytes."""

from benchmark import work, work_durable

PROGRAMS = [r"^jit_run\.extend\.row_levels\b"]


def read(run):
    ns, runs = run.device_ns(PROGRAMS)
    blocks = run.stats.get("blocks")
    if not runs or not blocks:
        return None
    least = blocks * work_durable.row_levels_bytes(run.cell.config["square_size"])
    bw = work.peak(run.stats["device_kind"], "hbm_bytes_per_s")
    return 100.0 * (least / bw) / (ns / 1e9)
