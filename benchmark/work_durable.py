"""The least HBM bytes of the program a durable node adds per block: the
row-levels program (extend_tpu's `extend.row_levels`) reads the EDS and
writes every level of every row's NMT. Counts depend only on k."""

from __future__ import annotations

from benchmark.work import ROOT_BYTES, SHARE


def row_levels_bytes(k: int) -> int:
    """EDS in (2k x 2k shares), and out, for each of the 2k rows, its
    2k leaves, 2k-1 inner nodes and root: 4k-1 nodes of 90 bytes."""
    w = 2 * k
    return w * w * SHARE + w * (2 * w - 1) * ROOT_BYTES
