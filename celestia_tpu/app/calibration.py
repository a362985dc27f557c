"""Measured TPU/native backend crossover for `auto` (ADR-012).

The static `TPU_MIN_SQUARE = 16` gate was calibrated once from bench
configs 1–2 and never re-validated at the default governance square
k=64, where a host link's round-trip floor can flip the winner. This module replaces the guess with a measurement: at startup
(or on demand) the node times the actual proposal-path work — square →
DAH roots — on each available backend at a ladder of square sizes, and
`auto` then picks the measured winner for the square it is about to
extend. The table persists as JSON next to the node's TOML config
(`config/crossover.json`) so restarts skip the measurement, and a
`--calibrate-crossover` start refreshes it.

The measurement includes the transfers (roots_device uploads the square
and fetches the roots) — the whole point: the crossover is a property of
compute AND interconnect, not of the MXU alone.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import time

import numpy as np

from celestia_tpu.appconsts import SHARE_SIZE
from celestia_tpu.log import logger

log = logger("calibration")

DEFAULT_KS = (16, 32, 64, 128)
FILENAME = "crossover.json"
XOR_FILENAME = "xor_schedule.json"
XOR_DEFAULT_KS = (32, 64)


@dataclasses.dataclass
class CrossoverTable:
    """Per-k best-of latencies (ms) per backend, e.g.
    {64: {"tpu": 120.3, "native": 95.1}}. Only backends that were
    actually available at measurement time appear; the resolver
    re-checks availability at decision time, so a table measured on a
    TPU host degrades safely on a CPU-only one."""

    entries: dict[int, dict[str, float]]
    measured_at: float = 0.0

    def winner(self, k: int) -> str | None:
        """Measured fastest backend for a k×k square, or None when the
        table is empty. Unmeasured k use the nearest measured rung in
        log2 distance (latency is roughly polynomial in k, so the
        geometrically nearest measurement extrapolates best); ties go
        to the smaller rung."""
        if not self.entries:
            return None
        target = math.log2(max(1, k))
        best_k = min(
            self.entries,
            key=lambda m: (abs(math.log2(m) - target), m),
        )
        timings = self.entries[best_k]
        if not timings:
            return None
        return min(timings, key=lambda b: timings[b])

    def to_json(self) -> dict:
        return {
            "entries": {
                str(k): dict(v) for k, v in sorted(self.entries.items())
            },
            "measured_at": self.measured_at,
        }

    @classmethod
    def from_json(cls, d: dict) -> "CrossoverTable":
        return cls(
            entries={
                int(k): {str(b): float(ms) for b, ms in v.items()}
                for k, v in d.get("entries", {}).items()
            },
            measured_at=float(d.get("measured_at", 0.0)),
        )

    def save(self, path: str | pathlib.Path) -> None:
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.to_json(), indent=2))

    @classmethod
    def load(cls, path: str | pathlib.Path) -> "CrossoverTable | None":
        """None when missing or unreadable — a corrupt table must never
        keep a node from starting (auto falls back to the static gate)."""
        try:
            return cls.from_json(json.loads(pathlib.Path(path).read_text()))
        except Exception:  # noqa: BLE001 — absent/corrupt == uncalibrated
            return None


def crossover_path(home: str | pathlib.Path) -> pathlib.Path:
    # mirrors config.config_dir(home) without importing config (whose
    # tomllib dependency needs Python 3.11+; this module stays light)
    return pathlib.Path(home) / "config" / FILENAME


_default_table: "CrossoverTable | None" = None
_default_loaded = False


def load_default_table() -> "CrossoverTable | None":
    """The repo-committed default table (`<repo>/config/crossover.json`),
    recalibrated whenever a PR lands a measured step-change (ADR-019).

    Every fresh App attaches this so `auto` routes on measured numbers
    even before a node-home calibration exists; a home table (cli start)
    or an explicit `calibrate_crossover()` always overrides it. The
    committed file carries `measured_at: 0`, which the SLO freshness
    check treats as never-stale — it is a default, not a live
    measurement of this host's hardware, and the winner re-check in
    `resolve_extend_backend` keeps it from routing to absent backends.
    Loaded once per process; None when the file is absent or corrupt."""
    global _default_table, _default_loaded
    if not _default_loaded:
        repo_root = pathlib.Path(__file__).resolve().parents[2]
        _default_table = CrossoverTable.load(repo_root / "config" / FILENAME)
        _default_loaded = True
    return _default_table


_xor_table: "CrossoverTable | None" = None
_xor_loaded = False


def load_xor_table() -> "CrossoverTable | None":
    """The repo-committed XOR-schedule A/B table
    (`<repo>/config/xor_schedule.json`), same CrossoverTable format as
    the backend table but with contraction-spelling keys
    ("dense"/"xor") instead of backend names. Refreshed whenever
    `bench.py --xor-schedule` lands a measured step-change (ADR-024).
    Loaded once per process; None when absent or corrupt."""
    global _xor_table, _xor_loaded
    if not _xor_loaded:
        repo_root = pathlib.Path(__file__).resolve().parents[2]
        _xor_table = CrossoverTable.load(repo_root / "config" / XOR_FILENAME)
        _xor_loaded = True
    return _xor_table


def xor_winner(k: int) -> str:
    """Measured winner ("dense" or "xor") for the contraction spelling
    at square size k. Dense when the table is absent or empty — the
    dense bit-matmul is the always-correct default; the schedule only
    routes on a measurement that says it is faster."""
    table = load_xor_table()
    if table is None:
        return "dense"
    return table.winner(k) or "dense"


def measure_xor_crossover(
    ks: tuple[int, ...] = XOR_DEFAULT_KS, repeats: int = 3
) -> CrossoverTable:
    """A/B the two contraction spellings through the SAME jitted
    roots-only core the proposal path runs (`_jitted_roots_noeds` with
    the spelling pinned), per k. Both spellings are plain XLA programs,
    so this measures on any backend — the fused-kernel choice is
    resolved independently and left at its default here."""
    import jax

    from celestia_tpu.ops import extend_tpu

    entries: dict[int, dict[str, float]] = {}
    for k in ks:
        rng = np.random.default_rng(k)
        arr = rng.integers(0, 256, size=(k, k, SHARE_SIZE), dtype=np.uint8)
        dev = jax.device_put(arr)
        timings: dict[str, float] = {}
        for name, pin in (("dense", False), ("xor", True)):
            fn = extend_tpu._jitted_roots_noeds(k, xor=pin)
            timings[name] = _best_of(
                lambda: jax.block_until_ready(fn(dev)), repeats
            )
        entries[k] = timings
        log.info("xor crossover rung", k=k,
                 **{s: round(ms, 3) for s, ms in timings.items()})
    return CrossoverTable(entries, measured_at=time.time())


def _best_of(fn, repeats: int) -> float:
    """Best-of wall ms after one untimed warmup (absorbs jit compiles /
    library init — the steady-state number is what the node lives on)."""
    fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def measure_crossover(
    ks: tuple[int, ...] = DEFAULT_KS, repeats: int = 2
) -> CrossoverTable:
    """Time the proposal-path unit of work — square bytes in, DAH axis
    roots out, transfers included — per available backend per k.

    Share bytes are random (roots cost is content-independent; namespace
    validity only matters to square construction, which is not what is
    being timed). numpy is not measured: when neither accelerator nor
    native toolchain is present the resolver's fallback order already
    lands there, and timing k=128 host extensions would stall startup."""
    from celestia_tpu import native
    from celestia_tpu.app.app import accelerator_available

    entries: dict[int, dict[str, float]] = {}
    for k in ks:
        rng = np.random.default_rng(k)
        arr = rng.integers(0, 256, size=(k, k, SHARE_SIZE), dtype=np.uint8)
        timings: dict[str, float] = {}
        if accelerator_available():
            from celestia_tpu.ops import extend_tpu

            timings["tpu"] = _best_of(
                lambda: extend_tpu.roots_device(arr), repeats
            )
        if native.available():
            timings["native"] = _best_of(
                lambda: native.extend_and_root_native(arr), repeats
            )
        if timings:
            entries[k] = timings
            log.info("crossover rung", k=k,
                     **{b: round(ms, 3) for b, ms in timings.items()})
    return CrossoverTable(entries, measured_at=time.time())
