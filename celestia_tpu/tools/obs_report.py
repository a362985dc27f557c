"""Render a recorded `.ctts` run — sparkline dashboard + drift report.

``python -m celestia_tpu.tools.obs_report run.ctts`` turns a durable
recording (tools/tsdb.py) into the two artifacts a soak review needs:

  * a terminal dashboard — one ASCII sparkline row per series, with
    first/last values and the Theil–Sen slope, so a 20-minute soak's
    memory trajectory is legible at a glance without any plotting
    dependency;
  * ``--json`` — the same content machine-readable (CI attaches it to
    the run artifacts next to soak_ledger.json).

Series selection: ``--series`` takes exact keys or ``prefix*`` globs
and may repeat; the default picks the process gauges plus any series
the recording's drift verdict would judge. ``--drift`` reruns
``tsdb.analyze_drift`` over named series (``family:p99`` quantile
specs work, same grammar as Scenario.drift_series) and the exit code
is nonzero when anything drifts — the CLI doubles as a standalone
offline drift gate over any saved recording.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import sys

from celestia_tpu.tools import tsdb

# eight-level unicode sparkline alphabet, lowest to highest
_TICKS = "▁▂▃▄▅▆▇█"

# when --series is not given: host-resource gauges plus the store and
# cache residency series a soak watches, and the device runtime ledger
# plane (ADR-025): per-owner HBM attribution, the unattributed
# remainder, compile/retrace counters, and device-lane occupancy
DEFAULT_SELECT = (
    "process_rss_bytes", "process_open_fds", "process_threads",
    "store_bytes", "store_heights", "store_read_only", "eds_cache_*",
    "device_ledger_*", "dispatch_busy_ratio", "xla_compile_total*",
    "xla_retrace_total*",
)


def sparkline(values: list[float], width: int = 48) -> str:
    """Downsample ``values`` to ``width`` buckets (bucket mean) and
    render each against the series' own min..max range. A flat series
    renders as a run of mid ticks rather than dividing by zero."""
    if not values:
        return ""
    if len(values) > width:
        # mean-pool into exactly `width` buckets
        n = len(values)
        pooled = []
        for b in range(width):
            lo, hi = b * n // width, max(b * n // width + 1,
                                         (b + 1) * n // width)
            chunk = values[lo:hi]
            pooled.append(sum(chunk) / len(chunk))
        values = pooled
    vmin, vmax = min(values), max(values)
    span = vmax - vmin
    if span <= 0:
        return _TICKS[3] * len(values)
    top = len(_TICKS) - 1
    return "".join(_TICKS[min(top, int((v - vmin) / span * top))]
                   for v in values)


def _fmt(v: float) -> str:
    if abs(v) >= 1 << 20:
        return f"{v / (1 << 20):.1f}Mi"
    if abs(v) >= 10_000:
        return f"{v / 1000:.1f}k"
    if v == int(v):
        return str(int(v))
    return f"{v:.4g}"


def select_series(rec: tsdb.Recording,
                  patterns: tuple[str, ...]) -> list[str]:
    out: list[str] = []
    for pat in patterns:
        if any(ch in pat for ch in "*?["):
            out.extend(k for k in rec.names if fnmatch.fnmatch(k, pat))
        elif pat in rec.names:
            out.append(pat)
    # de-dup preserving order
    seen: set[str] = set()
    return [k for k in out if not (k in seen or seen.add(k))]


def series_row(rec: tsdb.Recording, key: str, width: int) -> dict:
    pts = rec.series(key)
    values = [v for _, v in pts]
    slope = tsdb.theil_sen(pts) if len(pts) >= 2 else 0.0
    return {
        "series": key,
        "points": len(pts),
        "first": values[0] if values else 0.0,
        "last": values[-1] if values else 0.0,
        "min": min(values) if values else 0.0,
        "max": max(values) if values else 0.0,
        "slope_per_s": slope,
        "spark": sparkline(values, width),
    }


def build_report(rec: tsdb.Recording, patterns: tuple[str, ...],
                 drift_specs: tuple[str, ...],
                 width: int = 48) -> dict:
    keys = select_series(rec, patterns)
    report = {
        "meta": rec.meta,
        "span_s": round(rec.t1 - rec.t0, 3),
        "samples": len(rec.samples),
        "series_total": len(rec.names),
        "counter_resets": sum(rec.resets.values()),
        "rows": [series_row(rec, k, width) for k in keys],
        "drift": tsdb.analyze_drift(rec, drift_specs)
        if drift_specs else [],
    }
    return report


def render_text(report: dict) -> str:
    lines = []
    meta = report["meta"]
    head = meta.get("scenario") or meta.get("source") or ""
    lines.append(f"recording {head}: {report['samples']} samples / "
                 f"{report['series_total']} series over "
                 f"{report['span_s']}s"
                 + (f", {report['counter_resets']} counter resets"
                    if report["counter_resets"] else ""))
    name_w = max((len(r["series"]) for r in report["rows"]), default=0)
    for r in report["rows"]:
        lines.append(
            f"  {r['series']:{name_w}s} {r['spark']} "
            f"{_fmt(r['first'])} -> {_fmt(r['last'])} "
            f"(slope {r['slope_per_s']:+.3g}/s)")
    for d in report["drift"]:
        mark = "DRIFTING" if d.get("drifting") else "flat"
        note = d.get("note")
        extra = (f" rel_growth={d['rel_growth']:.2f}"
                 if "rel_growth" in d else f" ({note})" if note else "")
        lines.append(f"  drift {mark:8s} {d['series']}{extra}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m celestia_tpu.tools.obs_report",
        description="sparkline dashboard + offline drift gate over a "
                    "recorded .ctts run")
    ap.add_argument("path", help="the .ctts recording to render")
    ap.add_argument("--series", action="append", default=[],
                    metavar="KEY_OR_GLOB",
                    help="series to render (exact key or glob; "
                         "repeatable; default: process gauges + "
                         "store/cache residency)")
    ap.add_argument("--drift", action="append", default=[],
                    metavar="SERIES[:pNN]",
                    help="rerun the Theil-Sen drift verdict over this "
                         "series (repeatable; exit 1 if any drifts)")
    ap.add_argument("--width", type=int, default=48,
                    help="sparkline width in cells (default 48)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report instead of "
                         "the dashboard")
    args = ap.parse_args(argv)
    try:
        rec = tsdb.read(args.path)
    except tsdb.IntegrityError as e:
        print(f"refusing corrupt recording: {e}", file=sys.stderr)
        return 2
    patterns = tuple(args.series) or DEFAULT_SELECT
    report = build_report(rec, patterns, tuple(args.drift),
                          width=args.width)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render_text(report))
    return 1 if any(d.get("drifting") for d in report["drift"]) else 0


if __name__ == "__main__":
    sys.exit(main())
