"""Perf-regression sentinel over the bench ledger (`make bench-gate`).

Bench rounds were recorded as ``BENCH_r<N>.json`` files under the repo
root; a kernel regression (losing the repair speedup, a transfer path
going quadratic) would ship silently. This module turns that history
into a per-metric LEDGER and gates on it:
``python bench.py --check-regressions`` / ``make bench-gate`` exits
nonzero with a readable table when any tracked wall regresses beyond
threshold against its own noise-aware baseline.

Since PR 21 the repo carries no ``BENCH_r*.json`` rounds (the driver's
``PERF_LEDGER.jsonl`` is the chip record), so the bench-wall series —
every TRACKED entry with extraction paths — are inert: they gate
nothing until the first benchmark PR feeds them from that ledger. The
series folded from the storm/scenario/soak ledgers still gate.

Input reality (ADR-014): the round records are heterogeneous —
``parsed`` may be a clean dict, null (the stored ``tail`` keeps only
the LAST 2000 chars of output, decapitating the JSON line), or an
error record from a round where the accelerator was unreachable. The
loader therefore parses in three tiers:

    1. ``parsed`` dict (not an error record) — trust it outright;
    2. a full ``{``-prefixed JSON line found in ``tail``;
    3. SALVAGE: balanced-brace extraction of individual
       ``"<config>": {...}`` objects out of the truncated tail — the
       decapitated rounds still carry complete per-config objects.

Baselines are median ± MAD over the metric's history (ADR-014: the
median ignores the odd outlier round; MAD is the matching robust
spread — a couple of noisy rounds cannot widen a stdev-based
band into uselessness). The newest point regresses only when it is
BOTH beyond ``threshold ×`` the baseline AND outside the noise band
(baseline + 3·1.4826·MAD, floored at 5% of baseline) — the double
gate keeps a low-noise metric from tripping on a rounding wiggle and a
high-noise metric from hiding a real 2× loss. Metrics with fewer than
``min_history`` points report informationally and never gate.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys

# every tracked wall is milliseconds, lower-is-better. Each entry lists
# (config, field) extraction paths tried in order — the bench output
# schema grew across rounds, so older rounds expose the same wall under
# the headline record while newer ones nest it in configs.
TRACKED: dict[str, list[tuple[str | None, str]]] = {
    # extend: the headline k=128 device wall
    "extend_k128_tpu_ms": [(None, "value"), ("3_headline_k128", "tpu_ms")],
    # repair: k=128 25% erasure device wall
    "repair_k128_tpu_ms": [("4_repair_k128_25pct", "tpu_ms")],
    # node-path: proposal wall, roots-only (the serving-critical wall)
    "node_path_k128_wall_ms": [("8_node_path_k128",
                                "tpu_wall_roots_only_ms")],
    # transfer: the two transfer-dominated walls
    "repair_k128_transfers_wall_ms": [("4_repair_k128_25pct",
                                       "tpu_wall_with_transfers_ms")],
    "node_path_k128_eds_fetch_ms": [("8_node_path_k128",
                                     "tpu_wall_with_eds_fetch_ms")],
    # fused extend+hash roots-only pipeline at the governance-default
    # square (ADR-019, bench.py --fused-kernels): the wall that decides
    # the k=64 crossover. A regression here silently re-opens the gap
    # the fused kernel closed, so the step-change gates once it has
    # history.
    "fused_ms_per_square_k64": [("12_fused_kernels_k64",
                                 "fused_ms_per_square")],
    # XOR-schedule contraction at the governance-default square
    # (ADR-024, bench.py --xor-schedule): ms/square of the sparse
    # CSE-shared schedule through the roots-only core. Rides the same
    # lower-is-better double gate as the walls above once it has
    # min_history points — a regression here means the schedule
    # compiler (or its XLA lowering) lost the ground the A/B won.
    "xor_schedule_ms_per_square_k64": [("13_xor_schedule_k64",
                                        "xor_ms_per_square")],
    # the recalibrated crossover point: the TPU side of the k=64 rung.
    # History accrues from the measured fused config like the series
    # above, but the loader appends the COMMITTED table's rung
    # (config/crossover.json entries["64"]["tpu"]) as the final point —
    # committing a recalibration whose k=64 TPU wall regressed against
    # the measured trajectory fails the gate, tying `auto` routing to
    # real numbers.
    "crossover_k64_tpu_ms": [("12_fused_kernels_k64",
                              "fused_ms_per_square")],
    # serving: per-accepted-sample wall of the batched das-storm phase
    # (`make storm-bench`). Not extracted from BENCH rounds — the
    # loader folds it in from storm_ledger.json, hence no paths here.
    "storm_ms_per_accepted_sample": [],
    # ragged serving (ISSUE 14): per-accepted-sample wall of the
    # crowd-ragged das-storm phase — the multi-height flash crowd
    # answered through the widened ("sample",) key + page-table gather.
    # Folded from storm_ledger.json runs that carry the ragged series
    # key.
    "ragged_ms_per_accepted_sample": [],
    # horizontal serving: per-accepted-sample wall of the fleet phase
    # of `bench.py --gateway-fleet` (`make gateway-bench`, ADR-021) —
    # N backends behind the consistent-hash gateway, every accepted
    # sample NMT-verified. Folded from storm_ledger.json runs that
    # carry the gateway series key.
    "gateway_ms_per_accepted_sample": [],
    # robustness: contract breaches per scenario run (`make scenario-*`,
    # specs/scenarios.md) — 0 means every SLO and invariant held. Folded
    # from scenario_ledger.json; a breaching run judges as a regression
    # against the all-zero baseline.
    "scenario_slo_pass": [],
    # scale-out: aggregate blocks/sec of the mesh phase of
    # `bench.py --multichip-pipeline` (`make multichip-bench`,
    # specs/parallel.md §Block pipeline) — the row-sharded 3-deep
    # pipeline on the dp·sp virtual mesh. HIGHER is better (the only
    # such series): a collapse here means sharding overhead ate the
    # scale-out win. Folded from storm_ledger.json runs.
    "multichip_blocks_per_sec": [],
    # OS-process fleet (ADR-023): per-accepted-sample wall of the
    # fleet-N phase of `bench.py --gateway-fleet --processes N` — N
    # real supervised backend subprocesses behind the gateway with a
    # live block stream. Folded from storm_ledger.json runs that carry
    # the fleet series keys.
    "fleet_ms_per_accepted_sample": [],
    # OS-process fleet block stream: blocks/sec the supervisor pushed
    # through every ready process during the same phase. HIGHER is
    # better: a collapse means the fan-out grow path stopped scaling.
    "fleet_blocks_per_sec": [],
    # longitudinal soak (specs/observability.md §Longitudinal
    # telemetry): count of drift-judged series the Theil–Sen detector
    # flagged in a soak run. Folded from soak_ledger.json; the healthy
    # trajectory is all zeros, so a drifting run regresses against the
    # all-zero baseline exactly like scenario_slo_pass.
    "soak_drift_breaches": [],
    # open-loop sweep knee: the last sustainable offered rate of the
    # das-sweep load curve (samples/s at the knee, or the top measured
    # step when the knee was not reached). HIGHER is better — a falling
    # knee means the serving path lost headroom. Folded from
    # soak_ledger.json runs that carry a knee.
    "soak_knee_samples_per_sec": [],
    # compile watchdog (ADR-025): post-warmup recompiles of known
    # jitted entries per recorded run. Lower is better and the healthy
    # trajectory is all zeros — a geometry-churn regression (a builder
    # keyed on something unstable, a cache losing its shape memo)
    # regresses against the all-zero baseline exactly like
    # soak_drift_breaches. Folded from soak_ledger.json.
    "soak_steadystate_retraces": [],
}

# throughput series: the regression direction is inverted — the gate
# trips when the newest point FALLS below the baseline beyond
# threshold+band. Everything else in TRACKED is a wall (lower-better).
HIGHER_IS_BETTER = {"multichip_blocks_per_sec", "fleet_blocks_per_sec",
                    "soak_knee_samples_per_sec"}

DEFAULT_THRESHOLD = 1.5  # newest/baseline ratio that counts as regression
DEFAULT_MIN_HISTORY = 3  # points before a metric gates


# ---------------------------------------------------------------------- #
# tier-3 salvage: pull per-config objects out of a decapitated JSON line


def salvage_configs(tail: str) -> dict:
    """Balanced-brace extraction of ``"<name>": {...}`` objects from a
    truncated bench line. Only top-level-looking config names (leading
    digit, e.g. ``4_repair_k128_25pct``) are kept; fragments that do
    not parse are skipped — a half-truncated object yields nothing
    rather than garbage."""
    out: dict = {}
    for m in re.finditer(r'"([0-9][0-9a-z_]*)"\s*:\s*\{', tail):
        name, start = m.group(1), m.end() - 1
        depth = 0
        for i in range(start, len(tail)):
            ch = tail[i]
            if ch == "{":
                depth += 1
            elif ch == "}":
                depth -= 1
                if depth == 0:
                    try:
                        out[name] = json.loads(tail[start:i + 1])
                    except ValueError:
                        pass
                    break
        # unbalanced to EOF: the object itself was truncated — drop it
    return out


def parse_round(doc: dict) -> dict | None:
    """One BENCH_r*.json record -> {"headline": float|None,
    "configs": dict} or None when the round carries no usable data
    (nonzero rc / error record)."""
    if doc.get("rc", 1) != 0:
        return None
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "error" in parsed:
        return None
    headline = None
    configs: dict = {}
    if isinstance(parsed, dict):
        headline = parsed.get("value")
        configs = parsed.get("configs") or {}
    if not configs:
        tail = doc.get("tail", "") or ""
        for line in tail.splitlines():
            if line.startswith("{"):
                try:
                    j = json.loads(line)
                except ValueError:
                    continue
                headline = headline if headline is not None else j.get("value")
                configs = j.get("configs") or {}
                break
        if not configs:
            configs = salvage_configs(tail)
    if headline is None and not configs:
        return None
    return {"headline": headline, "configs": configs}


def _extract(metric: str, parsed: dict) -> float | None:
    for config, field in TRACKED[metric]:
        if config is None:
            v = parsed.get("headline")
        else:
            cfg = parsed.get("configs", {}).get(config)
            v = cfg.get(field) if isinstance(cfg, dict) else None
        if isinstance(v, (int, float)):
            return float(v)
    return None


# ---------------------------------------------------------------------- #
# ledger assembly


def load_ledger(root: str) -> dict[str, list[tuple[str, float]]]:
    """Repo-root history -> {metric: [(round_label, value_ms), ...]}
    oldest→newest."""
    ledger: dict[str, list[tuple[str, float]]] = {m: [] for m in TRACKED}
    rounds = sorted(
        glob.glob(os.path.join(root, "BENCH_r*.json")),
        key=lambda p: int(re.search(r"r(\d+)", os.path.basename(p)).group(1)),
    )
    for path in rounds:
        label = os.path.basename(path)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = parse_round(doc)
        if parsed is None:
            continue
        for metric in TRACKED:
            v = _extract(metric, parsed)
            if v is not None:
                ledger[metric].append((label, v))
    # committed crossover table (ADR-019): its k=64 TPU rung becomes
    # the FINAL point of the crossover series, so the gate judges the
    # committed routing numbers against the measured fused-config
    # history
    xover_path = os.path.join(root, "config", "crossover.json")
    if os.path.exists(xover_path):
        try:
            with open(xover_path) as f:
                xover = json.load(f)
        except (OSError, ValueError):
            xover = None
        if isinstance(xover, dict):
            v = (xover.get("entries", {}).get("64") or {}).get("tpu")
            if isinstance(v, (int, float)):
                ledger["crossover_k64_tpu_ms"].append(
                    ("config/crossover.json", float(v)))
    # storm ledger (`bench.py --das-storm --ledger`): its own capped
    # run history, already oldest→newest — each run is one point of the
    # storm_ms_per_accepted_sample series
    storm_path = os.path.join(root, "storm_ledger.json")
    if os.path.exists(storm_path):
        try:
            with open(storm_path) as f:
                storm = json.load(f)
        except (OSError, ValueError):
            storm = None
        if isinstance(storm, dict):
            for idx, run in enumerate(storm.get("runs") or []):
                v = (run.get("ms_per_accepted_sample")
                     if isinstance(run, dict) else None)
                if isinstance(v, (int, float)):
                    ledger["storm_ms_per_accepted_sample"].append(
                        (f"storm_ledger.json#{idx}", float(v)))
                g = (run.get("gateway_ms_per_accepted_sample")
                     if isinstance(run, dict) else None)
                if isinstance(g, (int, float)):
                    ledger["gateway_ms_per_accepted_sample"].append(
                        (f"storm_ledger.json#{idx}", float(g)))
                r = (run.get("ragged_ms_per_accepted_sample")
                     if isinstance(run, dict) else None)
                if isinstance(r, (int, float)):
                    ledger["ragged_ms_per_accepted_sample"].append(
                        (f"storm_ledger.json#{idx}", float(r)))
                b = (run.get("multichip_blocks_per_sec")
                     if isinstance(run, dict) else None)
                if isinstance(b, (int, float)):
                    ledger["multichip_blocks_per_sec"].append(
                        (f"storm_ledger.json#{idx}", float(b)))
                fm = (run.get("fleet_ms_per_accepted_sample")
                      if isinstance(run, dict) else None)
                if isinstance(fm, (int, float)):
                    ledger["fleet_ms_per_accepted_sample"].append(
                        (f"storm_ledger.json#{idx}", float(fm)))
                fb = (run.get("fleet_blocks_per_sec")
                      if isinstance(run, dict) else None)
                if isinstance(fb, (int, float)):
                    ledger["fleet_blocks_per_sec"].append(
                        (f"storm_ledger.json#{idx}", float(fb)))
    # scenario ledger (`python -m celestia_tpu.scenarios --ledger`):
    # each run's breach count is one point of the scenario_slo_pass
    # series — the healthy trajectory is all zeros, so any breaching
    # scenario run fails the gate against its median baseline
    scen_path = os.path.join(root, "scenario_ledger.json")
    if os.path.exists(scen_path):
        try:
            with open(scen_path) as f:
                scen = json.load(f)
        except (OSError, ValueError):
            scen = None
        if isinstance(scen, dict):
            for idx, run in enumerate(scen.get("runs") or []):
                v = run.get("breaches") if isinstance(run, dict) else None
                if isinstance(v, (int, float)):
                    name = run.get("scenario", "?")
                    ledger["scenario_slo_pass"].append(
                        (f"scenario_ledger.json#{idx}:{name}", float(v)))
    # soak ledger (`python -m celestia_tpu.scenarios soak
    # --soak-ledger`): each run contributes its drift-breach count and,
    # when the run carried a load sweep, the knee rate
    soak_path = os.path.join(root, "soak_ledger.json")
    if os.path.exists(soak_path):
        try:
            with open(soak_path) as f:
                soak = json.load(f)
        except (OSError, ValueError):
            soak = None
        if isinstance(soak, dict):
            for idx, run in enumerate(soak.get("runs") or []):
                if not isinstance(run, dict):
                    continue
                name = run.get("scenario", "?")
                d = run.get("drift_breaches")
                if isinstance(d, (int, float)):
                    ledger["soak_drift_breaches"].append(
                        (f"soak_ledger.json#{idx}:{name}", float(d)))
                k = run.get("knee_samples_per_sec")
                if isinstance(k, (int, float)):
                    ledger["soak_knee_samples_per_sec"].append(
                        (f"soak_ledger.json#{idx}:{name}", float(k)))
                sr = run.get("steadystate_retraces")
                if isinstance(sr, (int, float)):
                    ledger["soak_steadystate_retraces"].append(
                        (f"soak_ledger.json#{idx}:{name}", float(sr)))
    return ledger


# ---------------------------------------------------------------------- #
# baselines + verdicts


def judge(history: list[tuple[str, float]], threshold: float,
          min_history: int, higher_is_better: bool = False) -> dict:
    """Newest point vs the median±MAD baseline of its predecessors.

    ``ratio`` is always the BADNESS ratio (>1 means worse): newest ÷
    baseline for walls, baseline ÷ newest for throughput series — so
    the threshold and the rendered table read identically either way."""
    values = [v for _, v in history]
    n = len(values)
    if n < min_history:
        return {"n": n, "gating": False, "regressed": False,
                "note": f"informational (<{min_history} points)"}
    current_label, current = history[-1]
    prior = values[:-1]
    baseline = statistics.median(prior)
    mad = statistics.median(abs(v - baseline) for v in prior)
    # 1.4826·MAD ≈ σ for normal noise; floor at 5% of baseline so a
    # zero-MAD series (best-of cache repeats identical values) still
    # tolerates measurement wiggle
    band = max(3 * 1.4826 * mad, 0.05 * baseline)
    if higher_is_better:
        ratio = baseline / current if current else float("inf")
        regressed = ratio > threshold and current < baseline - band
    else:
        ratio = current / baseline if baseline else float("inf")
        regressed = ratio > threshold and current > baseline + band
    return {
        "n": n, "gating": True, "regressed": regressed,
        "current": current, "current_label": current_label,
        "baseline": baseline, "mad": mad, "band": band,
        "ratio": ratio,
    }


def check(root: str, threshold: float = DEFAULT_THRESHOLD,
          min_history: int = DEFAULT_MIN_HISTORY) -> dict:
    ledger = load_ledger(root)
    report = {}
    for metric, history in ledger.items():
        report[metric] = judge(history, threshold, min_history,
                               higher_is_better=metric in HIGHER_IS_BETTER)
        report[metric]["history"] = history
    report_ok = not any(r["regressed"] for r in report.values())
    return {"ok": report_ok, "threshold": threshold,
            "min_history": min_history, "metrics": report}


def render_table(result: dict) -> str:
    """The human-readable gate output (one row per tracked wall)."""
    rows = [("metric", "n", "baseline", "current", "ratio", "verdict")]
    for metric, r in sorted(result["metrics"].items()):
        if not r["gating"]:
            rows.append((metric, str(r["n"]), "-", "-", "-", r["note"]))
            continue
        verdict = "REGRESSED" if r["regressed"] else "ok"
        rows.append((
            metric, str(r["n"]),
            f"{r['baseline']:.3f}", f"{r['current']:.3f}",
            f"{r['ratio']:.2f}x", verdict,
        ))
    widths = [max(len(row[i]) for row in rows) for i in range(len(rows[0]))]
    lines = []
    for i, row in enumerate(rows):
        lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    tail = ("PASS: no tracked wall regressed beyond "
            f"{result['threshold']}x its baseline"
            if result["ok"] else
            "FAIL: tracked wall regression detected (see table)")
    return "\n".join(lines) + "\n" + tail


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_ledger",
        description="Gate on bench-ledger perf regressions",
    )
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        help="directory holding the BENCH_r*.json rounds and the "
             "storm/scenario/soak ledgers (default: the repo root)")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="current/baseline ratio that counts as a "
                         f"regression (default {DEFAULT_THRESHOLD})")
    ap.add_argument("--min-history", type=int, default=DEFAULT_MIN_HISTORY,
                    help="points a metric needs before it gates "
                         f"(default {DEFAULT_MIN_HISTORY})")
    ap.add_argument("--json", action="store_true",
                    help="emit the full machine-readable report")
    args = ap.parse_args(argv)
    result = check(args.root, threshold=args.threshold,
                   min_history=args.min_history)
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        print(render_table(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
