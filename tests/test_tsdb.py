"""Longitudinal telemetry plane (tools/tsdb.py + tools/obs_report.py,
specs/observability.md §Longitudinal telemetry).

Covers the exposition parser against the repo's own renderer (the
parse-everything round trip), the CRC32C-framed `.ctts` writer/reader
(budget downsampling, torn-tail tolerance, flipped-byte refusal),
counter-reset rebasing across simulated restarts, the Theil–Sen drift
detectors that judge the soak scenario, offline SLO re-judging via
``Recording.capture_at``, and the sparkline report renderer."""

import math
import os

import pytest

from celestia_tpu.slo import SloEngine
from celestia_tpu.telemetry import Registry
from celestia_tpu.tools import obs_report, tsdb


def _scraper(tmp_path, registry, **kw):
    path = os.path.join(tmp_path, "t.ctts")
    return tsdb.RegistryScraper(registry, path, **kw), path


# ---------------------------------------------------------------------- #
# exposition parsing


class TestParseExposition:
    def test_roundtrip_parses_everything_the_renderer_emits(self):
        """Every non-comment sample line the repo's renderer produces
        must come back as exactly one parsed sample with the same
        value — the scraper and the renderer are duals."""
        reg = Registry()
        reg.incr_counter("requests_total", 3.0)
        reg.incr_counter("requests_total", 2.0, route="/sample", code="200")
        reg.set_gauge("rss_bytes", 123456.0)
        reg.set_gauge("queue_depth", 7.0, shard='we"ird\nname\\x')
        reg.observe("serve", 0.004, exemplar="trace-abc")
        reg.observe("serve", 0.250, route="/dah")
        text = reg.prometheus_text()
        samples, types = tsdb.parse_exposition(text)

        rendered = [ln for ln in text.splitlines()
                    if ln.strip() and not ln.startswith("#")]
        assert len(samples) == len(rendered)

        by_key = {k: v for k, _f, _l, v in samples}
        assert by_key["requests_total"] == 3.0
        assert by_key['requests_total{code="200",route="/sample"}'] == 2.0
        assert by_key["rss_bytes"] == 123456.0
        assert types["requests_total"] == "counter"
        assert types["rss_bytes"] == "gauge"
        assert types["serve_seconds"] == "histogram"
        # escaped label values come back unescaped
        weird = next(labels for _k, _f, labels, _v in samples
                     if labels.get("shard"))
        assert weird["shard"] == 'we"ird\nname\\x'
        # histogram children map back to their TYPE family
        fams = {f for _k, f, _l, _v in samples}
        assert "serve_seconds" in fams
        assert not any(f.endswith("_bucket") for f in fams)

    def test_exemplar_and_malformed_lines_ignored(self):
        text = ("# TYPE x_total counter\n"
                "x_total 5\n"
                "# EXEMPLAR serve_seconds trace_id=t1 value=0.2\n"
                "garbage line without a number\n"
                "lonely_name\n")
        samples, _types = tsdb.parse_exposition(text)
        assert [(k, v) for k, _f, _l, v in samples] == [("x_total", 5.0)]

    def test_series_key_split_key_inverse(self):
        labels = {"b": "2", "a": "1"}
        key = tsdb.series_key("m_total", labels)
        assert key == 'm_total{a="1",b="2"}'
        assert tsdb.split_key(key) == ("m_total", {"a": "1", "b": "2"})
        assert tsdb.split_key("bare") == ("bare", {})


# ---------------------------------------------------------------------- #
# .ctts framing: write, read, rot


class TestCttsFile:
    def test_write_read_roundtrip_with_meta(self, tmp_path):
        reg = Registry()
        s, path = _scraper(tmp_path, reg, meta={"scenario": "unit"})
        reg.incr_counter("a_total", 1.0)
        s.scrape_once(t=1.0)
        reg.incr_counter("a_total", 2.0)
        reg.set_gauge("g", 9.0)
        s.scrape_once(t=2.0)
        s.stop(final_scrape=False)
        rec = tsdb.read(path)
        assert rec.meta["scenario"] == "unit"
        assert rec.meta["source"] == "registry://in-process"
        assert rec.series("a_total") == [(1.0, 1.0), (2.0, 3.0)]
        assert rec.series("g") == [(2.0, 9.0)]
        assert rec.t0 == 1.0 and rec.t1 == 2.0
        assert rec.types["a_total"] == "counter"

    def test_flipped_byte_is_refused(self, tmp_path):
        """Exhaustive single-byte corruption sweep: flipping ANY byte
        of the file must make the reader refuse. The per-frame header
        CRC is what makes this total — without it, a flipped length
        byte that overruns EOF would masquerade as a torn tail."""
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        for t in range(1, 6):
            reg.incr_counter("a_total", 1.0)
            s.scrape_once(t=float(t))
        s.stop(final_scrape=False)
        blob = bytearray(open(path, "rb").read())
        tolerated = []
        for i in range(len(blob)):
            broken = bytearray(blob)
            broken[i] ^= 0x01
            with open(path, "wb") as f:
                f.write(bytes(broken))
            try:
                tsdb.read(path)
                tolerated.append(i)
            except tsdb.IntegrityError:
                pass
        assert not tolerated, (
            f"{len(tolerated)} byte offsets of {len(blob)} survive a "
            f"flip unrefused (first: {tolerated[:5]})")

    def test_torn_tail_frame_is_tolerated(self, tmp_path):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        for t in range(1, 5):
            reg.incr_counter("a_total", 1.0)
            s.scrape_once(t=float(t))
        s.stop(final_scrape=False)
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[:-7])  # crash mid-append: half a tail frame
        rec = tsdb.read(path)
        # one sample short, nothing else lost, no corruption error
        assert len(rec.samples) == 3
        assert rec.series("a_total")[-1] == (3.0, 3.0)

    def test_bad_header_is_refused(self, tmp_path):
        path = os.path.join(tmp_path, "x.ctts")
        with open(path, "wb") as f:
            f.write(b"NOPE" + bytes(20))
        with pytest.raises(tsdb.IntegrityError):
            tsdb.read(path)

    def test_budget_downsamples_keeping_newest(self, tmp_path):
        reg = Registry()
        path = os.path.join(tmp_path, "b.ctts")
        s = tsdb.RegistryScraper(reg, path, budget_bytes=6_000)
        for t in range(1, 201):
            reg.incr_counter("a_total", 1.0)
            reg.set_gauge("g", float(t))
            s.scrape_once(t=float(t))
        s.stop(final_scrape=False)
        assert os.path.getsize(path) <= 6_000
        rec = tsdb.read(path)
        ts = [t for t, _ in rec.series("g")]
        # newest sample survives at full resolution; the oldest tail
        # was thinned/dropped — the recording still ENDS at now
        assert ts[-1] == 200.0
        assert len(ts) < 200
        assert ts == sorted(ts)


# ---------------------------------------------------------------------- #
# counter-reset rebasing (fleet respawns)


class TestResetRebasing:
    def test_restart_stays_monotone_and_is_counted(self, tmp_path):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        reg.incr_counter("req_total", 10.0)
        reg.observe("serve", 0.01)
        s.scrape_once(t=1.0)
        # process death: every cumulative series restarts at zero
        reg.reset()
        reg.incr_counter("req_total", 2.0)
        reg.observe("serve", 0.02)
        s.scrape_once(t=2.0)
        reg.incr_counter("req_total", 3.0)
        s.scrape_once(t=3.0)
        s.stop(final_scrape=False)
        assert s.reset_counts["req_total"] == 1
        rec = tsdb.read(path)
        assert rec.series("req_total") == [(1.0, 10.0), (2.0, 12.0),
                                           (3.0, 15.0)]
        assert sum(rec.resets.values()) >= 1

    def test_gauges_are_not_rebased(self, tmp_path):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        reg.set_gauge("g", 100.0)
        s.scrape_once(t=1.0)
        reg.set_gauge("g", 5.0)  # gauges legitimately fall
        s.scrape_once(t=2.0)
        s.stop(final_scrape=False)
        rec = tsdb.read(path)
        assert rec.series("g") == [(1.0, 100.0), (2.0, 5.0)]
        assert not s.reset_counts


# ---------------------------------------------------------------------- #
# drift detection


def _ramp(n, slope, base=100.0, noise=None):
    pts = []
    for i in range(n):
        v = base + slope * i
        if noise:
            v += noise[i % len(noise)]
        pts.append((float(i), v))
    return pts


class TestDrift:
    def test_theil_sen_ignores_outlier(self):
        pts = _ramp(30, 1.0)
        pts[15] = (15.0, 10_000.0)  # one garbage scrape
        assert abs(tsdb.theil_sen(pts) - 1.0) < 0.05

    def test_leak_drifts(self):
        v = tsdb.drift_verdict(_ramp(40, 5.0, base=100.0))
        assert v["drifting"] is True
        assert v["rel_growth"] > tsdb.DRIFT_REL_GROWTH

    def test_flat_does_not_drift(self):
        v = tsdb.drift_verdict(_ramp(40, 0.0, noise=[0.5, -0.5, 0.1]))
        assert v["drifting"] is False

    def test_compaction_sawtooth_does_not_drift(self):
        # grows 10 steps, compaction drops it back — bounded churn
        pts = [(float(i), 100.0 + (i % 10) * 20.0) for i in range(60)]
        v = tsdb.drift_verdict(pts)
        assert v["drifting"] is False

    def test_warmup_ramp_then_plateau_does_not_drift(self):
        pts = ([(float(i), 10.0 * i) for i in range(10)]
               + [(float(i), 100.0) for i in range(10, 60)])
        v = tsdb.drift_verdict(pts)
        assert v["drifting"] is False

    def test_too_few_samples_notes(self):
        v = tsdb.drift_verdict(_ramp(4, 5.0))
        assert v["drifting"] is False and v["note"] == "too few samples"

    def test_analyze_drift_absent_series_and_quantile_spec(self, tmp_path):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        for t in range(1, 21):
            reg.set_gauge("leak_bytes", float(t) * 1000.0)
            reg.observe("serve", 0.001 * t)
            s.scrape_once(t=float(t))
        s.stop(final_scrape=False)
        rec = tsdb.read(path)
        out = {d["series"]: d for d in tsdb.analyze_drift(
            rec, ("leak_bytes", "no_such_series", "serve:p99"))}
        assert out["leak_bytes"]["drifting"] is True
        assert out["no_such_series"]["drifting"] is False
        assert "absent" in out["no_such_series"]["note"]
        assert "drifting" in out["serve:p99"]

    def test_windowed_quantile_series_sees_interval_not_cumulative(
            self, tmp_path):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        # interval 1: fast observations; interval 2: slow ones. The
        # cumulative histogram dilutes the slowdown; the windowed diff
        # must expose it.
        for _ in range(100):
            reg.observe("serve", 0.001)
        s.scrape_once(t=1.0)
        for _ in range(100):
            reg.observe("serve", 0.001)
        s.scrape_once(t=2.0)
        for _ in range(100):
            reg.observe("serve", 0.5)
        s.scrape_once(t=3.0)
        s.stop(final_scrape=False)
        rec = tsdb.read(path)
        pts = tsdb.windowed_quantile_series(rec, "serve", q=0.5)
        assert len(pts) == 2
        assert pts[0][1] < 0.01  # first interval: fast
        assert pts[1][1] > 0.1   # last interval: the slowdown, undiluted


# ---------------------------------------------------------------------- #
# offline SLO re-judging


class TestCaptureAt:
    def test_recorded_capture_matches_live_judgement(self, tmp_path):
        reg = Registry()
        engine = SloEngine(registry=reg)
        s, path = _scraper(tmp_path, reg)
        reg.incr_counter("probe_sample_total", 10.0)
        reg.incr_counter("probe_sample_verified_total", 10.0)
        s.scrape_once(t=1.0)
        cap0_live = engine.capture()
        reg.incr_counter("probe_sample_total", 90.0)
        reg.incr_counter("probe_sample_verified_total", 90.0)
        for _ in range(50):
            reg.observe("extend_block", 0.002)
        s.scrape_once(t=2.0)
        cap1_live = engine.capture()
        s.stop(final_scrape=False)
        rec = tsdb.read(path)
        cap0 = rec.capture_at(engine.objectives, rec.t0)
        cap1 = rec.capture_at(engine.objectives, rec.t1)
        live = engine.evaluate_at((cap0_live, cap1_live))
        recorded = engine.evaluate_at((cap0, cap1))
        assert recorded["ok"] == live["ok"]
        by_live = {o["name"]: o for o in live["objectives"]}
        for o in recorded["objectives"]:
            assert o["ok"] == by_live[o["name"]]["ok"], o["name"]

    def test_histogram_at_reconstructs_cells(self, tmp_path):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        for v in (0.001, 0.001, 0.1, 2.0):
            reg.observe("serve", v)
        s.scrape_once(t=1.0)
        s.stop(final_scrape=False)
        rec = tsdb.read(path)
        cells, total_sum, count, bounds = rec.histogram_at("serve", 1.0)
        assert count == 4
        assert sum(cells) == 4
        assert math.isclose(total_sum, 2.102, rel_tol=1e-6)
        assert list(bounds) == sorted(bounds)


# ---------------------------------------------------------------------- #
# report renderer


class TestObsReport:
    def _recording(self, tmp_path):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        for t in range(1, 31):
            reg.set_gauge("leak_bytes", 1e6 + t * 50_000.0)
            reg.set_gauge("store_bytes", float(t % 7) * 1000.0)
            s.scrape_once(t=float(t))
        s.stop(final_scrape=False)
        return tsdb.read(path)

    def test_sparkline_shapes(self):
        assert obs_report.sparkline([]) == ""
        assert obs_report.sparkline([5.0, 5.0, 5.0]) == "▄▄▄"
        line = obs_report.sparkline([float(i) for i in range(100)], width=10)
        assert len(line) == 10
        assert line[0] == "▁" and line[-1] == "█"

    def test_report_rows_and_drift_gate(self, tmp_path):
        rec = self._recording(tmp_path)
        report = obs_report.build_report(
            rec, ("process_*", "leak_bytes", "store_bytes"),
            ("leak_bytes",))
        names = [r["series"] for r in report["rows"]]
        # the glob matches the auto-refreshed process gauges the
        # RegistryScraper writes into every recording
        assert "process_rss_bytes" in names and "store_bytes" in names
        assert "leak_bytes" in names
        assert all(r["spark"] for r in report["rows"])
        assert report["drift"][0]["drifting"] is True
        text = obs_report.render_text(report)
        assert "process_rss_bytes" in text and "DRIFTING" in text

    def test_cli_refuses_corrupt_and_gates_on_drift(self, tmp_path,
                                                    capsys):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        for t in range(1, 21):
            reg.set_gauge("leak", float(t))
            s.scrape_once(t=float(t))
        s.stop(final_scrape=False)
        assert obs_report.main([path, "--series", "leak"]) == 0
        assert obs_report.main([path, "--series", "leak",
                                "--drift", "leak"]) == 1
        capsys.readouterr()
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0x20
        with open(path, "wb") as f:
            f.write(bytes(blob))
        assert obs_report.main([path]) == 2


# ---------------------------------------------------------------------- #
# labeled gauge families: exposition <-> .ctts round trip (ADR-025)


class TestGaugeFamilyRoundTrip:
    """The device ledger exports its per-owner bytes as ONE gauge
    family fanned out by an `owner` label whose values are arbitrary
    registration strings — the full escape surface (`\\`, `"`,
    newline) must survive render -> parse -> durable file -> read."""

    NASTY = ('plain', 'quo"te', 'back\\slash', 'new\nline',
             'all\\three\n"at once')

    @pytest.fixture
    def no_ledger_owners(self, monkeypatch):
        """The scrape pull-publishes the process-wide device ledger into
        the test's registry; owners an earlier test on this worker left
        registered would join the family. Hide them for the test."""
        from celestia_tpu import devledger

        monkeypatch.setattr(devledger.ledger, "_owners", [])

    def test_owner_labeled_family_round_trips_to_disk(self, tmp_path,
                                                      no_ledger_owners):
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        for t in range(1, 5):
            for i, owner in enumerate(self.NASTY):
                reg.set_gauge("device_ledger_bytes",
                              t * 1000.0 + i, owner=owner)
            reg.set_gauge("dispatch_busy_ratio", 0.25 * t)
            s.scrape_once(t=float(t))
        s.stop(final_scrape=False)
        rec = tsdb.read(path)

        fam = [k for k in rec.names
               if k.split("{", 1)[0] == "device_ledger_bytes"]
        assert len(fam) == len(self.NASTY)
        owners = set()
        for key in fam:
            name, labels = tsdb.split_key(key)
            assert name == "device_ledger_bytes"
            owners.add(labels["owner"])
            # gauges are NOT rebased: the recorded points are the raw
            # set values at each scrape
            i = self.NASTY.index(labels["owner"])
            assert rec.series(key) == [
                (float(t), t * 1000.0 + i) for t in range(1, 5)]
            assert rec.types[key] == "gauge"
        assert owners == set(self.NASTY)
        # the scrape path pull-publishes the live ledger over this
        # gauge, so assert the series (not the injected value)
        assert len(rec.series("dispatch_busy_ratio")) == 4
        assert rec.types["dispatch_busy_ratio"] == "gauge"

    def test_renderer_parser_dual_fuzz_on_label_values(self):
        """Seeded fuzz: random label values drawn from the escape
        alphabet must come back verbatim through prometheus_text ->
        parse_exposition, and series_key/split_key must agree with the
        parse on every key."""
        import random

        rng = random.Random(20250807)
        alphabet = list('ab7/:-_ .') + ['\\', '"', '\n']
        for trial in range(40):
            value = "".join(rng.choice(alphabet)
                            for _ in range(rng.randint(0, 12)))
            owner = f"o{trial}"
            reg = Registry()
            reg.set_gauge("device_ledger_bytes", float(trial),
                          owner=owner, tag=value)
            samples, types = tsdb.parse_exposition(reg.prometheus_text())
            (key, _fam, labels, got), = samples
            assert labels == {"owner": owner, "tag": value}, repr(value)
            assert got == float(trial)
            assert tsdb.split_key(key) == (
                "device_ledger_bytes", {"owner": owner, "tag": value})
            assert tsdb.series_key("device_ledger_bytes", labels) == key


class TestObsReportDeviceSeries:
    def test_default_selection_renders_ledger_and_compile_series(
            self, tmp_path):
        """The obs_report default glob set must pick up the ADR-025
        series a soak recording carries: per-owner ledger bytes, the
        unattributed residue, the busy ratio, and the compile/retrace
        counters."""
        reg = Registry()
        s, path = _scraper(tmp_path, reg)
        for t in range(1, 11):
            reg.set_gauge("device_ledger_bytes", 4096.0 * t,
                          owner="eds_cache_paged")
            reg.set_gauge("device_ledger_unattributed_bytes", 512.0)
            reg.set_gauge("dispatch_busy_ratio", 0.5)
            reg.incr_counter("xla_compile_total", 1.0, entry="extend.roots")
            reg.incr_counter("xla_retrace_total", 1.0, entry="extend.roots")
            s.scrape_once(t=float(t))
        s.stop(final_scrape=False)
        rec = tsdb.read(path)

        report = obs_report.build_report(rec, obs_report.DEFAULT_SELECT, ())
        names = [r["series"] for r in report["rows"]]
        assert 'device_ledger_bytes{owner="eds_cache_paged"}' in names
        assert "device_ledger_unattributed_bytes" in names
        assert "dispatch_busy_ratio" in names
        assert 'xla_compile_total{entry="extend.roots"}' in names
        assert 'xla_retrace_total{entry="extend.roots"}' in names
        text = obs_report.render_text(report)
        assert "device_ledger_unattributed_bytes" in text
        assert "xla_retrace_total" in text
        # drift-judging the residue works over the same recording
        verdict = tsdb.analyze_drift(
            rec, ("device_ledger_unattributed_bytes",))[0]
        assert verdict["drifting"] is False
