"""Tracing tests (specs/observability.md): span nesting/ordering,
explicit parent handoff, fault-site attribution through an ops call,
the Chrome trace-event export schema, the /debug/flight recorder
round-trip over a live RPC server, and the ADR-022 fleet layer —
trace-context parse/inject round-trip (malformed fuzz included), batch
span links under max_batch>1, merged-trace well-formedness via
tools/trace_merge, and the disabled path allocating nothing."""

import json
import threading
import time
import tracemalloc
import urllib.error
import urllib.request

import numpy as np
import pytest

from celestia_tpu import faults, tracing


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.reset()
    yield
    tracing.reset()


def _square(k: int, seed: int = 7) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(k, k, 512), dtype=np.uint8)


class TestSpans:
    def test_disabled_path_is_shared_noop(self):
        assert not tracing.enabled()
        s1 = tracing.span("a", k=1)
        s2 = tracing.span("b")
        assert s1 is s2  # one stateless object serves every call site
        with s1 as sp:
            assert sp.set(x=1) is sp
            assert tracing.current() is None
        assert tracing.flight() == []

    def test_nesting_ordering_and_parent_ids(self):
        with tracing.record() as rec:
            with tracing.span("outer", k=32) as outer:
                with tracing.span("mid") as mid:
                    assert tracing.current() is mid
                    with tracing.span("inner"):
                        pass
                with tracing.span("sibling"):
                    pass
        # children finish before parents: inner, mid, sibling, outer
        names = [s.name for s in rec.spans]
        assert names == ["inner", "mid", "sibling", "outer"]
        by_name = {s.name: s for s in rec.spans}
        assert by_name["outer"].parent_id is None
        assert by_name["mid"].parent_id == outer.span_id
        assert by_name["inner"].parent_id == mid.span_id
        assert by_name["sibling"].parent_id == outer.span_id
        assert by_name["outer"].attrs["k"] == 32
        # children are contained in the parent's interval
        for child in ("mid", "inner", "sibling"):
            s = by_name[child]
            assert s.start >= by_name["outer"].start
            assert s.start + s.duration <= (
                by_name["outer"].start + by_name["outer"].duration + 1e-6
            )

    def test_explicit_parent_handoff_across_threads(self):
        got = {}
        with tracing.record() as rec:
            with tracing.span("producer") as prod:
                handle = tracing.current()

                def consumer():
                    # fresh thread: empty stack, so parent= is the only link
                    assert tracing.current() is None
                    with tracing.span("consumer", parent=handle) as sp:
                        got["parent"] = sp.parent_id

                t = threading.Thread(target=consumer)
                t.start()
                t.join()
        assert got["parent"] == prod.span_id
        assert {s.name for s in rec.spans} == {"producer", "consumer"}

    def test_error_status_and_emit(self):
        with tracing.record() as rec:
            with pytest.raises(ValueError):
                with tracing.span("boom"):
                    raise ValueError("nope")
            import time

            t0 = time.perf_counter()
            tracing.emit("pre.timed", t0, end=t0 + 0.25, site="x")
        boom = next(s for s in rec.spans if s.name == "boom")
        assert boom.status == "error"
        assert boom.attrs["error"] == "ValueError"
        timed = next(s for s in rec.spans if s.name == "pre.timed")
        assert timed.duration == pytest.approx(0.25)
        assert timed.attrs["site"] == "x"

    def test_fault_attribution_through_ops_call(self):
        """A chaos-armed extend records WHICH fault sites struck inside
        the span (delay kind: fires without raising)."""
        from celestia_tpu.ops import extend_tpu

        sq = _square(8)
        with tracing.record() as rec:
            with faults.inject(
                faults.rule("device.extend", "delay", delay_s=0.0)
            ):
                extend_tpu.extend_roots_device(sq)
        dev = next(s for s in rec.spans if s.name == "extend.device")
        assert dev.attrs["backend"] == "tpu"
        assert dev.attrs["fault_hits"] == 1
        assert dev.attrs["fault_sites"] == "device.extend:delay"
        # the stage spans nest under the device span
        children = {s.name for s in rec.spans if s.parent_id == dev.span_id}
        assert {"extend.stage", "extend.rs_nmt"} <= children


class TestChromeExport:
    def test_schema_golden(self):
        """The exported document's structural contract — what Perfetto
        and the trace-smoke gate both rely on."""
        with tracing.record() as rec:
            with tracing.span("extend.block", backend="host", k=4):
                with tracing.span("extend.rs"):
                    pass
        doc = json.loads(json.dumps(rec.chrome()))  # must round-trip
        assert tracing.validate_chrome_trace(doc) == []
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        meta, *xs = events
        assert meta["ph"] == "M" and meta["name"] == "process_name"
        assert meta["args"] == {"name": "celestia_tpu"}
        assert [e["name"] for e in xs] == ["extend.rs", "extend.block"]
        for e in xs:
            assert set(e) == {"name", "cat", "ph", "ts", "dur",
                              "pid", "tid", "args"}
            assert e["ph"] == "X"
            assert e["cat"] == "extend"
            assert e["dur"] >= 0
            assert isinstance(e["args"]["span_id"], int)
        child, parent = xs
        assert child["args"]["parent_id"] == parent["args"]["span_id"]
        assert parent["args"]["backend"] == "host"
        assert "parent_id" not in parent["args"]  # root span

    def test_validator_catches_malformed_docs(self):
        assert tracing.validate_chrome_trace([]) == [
            "top level is not an object"
        ]
        assert tracing.validate_chrome_trace({}) == [
            "traceEvents is not a list"
        ]
        bad = {"traceEvents": [
            {"ph": "Q"},
            {"ph": "X", "name": "x", "pid": 1, "ts": 0.0, "dur": -1.0,
             "args": {}},
            {"ph": "X", "name": "y", "pid": 1, "args": {}},
        ]}
        problems = tracing.validate_chrome_trace(bad)
        assert any("unexpected ph" in p for p in problems)
        assert any("negative dur" in p for p in problems)
        assert any("missing ts" in p for p in problems)


class TestFlightRecorder:
    def test_ring_is_bounded_and_ordered(self):
        tracing.enable(flight_capacity=8)
        for i in range(20):
            with tracing.span(f"s{i}"):
                pass
        ring = tracing.flight()
        assert tracing.flight_capacity() == 8
        assert [d["name"] for d in ring] == [f"s{i}" for i in range(12, 20)]
        assert all(d["status"] == "ok" for d in ring)

    def test_debug_flight_roundtrip_over_rpc(self):
        """A traced request lands in /debug/flight, served next to
        /metrics (which must carry the v0.0.4 content type).

        Uses a stub node: the routes exercised here read only scalar
        app fields, and the stub keeps this test independent of the
        signing stack (full-node RPC coverage lives in test_node.py)."""
        from celestia_tpu.node.rpc import RpcServer

        class _App:
            chain_id = "trace-test"
            app_version = 3
            extend_backend = "numpy"
            _active_backend = None
            _tpu_strikes = 0
            _tpu_disabled = False

        class _Node:
            app = _App()
            mempool = ()
            started_at = 0.0

            def latest_height(self):
                return 0

        srv = RpcServer(_Node(), port=0)
        srv.start()
        tracing.enable()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            urllib.request.urlopen(f"{base}/status").read()
            with urllib.request.urlopen(f"{base}/metrics") as resp:
                assert resp.headers["Content-Type"] == (
                    "text/plain; version=0.0.4"
                )
            doc = json.loads(
                urllib.request.urlopen(f"{base}/debug/flight").read()
            )
        finally:
            srv.stop()
        assert doc["enabled"] is True
        assert doc["capacity"] == tracing.flight_capacity()
        reqs = [s for s in doc["spans"] if s["name"] == "rpc.request"]
        assert any(s["attrs"]["path"] == "/status" for s in reqs)
        status_span = next(
            s for s in reqs if s["attrs"]["path"] == "/status"
        )
        assert status_span["attrs"]["method"] == "GET"
        assert status_span["attrs"]["status"] == 200
        assert status_span["dur_us"] >= 0


def _stub_rpc_server():
    """Scalar-fields-only stub node behind the REAL RpcServer (same
    pattern as TestFlightRecorder — keeps these tests signing-free)."""
    from celestia_tpu.node.rpc import RpcServer

    class _App:
        chain_id = "trace-test"
        app_version = 3
        extend_backend = "numpy"
        _active_backend = None
        _tpu_strikes = 0
        _tpu_disabled = False

    class _Node:
        app = _App()
        mempool = ()
        started_at = 0.0

        def latest_height(self):
            return 0

    return RpcServer(_Node(), port=0)


class TestTraceContext:
    """ADR-022 wire format: X-Trace-Context parse/inject round-trip."""

    def test_mint_extract_round_trip(self):
        ctx = tracing.mint()
        assert len(ctx.trace_id) == 32 and int(ctx.trace_id, 16) != 0
        assert len(ctx.span_id) == 16
        back = tracing.extract(ctx.header_value())
        assert back is not None
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id
        assert back.flags == ctx.flags
        # the functional spelling (gateway hedge injection) agrees
        hdr = tracing.header_value(ctx.trace_id, ctx.span_id)
        assert tracing.extract(hdr).trace_id == ctx.trace_id

    def test_extract_normalizes_case_and_whitespace(self):
        ctx = tracing.extract(f"  00-{'AB' * 16}-{'CD' * 8}-01  ")
        assert ctx is not None
        assert ctx.trace_id == "ab" * 16
        assert ctx.span_id == "cd" * 8

    def test_wire_span_id_embeds_pid(self):
        import os

        wire = tracing.wire_span_id(7)
        assert len(wire) == 16
        assert wire[:8] == f"{os.getpid() & 0xFFFFFFFF:08x}"
        assert int(wire[8:], 16) == 7

    def test_malformed_fuzz_counted_and_ignored(self):
        """Every malformed shape returns None and bumps the counter —
        extract never raises (a bad header must never fail a request)."""
        from celestia_tpu.telemetry import metrics

        malformed = [
            "",
            "garbage",
            "00-abc-def-01",                          # wrong lengths
            "00-" + "z" * 32 + "-" + "1" * 16 + "-01",  # non-hex trace
            "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace
            "00-" + "a" * 32 + "-" + "b" * 16 + "-xx",  # non-hex flags
            "00-" + "a" * 32 + "-" + "b" * 16,          # missing flags
            "00-a-b-c-d",                             # too many fields
            "\x00\xff" * 8,
        ]
        before = metrics.get_counter("trace_context_invalid_total")
        for raw in malformed:
            assert tracing.extract(raw) is None, raw
        after = metrics.get_counter("trace_context_invalid_total")
        assert after == before + len(malformed)
        # absent header is NOT malformed: no count
        assert tracing.extract(None) is None
        assert metrics.get_counter("trace_context_invalid_total") == after

    def test_rpc_responses_carry_trace_id_even_on_errors(self):
        """X-Trace-Id rides every response — 404s included — and a
        malformed inbound context is ignored, never a 500."""
        srv = _stub_rpc_server()
        srv.start()
        tracing.enable()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            ctx = tracing.mint()
            req = urllib.request.Request(f"{base}/status")
            req.add_header(tracing.TRACE_HEADER, ctx.header_value())
            with urllib.request.urlopen(req) as resp:
                assert resp.headers[tracing.TRACE_ID_HEADER] == ctx.trace_id
            # 404 still answers with a (freshly minted) trace id
            try:
                urllib.request.urlopen(f"{base}/nope")
                raise AssertionError("expected 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
                assert e.headers[tracing.TRACE_ID_HEADER]
            # malformed context: request succeeds, fresh id minted
            req = urllib.request.Request(f"{base}/status")
            req.add_header(tracing.TRACE_HEADER, "not-a-context")
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
                tid = resp.headers[tracing.TRACE_ID_HEADER]
                assert tid and tid != ctx.trace_id
        finally:
            srv.stop()


class TestBatchSpanLinks:
    def test_members_and_batch_cross_link(self):
        """Under max_batch>1 the dispatch.batch span records every
        member's span id and each member's request span records the
        batch span id + the occupancy it rode at (ADR-022)."""
        from celestia_tpu.node.dispatch import DeviceDispatcher

        tracing.enable()
        d = DeviceDispatcher(capacity=16, batch_window_s=0.05,
                             max_batch=4).start()
        gate = threading.Event()
        results = {}
        try:
            with tracing.record() as rec:
                blocker = threading.Thread(
                    target=lambda: d.submit(lambda: gate.wait(5.0),
                                            label="blocker"))
                blocker.start()
                time.sleep(0.05)  # blocker now occupies the dispatcher

                def member(i):
                    with tracing.span("rpc.request", path=f"/sample/{i}"):
                        results[i] = d.submit(
                            batch_key="grp",
                            batch_exec=lambda ps: [p * 2 for p in ps],
                            payload=i, label="sample")

                threads = [threading.Thread(target=member, args=(i,))
                           for i in range(3)]
                for t in threads:
                    t.start()
                time.sleep(0.15)  # all three queued behind the blocker
                gate.set()
                for t in threads:
                    t.join()
                blocker.join()
        finally:
            assert d.drain(5.0)
        assert results == {0: 0, 1: 2, 2: 4}
        batch = next(s for s in rec.spans if s.name == "dispatch.batch")
        assert batch.attrs["jobs"] == 3
        member_ids = {int(x)
                      for x in batch.attrs["member_span_ids"].split(",")}
        reqs = [s for s in rec.spans if s.name == "rpc.request"]
        assert len(reqs) == 3
        assert {s.span_id for s in reqs} == member_ids
        for s in reqs:
            assert s.attrs["batch_span_id"] == batch.span_id
            assert s.attrs["batch_occupancy"] == 3
        # the batch span parents under the LEAD member's request span
        assert batch.parent_id in member_ids


class TestTraceMerge:
    def test_merged_trace_is_well_formed(self):
        """Two per-process documents joined by the hedge handshake merge
        into one valid doc: single trace id, distinct pids, every
        parent_id resolving inside its own process, and the wire-level
        parent link surviving the merge."""
        from celestia_tpu.tools import trace_merge

        tracing.enable()
        ctx = tracing.mint()
        # "gateway" process: route span + hedge span carrying the wire
        # id it injected as X-Trace-Context
        with tracing.record() as rec_gw:
            with tracing.span("gateway.route", key="/sample/1/0/0") as rt:
                rt.trace_id = ctx.trace_id
                rt.set(wire_parent=ctx.span_id)
                with tracing.span("gateway.hedge", backend="b0",
                                  attempt=0) as h:
                    wire = tracing.wire_span_id(h)
                    h.set(outcome="served", status=200)
                    time.sleep(0.002)
        # "backend" process: handler span recording that wire id as its
        # remote parent
        with tracing.record() as rec_be:
            with tracing.span("rpc.request", path="/sample/1/0/0") as sp:
                sp.trace_id = ctx.trace_id
                sp.set(wire_parent=wire)
                time.sleep(0.001)
        merged = trace_merge.merge_traces(
            [rec_gw.chrome(), rec_be.chrome()], ["gw", "b0"])
        assert tracing.validate_chrome_trace(merged) == []
        xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        assert {e["args"]["trace_id"] for e in xs} == {ctx.trace_id}
        # same OS pid in both files -> the merge must remap one
        assert len({e["pid"] for e in xs}) == 2
        ids_by_pid = {}
        for e in xs:
            ids_by_pid.setdefault(e["pid"], set()).add(e["args"]["span_id"])
        for e in xs:
            parent = e["args"].get("parent_id")
            if parent is not None:
                assert parent in ids_by_pid[e["pid"]]
        hedge = next(e for e in xs if e["name"] == "gateway.hedge")
        req = next(e for e in xs if e["name"] == "rpc.request")
        assert req["args"]["wire_parent"] == hedge["args"]["wire_span_id"]
        # the handshake put both files on one clock: the labelled
        # process_name metadata survived for Perfetto's track names
        labels = {e["args"]["name"]
                  for e in merged["traceEvents"] if e["ph"] == "M"}
        assert labels == {"celestia_tpu [gw]", "celestia_tpu [b0]"}


class TestDisabledPathAllocation:
    def test_disabled_hot_path_allocates_nothing(self):
        """With tracing off, the whole ADR-022 surface — spans and
        stages — must not allocate inside tracing.py (the
        <2% storm-bench bar depends on it)."""
        assert not tracing.enabled()

        def hot():
            for _ in range(50):
                with tracing.span("x", k=1) as sp:
                    sp.set(y=2)
                    assert tracing.current() is None
                tracing.emit("e", 0.0, end=0.0)
                with tracing.stage("device"):
                    pass
                tracing.add_stage("d2h", 0.001)
                tracing.merge_stages({"prove": 0.1})

        hot()  # warm lazy state (thread-local attrs, code objects)
        filt = [tracemalloc.Filter(True, tracing.__file__)]
        tracemalloc.start()
        try:
            base = tracemalloc.take_snapshot().filter_traces(filt)
            hot()
            snap = tracemalloc.take_snapshot().filter_traces(filt)
        finally:
            tracemalloc.stop()
        grew = [s for s in snap.compare_to(base, "lineno")
                if s.size_diff > 0]
        assert grew == [], [str(s) for s in grew]
