"""Observability plane (edl_tpu/obs): registry concurrency under the
lockgraph detector, windowed-vs-cumulative histogram contract, trace
context across BOTH wire seams (incl. 0-d tensors and garbled frames),
Prometheus text-format conformance, recorder overflow/dump, and the
jax-free import assert."""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from edl_tpu.obs import metrics, recorder, trace


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Tracing on with a per-test sink dir; ring cleared both ways."""
    # the traces counted here are the tests' own: no `host.clock_gap`
    # of a loaded test machine among them (tests/test_tracing.py has
    # the sampler's tests)
    monkeypatch.setattr(trace, "_start_sampler", lambda: None)
    monkeypatch.setenv("EDL_TPU_TRACE", str(tmp_path / "trace"))
    trace.reconfigure()
    yield str(tmp_path / "trace")
    monkeypatch.delenv("EDL_TPU_TRACE", raising=False)
    trace.reconfigure()


# -- histogram: the windowed-vs-cumulative contract --------------------------

class TestHistogram:
    def test_snapshot_shape_matches_the_teacher_wire(self):
        h = metrics.Histogram(metrics.LOG_BUCKETS_MS)
        for v in (0.5, 3.0, 70.0, 99999.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap == {1.0: 1, 5.0: 1, 100.0: 1, float("inf"): 1}
        assert h.count == 4 and h.sum == pytest.approx(100072.5)

    def test_windowed_differencing_is_exact(self):
        """The registrar contract pinned as a regression: a teacher
        whose lifetime histogram says 10ms but whose WINDOW says
        1000ms must show the slow window, not the fast past."""
        h = metrics.Histogram(metrics.LOG_BUCKETS_MS)
        for _ in range(1000):
            h.observe(10.0)           # a long fast history
        fast_cum = h.snapshot()
        for _ in range(50):
            h.observe(900.0)          # this interval: slow
        win = metrics.Histogram.window(h.snapshot(), fast_cum)
        assert win == {1000.0: 50}
        # windowed p95 sees the regression; cumulative hides it
        assert metrics.Histogram.quantile(win, 0.95) == 1000.0
        assert metrics.Histogram.quantile(h.snapshot(), 0.5) == 10.0

    def test_window_accepts_wire_string_keys(self):
        win = metrics.Histogram.window({"5.0": 3, "inf": 1},
                                       {"5.0": 1})
        assert win == {5.0: 2, float("inf"): 1}

    def test_quantile_is_conservative_upper_edge(self):
        assert metrics.Histogram.quantile({"5.0": 1, "10.0": 1},
                                          0.5) == 5.0
        assert metrics.Histogram.quantile({}, 0.5) is None

    def test_teacher_buckets_are_the_shared_ladder(self):
        from edl_tpu.distill.teacher_server import (LATENCY_BUCKETS_MS,
                                                    latency_quantile)
        assert tuple(LATENCY_BUCKETS_MS) == metrics.LOG_BUCKETS_MS
        assert latency_quantile({"25.0": 3}, 0.95) == 25.0


# -- registry ----------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+=\"[^\"]*\""
    r"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})? [0-9eE.+-]+|\+Inf|-Inf)$")


class TestRegistry:
    def test_prometheus_text_conformance(self):
        reg = metrics.Registry()
        reg.counter("ops", "operations").inc(3)
        reg.gauge("depth").set(1.5)
        h = reg.histogram("lat_ms", (1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        reg.register_stats("src", lambda: {
            "rows": 7, "flag": True, "skip_me": "strings dropped",
            "hist": {"4": 2}})
        text = reg.render()
        for line in text.strip().split("\n"):
            assert _PROM_LINE.match(line), f"malformed line: {line!r}"
        # histogram buckets are CUMULATIVE with a +Inf terminator
        assert 'edl_lat_ms_bucket{le="1"} 1' in text
        assert 'edl_lat_ms_bucket{le="10"} 2' in text
        assert 'edl_lat_ms_bucket{le="+Inf"} 3' in text
        assert "edl_lat_ms_count 3" in text
        # stats-dict sources render as gauges; bools as 0/1, strings
        # dropped, nested dicts as bucket-labeled samples
        assert 'edl_src_rows{iid="0"} 7' in text
        assert 'edl_src_flag{iid="0"} 1' in text
        assert "skip_me" not in text
        assert 'edl_src_hist{iid="0",bucket="4"} 2' in text

    def test_kind_clash_raises(self):
        reg = metrics.Registry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")

    def test_source_errors_do_not_break_the_scrape(self):
        reg = metrics.Registry()

        def dying():
            raise RuntimeError("subsystem mid-teardown")

        reg.register_stats("dead", dying)
        reg.counter("alive").inc()
        assert "edl_alive 1" in reg.render()

    def test_unregister_drops_the_source(self):
        reg = metrics.Registry()
        handle = reg.register_stats("gone", lambda: {"x": 1})
        reg.unregister(handle)
        assert "gone" not in reg.render()

    def test_scrape_endpoint_round_trip(self):
        reg = metrics.Registry()
        reg.counter("served").inc(9)
        srv = metrics.MetricsServer(reg, port=0)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read()
            assert b"edl_served 9" in body
            snap = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/snapshot", timeout=5).read())
            assert snap["metrics"]["served"]["value"] == 9
        finally:
            srv.close()

    def test_store_published_snapshot(self):
        from edl_tpu.coord.store import InMemStore
        reg = metrics.Registry()
        reg.gauge("world").set(4)
        store = InMemStore()
        reg.publish(store, "/obs/metrics/pod0")
        doc = json.loads(store.get("/obs/metrics/pod0").value)
        assert doc["metrics"]["world"]["value"] == 4

    def test_registry_concurrency_under_lockgraph(self):
        """Writers on every metric type + scrapers + register/unregister
        churn, under the lock-order detector: 0 cycles, 0 hazards —
        and collection never runs a source callback while holding the
        registry lock (the callback takes a subsystem lock; a cycle
        would convict immediately)."""
        from edl_tpu.analysis import lockgraph
        graph = lockgraph.install(wrap_all=True)
        try:
            reg = metrics.Registry()
            sys_lock = threading.Lock()

            def stats():
                with sys_lock:   # a subsystem's own stats lock
                    return {"x": 1}

            reg.register_stats("sys", stats)
            c = reg.counter("ops")
            h = reg.histogram("lat", (1.0, 10.0))
            stop = threading.Event()
            errors: list[BaseException] = []

            def writer():
                try:
                    while not stop.is_set():
                        c.inc()
                        h.observe(3.0)
                        with sys_lock:  # subsystem work outside stats
                            pass
                except BaseException as exc:  # noqa: BLE001
                    errors.append(exc)

            def scraper():
                try:
                    for _ in range(50):
                        reg.render()
                        reg.snapshot()
                        handle = reg.register_stats("churn",
                                                    lambda: {"y": 2})
                        reg.unregister(handle)
                except BaseException as exc:  # noqa: BLE001
                    errors.append(exc)

            threads = [threading.Thread(target=writer) for _ in range(2)]
            threads += [threading.Thread(target=scraper) for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads[2:]:
                t.join()
            stop.set()
            for t in threads[:2]:
                t.join()
            assert not errors
            rep = graph.report()
        finally:
            lockgraph.uninstall()
        assert rep["cycles"] == []
        assert rep["hazards"] == []


# -- trace: propagation across both wire seams -------------------------------

class TestTrace:
    def test_disabled_is_a_noop(self, monkeypatch):
        monkeypatch.delenv("EDL_TPU_TRACE", raising=False)
        trace.reconfigure()
        with trace.span("x") as s:
            assert s is None
            assert trace.current() is None
        assert trace.inject() is None
        d = {"op": "put"}
        assert trace.attach(d) is d  # no copy, no key

    def test_coord_wire_propagates_context(self, traced):
        """A request sent under a span arrives server-side carrying the
        context; the server adopts it and the op lands in the SAME
        trace as a child of the caller's span."""
        from edl_tpu.coord.client import StoreClient
        from edl_tpu.coord.server import StoreServer
        with StoreServer(port=0, host="127.0.0.1") as srv:
            client = StoreClient(f"127.0.0.1:{srv.port}")
            try:
                with trace.span("resize.request") as root:
                    client.put("/k", "v")
                    root_ctx = root.context
            finally:
                client.close()
        spans = trace.load_spans(traced)
        store_ops = [s for s in spans if s["name"] == "store.put"]
        assert len(store_ops) == 1
        assert store_ops[0]["tid"] == root_ctx[0]
        assert store_ops[0]["parent"] == root_ctx[1]

    def test_tensor_wire_propagates_context_with_0d_tensors(self, traced):
        """Context rides the tensor-frame meta without disturbing the
        payload contract — including 0-d tensors (the shape-intact
        scalar guarantee r12 pinned)."""
        from edl_tpu.data import tensor_wire
        a, b = socket.socketpair()
        try:
            with trace.span("resize.restore_peers") as sp:
                ctx = sp.context
                tensor_wire.send_tensors(
                    a, {"op": "fetch"},
                    {"scalar": np.array(3, np.int64),
                     "grid": np.arange(6, dtype=np.float32).reshape(2, 3)})
            meta, tensors = tensor_wire.recv_tensors(b)
            assert trace.extract(meta) == ctx
            assert meta == {"op": "fetch"}  # _tc popped, meta intact
            assert tensors["scalar"].shape == ()
            assert int(tensors["scalar"]) == 3
            assert tensors["grid"].shape == (2, 3)
        finally:
            a.close()
            b.close()

    def test_garbled_context_never_breaks_the_consumer(self, traced):
        """A garbled/hostile _tc value (wrong type, wrong arity, junk)
        degrades to 'no context' — the frame still parses."""
        from edl_tpu.data import tensor_wire
        for bad in ("junk", [1, 2], ["a"], ["x" * 100, "y"], None, {}):
            a, b = socket.socketpair()
            try:
                tensor_wire.send_tensors(
                    a, {"op": "fetch", "_tc": bad},
                    {"x": np.zeros(2, np.float32)})
                meta, tensors = tensor_wire.recv_tensors(b)
                assert trace.extract(meta) is None
                assert tensors["x"].shape == (2,)
            finally:
                a.close()
                b.close()

    def test_resize_actuation_is_one_causal_trace(self, traced):
        """request_resize -> /resize -> epoch publication: one trace id
        end to end, with the epoch doc carrying the context a trainer
        adopts (the decision->actuation->restore linkage)."""
        from edl_tpu.collective import migration as mig
        from edl_tpu.collective.job_server import (JobServer, JobState,
                                                   request_resize)
        from edl_tpu.coord.store import InMemStore
        store = InMemStore()
        state = JobState("tracejob", 1, 4, desired=2, store=store)
        server = JobServer(state, port=0).start()
        try:
            request_resize(f"127.0.0.1:{server.port}", 3)
        finally:
            server.stop()
        spans = trace.load_spans(traced)
        tids = {s["tid"] for s in spans}
        assert len(tids) == 1, f"split trace: {spans}"
        names = {s["name"] for s in spans}
        assert {"resize.request", "resize.actuate",
                "resize.publish_epoch"} <= names
        # the epoch doc carries a context from that same trace
        doc = json.loads(store.get(mig.epoch_key("tracejob")).value)
        ctx = trace.parse_context(doc.get("trace"))
        assert ctx is not None and ctx[0] in tids
        assert mig.resize_trace_ctx(store, "tracejob") == ctx
        # and the phase summary sees decision + actuation
        summary = trace.resize_phase_summary(spans)
        assert len(summary) == 1
        assert {"decision", "actuation"} <= set(summary[0]["phases"])

    def test_span_tree_orphans_surface(self, traced):
        with trace.span("parent"):
            with trace.span("child"):
                pass
        spans = trace.load_spans(traced)
        child = next(s for s in spans if s["name"] == "child")
        tree = trace.span_tree([child])  # parent record lost (killed pod)
        assert tree == [(child, 0)]

    def test_chrome_export_and_event(self, traced):
        trace.event("ckpt.write", 0.25, attrs={"version": 3})
        spans = trace.finished("ckpt.write")
        assert len(spans) == 1 and spans[0]["dur"] == 0.25
        chrome = trace.to_chrome(spans)
        ev = chrome["traceEvents"][0]
        assert ev["ph"] == "X" and ev["dur"] == pytest.approx(250000, rel=0.01)
        assert ev["args"]["version"] == 3

    def test_timeline_shim_routes_into_trace(self, traced, monkeypatch):
        from edl_tpu.utils import timeline as tl
        t = tl.timeline("ckpt")
        assert t.enabled
        with t.span("write"):
            pass
        assert trace.finished("ckpt.write")
        # profile off, trace off -> the zero-cost nop again
        monkeypatch.delenv("EDL_TPU_TRACE", raising=False)
        trace.reconfigure()
        assert not tl.timeline("ckpt").enabled


# -- flight recorder ---------------------------------------------------------

class TestRecorder:
    def test_ring_overflow_and_dump(self, tmp_path):
        rec = recorder.FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("resize", to=i)
        assert [e["to"] for e in rec.events("resize")] == [6, 7, 8, 9]
        assert rec.dropped == 6
        path = rec.dump(str(tmp_path / "flight.json"), reason="test")
        doc = json.load(open(path))
        assert doc["dropped"] == 6 and len(doc["events"]) == 4
        assert doc["reason"] == "test"

    def test_capacity_zero_disables(self):
        rec = recorder.FlightRecorder(capacity=0)
        rec.record("resize", to=1)
        assert rec.events() == []

    def test_job_resize_lands_in_the_global_ring(self):
        from edl_tpu.collective.job_server import JobState
        recorder.recorder().clear()
        state = JobState("rj", 1, 4, desired=2)
        state.resize(3)
        events = recorder.recorder().events("resize")
        assert events and events[-1]["to"] == 3 \
            and events[-1]["plane"] == "job"

    def test_auditor_third_witness(self):
        """I2's recorder witness: agreement passes, a ring that saw a
        resize the journal/log pair did not breaches, an overflowed
        ring voids the comparison instead of lying."""
        from edl_tpu.chaos.audit import InvariantAuditor

        def auditor(events, dropped=0):
            return InvariantAuditor(
                injections=[], worker_reports={}, probe={},
                scaler_journal=[{"action": "resize", "applied": 3}],
                job_resize_log=[{"to": 3, "source": "resize"}],
                pool_journal=[], pool_resize_log=[], drain_log=[],
                drain_deadline_s=5.0,
                recorder={"events": events, "dropped": dropped})

        good = [{"kind": "resize", "plane": "job", "source": "resize",
                 "to": 3}]
        rep = auditor(good).audit()
        assert not [b for b in rep.breaches if "recorder" in b]
        assert rep.stats["recorder_witness"] == "ok"

        rep = auditor(good + [{"kind": "resize", "plane": "job",
                               "source": "resize", "to": 9}]).audit()
        assert any("flight recorder" in b for b in rep.breaches)

        rep = auditor([], dropped=5).audit()
        assert rep.stats["recorder_witness"] == "overflowed"
        assert not [b for b in rep.breaches if "recorder" in b]


# -- the stdlib-only contract ------------------------------------------------

class TestLayering:
    def test_obs_imports_jax_and_numpy_free(self):
        """The obs plane must be importable on a scheduler node / bare
        CI runner: importing it (fresh interpreter) pulls neither jax
        nor numpy."""
        code = ("import sys; import edl_tpu.obs; "
                "assert 'jax' not in sys.modules, 'jax leaked'; "
                "assert 'numpy' not in sys.modules, 'numpy leaked'; "
                "print('clean')")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr
        assert "clean" in out.stdout

    def test_selftest_gate_passes(self):
        from edl_tpu.obs.__main__ import selftest
        assert selftest(verbose=False) == 0
