"""Request-lifecycle observability: structured logs + request-id
correlation, flight recorder, SLO/health endpoints, on-demand profiling,
and the end-to-end correlation contract (response header -> /logs.json ->
/traces.json -> /debug/flight.json)."""

from __future__ import annotations

import json
import logging
import threading
import time
import types
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.obs import flight as flight_mod
from predictionio_tpu.obs import logging as obs_logging
from predictionio_tpu.obs import profiler as profiler_mod
from predictionio_tpu.obs import slo as slo_mod
from predictionio_tpu.obs.flight import FlightRecorder
from predictionio_tpu.obs.logging import (
    JsonLineFormatter,
    LogRing,
    new_request_id,
    reset_request_context,
    set_request_context,
)
from predictionio_tpu.obs.metrics import TRAIN_BUCKETS, MetricsRegistry
from predictionio_tpu.obs.slo import SLOTracker
from predictionio_tpu.obs.tracing import clear_traces, recent_traces, trace
from predictionio_tpu.server.httpd import HTTPApp, Request


# ---------------------------------------------------------------------------
# structured logging


class TestStructuredLogging:
    def _record(self, msg="hello", **extra):
        rec = logging.LogRecord(
            "predictionio_tpu.test", logging.INFO, __file__, 1, msg, (), None
        )
        for k, v in extra.items():
            setattr(rec, k, v)
        return rec

    def test_json_formatter_emits_parseable_line_with_context(self):
        tokens = set_request_context("rid-123")
        try:
            line = JsonLineFormatter().format(
                self._record("served", route="/queries.json")
            )
        finally:
            reset_request_context(tokens)
        parsed = json.loads(line)
        assert parsed["message"] == "served"
        assert parsed["level"] == "INFO"
        assert parsed["request_id"] == "rid-123"
        assert parsed["route"] == "/queries.json"  # extra= field folded in

    def test_context_cleared_outside_request(self):
        parsed = json.loads(JsonLineFormatter().format(self._record()))
        assert "request_id" not in parsed

    def test_ring_bounded_and_filterable(self):
        ring = LogRing(maxlen=8)
        for i in range(20):
            tokens = set_request_context(f"r{i}")
            try:
                ring.emit(self._record(f"line {i}"))
            finally:
                reset_request_context(tokens)
        assert len(ring.records(limit=100)) == 8  # bounded
        only = ring.records(request_id="r19")
        assert len(only) == 1 and only[0]["message"] == "line 19"
        # wave-style correlation: request_ids list also matches the filter
        ring.emit(self._record("wave", request_ids=["r19", "r18"]))
        assert any(
            r["message"] == "wave" for r in ring.records(request_id="r19")
        )

    def test_ring_level_filter(self):
        ring = LogRing(maxlen=8)
        ring.emit(self._record("info-line"))
        rec = self._record("error-line")
        rec.levelno, rec.levelname = logging.ERROR, "ERROR"
        ring.emit(rec)
        errors = ring.records(min_level="error")
        assert [r["message"] for r in errors] == ["error-line"]

    def test_configure_logging_idempotent(self, capsys):
        root = logging.getLogger()
        before = list(root.handlers)
        try:
            obs_logging.configure_logging(level="INFO")
            obs_logging.configure_logging(level="INFO")
            ours = [
                h
                for h in root.handlers
                if getattr(h, "_pio_structured", False)
            ]
            assert len(ours) == 1  # re-configuring replaces, never stacks
        finally:
            for h in list(root.handlers):
                if getattr(h, "_pio_structured", False):
                    root.removeHandler(h)
            assert [
                h for h in root.handlers if h not in before
            ] == []  # third-party handlers untouched


# ---------------------------------------------------------------------------
# histogram range regression (satellite: bucket saturation)


class TestTrainBucketRange:
    def test_40s_span_does_not_pin_at_10s(self):
        """Regression: a 40 s train/event-store stage (BENCH_r05) must keep
        a meaningful quantile — the old 10 µs–10 s serving set pinned its
        p99 to 10 s."""
        from predictionio_tpu.obs.tracing import observe_span

        reg = MetricsRegistry()
        observe_span("train.algorithm.als", 42.0, registry=reg)
        h = reg.get("pio_span_seconds").labels("train.algorithm.als")
        assert h.bounds == TRAIN_BUCKETS
        assert 31.0 < h.quantile(0.99) <= 100.0

    def test_train_buckets_cover_100us_to_600s(self):
        assert TRAIN_BUCKETS[0] == pytest.approx(1e-4)
        assert TRAIN_BUCKETS[-1] == 600.0

    def test_bucket_bounds_configurable_per_histogram(self):
        reg = MetricsRegistry()
        custom = (0.1, 1.0, 10.0, 100.0)
        h = reg.histogram("pio_custom_seconds", "c", buckets=custom)
        h.observe(50.0)
        assert h.bounds == custom
        assert 10.0 <= h.quantile(0.5) <= 100.0


# ---------------------------------------------------------------------------
# SLO tracker


class TestSLOTracker:
    @pytest.fixture()
    def clock(self, monkeypatch):
        t = {"now": 1000.0}
        monkeypatch.setattr(slo_mod, "_now", lambda: t["now"])
        return t

    def test_availability_and_error_burn(self, clock):
        slo = SLOTracker(window_s=600, bucket_s=10, availability_target=0.999)
        for _ in range(990):
            slo.record(True, 0.01)
        for _ in range(10):
            slo.record(False, 0.01)
        snap = slo.snapshot()
        assert snap["requests"] == 1000 and snap["errors"] == 10
        assert snap["availability"] == pytest.approx(0.99)
        # bad fraction 1% against a 0.1% budget: burning 10x too fast
        assert snap["error_burn_rate"] == pytest.approx(10.0)
        assert snap["status"] == "degraded"

    def test_latency_burn(self, clock):
        slo = SLOTracker(
            window_s=600,
            bucket_s=10,
            latency_threshold_s=0.1,
            latency_target=0.99,
        )
        for _ in range(98):
            slo.record(True, 0.01)
        for _ in range(2):
            slo.record(True, 0.5)  # slow but successful
        snap = slo.snapshot()
        assert snap["slow_requests"] == 2
        assert snap["latency_burn_rate"] == pytest.approx(2.0)
        assert snap["status"] == "degraded"
        assert snap["error_burn_rate"] == 0.0

    def test_window_expiry_recovers(self, clock):
        slo = SLOTracker(window_s=100, bucket_s=10)
        for _ in range(5):
            slo.record(False, 0.01)
        assert slo.snapshot()["status"] == "degraded"
        clock["now"] += 200  # the whole window ages out
        snap = slo.snapshot()
        assert snap["requests"] == 0
        assert snap["status"] == "ok"
        assert snap["availability"] == 1.0

    def test_healthz_is_liveness_not_slo(self, clock):
        slo = SLOTracker(window_s=100, bucket_s=10)
        slo.record(False, 0.01)
        h = slo.healthz()
        assert h["status"] == "alive"  # burning budget never flips liveness
        assert h["slo_status"] == "degraded"


# ---------------------------------------------------------------------------
# flight recorder


class TestFlightRecorder:
    def test_keeps_n_slowest(self):
        fr = FlightRecorder(keep_slowest=5)
        for i in range(50):
            fr.record(
                {"request_id": f"r{i}", "status": 200, "duration_s": i / 100}
            )
        snap = fr.snapshot()
        assert snap["recorded_total"] == 50
        durations = [e["duration_s"] for e in snap["slowest"]]
        assert durations == sorted(durations, reverse=True)
        assert durations == [0.49, 0.48, 0.47, 0.46, 0.45]

    def test_errored_always_retained(self):
        fr = FlightRecorder(keep_slowest=2, keep_errors=4)
        for i in range(3):
            fr.record({"request_id": f"ok{i}", "status": 200, "duration_s": 9.0})
        fr.record(
            {
                "request_id": "boom",
                "status": 500,
                "duration_s": 0.001,  # fast failure: evicted from slowest,
                "error": "RuntimeError: kaput",  # kept in the error ring
            }
        )
        snap = fr.snapshot()
        assert [e["request_id"] for e in snap["errors"]] == ["boom"]
        assert all(e["request_id"] != "boom" for e in snap["slowest"])

    def test_request_id_filter(self):
        fr = FlightRecorder()
        fr.record({"request_id": "a", "status": 200, "duration_s": 0.1})
        fr.record({"request_id": "b", "status": 200, "duration_s": 0.2})
        snap = fr.snapshot(request_id="a")
        assert [e["request_id"] for e in snap["slowest"]] == ["a"]

    def test_error_body_without_message_key_is_preserved(self):
        """A 500 body like {'error': ...} (no 'message' key) must surface
        its text in the flight entry, not 'unrenderable error body'."""
        from predictionio_tpu.obs.http import record_request_outcome
        from predictionio_tpu.server.httpd import Response

        app = HTTPApp("frtest")
        app.slo = None
        app.flight = FlightRecorder()
        req = Request("POST", "/queries.json", {}, {}, b"{}")
        resp = Response(500, {"error": "model blob missing"})
        span = trace("http.frtest", record=False)
        with span:
            pass
        record_request_outcome(app, req, resp, 0.01, span.span)
        entry = app.flight.snapshot()["errors"][0]
        assert "model blob missing" in entry["error"]

    def test_annotations_scoped_per_request(self):
        token = flight_mod.begin_annotations()
        try:
            flight_mod.annotate(queue_wait_s=0.01)
            flight_mod.annotate(wave_size=4)
            assert flight_mod.current_annotations() == {
                "queue_wait_s": 0.01,
                "wave_size": 4,
            }
        finally:
            flight_mod.end_annotations(token)
        assert flight_mod.current_annotations() == {}
        flight_mod.annotate(ignored=True)  # no open scope: a safe no-op
        assert flight_mod.current_annotations() == {}


# ---------------------------------------------------------------------------
# profiler


@pytest.fixture()
def stub_profiler(monkeypatch):
    """Replace the jax trace hooks and reset the process controller."""
    calls = {"start": [], "python": [], "stop": 0}

    def fake_start(out_dir, python_tracer=False):
        calls["start"].append(out_dir)
        calls["python"].append(python_tracer)

    def fake_stop():
        calls["stop"] += 1

    monkeypatch.setattr(profiler_mod, "_start_trace", fake_start)
    monkeypatch.setattr(profiler_mod, "_stop_trace", fake_stop)
    monkeypatch.setattr(
        profiler_mod, "PROFILER", profiler_mod.ProfilerController()
    )
    # the HTTP routes resolve PROFILER through the module at call time
    monkeypatch.setattr(
        "predictionio_tpu.obs.http.PROFILER", profiler_mod.PROFILER
    )
    return calls


def _wait_profiler_idle(controller, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not controller.status()["running"]:
            return
        time.sleep(0.01)
    raise TimeoutError("profiler capture never finished")


class TestProfiler:
    def test_capture_runs_off_calling_thread(self, stub_profiler):
        p = profiler_mod.PROFILER
        t0 = time.perf_counter()
        out = p.start(0.3, "/tmp/pio-prof-test")
        started_in = time.perf_counter() - t0
        assert started_in < 0.2  # armed + returned, did not wait 0.3 s
        assert out["profiling"] is True
        assert p.status()["running"] is True
        with pytest.raises(profiler_mod.ProfilerBusy):
            p.start(0.1)
        _wait_profiler_idle(p)
        last = p.status()["last"]
        assert last["dir"] == "/tmp/pio-prof-test" and last["error"] is None
        assert stub_profiler["stop"] == 1

    def test_unsupported_surfaces_and_unlocks(self, stub_profiler, monkeypatch):
        def broken(out_dir, python_tracer=False):
            raise RuntimeError("no profiler on this backend")

        monkeypatch.setattr(profiler_mod, "_start_trace", broken)
        p = profiler_mod.PROFILER
        with pytest.raises(profiler_mod.ProfilerUnsupported):
            p.start(0.1)
        assert p.status()["running"] is False  # busy flag released

    def test_seconds_bounds(self, stub_profiler):
        p = profiler_mod.PROFILER
        with pytest.raises(ValueError):
            p.start(0)
        with pytest.raises(ValueError):
            p.start(10_000)

    def test_sample_runtime_gauges_populates_registry(self):
        import jax

        # the populated path requires an initialized backend; without one
        # the function deliberately no-ops (returns False), and
        # test-selection order must not decide which path runs
        jax.devices()
        reg = MetricsRegistry()
        assert profiler_mod.sample_runtime_gauges(reg) is True
        assert reg.get("pio_jax_live_buffer_count") is not None
        assert reg.get("pio_jax_pjit_cache_entries") is not None


# ---------------------------------------------------------------------------
# route-level behavior on a bare app


def _obs_app(access_key=None, readiness=None, registry=None):
    from predictionio_tpu.obs.http import add_observability_routes

    app = HTTPApp("obstest")
    add_observability_routes(
        app,
        registry or MetricsRegistry(),
        access_key=access_key,
        readiness=readiness,
    )
    return app


class TestObservabilityRoutes:
    def test_logs_json_serves_ring(self):
        app = _obs_app()
        log = logging.getLogger("predictionio_tpu.obstest")
        tokens = set_request_context("logroute-rid")
        try:
            # warning: above the default root level, so the ring sees it
            # without any logging configuration (ensure_ring never forces
            # logger levels on an embedding application)
            log.warning("a line for the ring")
        finally:
            reset_request_context(tokens)
        r = app.handle(
            Request("GET", "/logs.json", {"request_id": "logroute-rid"}, {})
        )
        assert r.status == 200
        body = json.loads(r.encoded()[0])
        assert any(
            rec["message"] == "a line for the ring" for rec in body["logs"]
        )

    def test_flight_json_route(self):
        app = _obs_app()
        app.flight.record(
            {"request_id": "fr1", "status": 200, "duration_s": 0.5}
        )
        r = app.handle(Request("GET", "/debug/flight.json", {}, {}))
        assert r.status == 200
        body = json.loads(r.encoded()[0])
        assert body["slowest"][0]["request_id"] == "fr1"

    def test_profile_route_statuses(self, stub_profiler):
        app = _obs_app(access_key="pk")
        q = {"accessKey": "pk"}
        r = app.handle(
            Request("POST", "/debug/profile", {"seconds": "0.2", **q}, {})
        )
        assert r.status == 202
        r = app.handle(
            Request("POST", "/debug/profile", {"seconds": "0.2", **q}, {})
        )
        assert r.status == 409  # busy
        assert (
            app.handle(
                Request("POST", "/debug/profile", {"seconds": "nan2", **q}, {})
            ).status
            == 400
        )
        _wait_profiler_idle(profiler_mod.PROFILER)
        r = app.handle(Request("GET", "/debug/profile", q, {}))
        assert r.status == 200 and r.body["last"]["error"] is None

    def test_profile_route_501_when_unsupported(self, stub_profiler, monkeypatch):
        def broken(out_dir, python_tracer=False):
            raise RuntimeError("CPU wheel without profiler")

        monkeypatch.setattr(profiler_mod, "_start_trace", broken)
        app = _obs_app(access_key="pk")
        r = app.handle(
            Request(
                "POST",
                "/debug/profile",
                {"seconds": "0.2", "accessKey": "pk"},
                {},
            )
        )
        assert r.status == 501

    def test_profile_requires_a_configured_key(self, stub_profiler):
        """Arming the profiler is privileged: with NO key configured
        anywhere (route- or app-level) the route refuses outright — an
        anonymous client must never start a capture."""
        app = _obs_app()  # keyless
        r = app.handle(
            Request("POST", "/debug/profile", {"seconds": "0.2"}, {})
        )
        assert r.status == 403
        assert "access key" in r.body["message"]
        # status stays readable, and nothing was armed
        assert profiler_mod.PROFILER.status()["running"] is False

    def test_readyz_transitions(self):
        state = {"up": True}
        app = _obs_app(readiness={"dep": lambda: state["up"]})
        assert app.handle(Request("GET", "/readyz", {}, {})).status == 200
        state["up"] = False
        r = app.handle(Request("GET", "/readyz", {}, {}))
        assert r.status == 503 and r.body["checks"] == {"dep": False}

    def test_raising_readiness_check_is_not_ready(self):
        def boom():
            raise RuntimeError("store down")

        app = _obs_app(readiness={"store": boom})
        assert app.handle(Request("GET", "/readyz", {}, {})).status == 503


class TestAccessKeyGating:
    """Satellite: every observability route 401s on a bad/missing key when a
    key is configured — /healthz alone stays ungated for load balancers."""

    GATED = (
        ("GET", "/metrics"),
        ("GET", "/metrics.json"),
        ("GET", "/traces.json"),
        ("GET", "/logs.json"),
        ("GET", "/debug/flight.json"),
        ("POST", "/debug/profile"),
        ("GET", "/readyz"),
        ("GET", "/slo.json"),
    )

    def test_route_level_key_gates_all_but_healthz(self, stub_profiler):
        app = _obs_app(access_key="sekrit")
        for method, path in self.GATED:
            assert (
                app.handle(Request(method, path, {}, {})).status == 401
            ), path
            assert (
                app.handle(
                    Request(method, path, {"accessKey": "wrong"}, {})
                ).status
                == 401
            ), path
        assert app.handle(Request("GET", "/healthz", {}, {})).status == 200
        # the right key unlocks, via query param or Bearer header
        assert (
            app.handle(
                Request("GET", "/metrics", {"accessKey": "sekrit"}, {})
            ).status
            == 200
        )
        assert (
            app.handle(
                Request(
                    "GET",
                    "/logs.json",
                    {},
                    {"Authorization": "Bearer sekrit"},
                )
            ).status
            == 200
        )

    def test_app_level_key_still_exempts_healthz(self, storage):
        """Admin/dashboard-style servers gate at the app level; /healthz is
        registered public and must bypass that gate too."""
        from predictionio_tpu.server.admin import create_admin_app

        app = create_admin_app(storage, access_key="adminsecret")
        assert app.handle(Request("GET", "/healthz", {}, {})).status == 200
        assert app.handle(Request("GET", "/metrics", {}, {})).status == 401
        assert app.handle(Request("GET", "/logs.json", {}, {})).status == 401
        assert (
            app.handle(Request("GET", "/debug/flight.json", {}, {})).status
            == 401
        )
        assert (
            app.handle(
                Request("GET", "/metrics", {"accessKey": "adminsecret"}, {})
            ).status
            == 200
        )

    def test_prediction_server_key_gates_obs_routes(self):
        from predictionio_tpu.server.prediction_server import (
            create_prediction_server_app,
        )

        deployed = _stub_deployed()
        app = create_prediction_server_app(deployed, access_key="pk1")
        assert app.handle(Request("GET", "/healthz", {}, {})).status == 200
        for method, path in self.GATED:
            assert (
                app.handle(Request(method, path, {}, {})).status == 401
            ), path


# ---------------------------------------------------------------------------
# per-server health surface


class TestServerHealthSurface:
    def test_event_server(self, storage):
        from predictionio_tpu.server.event_server import (
            create_event_server_app,
        )

        app = create_event_server_app(storage, registry=MetricsRegistry())
        assert app.handle(Request("GET", "/healthz", {}, {})).status == 200
        r = app.handle(Request("GET", "/readyz", {}, {}))
        assert r.status == 200 and r.body["ready"] is True
        assert set(r.body["checks"]) == {"event_store", "metadata_store"}
        assert app.handle(Request("GET", "/slo.json", {}, {})).status == 200

    def test_event_server_hides_debug_surface_without_key(self, storage):
        """The ingest port faces anonymous clients: without an operator
        key the scrape surface stays open but the debug surface (logs,
        flight, profiler) must not exist at all."""
        from predictionio_tpu.server.event_server import (
            create_event_server_app,
        )

        app = create_event_server_app(storage, registry=MetricsRegistry())
        assert app.handle(Request("GET", "/metrics", {}, {})).status == 200
        for method, path in (
            ("GET", "/logs.json"),
            ("GET", "/debug/flight.json"),
            ("POST", "/debug/profile"),
            ("GET", "/debug/profile"),
        ):
            assert (
                app.handle(Request(method, path, {}, {})).status == 404
            ), path

    def test_event_server_debug_surface_behind_obs_key(self, storage):
        from predictionio_tpu.server.event_server import (
            create_event_server_app,
        )

        app = create_event_server_app(
            storage, registry=MetricsRegistry(), obs_access_key="obskey"
        )
        assert app.handle(Request("GET", "/healthz", {}, {})).status == 200
        assert app.handle(Request("GET", "/logs.json", {}, {})).status == 401
        assert (
            app.handle(
                Request("GET", "/logs.json", {"accessKey": "obskey"}, {})
            ).status
            == 200
        )

    def test_admin_server(self, storage):
        from predictionio_tpu.server.admin import create_admin_app

        app = create_admin_app(storage)
        for path in ("/healthz", "/readyz", "/slo.json"):
            assert app.handle(Request("GET", path, {}, {})).status == 200

    def test_dashboard_server_and_panels(self, storage):
        from predictionio_tpu.server.dashboard import create_dashboard_app

        clear_traces()
        tokens = set_request_context("dash-rid")
        try:
            with trace("dash.probe", record=False):
                pass
        finally:
            reset_request_context(tokens)
        app = create_dashboard_app(storage)
        for path in ("/healthz", "/readyz", "/slo.json"):
            assert app.handle(Request("GET", path, {}, {})).status == 200
        page = app.handle(Request("GET", "/", {}, {})).body
        assert "<h2>Health</h2>" in page
        assert "<h2>Recent traces</h2>" in page
        assert "<h2>Metrics</h2>" in page
        # trace rows link to the flight recorder entry by request id
        assert "/debug/flight.json?request_id=dash-rid" in page

    def test_dashboard_flight_links_carry_access_key(self, storage):
        """On a key-gated dashboard the trace-row links must include the
        accessKey, or clicking through from the authenticated page 401s."""
        from predictionio_tpu.server.dashboard import create_dashboard_app

        clear_traces()
        tokens = set_request_context("gated-rid")
        try:
            with trace("dash.gated", record=False):
                pass
        finally:
            reset_request_context(tokens)
        app = create_dashboard_app(storage, access_key="dk1")
        page = app.handle(Request("GET", "/", {"accessKey": "dk1"}, {})).body
        href = "/debug/flight.json?request_id=gated-rid&accessKey=dk1"
        assert href in page
        # and the link actually works
        assert (
            app.handle(
                Request(
                    "GET",
                    "/debug/flight.json",
                    {"request_id": "gated-rid", "accessKey": "dk1"},
                    {},
                )
            ).status
            == 200
        )

    def test_storage_server(self, tmp_path):
        from predictionio_tpu.data.storage.config import (
            StorageConfig,
            StorageRuntime,
        )
        from predictionio_tpu.server.storage_server import create_storage_app

        rt = StorageRuntime(
            StorageConfig.from_env({"PIO_HOME": str(tmp_path / "pio")})
        )
        try:
            app = create_storage_app(rt)
            for path in ("/healthz", "/readyz", "/slo.json"):
                assert (
                    app.handle(Request("GET", path, {}, {})).status == 200
                ), path
        finally:
            rt.close()

    def test_prediction_server_ready_then_draining(self):
        from predictionio_tpu.server.prediction_server import (
            create_prediction_server_app,
        )

        deployed = _stub_deployed()
        app = create_prediction_server_app(deployed, use_microbatch=True)
        r = app.handle(Request("GET", "/readyz", {}, {}))
        assert r.status == 200
        assert r.body["checks"] == {
            "model_loaded": True,
            "microbatcher": True,
            "event_store": True,
            "storage_breakers": True,
        }
        app.microbatcher.close()  # draining: stop routing traffic here
        r = app.handle(Request("GET", "/readyz", {}, {}))
        assert r.status == 503 and r.body["checks"]["microbatcher"] is False
        # liveness is unaffected — the process still answers
        assert app.handle(Request("GET", "/healthz", {}, {})).status == 200


# ---------------------------------------------------------------------------
# CLI: pio metrics --watch, pio status --url


class TestCLIVerbs:
    def test_metrics_watch_rerenders(self, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        assert (
            cli_main(
                ["metrics", "--watch", "0.01", "--watch-count", "3"]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert out.count("--- pio metrics @") == 3

    def test_metrics_watch_rejects_negative(self, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        assert cli_main(["metrics", "--watch", "-1"]) == 2

    def test_status_url_reads_health_surface(self, capsys):
        from predictionio_tpu.server.httpd import AppServer
        from predictionio_tpu.tools.cli import main as cli_main

        app = _obs_app(readiness={"dep": lambda: True})
        server = AppServer(app, "127.0.0.1", 0).start_background()
        try:
            base = f"http://127.0.0.1:{server.port}"
            assert cli_main(["status", "--url", base]) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["healthz"]["status"] == "alive"
            assert out["readyz"]["ready"] is True
            assert out["slo"]["status"] == "ok"
        finally:
            server.shutdown()

    def test_status_url_exit_1_when_not_ready(self, capsys):
        from predictionio_tpu.server.httpd import AppServer
        from predictionio_tpu.tools.cli import main as cli_main

        app = _obs_app(readiness={"dep": lambda: False})
        server = AppServer(app, "127.0.0.1", 0).start_background()
        try:
            base = f"http://127.0.0.1:{server.port}"
            assert cli_main(["status", "--url", base]) == 1
            out = json.loads(capsys.readouterr().out)
            assert out["readyz"]["ready"] is False
        finally:
            server.shutdown()

    def test_status_url_with_access_key_on_gated_server(self, capsys):
        """A key-gated production deploy must still be probe-able: the key
        rides as a Bearer header; without it /readyz 401s and status exits
        1, with it the real readiness answer comes back."""
        from predictionio_tpu.server.httpd import AppServer
        from predictionio_tpu.tools.cli import main as cli_main

        app = _obs_app(access_key="gk1", readiness={"dep": lambda: True})
        server = AppServer(app, "127.0.0.1", 0).start_background()
        try:
            base = f"http://127.0.0.1:{server.port}"
            assert cli_main(["status", "--url", base]) == 1  # keyless: 401
            capsys.readouterr()
            assert (
                cli_main(["status", "--url", base, "--access-key", "gk1"])
                == 0
            )
            out = json.loads(capsys.readouterr().out)
            assert out["readyz"]["ready"] is True
            assert out["slo"]["status"] == "ok"
        finally:
            server.shutdown()

    def test_status_url_daemon_down_exits_1_not_traceback(self, capsys):
        """Probing a dead daemon is the primary --url use case: it must
        report unreachable and exit 1, never raise."""
        import socket

        from predictionio_tpu.tools.cli import main as cli_main

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()  # nothing listens here now
        assert cli_main(["status", "--url", f"http://127.0.0.1:{port}"]) == 1
        out = json.loads(capsys.readouterr().out)
        assert "unreachable" in out["healthz"]["message"]

    def test_metrics_url_one_shot_unreachable_exits_1(self, capsys):
        import socket

        from predictionio_tpu.tools.cli import main as cli_main

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        assert (
            cli_main(["metrics", "--url", f"http://127.0.0.1:{port}"]) == 1
        )
        assert "scrape failed" in capsys.readouterr().err

    def test_metrics_watch_survives_scrape_failure(self, capsys):
        """A watch session must outlive server restarts: a failed scrape
        prints the error and keeps watching instead of dying."""
        import socket

        from predictionio_tpu.tools.cli import main as cli_main

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        assert (
            cli_main(
                [
                    "metrics",
                    "--url", f"http://127.0.0.1:{port}",
                    "--watch", "0.01",
                    "--watch-count", "2",
                ]
            )
            == 0
        )
        captured = capsys.readouterr()
        assert captured.out.count("--- pio metrics @") == 2
        assert "scrape failed" in captured.err


# ---------------------------------------------------------------------------
# end-to-end correlation: aio -> prediction server -> MicroBatcher


def _stub_deployed():
    """A DeployedEngine without storage/training: echo algorithm with a
    deliberately slow path (user == "slow") and a poison path."""
    from predictionio_tpu.core.base import Algorithm, FirstServing

    class EchoAlgo(Algorithm):
        def train(self, ctx, pd):
            return None

        def predict(self, model, q):
            user = q.get("user")
            if user == "poison":
                raise RuntimeError("poison query")
            if user == "slow":
                time.sleep(0.25)  # the forced-slow query
            return {"echo": user}

        def batch_predict(self, model, iq):
            return [(i, self.predict(model, q)) for i, q in iq]

    from predictionio_tpu.server.prediction_server import DeployedEngine

    deployed = DeployedEngine.__new__(DeployedEngine)
    deployed._lock = threading.RLock()
    deployed.instance = types.SimpleNamespace(id="e2e-instance")
    deployed.storage = None
    deployed.algorithms = [EchoAlgo()]
    deployed.models = [None]
    deployed.serving = FirstServing()
    deployed.engine = types.SimpleNamespace(
        params_from_json=lambda payload: None
    )
    deployed.extract_query = lambda payload: dict(payload)
    return deployed


def _post_json(url, payload, headers=None):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, dict(r.headers), json.loads(r.read())


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, json.loads(r.read())


class TestEndToEndCorrelation:
    """The acceptance path: one request id appears in the response header, a
    /logs.json line, a /traces.json span, and — for the forced-slow query —
    a /debug/flight.json entry with the queue-wait/device split."""

    @pytest.fixture()
    def server(self):
        from predictionio_tpu.server.aio import AsyncAppServer
        from predictionio_tpu.server.prediction_server import (
            create_prediction_server_app,
        )

        clear_traces()
        app = create_prediction_server_app(
            _stub_deployed(),
            use_microbatch=True,
            registry=MetricsRegistry(),
        )
        srv = AsyncAppServer(app, "127.0.0.1", 0).start_background()
        yield srv
        srv.shutdown()

    def test_request_id_correlates_across_surfaces(self, server):
        base = f"http://127.0.0.1:{server.port}"
        rid = f"e2e-{new_request_id()}"

        status, headers, body = _post_json(
            base + "/queries.json",
            {"user": "u1"},
            headers={"X-Pio-Request-Id": rid},
        )
        assert status == 200 and body == {"echo": "u1"}
        # 1) the response header echoes the id we supplied
        assert headers["X-Pio-Request-Id"] == rid

        slow_rid = f"e2e-slow-{new_request_id()}"
        status, headers, _ = _post_json(
            base + "/queries.json",
            {"user": "slow"},
            headers={"X-Pio-Request-Id": slow_rid},
        )
        assert status == 200 and headers["X-Pio-Request-Id"] == slow_rid

        # 2) /logs.json: the MicroBatcher wave that served the query names
        #    it in its request_ids
        status, logs = _get_json(
            base + f"/logs.json?request_id={rid}&limit=200"
        )
        assert status == 200
        wave_lines = [
            l
            for l in logs["logs"]
            if rid in (l.get("request_ids") or ())
        ]
        assert wave_lines, f"no wave log names {rid}"
        assert wave_lines[0]["wave_size"] >= 1

        # 3) /traces.json: the front-end root span carries the id
        status, traces = _get_json(base + "/traces.json?limit=100")
        assert status == 200
        spans = [
            t for t in traces["traces"] if t.get("request_id") == rid
        ]
        assert spans, f"no span carries {rid}"
        assert spans[0]["name"] == "http.predictionserver"
        assert spans[0]["status"] == 200
        assert [c["name"] for c in spans[0]["children"]] == [
            "serve.microbatch"
        ]

        # 4) /debug/flight.json: the forced-slow query was retained with
        #    its latency decomposition and span tree
        status, flight = _get_json(
            base + f"/debug/flight.json?request_id={slow_rid}"
        )
        assert status == 200
        assert flight["slowest"], f"slow query {slow_rid} not retained"
        entry = flight["slowest"][0]
        assert entry["duration_s"] > 0.2
        assert entry["path"] == "/queries.json"
        assert "queue_wait_s" in entry and "device_s" in entry
        assert entry["wave_request_ids"] == [slow_rid]
        assert entry["wave_seq"] >= 1  # which dispatch wave served it
        assert entry["span"]["request_id"] == slow_rid
        assert entry["payload_bytes"] > 0 and entry["response_bytes"] > 0

        # the health surface answers on the serving port too
        for path in ("/healthz", "/readyz", "/slo.json"):
            assert _get_json(base + path)[0] == 200, path
        status, slo = _get_json(base + "/slo.json")
        assert slo["requests"] >= 2  # obs routes themselves are excluded

    def test_errored_request_lands_in_flight_errors(self, server):
        base = f"http://127.0.0.1:{server.port}"
        rid = f"e2e-err-{new_request_id()}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_json(
                base + "/queries.json",
                {"user": "poison"},
                headers={"X-Pio-Request-Id": rid},
            )
        assert ei.value.code == 500
        assert ei.value.headers["X-Pio-Request-Id"] == rid
        status, flight = _get_json(
            base + f"/debug/flight.json?request_id={rid}"
        )
        assert status == 200
        assert [e["request_id"] for e in flight["errors"]] == [rid]
        assert "poison" in flight["errors"][0]["error"]

    def test_minted_id_when_client_sends_none(self, server):
        base = f"http://127.0.0.1:{server.port}"
        status, headers, _ = _post_json(
            base + "/queries.json", {"user": "u2"}
        )
        assert status == 200
        assert len(headers["X-Pio-Request-Id"]) == 16  # minted server-side
