"""Device-efficiency observability (obs/device.py): XLA cost capture on the
CPU backend, peak-table overrides, the recompile-storm detector, the
MicroBatcher wave-timeline split and /efficiency.json gating — including
the acceptance e2e on a real (tiny) NCF engine: nonzero achieved-vs-peak utilization from real
``cost_analysis()``, a shape-churning query stream trips
``pio_recompile_storm_total`` while stable traffic does not."""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.obs import device as device_obs
from predictionio_tpu.obs.device import (
    EfficiencyTracker,
    RecompileTracker,
    device_peaks,
    jit_cost_analysis,
    signature_of,
    split_breakdown,
    wave_stage,
    wave_timeline,
)
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.server.httpd import HTTPApp, Request
from predictionio_tpu.server.microbatch import MicroBatcher


# ---------------------------------------------------------------------------
# peak table


class TestPeakTable:
    def test_longest_prefix_wins(self):
        assert device_peaks("tpu v4 chip").hbm_gbps == 1228.0
        assert device_peaks("tpu v5 lite").hbm_gbps == 819.0
        assert device_peaks("cpu").source == "cpu"

    def test_unknown_kind_has_no_peaks(self):
        # a device the table does not list is not assumed to be a v5e (or
        # a CPU): no peaks, so no utilization is reported against it
        for kind in ("tpu v7x", "quantum abacus"):
            p = device_peaks(kind)
            assert (p.hbm_gbps, p.tflops, p.source) == (0.0, 0.0, "unknown")

    def test_unknown_kind_reports_no_utilization(self):
        reg = MetricsRegistry()
        eff = EfficiencyTracker(
            registry=reg, peaks=device_peaks("quantum abacus")
        )
        eff.record_cost("f", flops=1e9, nbytes=1e9)
        eff.observe("f", 0.5)
        assert not list(reg.get("pio_device_utilization_frac").series())
        entry = eff.snapshot()["functions"]["f"]
        assert entry["achieved_gbps"] > 0
        assert "utilization_hbm" not in entry
        assert eff.snapshot()["peaks"]["source"] == "unknown"

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("PIO_DEVICE_PEAK_GBPS", "123.5")
        monkeypatch.setenv("PIO_DEVICE_PEAK_TFLOPS", "7")
        p = device_peaks("tpu v5e")
        assert p.hbm_gbps == 123.5 and p.tflops == 7.0
        assert p.source == "env"

    def test_partial_and_invalid_env(self, monkeypatch):
        monkeypatch.setenv("PIO_DEVICE_PEAK_GBPS", "50")
        p = device_peaks("tpu v5e")
        assert p.hbm_gbps == 50.0 and p.tflops == 197.0  # table half kept
        monkeypatch.setenv("PIO_DEVICE_PEAK_GBPS", "not-a-number")
        p = device_peaks("tpu v5e")
        assert p.hbm_gbps == 819.0  # bad override ignored, table value

    def test_live_platform_resolves(self):
        # jax is imported in the test process, so the live path runs;
        # whatever the kind string, a positive peak must come back
        p = device_peaks()
        assert p.hbm_gbps > 0 and p.tflops > 0


# ---------------------------------------------------------------------------
# XLA cost capture (CPU backend: cost_analysis is real, not stubbed)


@jax.jit
def _matmul_sum(x):
    return (x @ x.T).sum()


class TestCostCapture:
    def test_cost_analysis_on_cpu_backend(self):
        cost = jit_cost_analysis(_matmul_sum, jnp.ones((64, 32)))
        assert cost is not None
        assert cost["flops"] > 0
        assert cost["bytes"] > 0

    def test_non_jitted_fn_returns_none(self):
        assert jit_cost_analysis(lambda x: x, jnp.ones((4,))) is None

    def test_capture_cached_per_signature(self, monkeypatch):
        tracker = EfficiencyTracker(registry=MetricsRegistry())
        calls = []
        real = device_obs.jit_cost_analysis

        def counting(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(device_obs, "jit_cost_analysis", counting)
        x = jnp.ones((16, 8))
        c1 = tracker.capture_cost("f", _matmul_sum, x)
        c2 = tracker.capture_cost("f", _matmul_sum, x)
        assert c1 == c2 and len(calls) == 1  # second call served from cache
        tracker.capture_cost("f", _matmul_sum, jnp.ones((32, 8)))
        assert len(calls) == 2  # new shape -> one more AOT analysis

    def test_observe_sets_achieved_and_utilization_gauges(self):
        reg = MetricsRegistry()
        tracker = EfficiencyTracker(registry=reg)
        x = jnp.ones((64, 32))
        cost = tracker.capture_cost("hot_fn", _matmul_sum, x)
        assert cost is not None
        tracker.observe("hot_fn", seconds=0.001)
        gbps = reg.get("pio_device_achieved_gbps").labels("hot_fn").value
        tflops = reg.get("pio_device_achieved_tflops").labels("hot_fn").value
        assert gbps == pytest.approx(cost["bytes"] / 0.001 / 1e9)
        assert tflops == pytest.approx(cost["flops"] / 0.001 / 1e12)
        util = reg.get("pio_device_utilization_frac")
        peaks = device_peaks()
        assert util.labels("hot_fn", "hbm").value == pytest.approx(
            gbps / peaks.hbm_gbps
        )
        assert util.labels("hot_fn", "mxu").value == pytest.approx(
            tflops / peaks.tflops
        )
        assert reg.get("pio_device_flops_total").labels("hot_fn").value == (
            cost["flops"]
        )

    def test_deferred_capture_runs_off_thread_and_lands(self):
        """The serving-path mode: defer=True returns None immediately (the
        AOT analysis compile must not stall a wave) and the cost lands for
        the NEXT wave of that signature after flush()."""
        tracker = EfficiencyTracker(registry=MetricsRegistry())
        x = jnp.ones((8, 4))
        first = tracker.capture_cost("f", _matmul_sum, x, defer=True)
        assert first is None  # never blocks the wave
        assert tracker.flush(timeout=30.0) is True
        sig = signature_of(x)
        landed = tracker.cached_cost("f", sig)
        assert landed is not None and landed["flops"] > 0
        # steady state: the cached cost comes back synchronously
        again = tracker.capture_cost("f", _matmul_sum, x, defer=True)
        assert again is not None and again["flops"] == landed["flops"]

    def test_observe_without_cost_is_a_noop(self):
        reg = MetricsRegistry()
        EfficiencyTracker(registry=reg).observe("never_captured", 0.5)
        fam = reg.get("pio_device_achieved_gbps")
        assert fam.series() == []

    def test_snapshot_shapes(self):
        tracker = EfficiencyTracker(registry=MetricsRegistry())
        tracker.record_cost("f", flops=2e9, nbytes=1e9, source="plan")
        tracker.observe("f", seconds=0.5)
        snap = tracker.snapshot()
        f = snap["functions"]["f"]
        assert f["calls"] == 1
        assert f["achieved_gbps"] == pytest.approx(2.0)
        assert f["achieved_tflops"] == pytest.approx(0.004)
        assert 0 < f["utilization_hbm"] <= 1.0
        assert snap["peaks"]["hbm_gbps"] > 0


# ---------------------------------------------------------------------------
# recompile accounting + storm detector


class TestRecompileStorm:
    def _tracker(self, reg=None, threshold=4, window=60.0):
        return RecompileTracker(
            registry=reg or MetricsRegistry(),
            storm_threshold=threshold,
            window_s=window,
        )

    def test_new_signature_counts_a_recompile(self):
        reg = MetricsRegistry()
        t = self._tracker(reg)
        assert t.note_signature("f", (32, 16), now=0.0) is True
        assert t.note_signature("f", (32, 16), now=1.0) is False  # cached
        assert reg.get("pio_jax_recompile_total").labels("f").value == 1

    def test_shape_churn_trips_the_storm_counter(self):
        reg = MetricsRegistry()
        t = self._tracker(reg)
        for i in range(6):
            t.note_signature("churner", (32, 16 << i), now=float(i))
        storms = reg.get("pio_recompile_storm_total").labels("churner")
        assert storms.value == 1  # one storm, not one per extra signature
        active = t.active_storms(now=5.0)
        assert "churner" in active
        # the operator-facing count is the IN-WINDOW one the storm was
        # detected on, not the lifetime tally
        assert active["churner"]["signatures"] == 6
        assert active["churner"]["total_signatures"] == 6

    def test_stable_shape_soak_does_not_trip(self):
        reg = MetricsRegistry()
        t = self._tracker(reg)
        for i in range(500):
            t.note_signature("stable", (32, 16), now=float(i) * 0.1)
        fam = reg.get("pio_recompile_storm_total")
        assert fam.series() == []
        assert t.active_storms(now=50.0) == {}

    def test_signatures_outside_the_window_do_not_storm(self):
        reg = MetricsRegistry()
        t = self._tracker(reg, threshold=4, window=10.0)
        # 6 distinct signatures, but spread far apart: never 4 in a window
        for i in range(6):
            t.note_signature("slow_drift", ("sig", i), now=float(i) * 100.0)
        assert reg.get("pio_recompile_storm_total").series() == []

    def test_storm_expires_with_the_window(self):
        t = self._tracker(threshold=2, window=10.0)
        t.note_signature("f", ("a",), now=0.0)
        t.note_signature("f", ("b",), now=1.0)
        assert "f" in t.active_storms(now=5.0)
        assert t.active_storms(now=100.0) == {}

    def test_env_tuned_threshold(self, monkeypatch):
        monkeypatch.setenv("PIO_RECOMPILE_STORM_N", "2")
        monkeypatch.setenv("PIO_RECOMPILE_STORM_WINDOW_S", "5")
        t = RecompileTracker(registry=MetricsRegistry())
        assert t.storm_threshold == 2 and t.window_s == 5.0

    def test_signature_of_mixes_arrays_and_scalars(self):
        sig = signature_of(np.zeros((3, 4), np.float32), 7, "mode")
        assert sig[0] == ((3, 4), "float32")
        assert sig[1] == "7" and sig[2] == "'mode'"


# ---------------------------------------------------------------------------
# wave timeline split


class TestWaveTimeline:
    def test_stage_marks_accumulate_in_scope(self):
        with wave_timeline() as tl:
            with wave_stage("h2d"):
                time.sleep(0.01)
            with wave_stage("h2d"):
                time.sleep(0.01)
            with wave_stage("compute"):
                time.sleep(0.02)
        assert tl.stages["h2d"] >= 0.02
        assert tl.stages["compute"] >= 0.02

    def test_stage_outside_scope_is_a_noop(self):
        with wave_stage("compute"):
            pass  # must not raise, must not leak state
        assert device_obs.current_timeline() is None

    def test_split_sums_to_device_s(self):
        with wave_timeline() as tl:
            with wave_stage("host_gather"):
                time.sleep(0.01)
            with wave_stage("compute"):
                time.sleep(0.02)
        device_s = 0.1  # the batcher's bracket is wider than the marks
        breakdown = split_breakdown(tl, device_s)
        assert set(breakdown) == {
            "host_gather", "h2d", "compute", "d2h", "other",
        }
        assert sum(breakdown.values()) == pytest.approx(device_s, abs=1e-4)
        assert breakdown["other"] > 0  # the unattributed remainder

    def test_microbatch_wave_meta_carries_the_breakdown(self):
        """The tentpole invariant end to end: a MicroBatcher wave whose
        batch_fn marks stages yields per-item meta where the 4-way split
        (+other) sums to device_s, and the stage/device histograms fill."""
        reg = MetricsRegistry()

        def batch_fn(items):
            with wave_stage("host_gather"):
                time.sleep(0.01)
            with wave_stage("compute"):
                time.sleep(0.03)
            device_obs.note_wave_device("cpu:0")
            return [x * 2 for x in items]

        batcher = MicroBatcher(batch_fn, registry=reg)

        async def run():
            meta: dict = {}
            out = await batcher.submit(21, meta)
            return out, meta

        try:
            out, meta = asyncio.run(run())
        finally:
            batcher.close()
        assert out == 42
        bd = meta["device_breakdown"]
        assert sum(bd.values()) == pytest.approx(
            meta["device_s"], abs=1e-4
        )
        assert bd["compute"] >= 0.03
        assert bd["host_gather"] >= 0.01
        assert meta["wave_device"] == "cpu:0"
        fam = reg.get("pio_microbatch_stage_seconds")
        series = dict(fam.series())
        assert series[("compute", "cpu:0")].count == 1
        assert series[("other", "cpu:0")].count == 1

    def test_uninstrumented_batch_fn_lands_in_other(self):
        reg = MetricsRegistry()
        batcher = MicroBatcher(lambda items: items, registry=reg)

        async def run():
            meta: dict = {}
            await batcher.submit(1, meta)
            return meta

        try:
            meta = asyncio.run(run())
        finally:
            batcher.close()
        bd = meta["device_breakdown"]
        assert bd["other"] == pytest.approx(meta["device_s"], abs=1e-4)
        assert bd["compute"] == 0.0

    def test_solo_retry_meta_carries_cost_fields(self):
        """A solo-retried item's flight meta must answer compute-vs-
        transfer too: wave_fn/wave_flops/wave_bytes ride the retry pass."""
        reg = MetricsRegistry()
        calls = {"n": 0}

        def batch_fn(items):
            calls["n"] += 1
            if items == [0]:  # slow opener: the next two coalesce behind it
                time.sleep(0.2)
                return items
            if len(items) > 1:
                raise RuntimeError("poisoned wave")
            with wave_stage("compute"):
                pass
            device_obs.note_wave_cost(
                "stub.fn", {"flops": 11.0, "bytes": 7.0}
            )
            return [x for x in items]

        batcher = MicroBatcher(batch_fn, registry=reg)

        async def run():
            metas = [{}, {}]
            first = asyncio.ensure_future(batcher.submit(0, {}))
            await asyncio.sleep(0.05)  # the opener wave is now in flight
            results = await asyncio.gather(
                batcher.submit(1, metas[0]),
                batcher.submit(2, metas[1]),
                first,
            )
            return results, metas

        try:
            (r1, r2, _), metas = asyncio.run(run())
        finally:
            batcher.close()
        if metas[0].get("solo_retry"):  # the two coalesced and solo-ran
            assert metas[0]["wave_fn"] == "stub.fn"
            assert metas[0]["wave_flops"] == 11.0
            assert metas[0]["wave_bytes"] == 7.0
        else:  # scheduling served them as singles: still cost-attributed
            assert metas[0]["wave_fn"] == "stub.fn"

    def test_note_transfer_accumulates(self):
        reg = MetricsRegistry()
        before = device_obs.transfer_totals()["h2d"]
        with wave_timeline() as tl:
            device_obs.note_transfer("h2d", 1024, registry=reg)
        assert tl.transfers["h2d"] == 1024
        assert device_obs.transfer_totals()["h2d"] == before + 1024
        fam = reg.get("pio_device_transfer_bytes_total")
        assert fam.labels("h2d").value == 1024


# ---------------------------------------------------------------------------
# runtime-gauge satellites (profiler)


class TestRuntimeGaugeSatellites:
    def test_compile_cache_growth_counter(self):
        from predictionio_tpu.obs.profiler import sample_runtime_gauges

        jax.devices()  # gauge sampling requires an initialized backend
        reg = MetricsRegistry()
        assert sample_runtime_gauges(reg) is True  # seeds the last-seen size

        @jax.jit
        def fresh(x):
            return x * 3 + 1

        np.asarray(fresh(jnp.ones((5,))))  # grows the pjit cache
        assert sample_runtime_gauges(reg) is True
        fam = reg.get("pio_jax_compile_cache_growth_total")
        assert fam is not None and fam.labels().value >= 1

    def test_transfer_bytes_gauge_mirrors_process_totals(self):
        from predictionio_tpu.obs.profiler import sample_runtime_gauges

        device_obs.note_transfer("d2h", 4096, registry=MetricsRegistry())
        jax.devices()  # gauge sampling requires an initialized backend
        reg = MetricsRegistry()
        sample_runtime_gauges(reg)
        gauge = reg.get("pio_device_transfer_bytes").labels("d2h")
        assert gauge.value >= 4096


# ---------------------------------------------------------------------------
# /efficiency.json exposure + gating


def _obs_app(access_key=None, debug_routes=True):
    from predictionio_tpu.obs.http import add_observability_routes

    app = HTTPApp("efftest")
    add_observability_routes(
        app,
        MetricsRegistry(),
        access_key=access_key,
        debug_routes=debug_routes,
    )
    return app


class TestEfficiencyRoute:
    def test_served_with_snapshot_shape(self):
        resp = _obs_app().handle(Request("GET", "/efficiency.json", {}, {}))
        assert resp.status == 200
        body = resp.body
        assert "peaks" in body and "recompiles" in body
        assert "functions" in body and "transfers" in body

    def test_gated_by_access_key(self):
        app = _obs_app(access_key="k1")
        assert (
            app.handle(Request("GET", "/efficiency.json", {}, {})).status
            == 401
        )
        ok = app.handle(
            Request("GET", "/efficiency.json", {"accessKey": "k1"}, {})
        )
        assert ok.status == 200

    def test_absent_without_debug_routes(self):
        app = _obs_app(debug_routes=False)
        resp = app.handle(Request("GET", "/efficiency.json", {}, {}))
        assert resp.status == 404


_EVENT_SERVER_SCRAPE = r"""
import json, sys
from predictionio_tpu.data.storage.config import StorageConfig, StorageRuntime
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.server.event_server import create_event_server_app
from predictionio_tpu.server.httpd import Request
from predictionio_tpu.utils.runtime import backend_initialized

assert "jax" in sys.modules  # the event server does import it
storage = StorageRuntime(StorageConfig.from_env({"PIO_HOME": sys.argv[1]}))
app = create_event_server_app(
    storage, registry=MetricsRegistry(), obs_access_key="k"
)
out = {}
for path in ("/efficiency.json", "/metrics"):
    resp = app.handle(Request("GET", path, {"accessKey": "k"}, {}))
    out[path] = resp.status
    if path == "/efficiency.json" and resp.status == 200:
        out["platform"] = resp.body["platform"]
out["initialized"] = backend_initialized()
print(json.dumps(out))
"""


class TestScrapeLeavesTheChipAlone:
    def test_event_server_scrape_initializes_no_backend(self, tmp_path):
        """One process per chip: the event server imports jax and computes
        nothing, so its observability endpoints must not initialize a
        backend — on a chip host that would claim the chip `pio deploy`
        needs, and under JAX_PLATFORMS=tpu with no chip (this child) it
        raises, i.e. a 500 from a scrape."""
        env = dict(os.environ, JAX_PLATFORMS="tpu")
        proc = subprocess.run(
            [sys.executable, "-c", _EVENT_SERVER_SCRAPE, str(tmp_path)],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
            "/efficiency.json": 200,
            "/metrics": 200,
            "platform": "cpu",
            "initialized": False,
        }


# ---------------------------------------------------------------------------
# acceptance e2e: a real (tiny) NCF engine on the CPU backend


@pytest.fixture(scope="module")
def ncf_model():
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.ncf.engine import NCFModel
    from predictionio_tpu.ops.ncf import NCFParams, NCFState, init_ncf

    n_users, n_items = 64, 600
    p = NCFParams(embed_dim=8, mlp_layers=())
    params = init_ncf(jax.random.PRNGKey(0), n_users, n_items, p)
    state = NCFState(
        params=params, n_users=n_users, n_items=n_items, config=p
    )
    return NCFModel(
        state=state,
        user_vocab=BiMap.from_keys(
            np.asarray([str(u) for u in range(n_users)])
        ),
        item_vocab=BiMap.from_keys(
            np.asarray([str(i) for i in range(n_items)])
        ),
    )


class TestNCFEfficiencyE2E:
    def _wave(self, model, num, n=32, seed=0):
        from predictionio_tpu.models.ncf.engine import NCFAlgorithm, Query

        algo = NCFAlgorithm()
        iq = [
            (i, Query(user=str((seed + i) % 64), num=num))
            for i in range(n)
        ]
        return algo.batch_predict(model, iq)

    def test_deployed_ncf_reports_real_cost_and_utilization(self, ncf_model):
        """Acceptance: after serving waves, /efficiency.json reports
        nonzero achieved-vs-peak utilization for ncf.batch_predict with
        FLOPs/bytes from the real CPU-backend cost_analysis().  The first
        wave of a signature defers its capture off-thread, so flush and
        serve one more wave before asserting."""
        out = self._wave(ncf_model, num=10)
        assert len(out) == 32 and out[0][1].item_scores
        assert device_obs.default_efficiency().flush(timeout=60.0)
        self._wave(ncf_model, num=10, seed=1)
        resp = _obs_app().handle(
            Request("GET", "/efficiency.json", {}, {})
        )
        assert resp.status == 200
        fns = resp.body["functions"]
        assert "ncf.batch_predict" in fns
        entry = fns["ncf.batch_predict"]
        assert entry["flops_per_call"] > 0  # real cost_analysis numbers
        assert entry["bytes_per_call"] > 0
        assert entry["calls"] >= 1
        assert entry["achieved_gbps"] > 0
        assert entry["utilization_hbm"] > 0
        assert entry["utilization_mxu"] > 0
        assert entry["source"] == "cost_analysis"

    def test_wave_transfer_bytes_accounted(self, ncf_model):
        before = device_obs.transfer_totals()
        self._wave(ncf_model, num=10, seed=3)
        after = device_obs.transfer_totals()
        assert after["h2d"] > before["h2d"]
        assert after["d2h"] > before["d2h"]

    def test_shape_churning_queries_trip_the_storm(self, ncf_model):
        """A client sweeping `num` walks the padded top-k width through
        the powers of two: distinct signatures inside the window must trip
        pio_recompile_storm_total for ncf.batch_predict."""
        from predictionio_tpu.obs.metrics import REGISTRY

        storms = REGISTRY.counter(
            "pio_recompile_storm_total", labelnames=("fn",)
        ).labels("ncf.batch_predict")
        before = storms.value
        for num in (10, 20, 40, 90, 180, 400):  # k: 16,32,64,128,256,512
            self._wave(ncf_model, num=num)
        assert storms.value > before
        assert (
            "ncf.batch_predict"
            in device_obs.default_recompiles().active_storms()
        )

    def test_stable_traffic_does_not_storm(self, ncf_model):
        from predictionio_tpu.obs.metrics import REGISTRY

        self._wave(ncf_model, num=10)  # signature now known
        storms = REGISTRY.counter(
            "pio_recompile_storm_total", labelnames=("fn",)
        ).labels("ncf.batch_predict")
        recompiles = REGISTRY.counter(
            "pio_jax_recompile_total", labelnames=("fn",)
        ).labels("ncf.batch_predict")
        s0, r0 = storms.value, recompiles.value
        for seed in range(20):  # a soak of identical-shape waves
            self._wave(ncf_model, num=10, seed=seed)
        assert storms.value == s0  # no new storm
        assert recompiles.value == r0  # and no new compiles at all


class TestFlightCarriesWaveCost:
    """Satellite: the flight-recorder entry of a slow request answers
    "compute-bound or transfer-bound?" directly — the wave's 4-way split
    and cost fields ride the per-item meta into /debug/flight.json."""

    def test_slow_request_flight_entry_has_breakdown(self, ncf_model):
        import threading
        import types
        import urllib.request

        from predictionio_tpu.core.base import FirstServing
        from predictionio_tpu.models.ncf.engine import NCFAlgorithm, Query
        from predictionio_tpu.obs.metrics import MetricsRegistry
        from predictionio_tpu.server.aio import AsyncAppServer
        from predictionio_tpu.server.prediction_server import (
            DeployedEngine,
            create_prediction_server_app,
        )

        deployed = DeployedEngine.__new__(DeployedEngine)
        deployed._lock = threading.RLock()
        deployed.instance = types.SimpleNamespace(id="eff-e2e")
        deployed.storage = None
        deployed.algorithms = [NCFAlgorithm()]
        deployed.models = [ncf_model]
        deployed.serving = FirstServing()
        deployed.extract_query = lambda payload: Query(
            user=str(payload.get("user", "0")),
            num=int(payload.get("num", 10)),
        )
        app = create_prediction_server_app(
            deployed, use_microbatch=True, registry=MetricsRegistry()
        )
        srv = AsyncAppServer(app, "127.0.0.1", 0).start_background()
        try:
            url = f"http://127.0.0.1:{srv.port}/queries.json"

            def post():
                req = urllib.request.Request(
                    url,
                    data=json.dumps({"user": "1", "num": 10}).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=10) as r:
                    assert r.status == 200

            post()
            # the first wave of a signature defers its cost capture; the
            # second wave carries the landed flops/bytes into its entry
            assert device_obs.default_efficiency().flush(timeout=60.0)
            post()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/flight.json", timeout=10
            ) as r:
                flight = json.loads(r.read())
        finally:
            srv.shutdown()
        assert flight["slowest"], "request not retained"
        for entry in flight["slowest"]:
            bd = entry["device_breakdown"]
            assert set(bd) == {
                "host_gather", "h2d", "compute", "d2h", "other",
            }
            assert sum(bd.values()) == pytest.approx(
                entry["device_s"], abs=1e-4
            )
            assert entry["wave_fn"] == "ncf.batch_predict"
            assert entry["wave_device"].startswith("cpu")
        costed = [
            e for e in flight["slowest"] if e.get("wave_flops", 0) > 0
        ]
        assert costed, "no flight entry carries the landed wave cost"
        assert costed[0]["wave_bytes"] > 0


# ---------------------------------------------------------------------------
# mesh shard attribution


class TestShardAttribution:
    def test_single_device_attribution(self):
        from predictionio_tpu.parallel.mesh import shard_attribution

        x = jnp.ones((128, 4), jnp.float32)
        attr = shard_attribution((x, x))
        assert len(attr) == 1
        (label, entry), = attr.items()
        assert label.startswith("cpu")
        assert entry["bytes"] == 2 * 128 * 4 * 4
        assert entry["shards"] == 2

    def test_host_arrays_contribute_nothing(self):
        from predictionio_tpu.parallel.mesh import shard_attribution

        assert shard_attribution(np.ones((8, 8))) == {}

    def test_meter_shards_records_gauges_and_seconds(self):
        from predictionio_tpu.parallel.mesh import meter_shards

        reg = MetricsRegistry()
        x = jnp.ones((64, 8), jnp.float32)
        attr = meter_shards("test.factors", x, seconds=0.25, registry=reg)
        label = next(iter(attr))
        assert reg.get("pio_shard_bytes").labels(
            "test.factors", label
        ).value == 64 * 8 * 4
        hist = reg.get("pio_shard_seconds").labels("test.factors", label)
        assert hist.count == 1

    def test_sharded_mesh_attributes_per_device(self):
        """The per-shard extension point ROADMAP item 1 needs: on the
        virtual 8-device CPU mesh, a data-sharded array attributes one
        slice of bytes to EACH device."""
        from predictionio_tpu.parallel.mesh import (
            MeshConfig,
            make_mesh,
            named_sharding,
            shard_attribution,
        )

        if len(jax.devices()) < 2:
            pytest.skip("needs the multi-device CPU mesh")
        mesh = make_mesh(MeshConfig(axes={"data": len(jax.devices())}))
        n = len(jax.devices())
        x = jax.device_put(
            np.ones((n * 16, 4), np.float32),
            named_sharding(mesh, "data", None),
        )
        attr = shard_attribution(x)
        assert len(attr) == n
        per_dev = 16 * 4 * 4
        assert all(e["bytes"] == per_dev for e in attr.values())

    def test_als_train_populates_shard_and_efficiency_metrics(self):
        """train_als on the scatter path meters its factors per device and
        lands als.train_step on the roofline gauges (real cost_analysis)."""
        from predictionio_tpu.obs.metrics import REGISTRY
        from predictionio_tpu.ops.als import ALSParams, train_als

        rng = np.random.default_rng(0)
        n = 2048
        train_als(
            rng.integers(0, 50, n),
            rng.integers(0, 40, n),
            rng.uniform(1, 5, n).astype(np.float32),
            50,
            40,
            params=ALSParams(rank=4, num_iterations=2, seed=1),
        )
        fam = REGISTRY.get("pio_shard_bytes")
        assert fam is not None
        labels = [lv for lv, _ in fam.series()]
        assert any(fn == "als.factors" for fn, _ in labels)
        eff = device_obs.default_efficiency().snapshot()
        step = eff["functions"].get("als.train_step")
        assert step is not None and step["calls"] >= 1
        assert step["achieved_gbps"] > 0
