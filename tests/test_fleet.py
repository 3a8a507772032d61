"""Fleet layer: membership, consistent-hash routing, capacity aggregation,
and the autoscaler controller.

In-process stub replicas (real HTTP servers with scripted /readyz,
/capacity.json, and /queries.json) drive the router and FleetState; the
autoscaler runs against a fake spawner with a frozen clock so hysteresis
and cooldown are exact assertions, not sleeps.  The cross-process trace
test spawns ONE real serving subprocess so the router lane provably
crosses a process boundary.  The full chaos scenario (SIGKILL a real
`pio deploy` replica mid-traffic) lives in tests/test_fleet_chaos.py.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.fleet.autoscaler import (
    Autoscaler,
    AutoscalerPolicy,
    ReplicaSpawner,
)
from predictionio_tpu.fleet.membership import (
    REPLICA_HEADER,
    FleetState,
    fleet_capacity,
    replica_id_of,
)
from predictionio_tpu.fleet.router import create_router_app
from predictionio_tpu.obs.http import add_observability_routes
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.resilience.breaker import reset_breakers
from predictionio_tpu.server.httpd import (
    AppServer,
    HTTPApp,
    Response,
    json_response,
)


@pytest.fixture(autouse=True)
def _isolate_breakers():
    reset_breakers()
    yield
    reset_breakers()


def _post(url: str, payload: dict, headers: dict | None = None, timeout=30):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            parsed = json.loads(body)
        except ValueError:
            parsed = {"raw": body.decode("utf-8", "replace")}
        return e.code, parsed, dict(e.headers)


def _get(url: str, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except ValueError:
            return e.code, None


class StubReplica:
    """A scriptable replica: answers /queries.json naming itself, /readyz
    per the ``ready`` flag, /capacity.json from the ``capacity`` dict, and
    records the headers of every forwarded query."""

    def __init__(self, name: str, shed: bool = False):
        self.name = name
        self.ready = True
        self.shed = shed
        self.capacity: dict = {}
        self.seen_headers: list[dict] = []
        self.hold: threading.Event | None = None
        app = HTTPApp(f"stub-{name}")

        @app.route("POST", "/queries\\.json")
        def queries(req):
            self.seen_headers.append(dict(req.headers))
            if self.hold is not None:
                self.hold.wait(30)
            if self.shed:
                resp = json_response(503, {"message": "shedding"})
                resp.headers["Retry-After"] = "1"
                return resp
            resp = json_response(
                200, {"replica": self.name, "echo": req.json()}
            )
            resp.headers["X-Pio-Engine-Instance"] = f"inst-{self.name}"
            resp.headers["X-Pio-Variant"] = "default"
            return resp

        @app.route("GET", "/capacity\\.json")
        def capacity(req):
            return json_response(200, self.capacity)

        @app.route("GET", "/readyz", public=True)
        def readyz(req):
            return Response(
                200 if self.ready else 503, {"ready": self.ready}
            )

        self.server = AppServer(app, "127.0.0.1", 0).start_background()
        self.url = f"http://127.0.0.1:{self.server.port}"

    def shutdown(self):
        self.server.shutdown()


def saturated_capacity(observed=150.0, ceiling=100.0, recommended=3):
    return {
        "max_sustainable_qps": ceiling,
        "headroom_frac": round(1.0 - observed / ceiling, 4),
        "recommended_replicas": recommended,
        "scale_hint": "up",
        "inputs": {"observed_qps": observed},
    }


def idle_capacity(observed=5.0, ceiling=100.0):
    return {
        "max_sustainable_qps": ceiling,
        "headroom_frac": round(1.0 - observed / ceiling, 4),
        "recommended_replicas": 1,
        "scale_hint": "hold_or_down",
        "inputs": {"observed_qps": observed},
    }


# ---------------------------------------------------------------------------
# membership + consistent hashing
# ---------------------------------------------------------------------------


class TestMembership:
    def test_replica_id_strips_scheme(self):
        assert replica_id_of("http://10.0.0.5:8101/") == "10.0.0.5:8101"

    def test_route_order_is_deterministic_per_entity(self):
        fleet = FleetState(
            [f"http://127.0.0.1:{8100 + i}" for i in range(4)],
            registry=MetricsRegistry(),
        )
        orders = {
            tuple(r.replica_id for r in fleet.route_order("user-42"))
            for _ in range(20)
        }
        assert len(orders) == 1  # same entity, same full failover order

    def test_entities_spread_across_replicas(self):
        fleet = FleetState(
            [f"http://127.0.0.1:{8100 + i}" for i in range(4)],
            registry=MetricsRegistry(),
        )
        homes = {
            fleet.route_order(f"user-{u}")[0].replica_id for u in range(200)
        }
        assert len(homes) == 4  # every replica is someone's home

    def test_rendezvous_minimal_disruption(self):
        """Removing one replica re-homes ONLY the entities that lived on
        it — the consistent-hashing contract that keeps warm caches warm
        through membership changes."""
        urls = [f"http://127.0.0.1:{8100 + i}" for i in range(4)]
        fleet = FleetState(urls, registry=MetricsRegistry())
        before = {
            f"u{u}": fleet.route_order(f"u{u}")[0].url for u in range(300)
        }
        victim = urls[2]
        fleet.remove(victim)
        for entity, home in before.items():
            after = fleet.route_order(entity)[0].url
            if home == victim:
                assert after != victim
            else:
                assert after == home, f"{entity} moved without cause"

    def test_entityless_queries_rotate(self):
        fleet = FleetState(
            [f"http://127.0.0.1:{8100 + i}" for i in range(3)],
            registry=MetricsRegistry(),
        )
        heads = {fleet.route_order(None)[0].replica_id for _ in range(9)}
        assert len(heads) == 3

    def test_set_replicas_reconciles_preserving_state(self):
        fleet = FleetState(
            ["http://127.0.0.1:8100", "http://127.0.0.1:8101"],
            registry=MetricsRegistry(),
        )
        rep = fleet.get("http://127.0.0.1:8100")
        fleet.note_inflight(rep, +3)
        fleet.set_replicas(
            ["http://127.0.0.1:8100", "http://127.0.0.1:8102"]
        )
        assert fleet.get("http://127.0.0.1:8101") is None
        assert fleet.get("http://127.0.0.1:8102") is not None
        # the survivor kept its counters (same record, not a rebuild)
        assert fleet.get("http://127.0.0.1:8100").inflight == 3

    def test_refresh_from_file_on_mtime_change(self, tmp_path):
        source = tmp_path / "replicas.json"
        source.write_text(json.dumps(["http://127.0.0.1:8100"]))
        fleet = FleetState(
            source_file=str(source), registry=MetricsRegistry()
        )
        assert fleet.refresh() is True
        assert [r.url for r in fleet.replicas()] == ["http://127.0.0.1:8100"]
        assert fleet.refresh() is False  # unchanged mtime: no-op
        source.write_text("http://127.0.0.1:8100\nhttp://127.0.0.1:8101\n")
        import os

        os.utime(source, (time.time() + 2, time.time() + 2))
        assert fleet.refresh() is True  # line-format file also accepted
        assert len(fleet.replicas()) == 2

    def test_refresh_rejects_malformed_json_keeping_membership(self, tmp_path):
        """A JSON object (or any non-list-of-strings) in the source file
        must NOT be applied as an empty membership — that would silently
        drain the whole fleet.  The current membership stays, and the
        mtime is not burned: once the file is fixed, the same refresh
        picks it up."""
        source = tmp_path / "replicas.json"
        source.write_text(json.dumps(["http://127.0.0.1:8100"]))
        fleet = FleetState(
            source_file=str(source), registry=MetricsRegistry()
        )
        assert fleet.refresh() is True
        assert len(fleet.replicas()) == 1
        source.write_text(json.dumps({"replicas": ["http://127.0.0.1:9999"]}))
        import os

        os.utime(source, (time.time() + 2, time.time() + 2))
        assert fleet.refresh() is False
        assert [r.url for r in fleet.replicas()] == ["http://127.0.0.1:8100"]
        # fixing the file (same mtime would be suspicious; bump it) applies
        source.write_text(json.dumps(["http://127.0.0.1:9999"]))
        os.utime(source, (time.time() + 4, time.time() + 4))
        assert fleet.refresh() is True
        assert [r.url for r in fleet.replicas()] == ["http://127.0.0.1:9999"]

    def test_forward_failures_do_not_eject_without_prober(self):
        """With no prober running, nothing could ever re-admit a
        traffic-ejected replica — so transport failures must leave
        ejection to the breaker (which recovers through half-open trials
        on its own)."""
        fleet = FleetState(
            ["http://127.0.0.1:8100"], registry=MetricsRegistry(),
            eject_after=2,
        )
        rep = fleet.replicas()[0]
        for _ in range(5):
            fleet.note_forward_failure(rep)
        assert fleet.routable(), "ejected with no path back to routing"

    def test_forward_success_resets_failure_streak(self):
        """Interleaved transient transport errors never accumulate to an
        ejection: every successful forward resets the streak."""
        fleet = FleetState(
            ["http://127.0.0.1:8100"], registry=MetricsRegistry(),
            eject_after=3,
        )
        # arm traffic ejection as if the prober loop were running, without
        # background probe passes racing the assertions
        fleet._thread = threading.current_thread()
        rep = fleet.replicas()[0]
        for _ in range(4):
            fleet.note_forward_failure(rep)
            fleet.note_forward_success(rep)
        with fleet._lock:
            streak = rep.consecutive_probe_failures
        assert streak == 0
        assert rep.healthy
        # without resets, the same failures WOULD eject
        for _ in range(3):
            fleet.note_forward_failure(rep)
        assert not rep.healthy

    def test_probe_ejects_after_patience_and_readmits(self):
        stub = StubReplica("a")
        try:
            fleet = FleetState(
                [stub.url], registry=MetricsRegistry(), eject_after=2
            )
            assert fleet.probe_once()[stub.url] is True
            stub.ready = False
            fleet.probe_once()
            assert fleet.routable(), "one failed probe must not eject"
            fleet.probe_once()
            assert not fleet.routable(), "second failed probe ejects"
            assert fleet.snapshot()["replicas"][0]["ejections_total"] == 1
            stub.ready = True
            fleet.probe_once()
            assert fleet.routable(), "readmission is immediate"
        finally:
            stub.shutdown()

    def test_ready_probe_closes_an_open_breaker(self):
        """A revived replica whose breaker is still OPEN (reset window not
        yet elapsed) must become routable on the first successful /readyz
        probe: 'a replica that answers ready IS ready' holds for
        routable(), not just healthy — the chaos rejoin phase on a slow
        box caught exactly this gap."""
        stub = StubReplica("a")
        try:
            fleet = FleetState(
                [stub.url], registry=MetricsRegistry(), eject_after=2,
                breaker_reset_s=3600.0,  # a window nobody waits out
            )
            rep = fleet.replicas()[0]
            for _ in range(5):
                rep.breaker.record_failure()
            assert rep.breaker.state == "open"
            assert not fleet.routable()
            assert fleet.probe_once()[stub.url] is True
            assert rep.breaker.state == "closed"
            assert fleet.routable()
        finally:
            stub.shutdown()

    def test_unreachable_replica_is_ejected(self):
        fleet = FleetState(
            ["http://127.0.0.1:1"], registry=MetricsRegistry(), eject_after=1
        )
        fleet.probe_once()
        assert not fleet.routable()
        snap = fleet.snapshot()["replicas"][0]
        assert "unreachable" in snap["last_probe_error"]


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


@pytest.fixture()
def duo():
    """Two stub replicas behind a router, probed healthy."""
    a, b = StubReplica("a"), StubReplica("b")
    registry = MetricsRegistry()
    fleet = FleetState([a.url, b.url], registry=registry)
    fleet.probe_once()
    router = AppServer(
        create_router_app(fleet, registry=registry), "127.0.0.1", 0
    ).start_background()
    base = f"http://127.0.0.1:{router.port}"
    try:
        yield a, b, fleet, base, registry
    finally:
        router.shutdown()
        a.shutdown()
        b.shutdown()


class TestRouter:
    def test_affinity_and_replica_header(self, duo):
        a, b, fleet, base, _ = duo
        seen = set()
        for _ in range(10):
            status, body, headers = _post(
                base + "/queries.json", {"user": "u42", "num": 1}
            )
            assert status == 200
            seen.add((body["replica"], headers[REPLICA_HEADER]))
        assert len(seen) == 1
        name, rid = seen.pop()
        assert rid.endswith(str((a if name == "a" else b).server.port))

    def test_passthrough_headers(self, duo):
        _a, _b, _fleet, base, _ = duo
        status, body, headers = _post(base + "/queries.json", {"user": "u1"})
        assert status == 200
        assert headers["X-Pio-Engine-Instance"] == f"inst-{body['replica']}"
        assert headers["X-Pio-Variant"] == "default"

    def test_propagation_headers_forwarded(self, duo):
        a, b, _fleet, base, _ = duo
        _post(
            base + "/queries.json",
            {"user": "u1"},
            {
                "X-Pio-Request-Id": "ridabc",
                "X-Pio-Trace-Id": "tracexyz",
                "X-Pio-Deadline": "5.0",
            },
        )
        seen = (a.seen_headers or b.seen_headers)[-1]
        lower = {k.lower(): v for k, v in seen.items()}
        assert lower["x-pio-request-id"] == "ridabc"
        assert lower["x-pio-trace-id"] == "tracexyz"
        assert lower["x-pio-parent-span"]  # the fleet.forward span id
        # the deadline forwarded is the REMAINING budget: decremented by
        # the router's own elapsed time, never inflated
        assert 0 < float(lower["x-pio-deadline"]) <= 5.0

    def test_bad_payload_400_without_forward(self, duo):
        a, b, _fleet, base, _ = duo
        status, _body, _ = _post(base + "/queries.json", ["not", "a", "dict"])
        assert status == 400
        assert not a.seen_headers and not b.seen_headers

    def test_no_replicas_sheds_503(self):
        registry = MetricsRegistry()
        fleet = FleetState(registry=registry)
        router = AppServer(
            create_router_app(fleet, registry=registry), "127.0.0.1", 0
        ).start_background()
        try:
            status, _body, headers = _post(
                f"http://127.0.0.1:{router.port}/queries.json", {"user": "u"}
            )
            assert status == 503
            assert "Retry-After" in headers
        finally:
            router.shutdown()

    def test_dead_replica_retries_elsewhere_zero_5xx(self, duo):
        a, b, fleet, base, registry = duo
        # find u42's home and kill exactly it
        home = fleet.route_order("u42")[0]
        victim = a if home.url == a.url else b
        survivor = b if victim is a else a
        victim.shutdown()
        for _ in range(10):
            status, body, headers = _post(
                base + "/queries.json",
                {"user": "u42"},
                {"X-Pio-Deadline": "10"},
            )
            assert status == 200
            assert body["replica"] == survivor.name
        fam = registry.get("pio_router_retry_elsewhere_total")
        retries = {
            labels[0]: c.value for labels, c in fam.series()
        }
        assert retries.get("transport_error", 0) >= 1

    def test_shedding_replica_retries_elsewhere(self):
        shedder = StubReplica("shedder", shed=True)
        ok = StubReplica("ok")
        registry = MetricsRegistry()
        fleet = FleetState([shedder.url, ok.url], registry=registry)
        fleet.probe_once()
        router = AppServer(
            create_router_app(fleet, registry=registry), "127.0.0.1", 0
        ).start_background()
        base = f"http://127.0.0.1:{router.port}"
        try:
            # whatever the entity's home, every answer comes from `ok`
            for u in range(8):
                status, body, _ = _post(
                    base + "/queries.json", {"user": f"u{u}"}
                )
                assert status == 200
                assert body["replica"] == "ok"
        finally:
            router.shutdown()
            shedder.shutdown()
            ok.shutdown()

    def test_all_replicas_shedding_returns_replica_503(self):
        shedders = [StubReplica(f"s{i}", shed=True) for i in range(2)]
        registry = MetricsRegistry()
        fleet = FleetState([s.url for s in shedders], registry=registry)
        fleet.probe_once()
        router = AppServer(
            create_router_app(fleet, registry=registry), "127.0.0.1", 0
        ).start_background()
        try:
            status, _body, headers = _post(
                f"http://127.0.0.1:{router.port}/queries.json", {"user": "u"}
            )
            assert status == 503
            assert headers.get("Retry-After")
            assert headers.get(REPLICA_HEADER)  # names who shed last
        finally:
            router.shutdown()
            for s in shedders:
                s.shutdown()

    def test_expired_budget_is_504_not_a_retry_storm(self, duo):
        a, b, _fleet, base, _ = duo
        status, _body, _ = _post(
            base + "/queries.json", {"user": "u1"}, {"X-Pio-Deadline": "0"}
        )
        assert status == 504
        assert not a.seen_headers and not b.seen_headers

    def test_fleet_json_and_aggregated_capacity(self, duo):
        a, b, _fleet, base, _ = duo
        a.capacity = saturated_capacity(observed=60.0, ceiling=100.0)
        b.capacity = idle_capacity(observed=10.0, ceiling=80.0)
        status, body = _get(base + "/fleet.json")
        assert status == 200
        assert body["total"] == 2 and body["routable"] == 2
        # the router's /capacity.json is the FLEET aggregate, not the
        # router process's own (empty) capacity model
        status, cap = _get(base + "/capacity.json")
        assert status == 200
        assert cap["max_sustainable_qps"] == pytest.approx(180.0)
        # min across replicas: a's 1 - 60/100 = 0.4 (b idles at 0.875)
        assert cap["headroom_frac"] == pytest.approx(0.4, abs=1e-6)
        assert cap["fleet"]["replicas"] == 2
        assert set(cap["fleet"]["per_replica"]) == {
            replica_id_of(a.url),
            replica_id_of(b.url),
        }

    def test_capacity_route_serves_cached_scrape_when_fresh(self, duo):
        """The router's /capacity.json must not re-fan N replica calls on
        every request: a scrape younger than the freshness window is
        served from cache (the autoscaler owns the scrape cadence)."""
        a, b, _fleet, base, _ = duo
        a.capacity = idle_capacity(observed=10.0, ceiling=100.0)
        b.capacity = idle_capacity(observed=10.0, ceiling=100.0)
        status, cap1 = _get(base + "/capacity.json")
        assert status == 200
        assert cap1["max_sustainable_qps"] == pytest.approx(200.0)
        # the stubs now report differently, but the cache is fresh
        a.capacity = idle_capacity(observed=10.0, ceiling=500.0)
        status, cap2 = _get(base + "/capacity.json")
        assert status == 200
        assert cap2["max_sustainable_qps"] == pytest.approx(200.0)

    def test_access_key_gates_fleet_surfaces(self):
        stub = StubReplica("a")
        registry = MetricsRegistry()
        fleet = FleetState([stub.url], registry=registry)
        fleet.probe_once()
        router = AppServer(
            create_router_app(fleet, registry=registry, access_key="sekret"),
            "127.0.0.1",
            0,
        ).start_background()
        base = f"http://127.0.0.1:{router.port}"
        try:
            assert _get(base + "/fleet.json")[0] == 401
            assert _get(base + "/capacity.json")[0] == 401
            assert _get(base + "/fleet.json?accessKey=sekret")[0] == 200
            assert _get(base + "/healthz")[0] == 200  # always open
            # serving stays open (the public surface)
            assert _post(base + "/queries.json", {"user": "u"})[0] == 200
        finally:
            router.shutdown()
            stub.shutdown()

    def test_router_readyz_follows_fleet(self, duo):
        a, b, fleet, base, _ = duo
        assert _get(base + "/readyz")[0] == 200
        a.ready = False
        b.ready = False
        fleet.probe_once()
        fleet.probe_once()
        assert _get(base + "/readyz")[0] == 503


# ---------------------------------------------------------------------------
# fleet capacity aggregation
# ---------------------------------------------------------------------------


class TestFleetCapacity:
    def _fleet_with(self, caps):
        fleet = FleetState(
            [f"http://127.0.0.1:{8100 + i}" for i in range(len(caps))],
            registry=MetricsRegistry(),
        )
        for rep, cap in zip(fleet.replicas(), caps):
            with fleet._lock:
                rep.last_capacity = cap
        return fleet

    def test_sums_min_and_recommendation(self):
        fleet = self._fleet_with(
            [
                saturated_capacity(observed=150.0, ceiling=100.0),
                idle_capacity(observed=30.0, ceiling=100.0),
            ]
        )
        cap = fleet_capacity(fleet, scrape=False)
        assert cap["max_sustainable_qps"] == pytest.approx(200.0)
        assert cap["headroom_frac"] == pytest.approx(-0.5)
        # ceil(180 / (0.7 * 100)) = ceil(2.57) = 3
        assert cap["recommended_replicas"] == 3
        assert cap["scale_hint"] == "up"

    def test_no_scrapes_yet_is_honest(self):
        fleet = self._fleet_with([None, None])
        cap = fleet_capacity(fleet, scrape=False)
        assert cap["max_sustainable_qps"] is None
        assert cap["recommended_replicas"] is None
        assert len(cap["caveats"]) == 2

    def test_burning_replica_adds_one(self):
        burning = saturated_capacity(observed=60.0, ceiling=100.0)
        burning["inputs"]["error_burn_rate"] = 2.0
        fleet = self._fleet_with([burning])
        cap = fleet_capacity(fleet, scrape=False)
        # ceil(60/70)=1, +1 for the burn
        assert cap["recommended_replicas"] == 2


# ---------------------------------------------------------------------------
# autoscaler
# ---------------------------------------------------------------------------


class FakeSpawner(ReplicaSpawner):
    def __init__(self, fail: bool = False):
        self.fail = fail
        self.spawned: list[str] = []
        self.drained: list[str] = []

    def spawn(self) -> str:
        if self.fail:
            raise RuntimeError("no capacity on this host")
        url = f"http://127.0.0.1:{9100 + len(self.spawned)}"
        self.spawned.append(url)
        return url

    def drain(self, url: str) -> None:
        self.drained.append(url)


class TestAutoscaler:
    def _setup(self, caps, policy=None, spawner=None):
        fleet = FleetState(
            [f"http://127.0.0.1:{8100 + i}" for i in range(len(caps))],
            registry=MetricsRegistry(),
        )
        for rep, cap in zip(fleet.replicas(), caps):
            with fleet._lock:
                rep.last_capacity = cap
        fleet.scrape_capacity_once = lambda: {}  # capacities are scripted
        clock = [0.0]
        auto = Autoscaler(
            fleet,
            spawner or FakeSpawner(),
            policy
            or AutoscalerPolicy(
                min_replicas=1,
                max_replicas=3,
                scale_up_patience=2,
                scale_down_patience=2,
                cooldown_s=10.0,
            ),
            registry=MetricsRegistry(),
            clock=lambda: clock[0],
        )
        return fleet, auto, clock

    def test_scale_up_needs_patience(self):
        fleet, auto, _clock = self._setup(
            [saturated_capacity(observed=150.0, ceiling=100.0)]
        )
        assert auto.tick() is None  # 1 of 2 agreeing ticks
        assert auto.tick() == "scale_up"
        assert fleet.active_count() == 2

    def test_cooldown_spaces_actions(self):
        fleet, auto, clock = self._setup(
            [saturated_capacity(observed=300.0, ceiling=100.0)]
        )
        auto.tick()
        assert auto.tick() == "scale_up"
        # streaks may re-accumulate, but no action inside the cooldown
        assert auto.tick() is None
        assert auto.tick() is None
        assert fleet.active_count() == 2
        clock[0] += 11.0
        assert auto.tick() == "scale_up"
        assert fleet.active_count() == 3

    def test_max_replicas_caps_growth(self):
        fleet, auto, clock = self._setup(
            [saturated_capacity(observed=900.0, ceiling=100.0, recommended=9)]
        )
        for _ in range(10):
            auto.tick()
            clock[0] += 11.0
        assert fleet.active_count() == 3  # the policy ceiling

    def test_scale_down_quiesces_then_drains_then_removes(self):
        spawner = FakeSpawner()
        caps = [idle_capacity(), idle_capacity(), idle_capacity()]
        fleet, auto, _clock = self._setup(caps, spawner=spawner)
        events: list[str] = []
        orig_quiesce = fleet.quiesce

        def spying_quiesce(url):
            events.append(f"quiesce:{url}")
            return orig_quiesce(url)

        fleet.quiesce = spying_quiesce
        orig_drain = spawner.drain

        def spying_drain(url):
            events.append(f"drain:{url}")
            rep = fleet.get(url)
            assert rep is not None and rep.draining, (
                "drain must happen AFTER routing stopped"
            )
            orig_drain(url)

        spawner.drain = spying_drain
        assert auto.tick() is None
        assert auto.tick() == "scale_down"
        assert fleet.active_count() == 2
        victim = spawner.drained[0]
        assert events == [f"quiesce:{victim}", f"drain:{victim}"]
        assert fleet.get(victim) is None

    def test_min_replicas_floor(self):
        fleet, auto, clock = self._setup([idle_capacity()])
        for _ in range(6):
            auto.tick()
            clock[0] += 11.0
        assert fleet.active_count() == 1

    def test_pinned_target_skips_hysteresis(self):
        fleet, auto, _clock = self._setup(
            [idle_capacity()]  # the model says hold at 1
        )
        auto.set_target(3)
        assert auto.tick() == "scale_up"
        assert auto.tick() == "scale_up"
        assert fleet.active_count() == 3
        auto.set_target(None)
        snap = auto.snapshot()
        assert snap["target_override"] is None

    def test_spawn_failure_is_contained(self):
        fleet, auto, _clock = self._setup(
            [saturated_capacity()], spawner=FakeSpawner(fail=True)
        )
        auto.tick()
        assert auto.tick() is None  # failed spawn, no crash
        assert fleet.active_count() == 1
        assert auto.snapshot()["last_event"]["event"] == "spawn_failed"

    def test_no_signal_holds(self):
        fleet, auto, _clock = self._setup([None])
        assert auto.tick() is None
        assert fleet.active_count() == 1


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------


class TestFleetCLI:
    @pytest.fixture()
    def router_stack(self):
        a, b = StubReplica("a"), StubReplica("b")
        registry = MetricsRegistry()
        fleet = FleetState([a.url, b.url], registry=registry)
        fleet.probe_once()
        spawner = FakeSpawner()
        auto = Autoscaler(
            fleet, spawner, AutoscalerPolicy(), registry=MetricsRegistry()
        )
        router = AppServer(
            create_router_app(fleet, registry=registry, autoscaler=auto),
            "127.0.0.1",
            0,
        ).start_background()
        base = f"http://127.0.0.1:{router.port}"
        try:
            yield a, b, fleet, auto, base
        finally:
            router.shutdown()
            a.shutdown()
            b.shutdown()

    def test_fleet_status_text_and_json(self, router_stack, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        _a, _b, _fleet, _auto, base = router_stack
        assert cli_main(["fleet", "status", "--url", base]) == 0
        out = capsys.readouterr().out
        assert "2 replicas" in out and "2 routable" in out
        assert cli_main(["fleet", "status", "--url", base, "--json"]) == 0
        body = json.loads(capsys.readouterr().out)
        assert body["total"] == 2
        assert body["autoscaler"]["enabled"] is True

    def test_fleet_status_exit_1_when_dead(self, router_stack, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        a, b, fleet, _auto, base = router_stack
        a.ready = False
        b.ready = False
        fleet.probe_once()
        fleet.probe_once()
        assert cli_main(["fleet", "status", "--url", base]) == 1
        assert "zero routable" in capsys.readouterr().err

    def test_fleet_scale_pins_target(self, router_stack, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        _a, _b, _fleet, auto, base = router_stack
        assert cli_main(["fleet", "scale", "3", "--url", base]) == 0
        assert auto.snapshot()["target_override"] == 3
        assert cli_main(["fleet", "scale", "auto", "--url", base]) == 0
        assert auto.snapshot()["target_override"] is None
        assert cli_main(["fleet", "scale", "0", "--url", base]) == 1
        capsys.readouterr()

    def test_fleet_watch_bounded(self, router_stack, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        _a, _b, _fleet, _auto, base = router_stack
        assert (
            cli_main(
                ["fleet", "watch", "--url", base, "--watch", "0.05",
                 "--watch-count", "2"]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert out.count("2 replicas") == 2

    def test_pio_capacity_url_renders_fleet(self, router_stack, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        a, b, _fleet, _auto, base = router_stack
        a.capacity = saturated_capacity(observed=60.0, ceiling=100.0)
        b.capacity = idle_capacity(observed=10.0, ceiling=80.0)
        assert cli_main(["capacity", "--url", base]) == 0
        out = capsys.readouterr().out
        assert "fleet:" in out
        assert "180 qps" in out  # sum of replica ceilings

    def test_pio_status_url_folds_fleet(self, router_stack, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        a, b, fleet, _auto, base = router_stack
        assert cli_main(["status", "--url", base, "--no-quality"]) == 0
        capsys.readouterr()
        a.ready = False
        fleet.probe_once()
        fleet.probe_once()
        # one ejected replica: WARNING, exit still 0 (fleet can serve)
        assert cli_main(["status", "--url", base, "--no-quality"]) == 0
        captured = capsys.readouterr()
        assert "WARNING: replica" in captured.err
        assert json.loads(captured.out)["fleet"]["healthy"] == 1
        # zero healthy replicas: exit 1 even though the router is alive
        b.ready = False
        fleet.probe_once()
        fleet.probe_once()
        assert cli_main(["status", "--url", base, "--no-quality"]) == 1
        capsys.readouterr()


# ---------------------------------------------------------------------------
# cross-process trace: the router lane in the assembled waterfall
# ---------------------------------------------------------------------------


class TestRouterTraceLane:
    def test_router_lane_appears_in_assembled_trace(self, tmp_path):
        """A traced request through router -> REAL serving subprocess
        assembles into one tree whose lanes show the router hop:
        http.router -> fleet.forward -> (other process) http.predictionserver."""
        import subprocess
        import sys as _sys

        import numpy as np

        from serving_fixture import _SERVER_SCRIPT
        from predictionio_tpu.obs import timeline as tlm

        blob = tmp_path / "m.npz"
        np.savez(
            blob,
            U=np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32),
            V=np.random.default_rng(1).normal(size=(24, 4)).astype(np.float32),
        )
        import os

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        srv = subprocess.Popen(
            [_sys.executable, "-c", _SERVER_SCRIPT, str(blob)],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=repo_root,
        )
        router = None
        try:
            port_line = srv.stdout.readline()
            assert port_line.strip(), srv.communicate(timeout=10)[1][-800:]
            port = int(port_line)
            registry = MetricsRegistry()
            fleet = FleetState(
                [f"http://127.0.0.1:{port}"], registry=registry
            )
            fleet.probe_once()
            router = AppServer(
                create_router_app(fleet, registry=registry), "127.0.0.1", 0
            ).start_background()
            tid = "fleetlane01"
            status, _body, headers = _post(
                f"http://127.0.0.1:{router.port}/queries.json",
                {"user": "7", "num": 3},
                {"X-Pio-Trace-Id": tid},
            )
            assert status == 200
            assert headers["X-Pio-Trace-Id"] == tid
            deadline = time.monotonic() + 10
            tl = None
            while time.monotonic() < deadline:
                tl = tlm.collect_trace(
                    tid,
                    urls=[f"http://127.0.0.1:{port}"],
                    include_local=True,
                    timeout=3.0,
                )
                names = {n.name for n in tl.nodes.values()}
                if "http.predictionserver" in names:
                    break
                time.sleep(0.2)
            txt = tl.render_text()
            assert "http.router" in txt
            assert "fleet.forward" in txt
            assert "http.predictionserver" in txt
            # the replica's root parents UNDER the router's forward span
            forward = next(
                n for n in tl.nodes.values() if n.name == "fleet.forward"
            )
            child_names = {c.name for c in forward.children}
            assert "http.predictionserver" in child_names
            # two distinct processes in the assembled timeline
            procs = {n.process for n in tl.nodes.values()}
            assert len(procs) >= 2
        finally:
            if router is not None:
                router.shutdown()
            try:
                srv.communicate(input="\n", timeout=15)
            except Exception:
                srv.kill()
