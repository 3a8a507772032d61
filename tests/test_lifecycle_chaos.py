"""Chaos end-to-end for the model lifecycle: the closed loop proven under
fire.

- **Run A** — drift injection on a real ALS deploy triggers a warm-start
  retrain; the new generation canaries on its entity-hash fraction under
  the ``canary`` variant in ``/quality.json``; a clean canary
  auto-promotes with zero dropped/torn requests while traffic hammers
  through the flip.
- **Run B** — a fault-injected garbage generation (every canary dispatch
  errors) breaches the error-rate guardrail and auto-rolls-back; live
  traffic is unaffected throughout.
- **Run C** — a REAL serving subprocess is SIGKILLed mid-swap (stalled at
  the ``lifecycle.swap`` seam between verification and the manifest
  commit); the restart binds the manifest's last-good generation and
  answers identically.
- **Swap atomicity** — a hammering client during repeated verify-and-swap
  flips (live and canary) observes only whole generations: every
  response's ``X-Pio-Engine-Instance`` matches both the body's model
  marker and the variant the QualityMonitor logged for that request id;
  zero 5xx, zero mixed pairs.
- **Corrupt-blob fallback** — a tampered live generation is refused by
  checksum at bind and the server comes up on the previous good one.

Deterministic throughout: seeded injector, manually-driven controller
ticks, no sleeps in the decision paths.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np
import pytest

from predictionio_tpu.core.base import (
    Algorithm,
    DataSource,
    EngineContext,
    FirstServing,
)
from predictionio_tpu.core.engine import Engine, EngineParams, engine_registry
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.lifecycle import (
    CanaryPolicy,
    GenerationStore,
    LifecycleController,
    LifecyclePolicy,
)
from predictionio_tpu.lifecycle.canary import CANARY_VARIANT, in_canary_fraction
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.quality import QualityMonitor
from predictionio_tpu.resilience import faults
from predictionio_tpu.server.aio import AsyncAppServer
from predictionio_tpu.server.prediction_server import (
    create_prediction_server_app,
    deploy_engine,
)


@pytest.fixture(autouse=True)
def _clear_faults():
    faults.clear()
    yield
    faults.clear()


def _post(url, payload, headers=None, timeout=30):
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
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


# ---------------------------------------------------------------------------
# shared ALS stack: events -> train gen1 -> deploy with manifest + controller
# ---------------------------------------------------------------------------


def _als_params(app="lc", iters=3, rank=4):
    from predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams,
        DataSourceParams,
    )

    return EngineParams(
        datasource=("ratings", DataSourceParams(app_name=app)),
        preparator=("ratings", None),
        algorithms=(
            ("als", ALSAlgorithmParams(rank=rank, num_iterations=iters)),
        ),
        serving=("first", None),
    )


def _seed_events(storage, app_name="lc", n_users=16, n_items=12, seed=11):
    app_id = storage.apps().insert(App(id=0, name=app_name))
    le = storage.l_events()
    le.init(app_id)
    rng = np.random.default_rng(seed)
    events = [
        Event(
            event="rate", entity_type="user", entity_id=f"u{u}",
            target_entity_type="item", target_entity_id=f"m{i}",
            properties=DataMap({"rating": float(rng.uniform(1, 5))}),
        )
        for u in range(n_users) for i in range(n_items)
        if rng.random() < 0.75
    ]
    le.insert_batch(events, app_id)
    return app_id


@dataclass
class Stack:
    server: object
    base: str
    deployed: object
    controller: LifecycleController
    quality: QualityMonitor
    registry: MetricsRegistry
    storage: object
    gen1: str

    def shutdown(self):
        self.server.shutdown()


@pytest.fixture()
def als_stack(storage):
    """Real ALS engine, trained + deployed in-process with a generation
    manifest, quality monitor (tiny drift windows), and a lifecycle
    controller whose ticks the test drives by hand."""
    from predictionio_tpu.models.recommendation import recommendation_engine  # noqa: F401

    _seed_events(storage)
    params = _als_params()
    engine_factory = "recommendation"
    from predictionio_tpu.core.engine import resolve_engine_factory

    engine = resolve_engine_factory(engine_factory)()
    inst1 = run_train(
        engine, params, ctx=EngineContext(storage=storage),
        storage=storage, engine_factory=engine_factory,
    )
    deployed = deploy_engine(engine_factory, storage=storage)
    assert deployed.instance.id == inst1.id
    registry = MetricsRegistry()
    quality = QualityMonitor(
        registry=registry, drift_window=16, drift_patience=1,
    )
    policy = LifecyclePolicy(
        canary=CanaryPolicy(
            fraction=0.5, min_requests=5, max_error_rate=0.2,
            min_joined=0, max_canary_s=600.0,
        ),
        cooldown_s=0.0,
    )
    controller = LifecycleController(
        deployed, deployed.generation_store, quality=quality,
        policy=policy, registry=registry,
    )
    app = create_prediction_server_app(
        deployed,
        use_microbatch=True,
        registry=registry,
        quality=quality,
        lifecycle=controller,
        lifecycle_autostart=False,
    )
    server = AsyncAppServer(app, "127.0.0.1", 0).start_background()
    stack = Stack(
        server=server, base=f"http://127.0.0.1:{server.port}",
        deployed=deployed, controller=controller, quality=quality,
        registry=registry, storage=storage, gen1=inst1.id,
    )
    yield stack
    stack.shutdown()


def _inject_drift(stack, window=16):
    """Seed the drift reference with num=10 queries, then shift num by
    ~4 orders of magnitude until the detector flips to drifting."""
    for i in range(window):
        code, _, _ = _post(
            stack.base + "/queries.json", {"user": f"u{i % 8}", "num": 10}
        )
        assert code == 200
    shifted = 0
    while stack.quality.drift_state() != "drifting" and shifted < 4 * window:
        _post(
            stack.base + "/queries.json",
            {"user": f"u{shifted % 8}", "num": 100000},
        )
        shifted += 1
    assert stack.quality.drift_state() == "drifting"


def _canary_users(n=64, fraction=0.5):
    users = [f"u{i}" for i in range(n)]
    canary = [u for u in users if in_canary_fraction(u, fraction)]
    live = [u for u in users if not in_canary_fraction(u, fraction)]
    assert canary and live
    return canary, live


class TestRunACleanPromotion:
    def test_drift_retrain_canary_promote_with_zero_dropped(self, als_stack):
        stack = als_stack
        _inject_drift(stack)

        # drift -> warm-start retrain -> staged canary
        assert stack.controller.tick() == "retrain"
        gen2 = stack.deployed.canary_instance.id
        assert gen2 != stack.gen1
        manifest = stack.deployed.generation_store.snapshot()
        assert manifest["canary"] == gen2
        assert manifest["live"] == stack.gen1

        canary_users, live_users = _canary_users()
        results = []
        results_lock = threading.Lock()

        def hammer(users):
            out = []
            for u in users:
                code, body, headers = _post(
                    stack.base + "/queries.json", {"user": u, "num": 3}
                )
                out.append((u, code, body, headers))
            with results_lock:
                results.extend(out)

        # canary serves its hash fraction under its own variant
        with ThreadPoolExecutor(4) as ex:
            for chunk in (canary_users[:16], live_users[:16]):
                ex.submit(hammer, chunk)
        with results_lock:
            assert all(code == 200 for _, code, _, _ in results)
            seen_variants = {
                h["X-Pio-Variant"] for _, _, _, h in results
            }
        assert seen_variants == {"default", CANARY_VARIANT}
        snap = stack.quality.snapshot()
        assert CANARY_VARIANT in snap["variants"]
        assert snap["variants"][CANARY_VARIANT]["predictions"] > 0
        code, lc = _get(stack.base + "/lifecycle.json")
        assert code == 200 and lc["canary_in_progress"]
        assert lc["canary_instance"] == gen2

        # promote WHILE traffic hammers through the flip: nothing drops
        flip_results: list = []

        def hammer_through_flip():
            out = []
            for i in range(30):
                u = (canary_users + live_users)[i % 48]
                out.append(
                    _post(stack.base + "/queries.json", {"user": u, "num": 3})
                )
            flip_results.extend(out)

        t = threading.Thread(target=hammer_through_flip)
        t.start()
        deadline = time.monotonic() + 10
        outcome = None
        while time.monotonic() < deadline:
            outcome = stack.controller.tick()
            if outcome in ("promote", "rollback"):
                break
        t.join()
        assert outcome == "promote"
        assert all(code == 200 for code, _, _ in flip_results)
        # every answer during the flip came from a WHOLE generation
        for code, _, headers in flip_results:
            assert headers["X-Pio-Engine-Instance"] in (stack.gen1, gen2)
        # the manifest flipped atomically: gen2 live, gen1 retired
        manifest = stack.deployed.generation_store.snapshot()
        assert manifest["live"] == gen2
        gens = {g["instance_id"]: g for g in manifest["generations"]}
        assert gens[stack.gen1]["status"] == "retired"
        assert gens[gen2]["promoted_at"] is not None
        # post-promote traffic serves gen2 with no canary split left
        code, body, headers = _post(
            stack.base + "/queries.json", {"user": "u1", "num": 3}
        )
        assert code == 200
        assert headers["X-Pio-Engine-Instance"] == gen2
        assert headers["X-Pio-Variant"] == "default"
        # lifecycle counters moved
        assert (
            stack.registry.get("pio_lifecycle_promotions_total")
            .labels().value == 1
        )
        assert (
            stack.registry.get("pio_lifecycle_retrains_total")
            .labels("drift").value == 1
        )


class TestRunBGarbageRollback:
    def test_guardrail_breach_rolls_back_live_unaffected(self, als_stack):
        stack = als_stack
        _inject_drift(stack)
        assert stack.controller.tick() == "retrain"
        gen2 = stack.deployed.canary_instance.id

        # the "garbage retrain": every canary dispatch errors (seeded plan)
        faults.install(
            [{"seam": "canary.predict", "kind": "error", "match": gen2}]
        )
        canary_users, live_users = _canary_users()
        canary_codes, live_codes = [], []
        for u in canary_users[:8]:
            code, _, headers = _post(
                stack.base + "/queries.json", {"user": u, "num": 3}
            )
            canary_codes.append(code)
            assert headers["X-Pio-Variant"] == CANARY_VARIANT
        for u in live_users[:8]:
            code, _, headers = _post(
                stack.base + "/queries.json", {"user": u, "num": 3}
            )
            live_codes.append(code)
            assert headers["X-Pio-Variant"] == "default"
        assert all(c == 500 for c in canary_codes)
        assert all(c == 200 for c in live_codes)  # live untouched

        outcome = stack.controller.tick()
        assert outcome == "rollback"
        assert stack.deployed.canary_instance is None
        manifest = stack.deployed.generation_store.snapshot()
        assert manifest["live"] == stack.gen1
        gens = {g["instance_id"]: g for g in manifest["generations"]}
        assert gens[gen2]["status"] == "rolled_back"
        assert (
            stack.registry.get("pio_lifecycle_rollbacks_total")
            .labels("error_rate").value == 1
        )
        # after rollback EVERY user serves live again, canary faults moot
        for u in canary_users[:4] + live_users[:4]:
            code, _, headers = _post(
                stack.base + "/queries.json", {"user": u, "num": 3}
            )
            assert code == 200
            assert headers["X-Pio-Engine-Instance"] == stack.gen1
            assert headers["X-Pio-Variant"] == "default"
        # the status surface reported the recent rollback as a note, not a
        # failure (exit code unchanged) — asserted at the manifest level
        assert manifest["rolled_back"] == 1
        assert manifest["last_rollback_at"] is not None


# ---------------------------------------------------------------------------
# swap atomicity under concurrency (marker engine, repeated flips)
# ---------------------------------------------------------------------------


class _MarkerTD:
    pass


class MarkerDataSource(DataSource):
    def __init__(self, params=None):
        pass

    def read_training(self, ctx):
        return _MarkerTD()


@dataclass(frozen=True)
class MarkerParams:
    marker: str = "A"


class MarkerAlgo(Algorithm):
    """A model that IS its generation marker: every answer names the
    generation that produced it, so a torn read is directly visible."""

    params_class = MarkerParams

    def __init__(self, params=None):
        self.params = params or MarkerParams()

    def train(self, ctx, pd):
        return {"marker": self.params.marker}

    def predict(self, model, q):
        return {"gen": model["marker"], "user": q.get("user")}

    def batch_predict(self, model, iq):
        return [(i, self.predict(model, q)) for i, q in iq]

    def make_persistent_model(self, ctx, model):
        return model

    def load_persistent_model(self, ctx, model):
        return model


class MarkerPreparator:
    def __init__(self, params=None):
        pass

    def prepare(self, ctx, td):
        return td


if "lifecycle-marker-test" not in engine_registry:
    engine_registry.register(
        "lifecycle-marker-test",
        lambda: Engine(
            MarkerDataSource, MarkerPreparator, {"marker": MarkerAlgo},
            FirstServing,
        ),
    )


class TestSwapAtomicityUnderConcurrency:
    def test_hammer_observes_only_whole_generations(self, storage):
        """Satellite acceptance: during repeated flips (live swaps AND a
        canary split), every response is a whole generation — the
        X-Pio-Engine-Instance header, the body's model marker, and the
        variant the QualityMonitor logged for that request id all agree;
        zero 5xx."""
        factory = "lifecycle-marker-test"

        def marker_params(m):
            return EngineParams(
                datasource=("", None),
                preparator=("", None),
                algorithms=(("marker", MarkerParams(marker=m)),),
                serving=("", None),
            )

        engine = engine_registry.get(factory)()
        inst_a = run_train(
            engine, marker_params("A"), ctx=EngineContext(storage=storage),
            storage=storage, engine_factory=factory,
        )
        inst_b = run_train(
            engine, marker_params("B"), ctx=EngineContext(storage=storage),
            storage=storage, engine_factory=factory,
        )
        deployed = deploy_engine(
            factory, storage=storage, engine_instance_id=inst_a.id
        )
        marker_of = {inst_a.id: "A", inst_b.id: "B"}
        registry = MetricsRegistry()
        quality = QualityMonitor(registry=registry)
        app = create_prediction_server_app(
            deployed, use_microbatch=True, registry=registry,
            quality=quality,
        )
        server = AsyncAppServer(app, "127.0.0.1", 0).start_background()
        base = f"http://127.0.0.1:{server.port}"
        inst_by_variant_lock = threading.Lock()

        results = []
        stop = threading.Event()

        def hammer(worker):
            n = 0
            while not stop.is_set():
                u = f"w{worker}-u{n % 40}"
                code, body, headers = _post(
                    base + "/queries.json", {"user": u}
                )
                results.append((code, body, headers))
                n += 1

        try:
            with ThreadPoolExecutor(4) as ex:
                for w in range(3):
                    ex.submit(hammer, w)
                # 12 live flips A<->B while the hammer runs
                flip_to = [inst_b, inst_a] * 6
                for inst in flip_to:
                    deployed.verify_and_swap(inst)
                # and a canary phase: B canaries at 50% over live A
                deployed.generation_store.record(inst_b.id, status="staged")
                deployed.stage_canary(inst_b, fraction=0.5)
                time.sleep(0.3)
                deployed.promote_canary()
                time.sleep(0.2)
                stop.set()
        finally:
            stop.set()
            server.shutdown()

        assert len(results) > 50
        mismatches = []
        for code, body, headers in results:
            if code != 200:
                mismatches.append(("status", code, body))
                continue
            inst = headers.get("X-Pio-Engine-Instance")
            variant = headers.get("X-Pio-Variant")
            # body vs header: the whole-generation check
            if body.get("gen") != marker_of.get(inst):
                mismatches.append(("torn", inst, body))
            # header variant vs the quality log for this request id
            rid = headers.get("X-Pio-Request-Id")
            rec = quality.record_for(rid) if rid else None
            if rec is None or rec["variant"] != variant:
                mismatches.append(("variant", rid, variant, rec))
            # a canary-labeled answer must be the canary generation
            if variant == CANARY_VARIANT and inst != inst_b.id:
                mismatches.append(("canary-inst", inst))
        assert mismatches == [], mismatches[:5]


# ---------------------------------------------------------------------------
# corrupt live blob at bind -> last-good fallback
# ---------------------------------------------------------------------------


class TestCorruptBindFallback:
    def test_startup_refuses_corrupt_live_and_binds_last_good(self, storage):
        factory = "lifecycle-marker-test"
        engine = engine_registry.get(factory)()
        params_a = EngineParams(
            datasource=("", None), preparator=("", None),
            algorithms=(("marker", MarkerParams(marker="A")),),
            serving=("", None),
        )
        params_b = EngineParams(
            datasource=("", None), preparator=("", None),
            algorithms=(("marker", MarkerParams(marker="B")),),
            serving=("", None),
        )
        inst_a = run_train(
            engine, params_a, ctx=EngineContext(storage=storage),
            storage=storage, engine_factory=factory,
        )
        inst_b = run_train(
            engine, params_b, ctx=EngineContext(storage=storage),
            storage=storage, engine_factory=factory,
        )
        store = GenerationStore(storage.models(), "default", "default", "default")
        store.record(inst_a.id, status="live")
        store.record(inst_b.id, status="live")  # b live, a retired
        # bit-rot b's stored bytes AFTER checksumming
        models = storage.models()
        manifest_blob = models.get(f"{inst_b.id}:manifest")
        if manifest_blob is not None:
            models.insert(
                f"{inst_b.id}:manifest",
                manifest_blob[:-1] + bytes([manifest_blob[-1] ^ 0xFF]),
            )
        else:
            blob = models.get(inst_b.id)
            models.insert(inst_b.id, blob[:-1] + bytes([blob[-1] ^ 0xFF]))
        deployed = deploy_engine(factory, storage=storage)
        # the corrupt head was refused; the previous good generation serves
        assert deployed.instance.id == inst_a.id
        assert store.get(inst_b.id).status == "rolled_back"
        assert "corrupt" in store.get(inst_b.id).note


# ---------------------------------------------------------------------------
# the gated /reload + CLI surfaces
# ---------------------------------------------------------------------------


def _marker_instances(storage, factory="lifecycle-marker-test"):
    engine = engine_registry.get(factory)()

    def params(m):
        return EngineParams(
            datasource=("", None), preparator=("", None),
            algorithms=(("marker", MarkerParams(marker=m)),),
            serving=("", None),
        )

    inst_a = run_train(
        engine, params("A"), ctx=EngineContext(storage=storage),
        storage=storage, engine_factory=factory,
    )
    # "latest COMPLETED" orders by startTime in whole milliseconds, and a
    # marker train takes 0.2 ms: B has to start in a later one than A
    time.sleep(0.002)
    inst_b = run_train(
        engine, params("B"), ctx=EngineContext(storage=storage),
        storage=storage, engine_factory=factory,
    )
    return inst_a, inst_b


class TestReloadGate:
    def _server(self, storage, inst_id, access_key=None):
        deployed = deploy_engine(
            "lifecycle-marker-test", storage=storage,
            engine_instance_id=inst_id,
        )
        app = create_prediction_server_app(
            deployed, registry=MetricsRegistry(),
            quality=QualityMonitor(registry=MetricsRegistry()),
            access_key=access_key,
        )
        server = AsyncAppServer(app, "127.0.0.1", 0).start_background()
        return server, deployed, f"http://127.0.0.1:{server.port}"

    def test_reload_verifies_then_flips(self, storage):
        inst_a, inst_b = _marker_instances(storage)
        server, deployed, base = self._server(storage, inst_a.id)
        try:
            code, body, _ = _post(base + "/reload", {})
            assert code == 200
            assert body["engineInstanceId"] == inst_b.id
            store = deployed.generation_store
            assert store.live().instance_id == inst_b.id
            assert store.get(inst_a.id).status == "retired"
        finally:
            server.shutdown()

    def test_reload_refuses_corrupt_candidate_with_409(self, storage):
        inst_a, inst_b = _marker_instances(storage)
        # bit-rot the candidate's bytes (inst_b is "latest COMPLETED")
        models = storage.models()
        key = f"{inst_b.id}:manifest"
        blob = models.get(key)
        models.insert(key, blob[:-1] + bytes([blob[-1] ^ 0xFF]))
        server, deployed, base = self._server(storage, inst_a.id)
        try:
            store = deployed.generation_store
            store.record(inst_b.id, status="staged")  # checksum of clean?
            # recompute AFTER corruption so record holds the corrupt sum —
            # then corrupt AGAIN so verify sees different bytes
            blob2 = models.get(key)
            models.insert(key, blob2[:-1] + bytes([blob2[-1] ^ 0x55]))
            code, body, _ = _post(base + "/reload", {})
            assert code == 409
            assert "refused" in body["message"]
            # the old generation keeps serving, untouched
            assert body["engineInstanceId"] == inst_a.id
            assert deployed.instance.id == inst_a.id
            assert store.live().instance_id == inst_a.id
            qcode, qbody, qh = _post(base + "/queries.json", {"user": "u1"})
            assert qcode == 200 and qbody["gen"] == "A"
            assert qh["X-Pio-Engine-Instance"] == inst_a.id
        finally:
            server.shutdown()

    def test_reload_refuses_failed_sanity_check(self, storage, monkeypatch):
        inst_a, inst_b = _marker_instances(storage)
        server, deployed, base = self._server(storage, inst_a.id)
        try:
            from predictionio_tpu.core.base import SanityCheckError

            real = deployed.load_binding

            def load_with_bad_sanity(instance, role="live"):
                binding = real(instance, role)
                if instance.id == inst_b.id:
                    class Bad(dict):
                        def sanity_check(self):
                            raise SanityCheckError("non-finite factors")

                    return binding._replace(
                        models=[Bad(m) for m in binding.models]
                    )
                return binding

            monkeypatch.setattr(deployed, "load_binding", load_with_bad_sanity)
            code, body, _ = _post(base + "/reload", {})
            assert code == 409
            assert "non-finite" in body["message"]
            assert deployed.instance.id == inst_a.id
        finally:
            server.shutdown()

    def test_reload_and_lifecycle_json_require_access_key(self, storage):
        inst_a, _ = _marker_instances(storage)
        server, deployed, base = self._server(
            storage, inst_a.id, access_key="sekret"
        )
        try:
            code, body, _ = _post(base + "/reload", {})
            assert code == 401
            code, _ = _get(base + "/lifecycle.json")
            assert code == 401
            code, body = _get(base + "/lifecycle.json?accessKey=sekret")
            assert code == 200
            assert body["manifest"]["live"] == inst_a.id
            code, body, _ = _post(base + "/reload?accessKey=sekret", {})
            assert code in (200, 409)  # authorized either way
        finally:
            server.shutdown()


class TestLifecycleCLI:
    def test_pio_lifecycle_url_and_status_warning(self, storage, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        inst_a, inst_b = _marker_instances(storage)
        deployed = deploy_engine(
            "lifecycle-marker-test", storage=storage,
            engine_instance_id=inst_a.id,
        )
        registry = MetricsRegistry()
        app = create_prediction_server_app(
            deployed, registry=registry,
            quality=QualityMonitor(registry=registry),
        )
        server = AsyncAppServer(app, "127.0.0.1", 0).start_background()
        base = f"http://127.0.0.1:{server.port}"
        try:
            # stage a canary so the status surface has something to warn on
            deployed.generation_store.record(inst_b.id, status="staged")
            deployed.stage_canary(inst_b, fraction=0.25)
            rc = cli_main(["lifecycle", "--url", base])
            out = capsys.readouterr().out
            assert rc == 0
            assert inst_a.id in out
            assert "canary" in out
            rc = cli_main(["lifecycle", "--url", base, "--json"])
            body = json.loads(capsys.readouterr().out)
            assert rc == 0
            assert body["canary_in_progress"] is True
            assert body["canary_instance"] == inst_b.id
            # pio status --url: WARNING line, exit code unchanged
            rc = cli_main(["status", "--url", base, "--no-quality"])
            captured = capsys.readouterr()
            assert rc == 0, captured.err
            assert "WARNING: canary rollout in progress" in captured.err
        finally:
            server.shutdown()

    def test_pio_lifecycle_local_manifest(self, storage, capsys):
        from predictionio_tpu.tools.cli import main as cli_main

        inst_a, _ = _marker_instances(storage)
        store = GenerationStore(
            storage.models(), "default", "default", "default"
        )
        store.record(inst_a.id, status="live")
        rc = cli_main(["lifecycle"])
        out = capsys.readouterr().out
        assert rc == 0
        assert inst_a.id in out and "live" in out


# ---------------------------------------------------------------------------
# run C: SIGKILL a real serving subprocess mid-swap
# ---------------------------------------------------------------------------


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_deploy(home, port, extra_env=None):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PIO_HOME=str(home),
        **(extra_env or {}),
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "predictionio_tpu.tools.cli", "deploy",
            "--engine", "recommendation", "--ip", "127.0.0.1",
            "--port", str(port),
        ],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        env=env,
    )
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            code, body = _get(f"http://127.0.0.1:{port}/status.json", timeout=2)
            if code == 200:
                return proc, body
        except Exception:
            pass
        if proc.poll() is not None:
            raise RuntimeError("deploy subprocess died at boot")
        time.sleep(0.25)
    proc.kill()
    raise TimeoutError("deploy subprocess never became ready")


class TestRunCSigkillMidSwap:
    def test_sigkill_mid_swap_restarts_on_last_good(self, tmp_path):
        """The crash-safety acceptance: a /reload stalled at the
        ``lifecycle.swap`` seam (after verification, BEFORE the manifest
        commit) is SIGKILLed; the restarted server binds the manifest's
        last-good generation and answers queries identically."""
        from predictionio_tpu.data.storage.config import (
            StorageConfig,
            StorageRuntime,
        )
        from predictionio_tpu.models.recommendation import (  # noqa: F401
            recommendation_engine,
        )
        from predictionio_tpu.core.engine import resolve_engine_factory

        home = tmp_path / "pio_home"
        storage = StorageRuntime(
            StorageConfig.from_env({"PIO_HOME": str(home)})
        )
        _seed_events(storage, app_name="lc")
        engine = resolve_engine_factory("recommendation")()
        inst1 = run_train(
            engine, _als_params(), ctx=EngineContext(storage=storage),
            storage=storage, engine_factory="recommendation",
        )
        port = _free_port()
        plan = json.dumps(
            [{"seam": "lifecycle.swap", "kind": "latency",
              "latency_s": 45, "match": "reload"}]
        )
        proc, status = _spawn_deploy(
            home, port, extra_env={"PIO_FAULT_PLAN": plan}
        )
        base = f"http://127.0.0.1:{port}"
        try:
            assert status["engineInstanceId"] == inst1.id
            code, baseline, _ = _post(
                base + "/queries.json", {"user": "u1", "num": 5}
            )
            assert code == 200

            # a second generation appears; /reload will try to swap to it
            inst2 = run_train(
                engine, _als_params(iters=2),
                ctx=EngineContext(storage=storage),
                storage=storage, engine_factory="recommendation",
            )
            assert inst2.id != inst1.id

            reload_err = []

            def fire_reload():
                try:
                    _post(base + "/reload", {}, timeout=60)
                except Exception as e:  # the server dies under us
                    reload_err.append(e)

            t = threading.Thread(target=fire_reload, daemon=True)
            t.start()
            # let the reload verify the candidate and hit the stalled seam
            time.sleep(3.0)
            # mid-swap: verification done, manifest commit NOT yet written
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=10)
            t.join(timeout=10)

            # the manifest still names gen1 live — the atomic commit never
            # happened
            store = GenerationStore(
                storage.models(), "default", "default", "default"
            )
            assert store.live().instance_id == inst1.id

            # restart WITHOUT the fault plan: binds last-good, answers
            # identically
            proc2, status2 = _spawn_deploy(home, port)
            try:
                assert status2["engineInstanceId"] == inst1.id
                code, after, headers = _post(
                    base + "/queries.json", {"user": "u1", "num": 5}
                )
                assert code == 200
                assert headers["X-Pio-Engine-Instance"] == inst1.id
                assert after == baseline
            finally:
                proc2.kill()
                proc2.wait(timeout=10)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
