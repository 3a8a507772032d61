"""Model-lifecycle unit tests: the crash-safe generation store (checksums,
atomic transitions, last-good fallback), localfs durability (injected
crash between write and rename, concurrent writers), the canary decider's
guardrails (frozen clocks), warm-start alignment, the controller state
machine, and the gated /reload.
"""

from __future__ import annotations

import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from datetime import datetime, timezone
from types import SimpleNamespace

import numpy as np
import pytest

from predictionio_tpu.data.storage import localfs_models
from predictionio_tpu.data.storage.localfs_models import LocalFSModels
from predictionio_tpu.lifecycle import (
    CanaryDecider,
    CanaryPolicy,
    CanaryTracker,
    CorruptModelError,
    GenerationStore,
    LifecycleController,
    LifecycleError,
    LifecyclePolicy,
    compute_checksum,
    in_canary_fraction,
)
from predictionio_tpu.lifecycle.canary import CONTINUE, PROMOTE, ROLLBACK
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clear_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture()
def models(tmp_path):
    return LocalFSModels(tmp_path / "models")


# ---------------------------------------------------------------------------
# localfs durability (satellite: fsync + unique tmp + crash injection)
# ---------------------------------------------------------------------------


class TestLocalFSDurability:
    def test_crash_between_write_and_rename_keeps_old_blob(
        self, models, monkeypatch
    ):
        """An injected crash AFTER the tmp write but BEFORE the rename
        must leave the previously-published blob fully readable — the
        commit point is the rename, nothing earlier."""
        models.insert("gen", b"old-good-bytes")

        real_replace = os.replace

        def crashing_replace(src, dst):
            if str(dst).endswith("pio_model_gen.bin"):
                raise OSError("injected crash before rename")
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", crashing_replace)
        with pytest.raises(OSError, match="injected crash"):
            models.insert("gen", b"new-half-published")
        monkeypatch.undo()
        assert models.get("gen") == b"old-good-bytes"
        # the failed publish cleaned up its unique tmp file
        leftovers = [
            p for p in models.root.iterdir() if p.name.endswith(".tmp")
        ]
        assert leftovers == []

    def test_concurrent_writers_cannot_clobber_each_other(self, models):
        """Two trainers staging the same key race only at the atomic
        rename: the final file is exactly ONE writer's complete blob,
        never an interleave."""
        blob_a = b"A" * 65536
        blob_b = b"B" * 65536
        with ThreadPoolExecutor(2) as ex:
            for _ in range(20):
                fa = ex.submit(models.insert, "contended", blob_a)
                fb = ex.submit(models.insert, "contended", blob_b)
                fa.result()
                fb.result()
                got = models.get("contended")
                assert got in (blob_a, blob_b)
        assert not any(
            p.name.endswith(".tmp") for p in models.root.iterdir()
        )

    def test_tmp_names_are_per_writer_unique(self, models, monkeypatch):
        seen = []
        real_open = os.open

        def spying_open(path, flags, *a, **kw):
            if str(path).endswith(".tmp"):
                seen.append(str(path))
            return real_open(path, flags, *a, **kw)

        monkeypatch.setattr(os, "open", spying_open)
        models.insert("x", b"one")
        models.insert("x", b"two")
        tmp_names = [s for s in seen if ".tmp" in s]
        assert len(tmp_names) == len(set(tmp_names)) >= 2

    # -- a multi-part checkpoint, several parts in flight (ISSUE 31) --------

    @pytest.mark.parametrize("kind", ["arrays", "bytes"])
    def test_insert_parts_flushes_in_commit_order(
        self, models, monkeypatch, kind
    ):
        """Each part's file is fsynced before its rename; the directory is
        fsynced after the last part's rename and before the manifest's; and
        again after the manifest's rename, before ``insert_parts`` returns."""
        parts = _checkpoint_parts(kind)
        events = _spy_on_files(monkeypatch, models.root)
        models.insert_parts("ck", b"manifest", parts)
        done = len(events)
        monkeypatch.undo()

        def final(key):
            return str(models._file(key))

        def index(event):
            (i,) = [i for i, e in enumerate(events) if e == event]
            return i

        renamed = {}
        for name in parts:
            dst = final(f"ck:part:{name}")
            renamed[name] = index(("replace", dst))
            (tmp,) = {
                path for op, path in events
                if op == "open" and path.startswith(dst + ".")
            }
            assert tmp.endswith(".tmp")
            assert index(("fsync", tmp)) < renamed[name]
        manifest = final("ck:manifest")
        manifest_renamed = index(("replace", manifest))
        (manifest_tmp,) = {
            path for op, path in events
            if op == "open" and path.startswith(manifest + ".")
        }
        assert index(("fsync", manifest_tmp)) < manifest_renamed
        dir_syncs = [
            i for i, e in enumerate(events) if e == ("fsync", str(models.root))
        ]
        assert any(
            max(renamed.values()) < i < manifest_renamed for i in dir_syncs)
        assert any(manifest_renamed < i < done for i in dir_syncs)
        # and what was committed reads back whole
        assert models.get_manifest("ck") == b"manifest"
        for name in parts:
            assert models.get_part("ck", name) == parts[name]

    @pytest.mark.parametrize("resave", [False, True], ids=["fresh", "resave"])
    @pytest.mark.parametrize("kind", ["arrays", "bytes"])
    @pytest.mark.parametrize("stage", ["write", "fsync", "rename"])
    def test_part_failure_leaves_no_manifest_and_no_tmp(
        self, models, monkeypatch, stage, kind, resave
    ):
        """An error in ONE part's write, fsync or rename: every writer is
        joined, no ``.tmp`` is left, the error is raised and no manifest is
        written — over a live id too, whose old manifest went first, so a
        reader never pairs the old part list with new bytes."""
        if resave:
            models.insert_parts(
                "ck", b"old", {"leaf00000": b"o0", "leaf00007": b"o7"})
        parts = _checkpoint_parts(kind)
        victim = "leaf00002"
        victim_final = str(models._file(f"ck:part:{victim}"))
        events = _spy_on_files(monkeypatch, models.root)
        fd_paths = events.fd_paths

        def is_victim(fd):
            return fd_paths.get(fd, "").startswith(victim_final + ".")

        if stage == "write":
            # the tmp file's writer, broken after its first bytes
            class Broken:
                def __init__(self, file):
                    self.file = file

                def write(self, data):
                    self.file.write(bytes(data)[:5])
                    raise OSError("injected write failure")

                def __enter__(self):
                    return self

                def __exit__(self, *exc):
                    return self.file.__exit__(*exc)

            def breaking_open(fd, *a, **kw):
                file = open(fd, *a, **kw)
                return Broken(file) if is_victim(fd) else file

            monkeypatch.setattr(
                localfs_models, "open", breaking_open, raising=False)
        elif stage == "fsync":
            spied_fsync = os.fsync

            def failing_fsync(fd):
                if is_victim(fd):
                    raise OSError("injected fsync failure")
                return spied_fsync(fd)

            monkeypatch.setattr(os, "fsync", failing_fsync)
        else:
            spied_replace = os.replace

            def failing_replace(src, dst):
                if str(dst) == victim_final:
                    raise OSError("injected rename failure")
                return spied_replace(src, dst)

            monkeypatch.setattr(os, "replace", failing_replace)

        threads_before = set(threading.enumerate())
        with pytest.raises(OSError, match=f"injected {stage} failure"):
            models.insert_parts("ck", b"manifest", parts)
        monkeypatch.undo()
        assert set(threading.enumerate()) <= threads_before
        assert models.get_manifest("ck") is None
        assert not any(
            p.name.endswith(".tmp") for p in models.root.iterdir())
        assert models.get_part("ck", victim) is None
        # the manifest was never opened, let alone renamed
        assert not any(
            "ck:manifest" in path for op, path in events if op == "open")
        # a re-save after the failure commits as ever
        models.insert_parts("ck", b"manifest", parts)
        assert models.get_manifest("ck") == b"manifest"
        assert models.get_part("ck", victim) == parts[victim]
        assert models.get_part("ck", "leaf00007") is None

    def test_resave_drops_the_old_manifest_before_any_new_byte(
        self, models, monkeypatch
    ):
        models.insert_parts("ck", b"old", {"leaf00000": b"old-bytes"})
        manifest = models._file("ck:manifest")
        real_open = os.open
        seen = []

        def checking_open(path, flags, *a, **kw):
            if ":part:" in str(path):
                seen.append(manifest.exists())
            return real_open(path, flags, *a, **kw)

        monkeypatch.setattr(os, "open", checking_open)
        models.insert_parts("ck", b"new", _checkpoint_parts("arrays"))
        assert seen and not any(seen)
        assert models.get_manifest("ck") == b"new"


class _FileEvents(list):
    """("open" | "fsync" | "replace", path) in the order they happened."""

    def __init__(self):
        super().__init__()
        self.fd_paths: dict[int, str] = {}


def _spy_on_files(monkeypatch, root) -> _FileEvents:
    """Record every ``os.open`` / ``os.fsync`` / ``os.replace`` under
    ``root`` (list.append is atomic: the writers' threads share the list)."""
    events = _FileEvents()
    real_open, real_fsync, real_replace = os.open, os.fsync, os.replace

    def spying_open(path, flags, *a, **kw):
        fd = real_open(path, flags, *a, **kw)
        if str(path).startswith(str(root)):
            events.fd_paths[fd] = str(path)
            events.append(("open", str(path)))
        return fd

    def spying_fsync(fd):
        real_fsync(fd)
        events.append(("fsync", events.fd_paths.get(fd, "?")))

    def spying_replace(src, dst):
        real_replace(src, dst)
        events.append(("replace", str(dst)))

    monkeypatch.setattr(os, "open", spying_open)
    monkeypatch.setattr(os, "fsync", spying_fsync)
    monkeypatch.setattr(os, "replace", spying_replace)
    return events


def _checkpoint_parts(kind: str):
    """Seven parts of unequal sizes, as ``save_models`` hands them over
    (``LazyParts`` over arrays) or as plain bytes (``replay/day.py``)."""
    from predictionio_tpu.core.persistence import LazyParts

    rng = np.random.default_rng(31)
    lazy = LazyParts({
        f"leaf{i:05d}": rng.standard_normal((rows, 16)).astype(np.float32)
        for i, rows in enumerate([64, 2048, 256, 8, 1024, 512, 128])
    })
    return lazy if kind == "arrays" else {n: lazy[n] for n in lazy}



# ---------------------------------------------------------------------------
# generation store
# ---------------------------------------------------------------------------


class TestGenerationStore:
    def test_record_verify_roundtrip_single_blob(self, models):
        models.insert("i1", b"model-bytes")
        store = GenerationStore(models, "e")
        gen = store.record("i1", status="live")
        assert gen.checksum == compute_checksum(models, "i1")
        store.verify(gen)  # no raise
        assert store.live().instance_id == "i1"

    def test_verify_refuses_tampered_blob(self, models):
        models.insert("i1", b"model-bytes")
        store = GenerationStore(models, "e")
        store.record("i1", status="live")
        models.insert("i1", b"model-byteX")  # same length, flipped tail
        with pytest.raises(CorruptModelError):
            store.verify("i1")

    def test_verify_covers_sharded_parts(self, models):
        models.insert_parts("i2", b"manifest", {"p0": b"aaa", "p1": b"bbb"})
        store = GenerationStore(models, "e")
        gen = store.record("i2")
        store.verify(gen)
        # corrupt ONE part: the composite checksum must catch it
        models.insert("i2:part:p1", b"bbc")
        with pytest.raises(CorruptModelError):
            store.verify("i2")
        # a missing part is corruption too, not a KeyError
        models.delete("i2:part:p0")
        with pytest.raises(CorruptModelError):
            store.verify("i2")

    def test_state_machine_transitions(self, models):
        store = GenerationStore(models, "e")
        models.insert("g1", b"one")
        models.insert("g2", b"two")
        store.record("g1", status="live")
        store.record("g2", status="staged")
        store.start_canary("g2")
        assert store.canary().instance_id == "g2"
        store.promote("g2")
        assert store.live().instance_id == "g2"
        # the old live retired in the SAME atomic write
        assert store.get("g1").status == "retired"
        # rolling back a live generation is an invalid transition
        with pytest.raises(LifecycleError):
            store.rollback("g2")

    def test_rollback_leaves_live_untouched(self, models):
        store = GenerationStore(models, "e")
        models.insert("g1", b"one")
        models.insert("g2", b"two")
        store.record("g1", status="live")
        store.record("g2", status="staged")
        store.start_canary("g2")
        store.rollback("g2", note="guardrail breach")
        assert store.live().instance_id == "g1"
        g2 = store.get("g2")
        assert g2.status == "rolled_back"
        assert g2.rolled_back_at is not None
        assert "guardrail" in g2.note

    def test_bind_candidates_walk_live_then_retired_newest_first(self, models):
        store = GenerationStore(models, "e")
        for name in ("g1", "g2", "g3"):
            models.insert(name, name.encode())
            store.record(name, status="live")  # each promote retires prior
        ids = [g.instance_id for g in store.bind_candidates()]
        assert ids == ["g3", "g2", "g1"]

    def test_manifest_write_is_whole_file_atomic(self, models):
        """Each transition is ONE whole-manifest write: a reader between
        any two transitions sees a complete, parseable manifest."""
        store = GenerationStore(models, "e")
        models.insert("g1", b"one")
        store.record("g1", status="live")
        raw = models.get(store.manifest_key)
        manifest = json.loads(raw.decode())
        assert manifest["generations"][0]["instance_id"] == "g1"
        assert manifest["schema"] == 1

    def test_fault_injected_corruption_via_models_read_seam(self, models):
        models.insert("i1", b"x" * 4096)
        store = GenerationStore(models, "e")
        gen = store.record("i1")
        faults.install(
            [{"seam": "models.read", "kind": "corrupt", "match": "i1"}]
        )
        with pytest.raises(CorruptModelError):
            store.verify(gen)
        faults.clear()
        store.verify(gen)  # heals when the plan clears

    def test_history_trims_but_keeps_active(self, models):
        store = GenerationStore(models, "e", max_history=3)
        for i in range(8):
            models.insert(f"g{i}", str(i).encode())
            store.record(f"g{i}", status="live")
        gens = store.generations()
        assert len(gens) <= 3
        assert store.live().instance_id == "g7"


# ---------------------------------------------------------------------------
# canary split + decider (frozen clock)
# ---------------------------------------------------------------------------


class TestCanarySplit:
    def test_deterministic_and_fractional(self):
        users = [f"u{i}" for i in range(4000)]
        picked = [u for u in users if in_canary_fraction(u, 0.2)]
        again = [u for u in users if in_canary_fraction(u, 0.2)]
        assert picked == again  # deterministic per entity
        assert 0.12 < len(picked) / len(users) < 0.28  # ~fraction
        # widening the fraction only ADDS entities (hash-prefix property)
        wider = {u for u in users if in_canary_fraction(u, 0.5)}
        assert set(picked) <= wider

    def test_no_entity_routes_live(self):
        assert not in_canary_fraction(None, 0.99)
        assert not in_canary_fraction("", 0.99)
        assert not in_canary_fraction("u1", 0.0)
        assert in_canary_fraction("u1", 1.0)


def _snapshot(canary_req, canary_err, live_req=200, live_err=0,
              canary_p95=0.01, live_p95=0.01):
    return {
        "started_at": 0.0,
        "live": {
            "requests": live_req, "errors": live_err,
            "error_rate": live_err / max(live_req, 1), "p95_s": live_p95,
        },
        "canary": {
            "requests": canary_req, "errors": canary_err,
            "error_rate": canary_err / max(canary_req, 1),
            "p95_s": canary_p95,
        },
    }


class TestCanaryDecider:
    def setup_method(self):
        self.policy = CanaryPolicy(
            min_requests=50, max_error_rate=0.05, min_joined=10,
            max_metric_regression=0.2, max_canary_s=600.0,
        )
        self.decider = CanaryDecider(self.policy)

    def test_continue_while_sample_too_small(self):
        verdict, _ = self.decider.evaluate(_snapshot(10, 5), None, 1.0)
        assert verdict == CONTINUE  # even at 50% errors: sample too small

    def test_error_rate_guardrail_rolls_back(self):
        verdict, reason = self.decider.evaluate(_snapshot(60, 6), None, 1.0)
        assert verdict == ROLLBACK
        assert "error rate" in reason

    def test_latency_guardrail_rolls_back(self):
        snap = _snapshot(60, 0, canary_p95=0.5, live_p95=0.01)
        verdict, reason = self.decider.evaluate(snap, None, 1.0)
        assert verdict == ROLLBACK
        assert "p95" in reason

    def test_promotion_needs_joined_evidence(self):
        comparison = {
            "metric": "hit_rate", "live_value": 0.5, "canary_value": 0.5,
            "live_joined": 40, "canary_joined": 3,
        }
        verdict, _ = self.decider.evaluate(_snapshot(60, 0), comparison, 1.0)
        assert verdict == CONTINUE  # 3 < min_joined=10

    def test_promotes_on_no_regression(self):
        comparison = {
            "metric": "hit_rate", "live_value": 0.5, "canary_value": 0.48,
            "live_joined": 40, "canary_joined": 15,
        }
        verdict, reason = self.decider.evaluate(
            _snapshot(60, 0), comparison, 1.0
        )
        assert verdict == PROMOTE, reason

    def test_metric_regression_rolls_back(self):
        comparison = {
            "metric": "hit_rate", "live_value": 0.5, "canary_value": 0.3,
            "live_joined": 40, "canary_joined": 15,
        }
        verdict, reason = self.decider.evaluate(
            _snapshot(60, 0), comparison, 1.0
        )
        assert verdict == ROLLBACK
        assert "regressed" in reason

    def test_undecided_canary_times_out_to_rollback(self):
        verdict, reason = self.decider.evaluate(
            _snapshot(5, 0), None, 601.0
        )
        assert verdict == ROLLBACK
        assert "burden of proof" in reason

    def test_tracker_frozen_clock_age(self):
        clock = [100.0]
        tracker = CanaryTracker(clock=lambda: clock[0])
        tracker.start()
        clock[0] = 250.0
        assert tracker.age_s() == 150.0
        tracker.observe(True, 200, 0.01)
        tracker.observe(True, 500, 0.02)
        tracker.observe(False, 200, 0.01)
        snap = tracker.snapshot()
        assert snap["canary"]["requests"] == 2
        assert snap["canary"]["errors"] == 1
        assert snap["live"]["requests"] == 1
        tracker.stop()
        assert tracker.age_s() is None


# ---------------------------------------------------------------------------
# warm-start alignment
# ---------------------------------------------------------------------------


class TestWarmStart:
    def test_align_maps_rows_through_vocab_drift(self):
        from predictionio_tpu.core.warmstart import align_warm_factors
        from predictionio_tpu.data.bimap import BiMap

        prev_vocab = BiMap.from_keys(["a", "b", "c"])
        prev = np.arange(12, dtype=np.float32).reshape(3, 4)
        # new vocab: "b" and "c" survive (different positions), "d" is new,
        # "a" dropped
        new_vocab = BiMap.from_keys(["c", "d", "b"])
        rng = np.random.default_rng(0)
        out = align_warm_factors(prev, prev_vocab, new_vocab, rng)
        assert out.shape == (3, 4)
        np.testing.assert_array_equal(out[new_vocab["c"]], prev[2])
        np.testing.assert_array_equal(out[new_vocab["b"]], prev[1])
        # the new entity got a random (but finite, scale-matched) row
        d_row = out[new_vocab["d"]]
        assert np.isfinite(d_row).all() and (d_row >= 0).all()

    def test_train_als_accepts_init_factors(self):
        from predictionio_tpu.ops.als import ALSParams, train_als

        rng = np.random.default_rng(3)
        n_u, n_i, rank = 12, 9, 4
        u = rng.integers(0, n_u, 200).astype(np.int32)
        i = rng.integers(0, n_i, 200).astype(np.int32)
        r = rng.uniform(1, 5, 200).astype(np.float32)
        params = ALSParams(rank=rank, num_iterations=2, seed=1)
        cold = train_als(u, i, r, n_u, n_i, params=params)
        U0 = np.asarray(cold.user_factors)
        V0 = np.asarray(cold.item_factors)
        warm = train_als(
            u, i, r, n_u, n_i, params=params, init_factors=(U0, V0)
        )
        # warm-started from a 2-iter solution, 2 more iters must not blow up
        assert np.isfinite(np.asarray(warm.user_factors)).all()
        # and a wrong shape is refused loudly
        with pytest.raises(ValueError, match="init_factors"):
            train_als(
                u, i, r, n_u, n_i, params=params,
                init_factors=(U0[:, :2], V0[:, :2]),
            )

    def test_run_train_warm_start_from_previous_instance(self, storage):
        """The workflow handle: warm_start_from loads the previous
        generation's persisted models onto ctx.warm_start and the ALS
        algorithm seeds from them (observable: identical vocab rows start
        from the previous factors, so 0 extra iterations reproduce them)."""
        from predictionio_tpu.core.base import EngineContext
        from predictionio_tpu.core.workflow import run_train
        from predictionio_tpu.data.datamap import DataMap
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithmParams,
            DataSourceParams,
            recommendation_engine,
        )
        from predictionio_tpu.core.engine import EngineParams

        app_id = storage.apps().insert(App(id=0, name="warm"))
        le = storage.l_events()
        le.init(app_id)
        rng = np.random.default_rng(5)
        events = [
            Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"m{i}",
                properties=DataMap({"rating": float(rng.uniform(1, 5))}),
            )
            for u in range(8) for i in range(10) if rng.random() < 0.8
        ]
        le.insert_batch(events, app_id)
        params = EngineParams(
            datasource=("ratings", DataSourceParams(app_name="warm")),
            preparator=("ratings", None),
            algorithms=(
                ("als", ALSAlgorithmParams(rank=4, num_iterations=3)),
            ),
            serving=("first", None),
        )
        engine = recommendation_engine()
        inst1 = run_train(
            engine, params, ctx=EngineContext(storage=storage),
            storage=storage, engine_factory="recommendation",
        )
        assert inst1.status == "COMPLETED"
        inst2 = run_train(
            engine, params, ctx=EngineContext(storage=storage),
            storage=storage, engine_factory="recommendation",
            warm_start_from=inst1.id,
        )
        assert inst2.status == "COMPLETED"
        assert inst2.id != inst1.id
        # a bogus warm-start id degrades to a cold start, never a failure
        inst3 = run_train(
            engine, params, ctx=EngineContext(storage=storage),
            storage=storage, engine_factory="recommendation",
            warm_start_from="no-such-instance",
        )
        assert inst3.status == "COMPLETED"


# ---------------------------------------------------------------------------
# controller state machine (fake deployed engine, frozen clock)
# ---------------------------------------------------------------------------


class FakeDeployed:
    def __init__(self):
        self.instance = SimpleNamespace(
            id="live-1", engine_id="e", engine_version="v",
            engine_variant="default", engine_factory="f",
        )
        self.variant_label = "default"
        self.canary_instance = None
        self.staged = []
        self.promoted = []
        self.cleared = 0
        self.drained = []

    def stage_canary(self, instance, fraction):
        self.canary_instance = instance
        self.staged.append((instance.id, fraction))

    def promote_canary(self):
        self.promoted.append(self.canary_instance.id)
        self.instance = self.canary_instance
        self.canary_instance = None

    def clear_canary(self):
        self.cleared += 1
        self.canary_instance = None

    def wait_drained(self, instance_id, timeout=5.0):
        self.drained.append(instance_id)
        return True


class FakeQuality:
    def __init__(self):
        self.drift = "ok"
        self.comparison = {
            "metric": "hit_rate", "live_value": None, "canary_value": None,
            "live_joined": 0, "canary_joined": 0,
        }

    def drift_state(self):
        return self.drift

    def compare_variants(self, live, canary, metric="hit_rate"):
        return dict(self.comparison)


@pytest.fixture()
def controller(models, monkeypatch):
    from predictionio_tpu.lifecycle import generations as gens_mod

    clock = [1000.0]
    # freeze the manifest timestamps to the same clock the controller reads
    monkeypatch.setattr(gens_mod, "_now", lambda: clock[0])
    store = GenerationStore(models, "e", "v", "default")
    models.insert("live-1", b"live-model")
    store.record("live-1", status="live")
    deployed = FakeDeployed()
    quality = FakeQuality()
    counter = [1]

    def retrain(warm_from):
        iid = f"gen-{counter[0]}"
        counter[0] += 1
        models.insert(iid, f"model-{iid}".encode())
        retrain.last_warm_from = warm_from
        return SimpleNamespace(id=iid)

    policy = LifecyclePolicy(
        canary=CanaryPolicy(
            fraction=0.25, min_requests=4, max_error_rate=0.25,
            min_joined=0, max_canary_s=600.0,
        ),
        staleness_s=None, cooldown_s=60.0,
    )
    ctl = LifecycleController(
        deployed, store, quality=quality, retrain=retrain,
        policy=policy, registry=MetricsRegistry(),
        clock=lambda: clock[0],
    )
    ctl._test = SimpleNamespace(
        clock=clock, deployed=deployed, quality=quality, store=store,
        retrain=retrain, models=models,
    )
    return ctl


class TestController:
    def test_idle_without_drift(self, controller):
        assert controller.tick() is None

    def test_drift_triggers_warm_start_retrain_and_canary(self, controller):
        t = controller._test
        t.quality.drift = "drifting"
        assert controller.tick() == "retrain"
        assert t.retrain.last_warm_from == "live-1"
        assert t.deployed.staged == [("gen-1", 0.25)]
        assert t.store.canary().instance_id == "gen-1"
        assert controller.last_event["event"] == "canary_started"

    def test_cooldown_blocks_back_to_back_retrains(self, controller):
        t = controller._test
        t.quality.drift = "drifting"
        controller.tick()
        # abort the canary so the idle path runs again
        controller.rollback(t.deployed.canary_instance, "test")
        assert controller.tick() is None  # still inside cooldown
        t.clock[0] += 61.0
        assert controller.tick() == "retrain"

    def test_staleness_triggers_retrain(self, controller):
        t = controller._test
        controller.policy = LifecyclePolicy(
            canary=controller.policy.canary, staleness_s=100.0,
            retrain_on_drift=False, cooldown_s=0.0,
        )
        assert controller.tick() is None  # fresh enough
        t.clock[0] += 5000.0
        assert controller.tick() == "retrain"

    def test_canary_promotes_and_manifest_flips(self, controller):
        t = controller._test
        t.quality.drift = "drifting"
        controller.tick()
        # clean canary: enough requests, no errors, no metric evidence
        # required (min_joined=0)
        for _ in range(6):
            controller.tracker.observe(True, 200, 0.01)
            controller.tracker.observe(False, 200, 0.01)
        assert controller.tick() == "promote"
        assert t.deployed.promoted == ["gen-1"]
        assert t.store.live().instance_id == "gen-1"
        assert t.store.get("live-1").status == "retired"
        assert "live-1" in t.deployed.drained

    def test_canary_error_guardrail_rolls_back(self, controller):
        t = controller._test
        t.quality.drift = "drifting"
        controller.tick()
        for _ in range(6):
            controller.tracker.observe(True, 500, 0.01)
            controller.tracker.observe(False, 200, 0.01)
        assert controller.tick() == "rollback"
        assert t.deployed.cleared == 1
        assert t.store.get("gen-1").status == "rolled_back"
        assert t.store.live().instance_id == "live-1"  # live untouched

    def test_corrupt_staged_blob_fails_retrain_and_counts(self, controller):
        t = controller._test
        t.quality.drift = "drifting"
        # after=1: the staging checksum reads clean bytes, every later
        # read (the verify) sees corrupt ones — bit-rot between write and
        # bind, deterministically
        faults.install(
            [{"seam": "models.read", "kind": "corrupt", "match": "gen-1",
              "after": 1}]
        )
        assert controller.tick() == "retrain_failed"
        assert t.deployed.staged == []  # never staged a corrupt generation
        assert controller.last_event["event"] == "retrain_failed"
        assert controller._m_corrupt.value == 1

    def test_injected_retrain_failure_is_contained(self, controller):
        t = controller._test
        t.quality.drift = "drifting"
        faults.install(
            [{"seam": "lifecycle.retrain", "kind": "error", "count": 1}]
        )
        assert controller.tick() == "retrain_failed"
        assert t.store.live().instance_id == "live-1"
        # next attempt (after cooldown) succeeds
        t.clock[0] += 61.0
        assert controller.tick() == "retrain"


# ---------------------------------------------------------------------------
# quality comparison hooks
# ---------------------------------------------------------------------------


class TestQualityComparisonHooks:
    def test_compare_variants_reads_both_sides(self):
        from predictionio_tpu.obs.quality import QualityMonitor

        q = QualityMonitor(registry=MetricsRegistry())
        pred = {"itemScores": [{"item": "m1", "score": 1.0}]}
        for n in range(10):
            q.observe_prediction(f"r-live-{n}", {"user": f"u{n}"}, pred,
                                 variant="default")
            q.observe_prediction(f"r-can-{n}", {"user": f"c{n}"}, pred,
                                 variant="canary")
        ev = SimpleNamespace(
            event="buy", entity_id=None, target_entity_id="m1",
            properties=None, pr_id=None,
        )
        for n in range(10):
            assert q.observe_feedback(ev, request_id=f"r-live-{n}")
        for n in range(4):
            assert q.observe_feedback(ev, request_id=f"r-can-{n}")
        cmp = q.compare_variants("default", "canary", metric="hit_rate")
        assert cmp["live_joined"] == 10
        assert cmp["canary_joined"] == 4
        assert cmp["live_value"] == 1.0
        assert cmp["canary_value"] == 1.0
        # unknown variant: no evidence, not an error
        cmp2 = q.compare_variants("default", "ghost")
        assert cmp2["canary_value"] is None
        assert cmp2["canary_joined"] == 0

    def test_record_for_exposes_logged_variant(self):
        from predictionio_tpu.obs.quality import QualityMonitor

        q = QualityMonitor(registry=MetricsRegistry())
        q.observe_prediction("rid-1", {"user": "u1"}, {"label": "x"},
                             variant="canary")
        rec = q.record_for("rid-1")
        assert rec["variant"] == "canary"
        assert q.record_for("missing") is None
