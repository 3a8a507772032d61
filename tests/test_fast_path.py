"""PR 12 hot-path tests: the fused score+top-k kernel (bit-identical to
lax.top_k, no full score row), the pipelined MicroBatcher (overlap proof,
bounded depth, fence deadline, solo retry), the device-resident factor
cache (hit/miss/evict under concurrency, generation-swap / canary-flip /
mesh-rebind invalidation — stale factors must never serve), and the
pipelined serving path end to end."""

from __future__ import annotations

import asyncio
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from predictionio_tpu.obs.metrics import REGISTRY
from predictionio_tpu.ops import topk as topk_mod
from predictionio_tpu.ops.topk import (
    MAX_FUSED_K,
    TILE_ROWS,
    FusedTopKUnsupported,
    fused_supported,
    fused_topk_batch,
    fused_topk_roofline,
    note_full_row_fallback,
)
from predictionio_tpu.parallel import device_cache
from predictionio_tpu.server.microbatch import MicroBatcher, PendingWave


# ---------------------------------------------------------------------------
# fused score + top-k


class TestFusedTopK:
    def _parity(self, B, N, r, k, tie_rows=()):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(B * 31 + N + k)
        q = rng.standard_normal((B, r)).astype(np.float32)
        t = rng.standard_normal((N, r)).astype(np.float32)
        for a, b in tie_rows:
            t[b] = t[a]  # exact score ties between rows a and b
        ev, ei = jax.lax.top_k(jnp.asarray(q @ t.T), k)
        packed = fused_topk_batch(q, t, k)
        np.testing.assert_array_equal(np.asarray(ev), np.asarray(packed[0]))
        np.testing.assert_array_equal(
            np.asarray(ei), np.asarray(packed[1]).astype(np.int64)
        )

    def test_parity_small(self):
        self._parity(8, 500, 10, 16)

    def test_parity_multi_tile_with_boundary_ties(self):
        # duplicate rows straddling the 1024-row tile boundary: the
        # streaming merge must resolve ties to the LOWEST global id,
        # exactly like lax.top_k on the full row
        self._parity(
            4, 3000, 8, 32,
            tie_rows=[(0, TILE_ROWS), (5, TILE_ROWS + 1), (10, 2999)],
        )

    def test_parity_all_equal_scores(self):
        import jax
        import jax.numpy as jnp

        q = np.ones((2, 4), np.float32)
        t = np.zeros((2500, 4), np.float32)
        ev, ei = jax.lax.top_k(jnp.asarray(q @ t.T), 16)
        packed = fused_topk_batch(q, t, 16)
        np.testing.assert_array_equal(
            np.asarray(ei), np.asarray(packed[1]).astype(np.int64)
        )

    def test_parity_batch_beyond_block(self):
        # B > BATCH_BLOCK sweeps the batch grid axis; still ONE launch
        self._parity(300, 2048, 6, 64)

    def test_limit_masks_catalog_tail(self):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(7)
        q = rng.standard_normal((4, 6)).astype(np.float32)
        t = rng.standard_normal((2048, 6)).astype(np.float32)
        n_items = 1500  # rows past this are sharding/pad fill
        ev, ei = jax.lax.top_k(jnp.asarray(q @ t[:n_items].T), 20)
        packed = fused_topk_batch(q, t, 20, limit=n_items)
        np.testing.assert_array_equal(np.asarray(ev), np.asarray(packed[0]))
        np.testing.assert_array_equal(
            np.asarray(ei), np.asarray(packed[1]).astype(np.int64)
        )

    def test_no_full_row_proof_hook(self):
        q = np.ones((8, 4), np.float32)
        t = np.ones((5000, 4), np.float32)
        fused_topk_batch(q, t, 10, name="proof.check")
        shapes = topk_mod.LAST_KERNEL_SHAPES["proof.check"]
        # the largest score slab that ever existed is one tile, NOT the
        # catalog: the no-full-row claim as a checkable fact
        assert shapes["rows_tile"] == TILE_ROWS < shapes["n_rows"] == 5000
        assert shapes["n_tiles"] == 5

    def test_off_menu_raises_and_fallback_counts(self):
        with pytest.raises(FusedTopKUnsupported):
            fused_topk_batch(
                np.ones((2, 4), np.float32),
                np.ones((4096, 4), np.float32),
                MAX_FUSED_K + 1,
            )
        assert not fused_supported(8, MAX_FUSED_K + 1, 4096)
        fam = REGISTRY.counter(
            "pio_topk_full_row_fallback_total",
            "Top-k dispatches that materialized a full score row",
            labelnames=("where",),
        )
        before = fam.labels("test.fallback").value
        note_full_row_fallback(8, 200, 4096, "test.fallback")
        assert fam.labels("test.fallback").value == before + 1

    def test_roofline_is_positive_and_scales(self):
        a = fused_topk_roofline(32, 16, 30_000, 16)
        b = fused_topk_roofline(32, 16, 60_000, 16)
        assert a["bytes"] > 0 and a["flops"] > 0
        assert b["flops"] == pytest.approx(2 * a["flops"])


class TestFusedShardedTopK:
    def test_als_sharded_wave_uses_fused_kernel_with_parity(self):
        """The 8-virtual-device sharded ALS wave runs the fused per-shard
        kernel (both proof hooks agree) and stays bit-identical to the
        single-device host answer — ties included."""
        import jax

        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models.recommendation.engine import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            ALSModel,
            Query,
        )
        from predictionio_tpu.parallel import placement

        if len(jax.devices()) < 2:
            pytest.skip("needs the virtual multi-device mesh")
        rng = np.random.default_rng(3)
        nu, ni, rank = 40, 613, 5  # ni NOT divisible by the shard count
        U = rng.standard_normal((nu, rank)).astype(np.float32)
        V = rng.standard_normal((ni, rank)).astype(np.float32)
        V[9] = V[600]  # a tie across distant shards
        uv = BiMap.from_keys(np.array([f"u{i}" for i in range(nu)]))
        iv = BiMap.from_keys(np.array([f"i{i}" for i in range(ni)]))
        algo = ALSAlgorithm(ALSAlgorithmParams(rank=rank, shard_serving=True))
        blob = algo.make_persistent_model(None, ALSModel(U, V, uv, iv))
        sharded = algo.load_persistent_model(None, blob)
        assert sharded.shards is not None
        single = ALSModel(U, V, uv, iv)
        queries = [(i, Query(user=f"u{i}", num=7)) for i in range(12)]
        got = dict(algo.batch_predict(sharded, queries))
        want = dict(algo.batch_predict(single, queries))
        for i in range(12):
            assert [s.item for s in got[i].item_scores] == [
                s.item for s in want[i].item_scores
            ]
            np.testing.assert_array_equal(
                [s.score for s in got[i].item_scores],
                [s.score for s in want[i].item_scores],
            )
        assert placement.LAST_KERNEL_SHAPES["als.sharded_topk"]["fused"] == 1
        local = topk_mod.LAST_KERNEL_SHAPES["als.sharded_topk.fused"]
        shard_shapes = placement.LAST_KERNEL_SHAPES["als.sharded_topk"]
        # per-shard: the score slab never exceeds the shard's OWN rows
        assert local["rows_tile"] <= shard_shapes["rows_local"] < ni


# ---------------------------------------------------------------------------
# pipelined MicroBatcher


def _run(coro):
    return asyncio.run(coro)


class TestPipelinedMicroBatcher:
    def test_dispatch_overlaps_unfenced_wave(self):
        """The worker dispatches wave N+1 while wave N's finalize is still
        blocked — the core overlap claim, proven with a gate."""
        gate = threading.Event()
        events: list = []

        def batch_fn(items):
            events.append(("dispatch", tuple(items)))

            def finalize():
                gate.wait(5)
                events.append(("finalize", tuple(items)))
                return [x * 2 for x in items]

            return PendingWave(finalize)

        async def main():
            b = MicroBatcher(batch_fn, max_batch=1, max_inflight_waves=2)
            metas = [{} for _ in range(3)]
            tasks = [
                asyncio.ensure_future(b.submit(i, metas[i]))
                for i in range(3)
            ]
            for _ in range(100):
                if len([e for e in events if e[0] == "dispatch"]) >= 2:
                    break
                await asyncio.sleep(0.01)
            # >=2 dispatches happened while finalize 1 was still gated
            assert len([e for e in events if e[0] == "dispatch"]) >= 2
            assert not any(e[0] == "finalize" for e in events)
            gate.set()
            assert await asyncio.gather(*tasks) == [0, 2, 4]
            # results resolve in wave order (FIFO fence)
            fin = [e[1] for e in events if e[0] == "finalize"]
            assert fin == sorted(fin)
            assert metas[0]["pipelined"] is True
            assert metas[0]["device_s"] == pytest.approx(
                metas[0]["dispatch_s"] + metas[0]["finalize_s"], abs=1e-3
            )
            assert metas[0]["inflight_depth"] >= 1
            b.close()
            assert not b.busy

        _run(main())

    def test_inflight_depth_is_bounded(self):
        gate = threading.Event()
        dispatched: list = []

        def batch_fn(items):
            dispatched.append(tuple(items))

            def finalize():
                gate.wait(5)
                return list(items)

            return PendingWave(finalize)

        async def main():
            b = MicroBatcher(batch_fn, max_batch=1, max_inflight_waves=1)
            tasks = [
                asyncio.ensure_future(b.submit(i, {})) for i in range(4)
            ]
            await asyncio.sleep(0.3)
            # depth 1: one wave unfenced in the queue + one being
            # finalized + one blocked in the worker's enqueue = at most 3
            # dispatched while the gate holds; wave 4 must wait
            assert len(dispatched) <= 3
            gate.set()
            assert await asyncio.gather(*tasks) == [0, 1, 2, 3]
            b.close()

        _run(main())

    def test_finalize_failure_triggers_solo_retry(self):
        calls: list = []

        def batch_fn(items):
            calls.append(tuple(items))

            def finalize():
                if len(items) > 1:
                    raise RuntimeError("wave poison")
                if items[0] == "bad":
                    raise RuntimeError("poison item")
                return [f"ok:{x}" for x in items]

            return PendingWave(finalize)

        async def main():
            # occupy the worker so the next three coalesce into one wave
            gate = threading.Event()
            first = asyncio.ensure_future(
                asyncio.get_running_loop().run_in_executor(None, gate.wait)
            )
            b = MicroBatcher(batch_fn, max_batch=8, max_inflight_waves=2)
            hold = asyncio.ensure_future(b.submit("hold", {}))
            await asyncio.sleep(0.05)
            rest = [
                asyncio.ensure_future(b.submit(x, {}))
                for x in ("a", "bad", "c")
            ]
            gate.set()
            out = await asyncio.gather(*rest, return_exceptions=True)
            assert await hold == "ok:hold"
            assert out[0] == "ok:a"
            assert isinstance(out[1], RuntimeError)  # poison fails ALONE
            assert out[2] == "ok:c"
            b.close()
            await first

        _run(main())

    def test_fence_deadline_expiry_answers_504_not_late_200(self):
        """A deadline that runs out while the wave sits in the pipeline
        resolves DeadlineExceeded at the fence — never a late answer."""
        from predictionio_tpu.resilience.deadline import (
            DeadlineExceeded,
            deadline_scope,
        )

        gate = threading.Event()

        def batch_fn(items):
            def finalize():
                gate.wait(5)
                return list(items)

            return PendingWave(finalize)

        async def main():
            reg_before = REGISTRY.counter(
                "pio_microbatch_deadline_expired_total",
                "Queued queries resolved with a deadline error before "
                "dispatch",
            ).value
            b = MicroBatcher(batch_fn, max_batch=1, max_inflight_waves=2)
            slow = asyncio.ensure_future(b.submit("slow", {}))
            meta: dict = {}
            with deadline_scope(budget_s=0.05):
                doomed = asyncio.ensure_future(b.submit("doomed", meta))
            await asyncio.sleep(0.3)  # both dispatched; budgets expire
            gate.set()
            assert await slow == "slow"
            with pytest.raises(DeadlineExceeded):
                await doomed
            assert meta.get("deadline_expired") is True
            assert (
                REGISTRY.counter(
                    "pio_microbatch_deadline_expired_total",
                    "Queued queries resolved with a deadline error before "
                    "dispatch",
                ).value
                > reg_before
            )
            b.close()

        _run(main())

    def test_close_drains_unfenced_waves_boundedly(self):
        gate = threading.Event()

        def batch_fn(items):
            def finalize():
                gate.wait(2)
                return list(items)

            return PendingWave(finalize)

        async def main():
            b = MicroBatcher(batch_fn, max_batch=1, max_inflight_waves=2)
            t = asyncio.ensure_future(b.submit(1, {}))
            await asyncio.sleep(0.1)
            assert b.busy
            loop = asyncio.get_running_loop()
            gate.set()
            await loop.run_in_executor(None, b.close)
            assert not b.busy
            assert await t == 1

        _run(main())

    def test_close_racing_dispatch_never_strands_a_wave(self):
        """Regression (review finding): close() can catch the worker
        MID-DISPATCH after an idle finalizer already exited — the wave
        must finalize inline, not sit stranded in a queue nobody drains."""
        in_dispatch = threading.Event()
        release = threading.Event()

        def batch_fn(items):
            if items[0] == "racer":
                in_dispatch.set()
                release.wait(5)  # close() arrives while we're in here
            return PendingWave(lambda: [f"ok:{x}" for x in items])

        async def main():
            b = MicroBatcher(batch_fn, max_batch=1, max_inflight_waves=2)
            assert await b.submit("warm", {}) == "ok:warm"  # finalizer born
            racer = asyncio.ensure_future(b.submit("racer", {}))
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, in_dispatch.wait, 5)
            closer = loop.run_in_executor(None, b.close)
            await asyncio.sleep(0.05)  # close() sets _closed, wakes all
            release.set()
            await closer
            # the racing wave resolved (either inline-finalized or via the
            # still-alive finalizer) — never a silent hang
            assert await asyncio.wait_for(racer, timeout=5) == "ok:racer"
            assert not b.busy

        _run(main())

    def test_depth_zero_finalizes_inline(self):
        """max_inflight_waves=0: the pre-PR-13 serial behavior — finalize
        runs on the worker, no finalizer thread appears."""

        def batch_fn(items):
            return PendingWave(lambda: [x + 1 for x in items])

        async def main():
            b = MicroBatcher(batch_fn, max_batch=4, max_inflight_waves=0)
            assert await b.submit(41, {}) == 42
            assert b._finalizer is None
            b.close()

        _run(main())


# ---------------------------------------------------------------------------
# factor cache


class TestFactorCache:
    def test_lru_hit_miss_evict(self):
        c = device_cache.FactorCache(capacity=3)
        for k in "abc":
            c.put(k, np.full(4, ord(k)))
        assert c.get("a") is not None  # refreshes recency
        c.put("d", np.ones(4))
        assert c.get("b") is None  # LRU victim
        assert c.get("a") is not None and len(c) == 3

    def test_capacity_zero_disables(self):
        c = device_cache.FactorCache(capacity=0)
        c.put("a", np.ones(2))
        assert c.get("a") is None and len(c) == 0

    def test_concurrent_get_put_evict(self):
        c = device_cache.FactorCache(capacity=64)
        err: list = []

        def worker(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(400):
                    k = int(rng.integers(0, 200))
                    row = c.get(k)
                    if row is None:
                        c.put(k, np.full(8, k, np.float32))
                    else:
                        # a hit must always return THAT entity's row
                        assert row[0] == k
            except Exception as e:  # noqa: BLE001
                err.append(e)

        with ThreadPoolExecutor(16) as ex:
            list(ex.map(worker, range(16)))
        assert not err
        assert len(c) <= 64

    def test_model_cache_identity_and_invalidation(self):
        class M:
            pass

        m = M()
        c = device_cache.model_cache(m)
        assert device_cache.model_cache(m) is c
        c.put("u", np.ones(3))
        fam = REGISTRY.counter(
            "pio_factor_cache_invalidations_total",
            "Factor-cache generation invalidations by reason",
            labelnames=("reason",),
        )
        before = fam.labels("swap").value
        dropped = device_cache.invalidate_model_caches([m], "swap")
        assert dropped == 1
        assert fam.labels("swap").value == before + 1
        # a fresh cache after invalidation: the old rows are gone
        assert device_cache.model_cache(m).get("u") is None


def _als_model(seed=0, nu=30, ni=200, rank=4):
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation.engine import ALSModel

    rng = np.random.default_rng(seed)
    return ALSModel(
        user_factors=rng.standard_normal((nu, rank)).astype(np.float32),
        item_factors=rng.standard_normal((ni, rank)).astype(np.float32),
        user_vocab=BiMap.from_keys(np.array([f"u{i}" for i in range(nu)])),
        item_vocab=BiMap.from_keys(np.array([f"i{i}" for i in range(ni)])),
    )


class TestEngineCacheCorrectness:
    def test_als_repeat_user_hits_and_matches_cold(self):
        from predictionio_tpu.models.recommendation.engine import (
            ALSAlgorithm,
            Query,
        )

        algo = ALSAlgorithm()
        warm = _als_model(seed=1)
        cold = _als_model(seed=1)
        s0 = device_cache.stats()
        first = algo.predict(warm, Query(user="u3", num=5))
        second = algo.predict(warm, Query(user="u3", num=5))
        s1 = device_cache.stats()
        assert s1["hits_total"] - s0["hits_total"] >= 1
        # byte-identical to a cold-cache model with the same factors
        reference = algo.predict(cold, Query(user="u3", num=5))
        assert second == first == reference

    def test_generation_swap_never_serves_stale_factors(self):
        """Chaos-style: serve generation A (cache hot), swap the binding to
        generation B mid-'traffic', keep serving — every post-swap answer
        must be byte-identical to a cold-cache B, never A's."""
        import threading as _t
        import types

        from predictionio_tpu.core.base import FirstServing
        from predictionio_tpu.models.recommendation.engine import (
            ALSAlgorithm,
            Query,
        )
        from predictionio_tpu.server.prediction_server import (
            Binding,
            DeployedEngine,
        )

        algo = ALSAlgorithm()
        model_a = _als_model(seed=2)
        model_b = _als_model(seed=9)  # different factors, same vocab
        deployed = DeployedEngine.__new__(DeployedEngine)
        deployed._lock = _t.RLock()
        deployed.instance = types.SimpleNamespace(id="genA")
        deployed.algorithms = [algo]
        deployed.models = [model_a]
        deployed.serving = FirstServing()
        q = Query(user="u7", num=5)
        before = algo.predict(model_a, q)
        assert algo.predict(model_a, q) == before  # cache hot on A
        binding_b = Binding(
            types.SimpleNamespace(id="genB"), None, [algo], [model_b],
            FirstServing(), "live",
        )
        deployed._install_live(binding_b)  # the swap (drops A's caches)
        after = algo.predict(deployed.models[0], q)
        cold_b = algo.predict(_als_model(seed=9), q)
        assert after == cold_b
        assert after != before
        # and A's cache rows were dropped, not merely bypassed
        assert len(device_cache.model_cache(model_a)) == 0

    def test_canary_flip_isolates_caches(self):
        import threading as _t
        import types

        from predictionio_tpu.core.base import FirstServing
        from predictionio_tpu.models.recommendation.engine import (
            ALSAlgorithm,
            Query,
        )
        from predictionio_tpu.server.prediction_server import (
            Binding,
            DeployedEngine,
        )

        algo = ALSAlgorithm()
        live = _als_model(seed=3)
        canary = _als_model(seed=4)
        deployed = DeployedEngine.__new__(DeployedEngine)
        deployed._lock = _t.RLock()
        deployed.instance = types.SimpleNamespace(id="live")
        deployed.algorithms = [algo]
        deployed.models = [live]
        deployed.serving = FirstServing()
        q = Query(user="u2", num=4)
        live_ans = algo.predict(live, q)
        canary_ans = algo.predict(canary, q)  # canary has its OWN cache
        assert live_ans != canary_ans
        deployed._canary_binding = Binding(
            types.SimpleNamespace(id="canary"), None, [algo], [canary],
            FirstServing(), "canary",
        )
        deployed.clear_canary()  # rollback: canary caches dropped
        assert len(device_cache.model_cache(canary)) == 0
        # live answers are untouched by the flip
        assert algo.predict(live, q) == live_ans

    def test_mesh_rebind_gets_fresh_cache_and_identical_answers(self):
        import jax

        from predictionio_tpu.models.recommendation.engine import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            Query,
        )

        if len(jax.devices()) < 4:
            pytest.skip("needs the virtual multi-device mesh")
        algo = ALSAlgorithm(ALSAlgorithmParams(rank=4, shard_serving=True))
        src = _als_model(seed=5, ni=96)
        blob = {
            "user_factors": np.asarray(src.user_factors),
            "item_factors": np.asarray(src.item_factors),
            "user_vocab": src.user_vocab.to_state(),
            "item_vocab": src.item_vocab.to_state(),
            "shard_plan": algo.serving_shard_plan(src).to_dict(),
        }
        m1 = algo.load_persistent_model(None, blob)
        q = Query(user="u1", num=5)
        ans1 = algo.predict(m1, q)
        algo.predict(m1, q)  # warm m1's cache
        # rebind the SAME blob onto a different mesh width: a new model
        # object, therefore a new empty cache — and identical answers
        from predictionio_tpu.parallel.placement import (
            ShardPlan,
            bind_shards,
        )

        m2 = algo.load_persistent_model(None, blob)
        m2.shards = bind_shards(
            ShardPlan.from_dict(blob["shard_plan"]),
            {
                "user_factors": blob["user_factors"],
                "item_factors": blob["item_factors"],
            },
            devices=jax.devices()[:2],
        )
        assert device_cache.model_cache(m2) is not device_cache.model_cache(
            m1
        )
        assert len(device_cache.model_cache(m2)) == 0
        assert algo.predict(m2, q) == ans1

    def test_ncf_solo_cache_hit_matches_cold(self):
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models.ncf.engine import (
            NCFAlgorithm,
            NCFModel,
            Query,
        )
        from predictionio_tpu.ops.ncf import NCFState

        rng = np.random.default_rng(11)
        nu, ni, d = 20, 50, 6

        def build():
            params = {
                "user_emb": rng.standard_normal((nu, d)).astype(np.float32),
                "item_emb": rng.standard_normal((ni, d)).astype(np.float32),
                "out_b": np.zeros(1, np.float32),
            }
            return params

        params = build()
        mk = lambda: NCFModel(  # noqa: E731
            state=NCFState(
                params={k: v.copy() for k, v in params.items()},
                n_users=nu, n_items=ni, config={},
            ),
            user_vocab=BiMap.from_keys(
                np.array([f"u{i}" for i in range(nu)])
            ),
            item_vocab=BiMap.from_keys(
                np.array([f"i{i}" for i in range(ni)])
            ),
        )
        algo = NCFAlgorithm()
        warm = mk()
        q = Query(user="u5", num=5)
        first = algo.predict(warm, q)
        s0 = device_cache.stats()
        second = algo.predict(warm, q)
        s1 = device_cache.stats()
        assert s1["hits_total"] - s0["hits_total"] >= 1
        assert second == first == algo.predict(mk(), q)


# ---------------------------------------------------------------------------
# pipelined serving path end to end


class _AsyncEchoAlgo:
    """Minimal algorithm with the dispatch_batch contract: records which
    thread ran each half so the test can prove the fence moved off the
    worker."""

    def __init__(self):
        self.dispatch_threads: list = []
        self.finalize_threads: list = []

    def predict(self, model, q):
        return {"echo": q.get("user")}

    def batch_predict(self, model, iq):
        return [(i, {"echo": q.get("user")}) for i, q in iq]

    def dispatch_batch(self, model, iq):
        self.dispatch_threads.append(threading.current_thread().name)

        def finalize():
            self.finalize_threads.append(threading.current_thread().name)
            time.sleep(0.01)  # a fence worth overlapping
            return [(i, {"echo": q.get("user")}) for i, q in iq]

        return finalize


class TestPipelinedServingE2E:
    @pytest.fixture()
    def server(self):
        import types

        from predictionio_tpu.core.base import FirstServing
        from predictionio_tpu.obs.metrics import MetricsRegistry
        from predictionio_tpu.server.aio import AsyncAppServer
        from predictionio_tpu.server.prediction_server import (
            DeployedEngine,
            create_prediction_server_app,
        )

        algo = _AsyncEchoAlgo()
        deployed = DeployedEngine.__new__(DeployedEngine)
        deployed._lock = threading.RLock()
        deployed.instance = types.SimpleNamespace(id="pipe-e2e")
        deployed.storage = None
        deployed.algorithms = [algo]
        deployed.models = [None]
        deployed.serving = FirstServing()
        deployed.extract_query = lambda payload: dict(payload)
        app = create_prediction_server_app(
            deployed,
            use_microbatch=True,
            registry=MetricsRegistry(),
            pipeline_depth=2,
        )
        srv = AsyncAppServer(app, "127.0.0.1", 0).start_background()
        srv.algo = algo
        yield srv
        srv.shutdown()

    def test_waves_pipeline_through_the_server(self, server):
        import json
        import urllib.request

        def post(user):
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/queries.json",
                data=json.dumps({"user": user}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                return json.loads(r.read())

        with ThreadPoolExecutor(8) as ex:
            results = list(ex.map(post, [f"u{i}" for i in range(24)]))
        assert all(r["echo"].startswith("u") for r in results)
        assert {r["echo"] for r in results} == {f"u{i}" for i in range(24)}
        algo = server.algo
        # every dispatch ran on the worker; every fence on the finalizer
        assert set(algo.dispatch_threads) == {"microbatch"}
        assert set(algo.finalize_threads) == {"microbatch-finalize"}
        # the stage table stays honest under overlap: full coverage, never
        # beyond the wall
        snap = server.app.hotpath.snapshot()
        assert snap["requests"] >= 24
        assert 0.95 <= snap["coverage_frac"] <= 1.0
        assert snap["overlap_frac"] >= 0.0


# ---------------------------------------------------------------------------
# hotpath overlap accounting


class TestOverlapAccounting:
    def test_coverage_clamps_and_overlap_surfaces(self):
        from predictionio_tpu.obs.hotpath import HotPathTracker
        from predictionio_tpu.obs.metrics import MetricsRegistry

        t = HotPathTracker(MetricsRegistry())
        # pipelined request: stages measured on other clocks sum to 1.5x
        # the request's own wall
        t.observe(0.010, {"queue_wait": 0.008, "compute": 0.007})
        snap = t.snapshot()
        assert snap["coverage_frac"] == 1.0  # clamped, never 1.5
        assert snap["overlap_frac"] == pytest.approx(0.5)
