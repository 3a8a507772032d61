"""Sharded serving & training (ISSUE 8): ShardPlan lifecycle, the
factor-sharded top-k on the virtual 8-device CPU mesh (parity incl. ties at
shard boundaries and k > per-shard candidates), sharded training state,
MicroBatcher wiring, and the generation-manifest round trip with per-part
checksums + last-good fallback."""

from __future__ import annotations

import asyncio
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.parallel import placement as pl
from predictionio_tpu.parallel.mesh import (
    MeshConfig,
    balance_local_chunks,
    make_mesh,
    pad_to_multiple,
    shard_attribution,
)


# ---------------------------------------------------------------------------
# ShardPlan


class TestShardPlan:
    def test_dict_round_trip(self):
        plan = pl.ShardPlan.model_parallel(
            ["user_factors", "item_factors"],
            rows={"user_factors": 21, "item_factors": 37},
        )
        d = plan.to_dict()
        assert d["schema"] == pl.PLAN_SCHEMA_VERSION
        back = pl.ShardPlan.from_dict(json.loads(json.dumps(d)))
        assert back == plan
        assert pl.ShardPlan.from_dict(None) is None
        assert pl.ShardPlan.from_dict({}) is None

    def test_rebind_wildcard_absorbs_devices(self):
        plan = pl.ShardPlan(axes={"model": -1}, specs={"t": ("model", None)})
        assert plan.rebind(8).axes == {"model": 8}
        assert plan.rebind(4).axes == {"model": 4}

    def test_rebind_on_device_count_mismatch_reshards(self):
        """A plan recorded on an 8-way mesh re-binds onto 4 devices: the
        sharding axis absorbs them (layout follows the mesh you HAVE)."""
        plan = pl.ShardPlan(axes={"model": 8}, specs={"t": ("model", None)})
        assert plan.rebind(4).axes == {"model": 4}
        multi = pl.ShardPlan(
            axes={"data": 2, "model": 4}, specs={"t": ("model", None)}
        )
        assert multi.rebind(8).axes == {"data": 2, "model": 4}  # still fits
        assert multi.rebind(2) .axes == {"data": 1, "model": 2}

    def test_mesh_over_device_subset(self):
        plan = pl.ShardPlan.model_parallel(["t"])
        mesh = plan.mesh(devices=jax.devices()[:4])
        assert dict(mesh.shape) == {"model": 4}

    def test_shard_multiple_unknown_axis_raises(self):
        plan = pl.ShardPlan(axes={"model": -1}, specs={"t": ("model", None)})
        mesh = make_mesh(MeshConfig(axes={"data": -1}))
        with pytest.raises(pl.ShardPlanError):
            plan.shard_multiple(mesh, "t")

    def test_two_wildcards_rejected(self):
        plan = pl.ShardPlan(axes={"a": -1, "b": -1})
        with pytest.raises(pl.ShardPlanError):
            plan.rebind(8)


# ---------------------------------------------------------------------------
# pad_to_multiple / balance_local_chunks edge cases (load-bearing under
# sharding — the satellite fixes)


class TestPadToMultipleEdges:
    def test_zero_or_negative_multiple_raises(self):
        with pytest.raises(ValueError, match="positive"):
            pad_to_multiple(np.arange(5), 0)
        with pytest.raises(ValueError, match="positive"):
            pad_to_multiple(np.arange(5), -4)

    def test_empty_array_pads_to_one_multiple(self):
        padded, n = pad_to_multiple(np.zeros(0, np.int32), 8)
        assert padded.shape == (8,) and n == 0

    def test_remainder_pads_up(self):
        padded, n = pad_to_multiple(np.arange(5, dtype=np.int32), 4, fill=-1)
        assert padded.shape == (8,) and n == 5
        assert list(padded[5:]) == [-1, -1, -1]

    def test_2d_axis_zero(self):
        padded, n = pad_to_multiple(np.ones((5, 3), np.float32), 8)
        assert padded.shape == (8, 3) and n == 5
        assert padded[5:].sum() == 0


class TestBalanceLocalChunksEdges:
    def test_zero_multiple_raises(self):
        with pytest.raises(ValueError, match="positive"):
            balance_local_chunks([np.arange(3)], 0)

    def test_no_arrays_raises(self):
        with pytest.raises(ValueError, match="at least one"):
            balance_local_chunks([], 4)

    def test_mismatched_lengths_raises(self):
        with pytest.raises(ValueError, match="share one local length"):
            balance_local_chunks([np.arange(3), np.arange(4)], 4)

    def test_empty_local_rows_pad_to_one_chunk(self):
        """The remainder-on-last-host shape: a process that read ZERO rows
        still contributes a full (all-padding) chunk with valid=0."""
        (a,), valid = balance_local_chunks([np.zeros(0, np.float32)], 4)
        assert a.shape == (4,) and valid.sum() == 0.0

    def test_remainder_rows_masked(self):
        (a, b), valid = balance_local_chunks(
            [np.arange(5, dtype=np.int64), np.ones(5, np.float32)], 4
        )
        assert a.shape == (8,) and valid.sum() == 5.0
        assert list(valid[5:]) == [0.0, 0.0, 0.0]


# ---------------------------------------------------------------------------
# the factor-sharded top-k kernel


def _als_fixture(n_users=21, n_items=37, rank=5, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank)).astype(np.float32)
    V = rng.normal(size=(n_items, rank)).astype(np.float32)
    plan = pl.ShardPlan.model_parallel(
        ["U", "V"], rows={"U": n_users, "V": n_items}
    )
    return U, V, plan


class TestShardedTopK:
    def test_parity_with_single_device_topk(self):
        U, V, plan = _als_fixture()
        bound = pl.bind_shards(plan, {"U": U, "V": V})
        uidx = jnp.asarray([0, 3, 7, 20])
        rows = pl.gather_rows(bound.mesh, bound.arrays["U"], uidx)
        np.testing.assert_allclose(
            np.asarray(rows), U[np.asarray(uidx)], rtol=1e-6
        )
        k = 10
        fn = pl.build_sharded_topk(
            bound.mesh, bound.plan, lambda Vl, q: q @ Vl.T, ["V"],
            n_items=37, k=k, name="test.topk",
        )
        packed = np.asarray(fn(bound.arrays["V"], rows))
        ref_v, ref_i = jax.lax.top_k(
            jnp.asarray(U[np.asarray(uidx)] @ V.T), k
        )
        np.testing.assert_allclose(packed[0], np.asarray(ref_v), rtol=1e-5)
        np.testing.assert_array_equal(
            packed[1].astype(np.int64), np.asarray(ref_i)
        )

    def test_k_larger_than_per_shard_candidates(self):
        """37 rows over 8 shards = 5 padded rows/shard; k=12 > 5 means every
        shard contributes ALL its rows and the merge must still be exact."""
        U, V, plan = _als_fixture()
        bound = pl.bind_shards(plan, {"U": U, "V": V})
        q = jnp.asarray(U[:3])
        fn = pl.build_sharded_topk(
            bound.mesh, bound.plan, lambda Vl, qq: qq @ Vl.T, ["V"],
            n_items=37, k=12, name="test.topk_wide",
        )
        packed = np.asarray(fn(bound.arrays["V"], q))
        shapes = pl.LAST_KERNEL_SHAPES["test.topk_wide"]
        assert shapes["k"] > shapes["rows_local"]
        ref_v, ref_i = jax.lax.top_k(jnp.asarray(U[:3] @ V.T), 12)
        np.testing.assert_allclose(packed[0], np.asarray(ref_v), rtol=1e-5)
        np.testing.assert_array_equal(
            packed[1].astype(np.int64), np.asarray(ref_i)
        )

    def test_duplicate_score_ties_at_shard_boundaries(self):
        """Equal scores straddling a shard boundary must resolve by lowest
        GLOBAL row id — bit-identical to an unsharded lax.top_k."""
        n = 16  # 8 shards x 2 rows: ties pair rows (1, 2), (3, 4), ...
        V = np.zeros((n, 2), np.float32)
        V[:, 0] = np.repeat(np.arange(n // 2)[::-1], 2).astype(np.float32)
        plan = pl.ShardPlan.model_parallel(["V"], rows={"V": n})
        bound = pl.bind_shards(plan, {"V": V})
        q = jnp.asarray([[1.0, 0.0]])
        for k in (3, 5, 16):
            fn = pl.build_sharded_topk(
                bound.mesh, bound.plan, lambda Vl, qq: qq @ Vl.T, ["V"],
                n_items=n, k=k, name=f"test.ties{k}",
            )
            got = np.asarray(fn(bound.arrays["V"], q))
            ref_v, ref_i = jax.lax.top_k(q @ jnp.asarray(V).T, k)
            np.testing.assert_array_equal(
                got[1].astype(np.int64), np.asarray(ref_i)
            )
            np.testing.assert_allclose(got[0], np.asarray(ref_v))

    def test_no_device_materializes_full_score_row(self):
        """The per-shard shape contract: each device's score block covers
        only its own rows (rows_local * n_shards == padded table, and
        rows_local < n_items)."""
        U, V, plan = _als_fixture()
        bound = pl.bind_shards(plan, {"U": U, "V": V})
        fn = pl.build_sharded_topk(
            bound.mesh, bound.plan, lambda Vl, q: q @ Vl.T, ["V"],
            n_items=37, k=8, name="test.shapes",
        )
        fn(bound.arrays["V"], jnp.asarray(U[:2]))
        shapes = pl.LAST_KERNEL_SHAPES["test.shapes"]
        assert shapes["n_shards"] == 8
        assert shapes["rows_local"] < shapes["n_items"]
        assert (
            shapes["rows_local"] * shapes["n_shards"]
            == bound.arrays["V"].shape[0]
        )

    def test_attribution_spreads_bytes_evenly(self):
        U, V, plan = _als_fixture()
        bound = pl.bind_shards(plan, {"U": U, "V": V})
        attr = bound.attribution()
        assert len(attr) == 8
        total = sum(e["bytes"] for e in attr.values())
        for e in attr.values():
            assert e["bytes"] == pytest.approx(total / 8)
            # the acceptance bound: every device holds < 1/4 of the tables
            assert e["bytes"] < total / 4


# ---------------------------------------------------------------------------
# sharded training state


class TestShardedTrainingState:
    def test_als_mesh_train_keeps_factor_state_sharded(self):
        """During the mesh train the factor tables persist row-sharded:
        the pio_shard_bytes attribution taken on the live (padded) arrays
        shows 8 participants with an equal 1/8 share each."""
        from predictionio_tpu.obs.metrics import REGISTRY
        from predictionio_tpu.ops.als import ALSParams, train_als
        from predictionio_tpu.parallel.mesh import default_mesh

        rng = np.random.default_rng(0)
        ui = rng.integers(0, 64, 2000).astype(np.int32)
        ii = rng.integers(0, 48, 2000).astype(np.int32)
        r = rng.uniform(1, 5, 2000).astype(np.float32)
        train_als(
            ui, ii, r, 64, 48,
            ALSParams(rank=4, num_iterations=2, chunk_size=512),
            mesh=default_mesh(),
        )
        fam = REGISTRY.get("pio_shard_bytes")
        per_dev = {
            labels[1]: child.value
            for labels, child in fam.series()
            if labels[0] == "als.factors"
        }
        assert len(per_dev) == 8
        values = set(per_dev.values())
        assert len(values) == 1  # equal shares
        share = values.pop()
        assert share == pytest.approx(sum(per_dev.values()) / 8)

    def test_ncf_tables_and_optimizer_state_shard_over_model_axis(self):
        """The data-parallel-dense / model-parallel-embedding recipe: with
        a {data: 2, model: 4} mesh the embedding tables AND the Adam
        moments over them live 1/4 per device (2 data-replicas each) —
        optimizer state is sharded, not replicated."""
        import optax

        from predictionio_tpu.ops.ncf import (
            NCFParams,
            init_ncf,
            param_shardings,
        )

        mesh = make_mesh(MeshConfig(axes={"data": 2, "model": 4}))
        p = NCFParams(embed_dim=8, mlp_layers=(16, 8))
        net = init_ncf(jax.random.PRNGKey(0), 64, 32, p)
        net = jax.device_put(net, param_shardings(mesh, net))
        opt_state = optax.adam(1e-3).init(net)

        table_bytes = sum(
            np.asarray(x).nbytes
            for x in (net["user_emb"], net["item_emb"])
        )
        # the tables themselves: each device holds exactly its model-axis
        # quarter (replicated only across the 2 data-axis peers)
        attr = shard_attribution(
            {"user_emb": net["user_emb"], "item_emb": net["item_emb"]}
        )
        assert len(attr) == 8
        for e in attr.values():
            assert e["bytes"] == pytest.approx(table_bytes / 4)
        # the Adam moments mirror the param placement: mu+nu table leaves
        # together cost 2x a table SLICE per device, never 2x a replica
        table_shapes = (net["user_emb"].shape, net["item_emb"].shape)
        opt_tables = [
            leaf
            for leaf in jax.tree_util.tree_leaves(opt_state)
            if getattr(leaf, "shape", None) in table_shapes
        ]
        assert len(opt_tables) == 4  # mu + nu for each of the two tables
        oattr = shard_attribution(opt_tables)
        assert len(oattr) == 8
        for e in oattr.values():
            assert e["bytes"] == pytest.approx(2 * table_bytes / 4)


class TestGlobalTrainEntry:
    def test_daemon_fed_global_train_matches_direct(self, tmp_path):
        """The multi-host data plane with the storage daemon in the loop,
        in one process: events -> loopback storage daemon -> entity-hash
        shard scan over HTTP (RemotePEvents.iter_shards) -> COO columns ->
        global sharded arrays -> SPMD train (train_als_global).  The
        storage plane may reorder rows but must not change the solution.
        (The two-process form is
        test_distributed.py::test_two_process_remote_daemon_train_parity.)"""
        from datetime import datetime, timezone

        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage.remote_backend import (
            RemoteClient,
            RemoteLEvents,
            RemotePEvents,
        )
        from predictionio_tpu.ops.als import ALSParams, train_als_global
        from predictionio_tpu.parallel.mesh import (
            default_mesh,
            global_data_array,
        )
        from predictionio_tpu.server.storage_server import StorageServer

        rng = np.random.default_rng(0)
        nnz, num_users, num_items, chunk = 2048, 64, 48, 256
        ru = rng.integers(0, num_users, nnz).astype(np.int32)
        ri = rng.integers(0, num_items, nnz).astype(np.int32)
        rr = rng.uniform(1.0, 5.0, nnz).astype(np.float32)
        daemon = StorageServer(
            tmp_path, host="127.0.0.1", port=0
        ).start_background()
        try:
            url = f"http://127.0.0.1:{daemon.port}"
            le = RemoteLEvents(RemoteClient(url))
            le.init(1)
            t0 = datetime(2024, 1, 1, tzinfo=timezone.utc)
            le.insert_batch(
                [
                    Event(
                        event="rate", entity_type="user", entity_id=f"u{a}",
                        target_entity_type="item", target_entity_id=f"i{b}",
                        properties={"rating": float(c)}, event_time=t0,
                    )
                    for a, b, c in zip(ru, ri, rr)
                ],
                1,
            )
            pe = RemotePEvents(RemoteClient(url))
            us, is_, rs = [], [], []
            for _, frame in pe.iter_shards(
                1, shards=list(range(pe.n_shards(1)))
            ):
                sel = frame.where_event("rate")
                us.append(
                    np.array([int(s[1:]) for s in sel.entity_id], np.int32)
                )
                is_.append(
                    np.array(
                        [int(s[1:]) for s in sel.target_entity_id], np.int32
                    )
                )
                rs.append(
                    sel.property_column("rating", default=0.0).astype(
                        np.float32
                    )
                )
        finally:
            daemon.shutdown()
        fed = [np.concatenate(us), np.concatenate(is_), np.concatenate(rs)]
        assert len(fed[0]) == nnz

        mesh = default_mesh()
        n_dev = mesh.devices.size

        def train(cols):
            (gu, gi, gr), valid = balance_local_chunks(cols, chunk * n_dev)
            return train_als_global(
                global_data_array(mesh, gu),
                global_data_array(mesh, gi),
                global_data_array(mesh, gr),
                global_data_array(mesh, valid),
                num_users,
                num_items,
                mesh,
                params=ALSParams(rank=8, num_iterations=1, chunk_size=chunk),
            )

        got, direct = train(fed), train([ru, ri, rr])
        assert np.isfinite(got.user_factors).all()
        np.testing.assert_allclose(
            got.user_factors, direct.user_factors, rtol=2e-4, atol=2e-4
        )


# ---------------------------------------------------------------------------
# engine-level sharded serving (the acceptance e2e)


def _vocab(prefix, n):
    return BiMap.from_keys(np.array([f"{prefix}{i}" for i in range(n)]))


@pytest.fixture(scope="module")
def als_sharded_model():
    from predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
    )
    from predictionio_tpu.ops.als import ALSParams, train_als

    rng = np.random.default_rng(2)
    nu, ni = 50, 37
    ui = rng.integers(0, nu, 2000).astype(np.int32)
    ii = rng.integers(0, ni, 2000).astype(np.int32)
    r = rng.uniform(1, 5, 2000).astype(np.float32)
    st = train_als(
        ui, ii, r, nu, ni, ALSParams(rank=4, num_iterations=5, chunk_size=512)
    )
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=4, shard_serving=True))
    model = ALSModel(
        np.asarray(st.user_factors), np.asarray(st.item_factors),
        _vocab("u", nu), _vocab("i", ni),
    )
    blob = algo.make_persistent_model(None, model)
    return algo, blob


class TestALSShardedServing:
    def test_round_trip_binds_shards_with_small_per_device_share(
        self, als_sharded_model
    ):
        algo, blob = als_sharded_model
        assert blob["shard_plan"]["axes"] == {"model": -1}
        loaded = algo.load_persistent_model(None, blob)
        assert loaded.shards is not None
        assert dict(loaded.shards.mesh.shape) == {"model": 8}
        attr = loaded.shards.attribution()
        total = sum(e["bytes"] for e in attr.values())
        assert len(attr) == 8
        assert all(e["bytes"] < total / 4 for e in attr.values())

    def test_batch_predict_matches_single_device(self, als_sharded_model):
        from predictionio_tpu.models.recommendation.engine import Query

        algo, blob = als_sharded_model
        sharded = algo.load_persistent_model(None, blob)
        plain = algo.load_persistent_model(
            None, {k: v for k, v in blob.items() if k != "shard_plan"}
        )
        assert plain.shards is None
        queries = [(i, Query(user=f"u{i}", num=5)) for i in range(12)]
        queries.append((99, Query(user="missing", num=5)))
        ref = dict(algo.batch_predict(plain, queries))
        got = dict(algo.batch_predict(sharded, queries))
        assert set(ref) == set(got)
        for i in ref:
            assert [
                (s.item, pytest.approx(s.score, rel=1e-5))
                for s in ref[i].item_scores
            ] == [(s.item, s.score) for s in got[i].item_scores]
        shapes = pl.LAST_KERNEL_SHAPES["als.sharded_topk"]
        assert shapes["rows_local"] < shapes["n_items"]

    def test_rebind_onto_smaller_mesh_serves_identically(
        self, als_sharded_model
    ):
        """Deploy onto a DIFFERENTLY-sized mesh: the recorded 8-way plan
        re-binds 4-way and answers byte-identically."""
        from predictionio_tpu.models.recommendation.engine import Query
        from predictionio_tpu.parallel.placement import ShardPlan, bind_shards

        algo, blob = als_sharded_model
        plain = algo.load_persistent_model(
            None, {k: v for k, v in blob.items() if k != "shard_plan"}
        )
        sharded = algo.load_persistent_model(None, blob)
        plan = ShardPlan.from_dict(blob["shard_plan"])
        sharded.shards = bind_shards(
            plan,
            {
                "user_factors": blob["user_factors"],
                "item_factors": blob["item_factors"],
            },
            devices=jax.devices()[:4],
        )
        assert dict(sharded.shards.mesh.shape) == {"model": 4}
        queries = [(i, Query(user=f"u{i + 3}", num=7)) for i in range(5)]
        ref = dict(algo.batch_predict(plain, queries))
        got = dict(algo.batch_predict(sharded, queries))
        for i in ref:
            assert [s.item for s in ref[i].item_scores] == [
                s.item for s in got[i].item_scores
            ]


@pytest.fixture(scope="module", params=["mlp", "gmf"])
def ncf_sharded_model(request):
    from predictionio_tpu.models.ncf.engine import (
        NCFAlgorithm,
        NCFAlgorithmParams,
        NCFModel,
    )
    from predictionio_tpu.ops.ncf import NCFParams, train_ncf

    rng = np.random.default_rng(3)
    nu, ni = 40, 30
    ui = rng.integers(0, nu, 1500).astype(np.int32)
    ii = rng.integers(0, ni, 1500).astype(np.int32)
    layers = (16, 8) if request.param == "mlp" else ()
    state = train_ncf(
        ui, ii, nu, ni,
        params=NCFParams(
            embed_dim=8, mlp_layers=layers, num_epochs=2, batch_size=256
        ),
    )
    algo = NCFAlgorithm(
        NCFAlgorithmParams(
            embed_dim=8, mlp_layers=layers, shard_serving=True
        )
    )
    model = NCFModel(state=state, user_vocab=_vocab("u", nu),
                     item_vocab=_vocab("i", ni))
    return algo, algo.make_persistent_model(None, model)


class TestNCFShardedServing:
    def test_predict_wave_matches_single_device(self, ncf_sharded_model):
        from predictionio_tpu.models.recommendation.engine import Query

        algo, blob = ncf_sharded_model
        sharded = algo.load_persistent_model(None, blob)
        plain = algo.load_persistent_model(
            None, {k: v for k, v in blob.items() if k != "shard_plan"}
        )
        assert sharded.shards is not None and plain.shards is None
        queries = [(i, Query(user=f"u{i}", num=6)) for i in range(10)]
        queries.append((77, Query(user="missing", num=6)))
        ref = dict(algo.batch_predict(plain, queries))
        got = dict(algo.batch_predict(sharded, queries))
        assert set(ref) == set(got)
        for i in ref:
            assert [s.item for s in ref[i].item_scores] == [
                s.item for s in got[i].item_scores
            ], i
            np.testing.assert_allclose(
                [s.score for s in ref[i].item_scores],
                [s.score for s in got[i].item_scores],
                rtol=1e-4, atol=1e-5,
            )
        shapes = pl.LAST_KERNEL_SHAPES["ncf.sharded_topk"]
        assert shapes["n_shards"] == 8
        assert shapes["rows_local"] < shapes["n_items"]

    def test_solo_predict_unchanged(self, ncf_sharded_model):
        """The solo path still answers from the host replica (no device
        dispatch) even when shards are bound."""
        from predictionio_tpu.models.recommendation.engine import Query

        algo, blob = ncf_sharded_model
        sharded = algo.load_persistent_model(None, blob)
        plain = algo.load_persistent_model(
            None, {k: v for k, v in blob.items() if k != "shard_plan"}
        )
        for user in ("u0", "u7", "missing"):
            a = algo.predict(plain, Query(user=user, num=4))
            b = algo.predict(sharded, Query(user=user, num=4))
            assert [s.item for s in a.item_scores] == [
                s.item for s in b.item_scores
            ]


# ---------------------------------------------------------------------------
# MicroBatcher wiring: a sharded model behind the coalescing wave path


class TestMicroBatcherSharded:
    def test_waves_serve_sharded_and_carry_shard_meta(self, als_sharded_model):
        from predictionio_tpu.models.recommendation.engine import Query
        from predictionio_tpu.server.microbatch import MicroBatcher

        algo, blob = als_sharded_model
        model = algo.load_persistent_model(None, blob)
        plain = algo.load_persistent_model(
            None, {k: v for k, v in blob.items() if k != "shard_plan"}
        )

        def batch_fn(items):
            indexed = list(enumerate(items))
            by_idx = dict(algo.batch_predict(model, indexed))
            return [by_idx[i] for i in range(len(items))]

        metas = [dict() for _ in range(16)]

        async def run():
            b = MicroBatcher(batch_fn, max_batch=16)
            results = await asyncio.gather(
                *(
                    b.submit(Query(user=f"u{i}", num=5), metas[i])
                    for i in range(16)
                )
            )
            b.close()
            return results

        results = asyncio.run(run())
        for i, res in enumerate(results):
            ref = algo.predict(plain, Query(user=f"u{i}", num=5))
            assert [s.item for s in ref.item_scores] == [
                s.item for s in res.item_scores
            ]
        # every wave carried the per-device shard attribution into meta
        assert any(m.get("wave_shards") for m in metas)
        shard_meta = next(m["wave_shards"] for m in metas if m.get("wave_shards"))
        assert len(shard_meta) == 8
        assert all("bytes" in entry for entry in shard_meta.values())

    def test_efficiency_snapshot_reports_shards(self, als_sharded_model):
        from predictionio_tpu.models.recommendation.engine import Query
        from predictionio_tpu.obs.device import device_snapshot

        algo, blob = als_sharded_model
        model = algo.load_persistent_model(None, blob)
        algo.batch_predict(model, [(0, Query(user="u1", num=5))])
        snap = device_snapshot()
        fns = snap["shards"]["functions"]
        assert "als.sharded_topk" in fns
        assert len(fns["als.sharded_topk"]) == 8
        assert len(snap["shards"]["devices"]) >= 8
        some = next(iter(fns["als.sharded_topk"].values()))
        assert some["bytes"] > 0 and some["waves"] >= 1


# ---------------------------------------------------------------------------
# generation-manifest round trip (per-part checksums + ShardPlan + fallback)


def _train_sharded_instance(storage, app_name, seed=3, num_iterations=3):
    from predictionio_tpu.core.base import EngineContext
    from predictionio_tpu.core.engine import resolve_engine_factory
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.models import recommendation  # noqa: F401

    engine = resolve_engine_factory("recommendation")()
    params = engine.params_from_json(
        {
            "datasource": {"params": {"appName": app_name}},
            "algorithms": [
                {
                    "name": "als",
                    "params": {
                        "rank": 8,
                        "numIterations": num_iterations,
                        "seed": seed,
                        "shardServing": True,
                    },
                }
            ],
        }
    )
    return run_train(
        engine,
        params,
        ctx=EngineContext(storage=storage),
        engine_factory="recommendation",
        storage=storage,
    )


@pytest.fixture()
def sharded_app(storage, monkeypatch):
    from predictionio_tpu.core import persistence
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.tools import commands as cmd

    # force the factor tables into named checkpoint parts so the per-part
    # checksums cover real shard blobs at test scale
    monkeypatch.setattr(persistence, "PART_THRESHOLD", 256)
    d = cmd.app_new(storage, "shardtest")
    rng = np.random.default_rng(5)
    events = [
        Event(
            event="rate",
            entity_type="user",
            entity_id=f"u{rng.integers(30)}",
            target_entity_type="item",
            target_entity_id=f"i{rng.integers(20)}",
            properties={"rating": float(rng.integers(1, 6))},
        )
        for _ in range(300)
    ]
    storage.l_events().insert_batch(events, d.app.id)
    return storage


class TestGenerationRoundTrip:
    def test_sharded_generation_records_plan_and_part_checksums(
        self, sharded_app
    ):
        from predictionio_tpu.core.workflow import read_shard_plan
        from predictionio_tpu.lifecycle.generations import GenerationStore

        storage = sharded_app
        inst = _train_sharded_instance(storage, "shardtest")
        assert inst is not None and inst.status == "COMPLETED"
        # run_train recorded the sidecar plan
        plan_dict = read_shard_plan(storage.models(), inst.id)
        assert plan_dict is not None and plan_dict["axes"] == {"model": -1}
        store = GenerationStore(storage.models())
        gen = store.record(inst.id, status="staged")
        assert gen.shard_plan == plan_dict
        assert gen.part_checksums is not None
        part_names = [k for k in gen.part_checksums if k.startswith("part:")]
        assert len(part_names) >= 2  # user + item factor tables
        store.verify(gen)  # intact bytes verify clean

    def test_one_corrupt_shard_is_named_and_triggers_fallback(
        self, sharded_app
    ):
        from predictionio_tpu.lifecycle.generations import (
            CorruptModelError,
            GenerationStore,
        )
        from predictionio_tpu.server.prediction_server import deploy_engine

        storage = sharded_app
        first = _train_sharded_instance(storage, "shardtest", seed=3)
        second = _train_sharded_instance(
            storage, "shardtest", seed=4, num_iterations=4
        )
        store = GenerationStore(storage.models())
        store.record(first.id, status="live")
        store.record(second.id, status="live")  # first retires
        gen2 = store.get(second.id)
        # corrupt exactly ONE factor-shard part of the live generation
        part_name = sorted(
            k for k in gen2.part_checksums if k.startswith("part:")
        )[0].split(":", 1)[1]
        key = f"{second.id}:part:{part_name}"
        blob = storage.models().get(key)
        storage.models().insert(key, blob[:-4] + b"XXXX")
        with pytest.raises(CorruptModelError) as e:
            store.verify(gen2)
        assert part_name in str(e.value)  # the corrupt shard is NAMED
        # bind walks live -> corrupt -> falls back to the last good
        deployed = deploy_engine("recommendation", storage=storage)
        assert deployed.instance.id == first.id
        assert store.get(second.id).status == "rolled_back"
        # and the bound model serves SHARDED (plan re-bound at load)
        model = deployed.models[0]
        assert model.shards is not None
        assert dict(model.shards.mesh.shape) == {"model": 8}

    def test_deploy_rebinds_plan_onto_current_mesh(self, sharded_app):
        """The deploy half of the ShardPlan lifecycle: the persisted plan
        (recorded {'model': -1}) binds 8-way here, and the SAME blob binds
        4-way on a 4-device mesh — re-sharding on device-count mismatch."""
        from predictionio_tpu.core.persistence import load_models
        from predictionio_tpu.models.recommendation.engine import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            Query,
        )
        from predictionio_tpu.parallel.placement import ShardPlan, bind_shards

        storage = sharded_app
        inst = _train_sharded_instance(storage, "shardtest")
        persisted = load_models(storage.models(), inst.id)
        data = persisted[0]
        algo = ALSAlgorithm(ALSAlgorithmParams(rank=8, shard_serving=True))
        full = algo.load_persistent_model(None, data)
        assert dict(full.shards.mesh.shape) == {"model": 8}
        small = algo.load_persistent_model(None, dict(data))
        small.shards = bind_shards(
            ShardPlan.from_dict(data["shard_plan"]),
            {
                "user_factors": data["user_factors"],
                "item_factors": data["item_factors"],
            },
            devices=jax.devices()[:2],
        )
        assert dict(small.shards.mesh.shape) == {"model": 2}
        q = [(0, Query(user=full.user_vocab.inverse(0), num=5))]
        ref = dict(algo.batch_predict(full, q))[0]
        got = dict(algo.batch_predict(small, q))[0]
        assert [s.item for s in ref.item_scores] == [
            s.item for s in got.item_scores
        ]
