"""ALS kernel: convergence, mesh-vs-single-device parity, implicit variant."""

import numpy as np
import pytest

import jax.numpy as jnp

from predictionio_tpu.ops.als import ALSParams, train_als
from predictionio_tpu.parallel.mesh import MeshConfig, default_mesh, make_mesh


@pytest.fixture(scope="module")
def ratings():
    rng = np.random.default_rng(0)
    nu, ni, k = 200, 100, 5
    U = np.abs(rng.normal(size=(nu, k)))
    V = np.abs(rng.normal(size=(ni, k)))
    n = 5000
    ui = rng.integers(0, nu, n).astype(np.int32)
    ii = rng.integers(0, ni, n).astype(np.int32)
    r = (U[ui] * V[ii]).sum(1).astype(np.float32)
    return nu, ni, ui, ii, r


def rmse(state, ui, ii, r):
    pred = (np.asarray(state.user_factors)[ui] * np.asarray(state.item_factors)[ii]).sum(1)
    return float(np.sqrt(((pred - r) ** 2).mean()))


P = ALSParams(rank=5, num_iterations=15, reg=0.01, chunk_size=1024,
              scale_reg_with_count=False)


class TestExplicit:
    def test_fits_low_rank_data(self, ratings):
        nu, ni, ui, ii, r = ratings
        st = train_als(ui, ii, r, nu, ni, P)
        assert rmse(st, ui, ii, r) < 0.05 * r.mean()

    def test_mesh_matches_single_device(self, ratings):
        nu, ni, ui, ii, r = ratings
        st1 = train_als(ui, ii, r, nu, ni, P)
        st8 = train_als(ui, ii, r, nu, ni, P, mesh=default_mesh())
        np.testing.assert_allclose(
            np.asarray(st1.user_factors),
            np.asarray(st8.user_factors),
            atol=2e-3,
        )

    def test_deterministic_given_seed(self, ratings):
        nu, ni, ui, ii, r = ratings
        a = train_als(ui, ii, r, nu, ni, P)
        b = train_als(ui, ii, r, nu, ni, P)
        np.testing.assert_array_equal(
            np.asarray(a.user_factors), np.asarray(b.user_factors)
        )

    def test_factor_shapes_unpadded(self, ratings):
        nu, ni, ui, ii, r = ratings
        st = train_als(ui, ii, r, nu, ni, P, mesh=default_mesh())
        assert np.asarray(st.user_factors).shape == (nu, P.rank)
        assert np.asarray(st.item_factors).shape == (ni, P.rank)


class TestImplicit:
    def test_observed_preference_near_one(self, ratings):
        nu, ni, ui, ii, r = ratings
        p = ALSParams(rank=5, num_iterations=5, reg=0.01, implicit_prefs=True,
                      alpha=40.0, chunk_size=1024, scale_reg_with_count=False)
        st = train_als(ui, ii, r, nu, ni, p, mesh=default_mesh())
        s = (np.asarray(st.user_factors)[ui] * np.asarray(st.item_factors)[ii]).sum(1)
        assert 0.8 < float(s.mean()) < 1.1


class TestMeshConfig:
    def test_axes_resolution(self):
        m = make_mesh(MeshConfig({"data": 4, "model": 2}))
        assert m.shape == {"data": 4, "model": 2}
        m2 = make_mesh(MeshConfig({"data": -1}))
        assert m2.devices.size == 8

    def test_bad_configs(self):
        with pytest.raises(ValueError):
            make_mesh(MeshConfig({"data": -1, "model": -1}))
        with pytest.raises(ValueError):
            make_mesh(MeshConfig({"data": 16}))


#: how far a Pallas rung's factors may sit from the scatter step's, as a
#: share of the largest factor.  Read over the 18 cases below under the
#: interpreter: 9e-6 ... 8e-5, and 4.5e-4 ... 7.3e-4 at rank 20 explicit
#: (20 x 20 systems of users with a handful of ratings at reg 0.01); the
#: reason for any gap is beside the assertion
RUNG_TOL = 2e-3


@pytest.mark.parametrize("backend,rank,pallas", [
    ("tpu", 10, True), ("tpu", 32, True), ("tpu", 33, False),
    ("cpu", 10, False),
])
def test_pallas_is_chosen_from_backend_and_rank(
    monkeypatch, backend, rank, pallas
):
    """The one choice between the Pallas kernel and the scatter step, from
    what the process can observe and nothing a user sets."""
    import jax

    from predictionio_tpu.ops.als import _use_pallas

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert _use_pallas(ALSParams(rank=rank)) is pallas


class TestOOMFallbackLadder:
    """HBM exhaustion degrades fused -> chunked -> per-iteration instead of
    killing the train."""

    def test_is_oom_error_matches_known_shapes(self):
        from predictionio_tpu.ops.als import _is_oom_error

        assert _is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: foo"))
        assert _is_oom_error(
            RuntimeError("Ran out of memory in memory space hbm.")
        )
        assert not _is_oom_error(ValueError("shape mismatch"))

    def test_ladder_falls_back_on_oom(self, monkeypatch):
        from predictionio_tpu.ops import als as als_mod

        attempts = []

        def fake_mode(user_idx, item_idx, rating, nu, ni, p, dtype, mode,
                      per_iter):
            attempts.append((mode, per_iter))
            if len(attempts) < 3:
                raise RuntimeError("Ran out of memory in memory space hbm.")
            return "sentinel-state"

        monkeypatch.setattr(als_mod, "_train_pallas_mode", fake_mode)
        monkeypatch.setattr(als_mod, "_first_rung", lambda nnz: "fused")
        p = als_mod.ALSParams(rank=4)
        with pytest.warns(RuntimeWarning):
            out = als_mod._train_pallas(
                np.zeros(4, np.int64), np.zeros(4, np.int64),
                np.ones(4, np.float32), 4, 4, p, np.float32,
            )
        assert out == "sentinel-state"
        assert attempts == [
            ("fused", False), ("chunked", False), ("chunked", True)
        ]

    def test_ladder_reraises_non_oom(self, monkeypatch):
        from predictionio_tpu.ops import als as als_mod

        def fake_mode(*a, **k):
            raise ValueError("genuine bug")

        monkeypatch.setattr(als_mod, "_train_pallas_mode", fake_mode)
        monkeypatch.setattr(
            als_mod, "_first_rung", lambda nnz: "chunked"
        )
        p = als_mod.ALSParams(rank=4)
        with pytest.raises(ValueError, match="genuine bug"):
            als_mod._train_pallas(
                np.zeros(4, np.int64), np.zeros(4, np.int64),
                np.ones(4, np.float32), 4, 4, p, np.float32,
            )

    @pytest.mark.parametrize("nnz,rung", [
        # the estimate's budget is 8 GiB, at every rank the kernel runs
        (20_000_263, "fused"),     # ML-20M: 5.7 GiB
        (28_000_000, "fused"),     # 7.96 GiB
        (100_000_000, "chunked"),  # 28 GiB
    ])
    def test_first_rung_follows_the_estimate(self, nnz, rung):
        from predictionio_tpu.ops.als import _first_rung

        assert _first_rung(nnz) == rung

    @pytest.mark.parametrize("rank", [4, 10, 20])  # 20 > _SOA_MAX_RANK
    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    @pytest.mark.parametrize("mode,per_iter", [
        ("fused", False), ("chunked", False), ("chunked", True),
    ], ids=["fused", "chunked", "chunked_per_iter"])
    def test_every_rung_trains_what_the_scatter_step_trains(
        self, pallas_on_cpu, monkeypatch, mode, per_iter, implicit, rank
    ):
        """A whole train through each rung of the ladder, under the Pallas
        interpreter, against the scatter step from the same seed: a
        fallback that has never run is not a fallback."""
        from predictionio_tpu.ops import als as als_mod

        rng = np.random.default_rng(28)
        nu, ni, n = 50, 20, 600
        ui, ii = rng.integers(0, nu, n), rng.integers(0, ni, n)
        r = rng.integers(1, 6, n).astype(np.float32)
        p = ALSParams(rank=rank, num_iterations=3, implicit_prefs=implicit)
        got = als_mod._train_pallas_mode(
            ui, ii, r, nu, ni, p, jnp.float32, mode, per_iter
        )
        assert als_mod.LAST_PLAN_INFO["mode"] == mode
        assert als_mod.LAST_PLAN_INFO["per_iter"] is per_iter
        monkeypatch.setattr(als_mod, "_use_pallas", lambda p: False)
        want = train_als(ui, ii, r, nu, ni, p)
        # not bit-equal: the kernel accumulates in two bf16 passes
        # ("hilo", ~2^-16 relative an entry) in another summation order
        # than the f32 scatter, and three rounds of solves at reg 0.01
        # carry that through; a wrong rung is off by O(1), not by 1e-3
        for side in ("user_factors", "item_factors"):
            a = np.asarray(getattr(got, side))
            b = np.asarray(getattr(want, side))
            assert np.abs(a - b).max() <= RUNG_TOL * np.abs(b).max(), side


class TestSolveFactors:
    def test_wide_rank_batched_solve_matches_numpy(self):
        """Ranks above _SOA_MAX_RANK route through batched lax.linalg; the
        solutions must match a dense numpy solve."""
        from predictionio_tpu.ops.als import _SOA_MAX_RANK, _solve_factors

        rng = np.random.default_rng(0)
        n, k = 40, _SOA_MAX_RANK + 4
        M = rng.standard_normal((n, k, k)).astype(np.float32)
        A = M @ M.transpose(0, 2, 1)  # SPD-ish, ridge added inside
        b = rng.standard_normal((n, k)).astype(np.float32)
        counts = rng.integers(1, 9, n).astype(np.float32)
        got = np.asarray(_solve_factors(
            jnp.asarray(A), jnp.asarray(b), jnp.asarray(counts), 0.1, True
        ))
        lhs = A + (0.1 * np.maximum(counts, 1.0))[:, None, None] * np.eye(k)
        want = np.linalg.solve(lhs, b[..., None])[..., 0]
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_narrow_and_wide_agree_at_boundary(self):
        from predictionio_tpu.ops import als as als_mod

        rng = np.random.default_rng(1)
        n, k = 16, 8
        M = rng.standard_normal((n, k, k)).astype(np.float32)
        A = M @ M.transpose(0, 2, 1)
        b = rng.standard_normal((n, k)).astype(np.float32)
        counts = np.ones(n, np.float32)
        soa = np.asarray(als_mod._solve_factors(
            jnp.asarray(A), jnp.asarray(b), jnp.asarray(counts), 0.05, False
        ))
        orig = als_mod._SOA_MAX_RANK
        try:
            als_mod._SOA_MAX_RANK = 4  # force the batched path
            batched = np.asarray(als_mod._solve_factors(
                jnp.asarray(A), jnp.asarray(b), jnp.asarray(counts), 0.05,
                False
            ))
        finally:
            als_mod._SOA_MAX_RANK = orig
        np.testing.assert_allclose(soa, batched, rtol=2e-3, atol=2e-3)
