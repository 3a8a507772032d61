"""chip_smoke.py rehearsed on the CPU at a tiny size: the same phase
functions, real CLI children (``pio train`` / ``deploy`` / ``batchpredict``),
the parquet store and the numpy reference checks — so the command is debugged
here and not on chip budget.  What only the chip can show (Mosaic-compiled
kernels, the Pallas train path, device memory) is asserted by the script's own
``__main__`` run; here the same fields are checked for their CPU values."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke as cs

TINY = cs.Size(nnz=60_000, num_users=600, num_items=200, batch_users=512)


def _child_env(tmp_path, devices: int) -> dict[str, str]:
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        # placeable from outside: every child's entries must land HERE
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
    }


@pytest.fixture()
def smoke_factory(tmp_path):
    made = []

    def make(devices: int) -> cs.Smoke:
        smoke = cs.Smoke(
            TINY, tmp_path / "work", _child_env(tmp_path, devices),
            deadline_s=600,
        )
        made.append(smoke)
        return smoke

    yield make
    for smoke in made:
        smoke.close()
        assert all(c.poll() is not None for c in smoke._children)
        assert not smoke.home.exists()  # the PIO_HOME is a throwaway


def test_whole_smoke_on_one_cpu_device(smoke_factory, tmp_path):
    smoke = smoke_factory(devices=1)
    report = cs.run(smoke)

    assert report["ok"] is True
    # the last stdout line, to the driver's contract: these keys, no others
    assert cs.verdict(report) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert list(report["phases"]) == [
        "probe", "load_events", "als_train", "als_serve", "als_batch", "ncf",
    ]
    assert all(p["ok"] for p in report["phases"].values())
    assert report["reduced"], "a tiny size is a cut and must be listed"
    assert report["dispatch_rtt_ms"] > 0

    train = report["phases"]["als_train"]
    # one CPU device: the single-device jit of the scatter step (the Pallas
    # path is the chip's, and chip_smoke demands it there)
    assert train["als_path"] == "als.train_step" and train["devices"] == 1
    assert train["compile_s"] > 0 and train["stage_s"]["train.algorithm.als"] > 0

    serve = report["phases"]["als_serve"]
    assert serve["engine_paths"] == ["als.host_replica"]
    assert serve["sequential"]["answers"] == 20
    assert serve["concurrent"]["answers"] == 32

    batch = report["phases"]["als_batch"]
    assert batch["answers"] == TINY.batch_users == batch["kernel"]["batch"]
    assert batch["kernel"]["interpret"] == 1  # CPU: the pallas interpreter
    assert batch["full_row_fallbacks"] == 0
    assert batch["max_abs_score_err"] <= cs.F32_TOL

    ncf = report["phases"]["ncf"]["serve"]
    assert set(ncf["steady"]["engine_paths"]) == {"ncf.device_wave"}
    assert ncf["steady"]["max_wave"]["ncf.device_wave"] > 1
    assert ncf["first_answer_ms"] > 0

    # one compile cache, placed from outside: the children wrote there, and
    # said so in their start-up line
    assert any((tmp_path / "jax_cache").iterdir())
    out = cs.ChildOutput(smoke, "als_train")
    assert out.record_with("compile_cache_dir")["compile_cache_dir"] == str(
        tmp_path / "jax_cache"
    )


def test_sharded_phase_on_four_cpu_devices(smoke_factory):
    """Phase 5 runs only where the machine shows >= 4 devices; rehearse it
    on a 4-virtual-device CPU mesh."""
    smoke = smoke_factory(devices=4)
    assert cs.probe(smoke)["device_count"] == 4
    cs.load_events(smoke)
    res = cs.als_sharded(smoke)
    assert res["train"]["als_path"] == "als.train_step"
    assert res["train"]["devices"] == 4
    assert set(res["serve"]["engine_paths"]) == {"als.sharded_topk"}
    assert sorted(res["shard_bytes"]) == ["cpu:0", "cpu:1", "cpu:2", "cpu:3"]
    assert len(set(res["shard_bytes"].values())) == 1


def test_probe_refuses_another_platform(smoke_factory):
    """The children ran on the CPU while the smoke demanded something else:
    the probe fails the run before any work is done."""
    smoke = smoke_factory(devices=1)
    smoke.platform = "tpu"
    with pytest.raises(cs.SmokeFailure, match="platform 'cpu'"):
        cs.probe(smoke)


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """The driver also runs the script without the program beside it."""
    shutil.copy(cs.REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


# ---------------------------------------------------------------------------
# the reference check itself


def _answer(idx, scores):
    return [{"item": f"i{i}", "score": float(s)} for i, s in zip(idx, scores)]


class TestCheckTopK:
    ref = np.linspace(5.0, 0.0, 50).astype(np.float32)
    index = {f"i{i}": i for i in range(50)}

    def test_exact_answer_passes(self):
        top = np.arange(cs.NUM)
        same, near_tie, err = cs.check_topk(
            _answer(top, self.ref[top]), self.ref, self.index, "t"
        )
        assert same and not near_tie and err == 0.0

    def test_wrong_item_fails(self):
        idx = list(range(cs.NUM - 1)) + [30]
        with pytest.raises(cs.SmokeFailure, match="not the reference top"):
            cs.check_topk(
                _answer(idx, self.ref[idx]), self.ref, self.index, "t"
            )

    def test_score_off_the_reference_fails(self):
        top = np.arange(cs.NUM)
        with pytest.raises(cs.SmokeFailure, match="off the reference"):
            cs.check_topk(
                _answer(top, self.ref[top] + 1e-2), self.ref, self.index, "t"
            )

    def test_one_bf16_pass_fails(self):
        """The error a TPU's DEFAULT-precision f32 matmul makes (operands
        rounded to 8 mantissa bits) is what the tolerance exists to catch,
        for NCF as for ALS."""
        rng = np.random.default_rng(1)
        u = rng.normal(size=10).astype(np.float32)
        v = rng.normal(size=(50, 10)).astype(np.float32)
        ref = v @ u

        def bf16(x):
            return (x.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)

        got = bf16(v) @ bf16(u)
        top = np.argsort(-got, kind="stable")[: cs.NUM]
        with pytest.raises(cs.SmokeFailure):
            cs.check_topk(_answer(top, got[top]), ref, self.index, "t")

    def test_swap_without_a_near_tie_fails(self):
        idx = list(range(cs.NUM))
        idx[3], idx[4] = idx[4], idx[3]
        scores = np.sort(self.ref[idx])[::-1]  # descending, as a server sends
        with pytest.raises(cs.SmokeFailure):
            cs.check_topk(_answer(idx, scores), self.ref, self.index, "t")

    def test_near_tie_may_swap_but_is_reported(self):
        ref = self.ref.copy()
        ref[cs.NUM] = ref[cs.NUM - 1] - 1e-6  # k-th and (k+1)-th tie
        idx = list(range(cs.NUM - 1)) + [cs.NUM]
        same, near_tie, _ = cs.check_topk(
            _answer(idx, ref[idx]), ref, self.index, "t"
        )
        assert not same and near_tie

    def test_short_or_unsorted_answers_fail(self):
        top = np.arange(cs.NUM)
        with pytest.raises(cs.SmokeFailure, match="wanted"):
            cs.check_topk(
                _answer(top[:-1], self.ref[top[:-1]]), self.ref, self.index,
                "t",
            )
        with pytest.raises(cs.SmokeFailure, match="descending"):
            cs.check_topk(
                _answer(top[::-1], self.ref[top[::-1]]), self.ref,
                self.index, "t",
            )


def test_ncf_reference_agrees_with_the_engines_host_replica():
    """Two independent numpy spellings of the flagship's scoring."""
    from predictionio_tpu.models.ncf.engine import _host_score_topk

    rng = np.random.default_rng(0)
    n_users, n_items, pad = 7, 40, 8
    params = {
        "user_emb": rng.normal(size=(n_users, 10)).astype(np.float32),
        "item_emb": rng.normal(size=(n_items + pad, 10)).astype(np.float32),
        "item_bias": rng.normal(size=n_items + pad).astype(np.float32),
        "out_b": np.array([0.25], np.float32),
    }
    ref = cs.NCFReference(
        {
            "params": params,
            "user_vocab": np.array([f"u{i}" for i in range(n_users)]),
            "item_vocab": np.array([f"i{i}" for i in range(n_items)]),
        }
    )
    scores, top = _host_score_topk(params, 3, n_items, cs.NUM)
    mine = ref.scores("u3")
    assert mine.shape == (n_items,)
    np.testing.assert_array_equal(np.argsort(-mine, kind="stable")[: cs.NUM], top)
    np.testing.assert_allclose(mine[top], scores, rtol=1e-6)
