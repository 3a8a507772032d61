#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls (``pio
train`` -> ``pio deploy`` -> ``POST /queries.json`` -> ``pio batchpredict``),
at the full size of the model the repo was built around: the MovieLens-20M
shape (138,493 users x 26,744 items, 20 M ratings), ALS rank 10 / 20
iterations, then the NCF flagship — and checks every answer against a plain
numpy reference over the factors it reads back from the model store.

    python chip_smoke.py          # no arguments, full size, demands the chip

One process per chip: this parent never initializes a JAX backend (numpy,
pyarrow, subprocess and urllib only).  Every phase that needs the device is
ONE child at a time, started through the real entry point with
``JAX_PLATFORMS=tpu`` so a child that cannot get the chip dies instead of
computing on the CPU; a server child is stopped (``POST /stop``, then wait for
exit) before the next one starts.

A failed check raises: there is no handler that turns a failed phase into a
logged line.  Exit 0 and the last stdout line ``{"ok": true, "device":
{"platform", "kind", "count"}}`` mean every phase that applies to the machine
passed; the line before it is the full report (versions, ``dispatch_rtt_ms``,
per-phase ``{ok, wall_s, compile_s, ...}``, ``reduced``).  The phase
functions take their size and the children's environment as arguments so
``tests/test_chip_smoke.py`` can rehearse them on the CPU at a tiny size;
``__main__`` has no such switch.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Iterator

import numpy as np

REPO = Path(__file__).resolve().parent
CLI = [sys.executable, "-m", "predictionio_tpu.tools.cli"]

#: the one seed data (and, through the engine params, every init) hangs off
SEED = 3
APP = "smoke"

#: never cut — the reference template's engine.json defaults
RANK, ITERATIONS, REG = 10, 20, 0.01
#: items per answer
NUM = 10

#: the driver's limit is 1200 s, compilation included
DEADLINE_S = 1150.0

#: the one tolerance, for every served score of either model.  f32 scores
#: in different summation orders (BLAS gemv vs gemm vs the MXU's multi-pass
#: contraction) differ in the last bits of an O(5) score: ~1e-6 on the
#: chip, far under 1e-4.  A single bf16 pass anywhere (the TPU's DEFAULT
#: precision for an f32 matmul) is off by ~5e-3 and fails.
F32_TOL = 1e-4


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def require(cond: Any, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclass(frozen=True)
class Size:
    """How much data the phases run on.  Users x items x rank are the
    model's widths and are never cut; ``nnz`` is the only depth."""

    nnz: int
    num_users: int
    num_items: int
    #: users in the phase-3 batch: at or past ALSAlgorithm.DEVICE_BATCH_MIN
    #: (512), so the wave takes the fused device top-k, not the host replica
    batch_users: int


#: make_movielens_like at the sizes of MovieLens-20M
FULL = Size(
    nnz=20_000_000, num_users=138_493, num_items=26_744, batch_users=1024
)


# ---------------------------------------------------------------------------
# the run: directories, the children's environment, child bookkeeping


class Smoke:
    """One smoke run: a throwaway PIO_HOME under ``work``, the environment
    every child gets, and the list of children so none outlives the run."""

    def __init__(
        self,
        size: Size,
        work: Path,
        child_env: dict[str, str],
        deadline_s: float = DEADLINE_S,
    ):
        self.size = size
        self.work = work
        self.home = work / "pio_home"
        self.logs = work / "logs"
        shutil.rmtree(self.home, ignore_errors=True)
        self.home.mkdir(parents=True)
        self.logs.mkdir(parents=True, exist_ok=True)
        #: JAX_PLATFORMS the children run under; "tpu" forbids the fallback
        self.platform = child_env["JAX_PLATFORMS"]
        self.env = {
            **child_env,
            "PYTHONPATH": os.pathsep.join(
                [str(REPO)]
                + [p for p in child_env.get("PYTHONPATH", "").split(os.pathsep) if p]
            ),
            "PIO_HOME": str(self.home),
            # events in the parquet store, metadata in sqlite, models on the
            # local filesystem (conf/pio-env.sh.template)
            "PIO_STORAGE_SOURCES_PARQUET_TYPE": "parquet",
            "PIO_STORAGE_SOURCES_PARQUET_PATH": str(self.home / "events_parquet"),
            "PIO_STORAGE_SOURCES_PARQUET_NSHARDS": "16",
            "PIO_STORAGE_SOURCES_LOCALFS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_LOCALFS_PATH": str(self.home / "models"),
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PARQUET",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "LOCALFS",
        }
        # the smoke reads the children's JSON log lines at INFO
        self.env.pop("PIO_LOG_FORMAT", None)
        self.env.pop("PIO_LOG_LEVEL", None)
        self._t_end = time.monotonic() + deadline_s
        self._children: list[subprocess.Popen] = []
        self._storage = None
        #: filled by probe(): what the machine has
        self.n_devices = 0
        #: filled by load_events(): users that have events, as vocab keys
        self.known_users: np.ndarray = np.array([], object)

    # -- storage (host side: sqlite + pyarrow, no JAX) -----------------------

    @property
    def storage(self):
        if self._storage is None:
            from predictionio_tpu.data.storage.config import (
                StorageConfig,
                StorageRuntime,
            )

            self._storage = StorageRuntime(StorageConfig.from_env(self.env))
        return self._storage

    def persisted_model(self, instance_id: str) -> dict:
        """The one algorithm's persisted model of a trained instance, as
        the host dict ``make_persistent_model`` stored."""
        from predictionio_tpu.core.persistence import load_models

        models = load_models(self.storage.models(), instance_id)
        require(models and len(models) == 1, f"no model for {instance_id}")
        return models[0]

    # -- children ------------------------------------------------------------

    def remaining(self) -> float:
        left = self._t_end - time.monotonic()
        require(left > 0, "out of time: the smoke's deadline has passed")
        return left

    def spawn(self, name: str, argv: list[str]) -> subprocess.Popen:
        """Start one child with its output in ``logs/<name>.{out,err}``."""
        require(
            all(c.poll() is not None for c in self._children),
            f"{name}: another child is still alive (one chip process at a time)",
        )
        out = open(self.logs / f"{name}.out", "w")
        err = open(self.logs / f"{name}.err", "w")
        try:
            proc = subprocess.Popen(
                argv, cwd=REPO, env=self.env, stdout=out, stderr=err,
                stdin=subprocess.DEVNULL,
            )
        finally:
            out.close()
            err.close()
        self._children.append(proc)
        return proc

    def run_child(self, name: str, argv: list[str]) -> "ChildOutput":
        """Run one child to its end; non-zero exit is fatal."""
        t0 = time.perf_counter()
        proc = self.spawn(name, argv)
        try:
            rc = proc.wait(timeout=self.remaining())
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise
        output = ChildOutput(self, name, time.perf_counter() - t0)
        require(rc == 0, f"{name} exited {rc}:\n{output.tail()}")
        return output

    def close(self) -> None:
        """No process the smoke started outlives it; nor does its PIO_HOME."""
        for proc in self._children:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if self._storage is not None:
            self._storage.close()
            self._storage = None
        # throwaway: ~0.1 GB of events and models at full size
        shutil.rmtree(self.home, ignore_errors=True)


class ChildOutput:
    """What a finished (or running) child wrote: stdout lines and the JSON
    log records of its stderr."""

    def __init__(self, smoke: Smoke, name: str, wall_s: float = 0.0):
        self._out = smoke.logs / f"{name}.out"
        self._err = smoke.logs / f"{name}.err"
        self.name = name
        self.wall_s = wall_s

    def stdout(self) -> str:
        return self._out.read_text()

    def stderr(self) -> str:
        return self._err.read_text()

    def tail(self, n: int = 3000) -> str:
        return self.stderr()[-n:]

    def records(self) -> list[dict]:
        """The child's structured log lines (obs/logging.py JSON lines);
        anything else on stderr — warnings, tracebacks — is not a record."""
        recs = []
        for line in self.stderr().splitlines():
            if line.startswith("{"):
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue
        return recs

    def record_with(self, key: str) -> dict:
        """The last log record carrying ``key``; its absence is fatal."""
        found = [r for r in self.records() if key in r]
        require(found, f"{self.name}: no log record with {key!r}:\n{self.tail()}")
        return found[-1]


def check_startup(smoke: Smoke, out: ChildOutput, verb: str) -> dict:
    """The verb's first log line says which device it got; a chip child
    that landed anywhere else fails here even if it then ran to the end."""
    rec = next(
        (r for r in out.records() if r.get("verb") == verb and "platform" in r),
        None,
    )
    require(rec is not None, f"{out.name}: no `pio {verb}` start-up record")
    require(
        rec["platform"] == smoke.platform,
        f"{out.name}: ran on {rec['platform']!r}, not {smoke.platform!r}",
    )
    return rec


# ---------------------------------------------------------------------------
# HTTP (urllib)


def http(
    method: str, url: str, body: dict | None = None, timeout: float = 60.0
) -> tuple[int, dict, Any, float]:
    """(status, lower-cased headers, parsed JSON or text, seconds).  An
    HTTP error status is a result the caller checks, not an exception."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, hdrs, raw = resp.status, resp.headers, resp.read()
    except urllib.error.HTTPError as e:
        status, hdrs, raw = e.code, e.headers, e.read()
    dt = time.perf_counter() - t0
    headers = {k.lower(): v for k, v in hdrs.items()}
    text = raw.decode("utf-8", "replace")
    try:
        return status, headers, json.loads(text), dt
    except ValueError:
        return status, headers, text, dt


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@contextlib.contextmanager
def deployed(
    smoke: Smoke, name: str, engine_json: Path, instance_id: str
) -> Iterator[str]:
    """``pio deploy`` as a child; yields its base URL once it answers.
    Leaving the block stops it the way an operator would (``POST /stop``)
    and waits for the process to exit, so the chip is free again."""
    port = free_port()
    proc = smoke.spawn(
        name,
        CLI + [
            "deploy", "--engine-json", str(engine_json),
            "--engine-instance-id", instance_id,
            "--ip", "127.0.0.1", "--port", str(port),
        ],
    )
    out = ChildOutput(smoke, name)
    base = f"http://127.0.0.1:{port}"
    try:
        while True:
            require(
                proc.poll() is None,
                f"{name} exited {proc.returncode} before serving:\n{out.tail()}",
            )
            smoke.remaining()
            try:
                status, _, _, _ = http("GET", base + "/status.json", timeout=5)
            except (urllib.error.URLError, OSError):
                time.sleep(0.25)
                continue
            require(status == 200, f"{name}: /status.json answered {status}")
            break
        check_startup(smoke, out, "deploy")
        yield base
        status, _, _, _ = http("POST", base + "/stop")
        require(status == 200, f"{name}: POST /stop answered {status}")
        rc = proc.wait(timeout=min(60.0, smoke.remaining()))
        require(rc == 0, f"{name} exited {rc} after /stop:\n{out.tail()}")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# ---------------------------------------------------------------------------
# the numpy reference


def check_topk(
    item_scores: list[dict],
    ref: np.ndarray,
    item_index: dict[str, int],
    where: str,
) -> tuple[bool, bool, float]:
    """One answer against the reference score vector ``ref`` ([n_items],
    plain numpy over the persisted model).  The answer must be ``NUM``
    distinct known items, finite, sorted by descending score; each score
    must be the reference score of ITS item within ``F32_TOL``; and the ids
    must EQUAL ``argsort(-ref)[:k]`` — unless the reference's own top
    ``k + 1`` scores hold a gap under twice the tolerance (a near-tie no
    f32 program orders reliably), where the items must still all score
    within the tolerance of the k-th.  Returns (ids exactly equal, the
    reference holds such a near-tie, max |score - ref|)."""
    require(
        len(item_scores) == NUM,
        f"{where}: {len(item_scores)} items, wanted {NUM}",
    )
    idx = np.array([item_index[e["item"]] for e in item_scores])
    got = np.array([e["score"] for e in item_scores], np.float64)
    require(np.isfinite(got).all(), f"{where}: non-finite scores {got}")
    require(len(set(idx.tolist())) == NUM, f"{where}: repeated items")
    require((np.diff(got) <= 0).all(), f"{where}: scores not descending")
    err = np.abs(got - ref[idx])
    require(
        (err <= F32_TOL).all(),
        f"{where}: scores off the reference by {err.max():.3g} "
        f"(tolerance {F32_TOL:g})",
    )
    order = np.argsort(-ref, kind="stable")[: NUM + 1]
    same = bool(np.array_equal(idx, order[:NUM]))
    near_tie = bool((-np.diff(ref[order]) <= 2 * F32_TOL).any())
    require(
        same
        or (near_tie and (ref[idx] >= ref[order[NUM - 1]] - F32_TOL).all()),
        f"{where}: items {idx.tolist()} are not the reference top-{NUM} "
        f"{order[:NUM].tolist()}",
    )
    return same, near_tie, float(err.max())


class ALSReference:
    """``argsort(V @ u)`` over the factors read back from the model store."""

    def __init__(self, model: dict):
        self.U = np.asarray(model["user_factors"], np.float32)
        self.V = np.asarray(model["item_factors"], np.float32)
        self.user_index = {k: i for i, k in enumerate(model["user_vocab"])}
        self.item_index = {k: i for i, k in enumerate(model["item_vocab"])}
        require(
            np.isfinite(self.U).all() and np.isfinite(self.V).all(),
            "persisted ALS factors are not finite",
        )

    def scores(self, user: str) -> np.ndarray:
        return self.V @ self.U[self.user_index[user]]


class NCFReference:
    """The flagship's scoring in plain numpy — pure GMF with an item bias:
    ``item_emb @ user_emb[u] + out_b + item_bias`` — independent of both
    the device program and the engine's own host replica."""

    def __init__(self, model: dict):
        p = model["params"]
        require("out_w" not in p, "NCF reference covers the pure-GMF flagship")
        self.n_items = len(model["item_vocab"])
        self.user_emb = np.asarray(p["user_emb"], np.float32)
        self.item_emb = np.asarray(p["item_emb"], np.float32)[: self.n_items]
        self.offset = np.float32(np.asarray(p["out_b"])[0]) + np.asarray(
            p["item_bias"], np.float32
        )[: self.n_items]
        self.user_index = {k: i for i, k in enumerate(model["user_vocab"])}
        self.item_index = {k: i for i, k in enumerate(model["item_vocab"])}
        require(
            np.isfinite(self.user_emb).all() and np.isfinite(self.item_emb).all(),
            "persisted NCF tables are not finite",
        )

    def scores(self, user: str) -> np.ndarray:
        return self.item_emb @ self.user_emb[self.user_index[user]] + self.offset


# ---------------------------------------------------------------------------
# engine variants (engine.json) — the reference template's parameters


def write_variant(smoke: Smoke, name: str, factory: str, algo: dict, **top) -> Path:
    path = smoke.work / f"{name}.engine.json"
    path.write_text(
        json.dumps(
            {
                "id": name,
                "engineFactory": factory,
                "datasource": {"params": {"appName": APP}},
                "algorithms": [algo],
                **top,
            },
            indent=2,
        )
    )
    return path


def als_algo(**extra) -> dict:
    return {
        "name": "als",
        "params": {
            "rank": RANK, "numIterations": ITERATIONS, "lambda": REG,
            "seed": SEED, **extra,
        },
    }


#: the shipped NCF flagship: pure-GMF tower at ALS's
#: width, implicit-ALS pretrain, one epoch of low-lr full-softmax fine-tune
NCF_ALGO = {
    "name": "ncf",
    "params": {
        "embedDim": 10, "mlpLayers": [], "loss": "full_softmax",
        "pretrain": "als", "learningRate": 1e-4, "batchSize": 8192,
        "numEpochs": 1, "seed": SEED,
    },
}


# ---------------------------------------------------------------------------
# phases


_PROBE = r"""
import importlib.metadata as md
import json
import time
import jax
import jax.numpy as jnp
import numpy as np
from predictionio_tpu.obs.device import device_peaks
from predictionio_tpu.utils.runtime import describe_devices

def version(pkg):
    try:
        return md.version(pkg)
    except md.PackageNotFoundError:
        return None

def dispatch_rtt_ms(n):
    x = jnp.zeros((8,), jnp.float32)
    f = jax.jit(lambda v: v + 1.0)
    np.asarray(f(x))  # compile
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(f(x))
        lat.append(time.perf_counter() - t0)
    lat.sort()
    return lat[len(lat) // 2] * 1000

dev = describe_devices()
stats = jax.devices()[0].memory_stats() or {}
print(json.dumps({
    **dev,
    "bytes_limit": stats.get("bytes_limit"),
    "dispatch_rtt_ms": dispatch_rtt_ms(30),
    "peak_row": device_peaks().source,
    "versions": {p: version(p) for p in ("jax", "jaxlib", "libtpu")},
}))
"""


def probe(smoke: Smoke) -> dict:
    """Phase 0 (child): what JAX reports, how much device memory there is,
    and ``dispatch_rtt_ms`` — p50 of 30 trivial jit + tiny d2h round trips,
    the floor under any synchronous device query."""
    out = smoke.run_child("probe", [sys.executable, "-c", _PROBE])
    res = json.loads(out.stdout().strip().splitlines()[-1])
    require(
        res["platform"] == smoke.platform,
        f"probe found platform {res['platform']!r}, not {smoke.platform!r}",
    )
    if smoke.platform == "tpu":
        # the chip this repo's records are for must resolve to its own
        # row of the peak table, not to a guess
        require(
            res["peak_row"] == "tpu v5 lite",
            f"device kind {res['device_kind']!r} resolved to peak row "
            f"{res['peak_row']!r}",
        )
    smoke.n_devices = res["device_count"]
    return {"wall_s": round(out.wall_s, 2), **res}


#: rank of the planted taste structure
RANK_PLANTED = 8
#: of every BROWSE_K popularity-drawn candidates the user picks the
#: preferred, for BROWSE_FRAC of the interactions
BROWSE_K, BROWSE_FRAC = 8, 0.7


def make_movielens_like(nnz: int, num_users: int, num_items: int, seed: int):
    """Deterministic ML-shaped ratings (COO): Zipf item popularity, lognormal
    user activity, item quality correlated with popularity, planted rank-8
    personal preference structure + noise.

    Exposure is preference-correlated the way real watch data is: for
    ``BROWSE_FRAC`` of interactions the user "browses" ``BROWSE_K``
    popularity-drawn candidates and watches the one they prefer most
    (best-of-K choice); the rest are pure popularity impressions.  Marginal
    item popularity stays Zipf-anchored (candidates are always drawn from
    the Zipf), so popularity is still a strong baseline — but which popular
    item a user watches, and rates highly, carries their planted taste.
    """
    rng = np.random.default_rng(seed)
    item_p = (np.arange(num_items) + 10.0) ** -0.8
    item_p /= item_p.sum()
    item_cdf = np.cumsum(item_p)
    user_w = rng.lognormal(0.0, 1.0, num_users)
    user_p = user_w / user_w.sum()
    user_cdf = np.cumsum(user_p)
    # inverse-CDF sampling: ~10x faster than rng.choice(p=...) at this scale
    user_idx = np.searchsorted(user_cdf, rng.random(nnz)).astype(np.int64)
    user_idx = np.minimum(user_idx, num_users - 1)
    uf = rng.standard_normal((num_users, RANK_PLANTED)).astype(np.float32)
    vf = rng.standard_normal((num_items, RANK_PLANTED)).astype(np.float32)

    item_idx = np.empty(nnz, np.int64)
    browse = rng.random(nnz) < BROWSE_FRAC
    n_plain = int((~browse).sum())
    plain = np.searchsorted(item_cdf, rng.random(n_plain)).astype(np.int64)
    item_idx[~browse] = np.minimum(plain, num_items - 1)
    b_users = user_idx[browse]
    browse_pos = np.flatnonzero(browse)
    # chunked best-of-K: candidates by popularity, winner by planted taste
    for c0 in range(0, len(b_users), 2_000_000):
        bu = b_users[c0 : c0 + 2_000_000]
        cand = np.searchsorted(
            item_cdf, rng.random((len(bu), BROWSE_K))
        ).astype(np.int64)
        cand = np.minimum(cand, num_items - 1)
        pref = np.einsum("nk,njk->nj", uf[bu], vf[cand])
        pick = cand[np.arange(len(bu)), pref.argmax(1)]
        item_idx[browse_pos[c0 : c0 + 2_000_000]] = pick

    zpop = -np.log(np.arange(num_items) + 10.0)
    zpop = (zpop - zpop.mean()) / zpop.std()
    item_bias = (
        0.3 * zpop + 0.2 * rng.standard_normal(num_items)
    ).astype(np.float32)
    # base 1.55: best-of-K selection raises the mean planted preference of
    # *watched* items by ~+1.3 stars, so the observed rating distribution
    # recenters near the ML-20M shape (mean ~3.4, ~40% of ratings >= 4)
    raw = (
        1.55
        + item_bias[item_idx]
        + 1.8
        * np.einsum("nk,nk->n", uf[user_idx], vf[item_idx])
        / np.sqrt(RANK_PLANTED)
        + 0.4 * rng.standard_normal(nnz).astype(np.float32)
    )
    rating = np.clip(np.round(raw * 2.0) / 2.0, 0.5, 5.0).astype(np.float32)
    return user_idx, item_idx, rating


def load_events(smoke: Smoke) -> dict:
    """Host phase: the MovieLens-shaped ratings from ``SEED``, bulk-written
    to the parquet event store the way an import does (EventFrame ->
    ParquetPEvents.write), under a new app."""
    from predictionio_tpu.data.storage.base import EventFrame
    from predictionio_tpu.tools import commands

    size = smoke.size
    t0 = time.perf_counter()
    user_idx, item_idx, rating = make_movielens_like(
        size.nnz, size.num_users, size.num_items, seed=SEED
    )
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    app = commands.app_new(smoke.storage, APP).app
    user_names = np.array([f"u{x}" for x in range(size.num_users)], object)
    item_names = np.array([f"i{x}" for x in range(size.num_items)], object)
    # ratings take ~10 distinct values: N property documents are a handful
    # of interned strings indexed per event (the EventFrame lazy-row form)
    rat_vals, rat_code = np.unique(rating, return_inverse=True)
    rat_docs = np.array(
        [json.dumps({"rating": float(v)}) for v in rat_vals], object
    )

    def const(value: str) -> np.ndarray:
        col = np.empty(size.nnz, object)
        col[:] = value
        return col

    frame = EventFrame(
        event=const("rate"),
        entity_type=const("user"),
        entity_id=user_names[user_idx],
        target_entity_type=const("item"),
        target_entity_id=item_names[item_idx],
        event_time_ms=np.full(size.nnz, 1_700_000_000_000, np.int64)
        + np.arange(size.nnz, dtype=np.int64) % 86_400_000,
        properties=rat_docs[rat_code],
    )
    smoke.storage.p_events().write(frame, app_id=app.id)
    write_s = time.perf_counter() - t0
    smoke.known_users = user_names[np.unique(user_idx)]
    return {
        "gen_s": round(gen_s, 2),
        "write_s": round(write_s, 2),
        "events": size.nnz,
        "users_with_events": len(smoke.known_users),
    }


def pick_users(smoke: Smoke, n: int, salt: int) -> list[str]:
    rng = np.random.default_rng(SEED * 1000 + salt)
    replace = n > len(smoke.known_users)
    return list(rng.choice(smoke.known_users, n, replace=replace))


def train(smoke: Smoke, name: str, variant: Path) -> tuple[str, ChildOutput, dict]:
    """``pio train`` as a child -> (instance id, its output, report)."""
    out = smoke.run_child(
        name, CLI + ["train", "--engine-json", str(variant)]
    )
    started = check_startup(smoke, out, "train")
    marker = "Training completed. Engine instance: "
    ids = [
        line[len(marker):].strip()
        for line in out.stdout().splitlines()
        if line.startswith(marker)
    ]
    require(ids, f"{name}: no engine instance id on stdout:\n{out.tail()}")
    stages = out.record_with("stages")["stages"]
    report = out.record_with("device_report")["device_report"]
    return ids[-1], out, {
        "wall_s": round(out.wall_s, 2),
        "compile_s": stages["jax_compile"],
        "compile_cache": report["compile_cache"],
        "stage_s": {k: v for k, v in stages.items() if k != "jax_compile"},
        "peak_hbm_bytes": report["peak_bytes_in_use"],
        "devices": started["device_count"],
    }


def als_train(smoke: Smoke) -> tuple[str, Path, dict]:
    """Phase 1: ``pio train --engine recommendation`` at the reference
    engine.json defaults.  The child's output must name the ALS path that
    ran; on one chip that is the Pallas kernel written for it."""
    variant = write_variant(smoke, "smoke-als", "recommendation", als_algo())
    instance_id, out, res = train(smoke, "als_train", variant)
    path = out.record_with("als_path")
    res["als_path"] = path["als_path"]
    res["als_mode"] = path.get("mode")
    res["als_train_s"] = path["wall_s"]
    # the fused -> chunked -> per-iteration ladder is fault recovery; on an
    # unshared chip it should not fire — reported, not fatal
    res["oom_ladder_fired"] = "ran out of HBM" in out.stderr()
    if smoke.platform == "tpu" and res["devices"] == 1:
        require(
            res["als_path"] == "als.pallas_step"
            and res["als_mode"] in ("fused", "chunked"),
            f"one-chip ALS train took {res['als_path']} "
            f"(mode {res['als_mode']}), not the Pallas path",
        )
    return instance_id, variant, res


def explain(base: str, headers: dict) -> dict:
    """The decision-provenance record of one answered request."""
    rid = headers.get("x-pio-request-id")
    require(rid, "answer carries no X-Pio-Request-Id")
    status, _, body, _ = http("GET", f"{base}/explain.json?request_id={rid}")
    require(status == 200, f"/explain.json?request_id={rid} answered {status}")
    return body["record"]


def query_burst(base: str, users: list[str], timeout: float) -> list[tuple]:
    """All of ``users`` at once, one request each, released together."""
    barrier = threading.Barrier(len(users))

    def one(user: str):
        barrier.wait()
        return http(
            "POST", base + "/queries.json", {"user": user, "num": NUM},
            timeout=timeout,
        )

    with ThreadPoolExecutor(len(users)) as pool:
        return list(pool.map(one, users))


def check_against(ref, users, item_lists, where) -> dict:
    """Each user's ``itemScores`` against the reference (check_topk);
    returns how many id lists were exactly ``argsort``, for how many users
    the reference itself holds a near-tie (the only place an id list may
    differ), and how close the scores came."""
    exact = near_ties = 0
    max_err = 0.0
    for user, item_scores in zip(users, item_lists):
        same, near_tie, err = check_topk(
            item_scores, ref.scores(user), ref.item_index,
            f"{where} user {user}",
        )
        exact += same
        near_ties += near_tie
        max_err = max(max_err, err)
    return {
        "answers": len(users), "ids_exact": exact,
        "ref_near_ties": near_ties, "max_abs_score_err": max_err,
    }


def check_answers(base, users, answers, ref, where) -> dict:
    """Every served answer is 200 and matches the reference; adds which
    engine paths answered and the largest wave each formed."""
    for user, (status, _, body, _) in zip(users, answers):
        require(status == 200, f"{where}: user {user} answered {status}: {body}")
    res = check_against(
        ref, users, [a[2]["itemScores"] for a in answers], where
    )
    paths: dict[str, int] = {}
    max_wave: dict[str, int] = {}
    for user, (_, headers, _, _) in zip(users, answers):
        rec = explain(base, headers)
        path = rec.get("engine_path")
        require(path, f"{where}: user {user}'s record names no engine_path")
        paths[path] = paths.get(path, 0) + 1
        wave = (rec.get("wave") or {}).get("size", 1)
        max_wave[path] = max(max_wave.get(path, 0), wave)
    return {**res, "engine_paths": paths, "max_wave": max_wave}


def ms(latencies: list[float]) -> dict:
    """Host-clock request latencies of a handful of requests: a reading of
    this run, not a benchmark."""
    lat = sorted(latencies)
    return {
        "n": len(lat),
        "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
        "max_ms": round(lat[-1] * 1e3, 3),
    }


def server_metrics(base: str) -> dict:
    """The server's registry (``/metrics.json``) with what every serving
    phase reports pulled out: compile seconds and persistent-cache events."""
    status, _, families, _ = http("GET", base + "/metrics.json")
    require(status == 200, f"/metrics.json answered {status}")

    def series(name: str) -> list[dict]:
        return families.get(name, {}).get("series", [])

    return {
        "series": series,
        "compile_s": round(
            sum(s["sum"] for s in series("pio_jax_compile_seconds")), 3
        ),
        "compile_cache": {
            s["labels"]["event"]: int(s["value"])
            for s in series("pio_jax_compile_cache_events_total")
        },
    }


def als_serve(smoke: Smoke, instance_id: str, variant: Path, ref: ALSReference) -> dict:
    """Phase 2: ``pio deploy`` (aio front end + MicroBatcher), 20
    sequential then 32 concurrent ``POST /queries.json`` for known users.
    Reports which engine path answered — at this size ALS serves waves
    under 512 from its host replica, and the smoke says so."""
    t0 = time.perf_counter()
    with deployed(smoke, "als_serve", variant, instance_id) as base:
        ready_s = time.perf_counter() - t0
        seq_users = pick_users(smoke, 20, salt=1)
        seq = [
            http("POST", base + "/queries.json", {"user": u, "num": NUM})
            for u in seq_users
        ]
        seq_res = check_answers(
            base, seq_users, seq, ref, "als_serve sequential"
        )
        burst_users = pick_users(smoke, 32, salt=2)
        burst = query_burst(base, burst_users, timeout=60.0)
        burst_res = check_answers(
            base, burst_users, burst, ref, "als_serve concurrent"
        )
        compiled = server_metrics(base)
    paths = sorted(set(seq_res["engine_paths"]) | set(burst_res["engine_paths"]))
    return {
        "wall_s": round(time.perf_counter() - t0, 2),
        "ready_s": round(ready_s, 2),
        "compile_s": compiled["compile_s"],
        "compile_cache": compiled["compile_cache"],
        "engine_paths": paths,
        "sequential": {**seq_res, "latency": ms([a[3] for a in seq])},
        "concurrent": {**burst_res, "latency": ms([a[3] for a in burst])},
    }


def als_batch(smoke: Smoke, instance_id: str, variant: Path, ref: ALSReference) -> dict:
    """Phase 3: ``pio batchpredict`` with a wave big enough for the device
    path, so the top-k runs in ``fused_topk_batch`` — compiled by Mosaic on
    the chip, never the interpreter — with no full-score-row fallback."""
    users = pick_users(smoke, smoke.size.batch_users, salt=3)
    queries = smoke.work / "batch_queries.jsonl"
    answers = smoke.work / "batch_answers.jsonl"
    queries.write_text(
        "".join(json.dumps({"user": u, "num": NUM}) + "\n" for u in users)
    )
    out = smoke.run_child(
        "als_batch",
        CLI + [
            "batchpredict", "--engine-json", str(variant),
            "--engine-instance-id", instance_id,
            "--input", str(queries), "--output", str(answers),
        ],
    )
    check_startup(smoke, out, "batchpredict")
    lines = [json.loads(line) for line in answers.read_text().splitlines()]
    require(len(lines) == len(users), f"{len(lines)} answers for {len(users)}")
    require(
        [line["query"]["user"] for line in lines] == users, "answers out of order"
    )
    res = check_against(
        ref, users, [line["prediction"]["itemScores"] for line in lines],
        "als_batch",
    )
    report = out.record_with("device_report")["device_report"]
    kernel = report["topk_kernels"].get("als.fused_topk")
    require(kernel, f"no fused top-k launch recorded: {report['topk_kernels']}")
    require(kernel["batch"] == len(users), f"fused top-k launch {kernel}")
    require(
        report["topk_full_row_fallbacks"] == 0,
        f"{report['topk_full_row_fallbacks']} full-score-row fallbacks",
    )
    if smoke.platform == "tpu":
        require(kernel["interpret"] == 0, "fused top-k ran in the interpreter")
    return {
        "wall_s": round(out.wall_s, 2),
        "compile_s": report["compile_s"],
        "compile_cache": report["compile_cache"],
        **res,
        "kernel": kernel,
        "full_row_fallbacks": report["topk_full_row_fallbacks"],
        "peak_hbm_bytes": report["peak_bytes_in_use"],
    }


def ncf(smoke: Smoke) -> dict:
    """Phase 4: the NCF flagship trained by ``pio train --engine ncf`` and
    served on the chip: concurrent queries form waves, waves take
    ``ncf.device_wave``.  The first wave of a padded shape compiles on the
    request path, so the first answer is timed apart from the steady ones
    and given no deadline but the smoke's own."""
    variant = write_variant(smoke, "smoke-ncf", "ncf", NCF_ALGO)
    instance_id, _, train_res = train(smoke, "ncf_train", variant)
    ref = NCFReference(smoke.persisted_model(instance_id))
    t0 = time.perf_counter()
    with deployed(smoke, "ncf_serve", variant, instance_id) as base:
        ready_s = time.perf_counter() - t0
        first_user = pick_users(smoke, 1, salt=4)
        first = [
            http(
                "POST", base + "/queries.json",
                {"user": first_user[0], "num": NUM}, timeout=smoke.remaining(),
            )
        ]
        first_res = check_answers(
            base, first_user, first, ref, "ncf_serve first"
        )
        steady: list[tuple] = []
        steady_users: list[str] = []
        for round_no in range(3):
            users = pick_users(smoke, 32, salt=5 + round_no)
            steady_users += users
            steady += query_burst(base, users, timeout=120.0)
        steady_res = check_answers(
            base, steady_users, steady, ref, "ncf_serve concurrent"
        )
        compiled = server_metrics(base)
    require(
        steady_res["max_wave"].get("ncf.device_wave", 0) > 1,
        f"no wave of more than one query took ncf.device_wave: {steady_res}",
    )
    return {
        "wall_s": round(train_res["wall_s"] + time.perf_counter() - t0, 2),
        "compile_s": round(train_res["compile_s"] + compiled["compile_s"], 3),
        "train": train_res,
        "serve": {
            "ready_s": round(ready_s, 2),
            "compile_s": compiled["compile_s"],
            "compile_cache": compiled["compile_cache"],
            "first_answer_ms": round(first[0][3] * 1e3, 1),
            "first": first_res,
            "steady": {**steady_res, "latency": ms([a[3] for a in steady])},
        },
    }


def als_sharded(smoke: Smoke) -> dict:
    """Phase 5, only where the machine shows >= 4 devices: ALS trained on
    a 4-device data mesh with a serving ShardPlan, deployed, and a
    concurrent burst through ``als.sharded_topk``.  ``pio_shard_bytes``
    must show every device holding its share — not everything on device 0."""
    variant = write_variant(
        smoke, "smoke-als-sharded", "recommendation",
        als_algo(shardServing=True), mesh={"axes": {"data": 4}},
    )
    instance_id, out, train_res = train(smoke, "als_sharded_train", variant)
    train_res["als_path"] = out.record_with("als_path")["als_path"]
    ref = ALSReference(smoke.persisted_model(instance_id))
    t0 = time.perf_counter()
    with deployed(smoke, "als_sharded_serve", variant, instance_id) as base:
        users = pick_users(smoke, 32, salt=9)
        # the first wave compiles the sharded kernel on the request path
        burst = query_burst(base, users, timeout=smoke.remaining())
        res = check_answers(base, users, burst, ref, "als_sharded_serve")
        compiled = server_metrics(base)
    require(
        set(res["engine_paths"]) == {"als.sharded_topk"},
        f"sharded deploy answered from {res['engine_paths']}",
    )
    shard_bytes = {
        s["labels"]["device"]: s["value"]
        for s in compiled["series"]("pio_shard_bytes")
        if s["labels"]["fn"] == "als.serving_factors"
    }
    require(
        len(shard_bytes) == smoke.n_devices,
        f"factors live on {sorted(shard_bytes)}, machine has {smoke.n_devices}",
    )
    share = sum(shard_bytes.values()) / len(shard_bytes)
    require(
        max(shard_bytes.values()) <= 1.1 * share
        and min(shard_bytes.values()) >= 0.9 * share,
        f"uneven factor placement: {shard_bytes}",
    )
    return {
        "wall_s": round(train_res["wall_s"] + time.perf_counter() - t0, 2),
        "compile_s": round(train_res["compile_s"] + compiled["compile_s"], 3),
        "train": train_res,
        "serve": {**res, "compile_cache": compiled["compile_cache"]},
        "shard_bytes": shard_bytes,
    }


# ---------------------------------------------------------------------------


def run(smoke: Smoke) -> dict:
    """Every phase that applies to the machine, in order; the first failed
    check raises.  Returns the report (the line before the verdict in a full
    run's stdout, and ``report.json`` in the work directory)."""
    t0 = time.perf_counter()
    phases: dict[str, dict] = {}

    def phase(name: str, result: dict) -> dict:
        phases[name] = {"ok": True, **result}
        print(f"# {name}: {json.dumps(phases[name])}", file=sys.stderr, flush=True)
        return result

    found = phase("probe", probe(smoke))
    phase("load_events", load_events(smoke))
    instance_id, variant, res = als_train(smoke)
    phase("als_train", res)
    ref = ALSReference(smoke.persisted_model(instance_id))
    phase("als_serve", als_serve(smoke, instance_id, variant, ref))
    phase("als_batch", als_batch(smoke, instance_id, variant, ref))
    phase("ncf", ncf(smoke))
    if smoke.n_devices >= 4:
        phase("als_sharded", als_sharded(smoke))
    size = smoke.size
    return {
        "ok": True,
        # the device as JAX reports it, from the one process that asked
        "platform": found["platform"],
        "device_kind": found["device_kind"],
        "n_devices": found["device_count"],
        "versions": found["versions"],
        "dispatch_rtt_ms": found["dispatch_rtt_ms"],
        "size": {
            "users": size.num_users, "items": size.num_items,
            "ratings": size.nnz, "rank": RANK, "iterations": ITERATIONS,
            "batch_users": size.batch_users,
        },
        # every cut against the MovieLens-20M shape (a full run lists none)
        "reduced": [
            f"{field}: {getattr(FULL, field)} -> {getattr(size, field)}"
            for field in ("nnz", "num_users", "num_items", "batch_users")
            if getattr(size, field) != getattr(FULL, field)
        ],
        "wall_s": round(time.perf_counter() - t0, 1),
        "phases": phases,
    }


def verdict(report: dict) -> dict:
    """The last stdout line, to the driver's contract: exactly ``ok`` and
    ``device`` = ``{platform, kind, count}``.  Everything else the run
    learned is in the report."""
    return {
        "ok": report["ok"],
        "device": {
            "platform": report["platform"],
            "kind": report["device_kind"],
            "count": report["n_devices"],
        },
    }


def main() -> int:
    work = REPO / "chiprun_out" / "chip_smoke"
    work.mkdir(parents=True, exist_ok=True)
    # JAX_PLATFORMS=tpu in every child: without it JAX answers a missing
    # chip with a warning and a CpuDevice, and the server would answer 200
    smoke = Smoke(FULL, work, {**os.environ, "JAX_PLATFORMS": "tpu"})
    try:
        report = run(smoke)
    finally:
        smoke.close()
    (work / "report.json").write_text(json.dumps(report, indent=2))
    print(json.dumps(report))
    print(json.dumps(verdict(report)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
