"""One benchmark run's world: a throwaway PIO_HOME, the environment of the
children that hold the chip, and child bookkeeping.

One process per chip: the harness process never initializes a JAX backend.
Everything that needs the device is ONE child at a time under the platform
the harness was given (``tpu`` from the command line, so a child that cannot
get the chip dies instead of computing on the CPU); each is ended and waited
for before the next starts, and ``close`` leaves none behind.  (Child handling
after ``chip_smoke.py``.)
"""

from __future__ import annotations

import contextlib
import copy
import json
import os
import shutil
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path
from typing import Any, Iterator

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
CLI = [sys.executable, "-m", "predictionio_tpu.tools.cli"]


class BenchFailure(RuntimeError):
    """The run cannot produce a result (not: the result is incorrect)."""


def require(cond: Any, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


class Run:
    """Directories, child environment and children of one run."""

    def __init__(self, work: Path, platform: str, extra_env: dict | None = None):
        self.work = Path(work)
        self.home = self.work / "pio_home"
        self.logs = self.work / "logs"
        shutil.rmtree(self.work, ignore_errors=True)
        self.home.mkdir(parents=True)
        (self.logs / "tpu").mkdir(parents=True)
        self.platform = platform
        env = {**os.environ, **(extra_env or {})}
        self.env = {
            **env,
            "JAX_PLATFORMS": platform,
            "PYTHONPATH": os.pathsep.join(
                [str(REPO)]
                + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
            ),
            "PIO_HOME": str(self.home),
            # libtpu logs to /tmp/tpu_logs unless told where
            "TPU_LOG_DIR": env.get("TPU_LOG_DIR") or str(self.logs / "tpu"),
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
        # children log JSON lines at INFO; the driver's variable is not ours
        for key in ("PIO_LOG_FORMAT", "PIO_LOG_LEVEL", "BENCH_RUN"):
            self.env.pop(key, None)
        self._children: list[subprocess.Popen] = []
        self._storage = None

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
        """The one algorithm's persisted model of a trained instance."""
        from predictionio_tpu.core.persistence import load_models

        models = load_models(self.storage.models(), instance_id)
        require(models and len(models) == 1, f"no model for {instance_id}")
        return models[0]

    def write_engine_json(self, name: str, config: dict, app_name: str) -> Path:
        """The configuration's engine.json body, pointed at ``app_name``."""
        body = copy.deepcopy(config["engine_json"])
        body["id"] = name
        body["engineFactory"] = config["engine_factory"]
        body.setdefault("datasource", {}).setdefault("params", {})[
            "appName"
        ] = app_name
        path = self.work / f"{name}.engine.json"
        path.write_text(json.dumps(body, indent=2))
        return path

    # -- children ------------------------------------------------------------

    def spawn(self, name: str, argv: list[str], **popen) -> subprocess.Popen:
        """Start one child with its stderr in ``logs/<name>.err`` (stdout
        too unless the caller pipes it)."""
        require(
            all(c.poll() is not None for c in self._children),
            f"{name}: another child is still alive (one chip process at a time)",
        )
        err = open(self.logs / f"{name}.err", "w")
        out = None
        if "stdout" not in popen:
            out = open(self.logs / f"{name}.out", "w")
            popen["stdout"] = out
        popen.setdefault("stdin", subprocess.DEVNULL)
        try:
            proc = subprocess.Popen(
                argv, cwd=REPO, env=self.env, stderr=err, **popen
            )
        finally:
            err.close()
            if out is not None:
                out.close()
        self._children.append(proc)
        return proc

    def run_child(self, name: str, argv: list[str], timeout: float) -> "ChildOutput":
        """Run one child to its end; non-zero exit is fatal."""
        t0 = time.perf_counter()
        proc = self.spawn(name, argv)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise BenchFailure(f"{name} did not end within {timeout:.0f} s")
        output = ChildOutput(self, name, time.perf_counter() - t0)
        require(rc == 0, f"{name} exited {rc}:\n{output.tail()}")
        return output

    def flush_to_disk(self) -> None:
        """What set-up wrote under the run's PIO_HOME (events, models,
        metadata) reaches the disk now: every file fsync'ed, and nothing else
        on the machine — ``os.sync`` would wait for another checkout's dirty
        pages too."""
        for root, _, files in os.walk(self.home):
            for name in files + ["."]:
                try:
                    fd = os.open(os.path.join(root, name), os.O_RDONLY)
                except FileNotFoundError:  # a live server's journal, gone again
                    continue
                try:
                    os.fsync(fd)
                finally:
                    os.close(fd)

    def close(self) -> None:
        """No process the run started outlives it; nor does its work dir."""
        for proc in self._children:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        if self._storage is not None:
            self._storage.close()
            self._storage = None
        shutil.rmtree(self.work, ignore_errors=True)


class ChildOutput:
    """What a child wrote: stdout lines and the JSON log records of stderr."""

    def __init__(self, run: Run, name: str, wall_s: float = 0.0):
        self._out = run.logs / f"{name}.out"
        self._err = run.logs / f"{name}.err"
        self.name = name
        self.wall_s = wall_s

    def stdout(self) -> str:
        return self._out.read_text()

    def stderr(self) -> str:
        return self._err.read_text()

    def tail(self, n: int = 3000) -> str:
        return self.stderr()[-n:]

    def records(self) -> list[dict]:
        """The child's structured log lines (obs/logging.py JSON lines)."""
        recs = []
        for line in self.stderr().splitlines():
            if line.startswith("{"):
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue
        return recs

    def record_with(self, key: str) -> dict:
        found = [r for r in self.records() if key in r]
        require(found, f"{self.name}: no log record with {key!r}:\n{self.tail()}")
        return found[-1]


def check_startup(run: Run, out: ChildOutput, verb: str) -> dict:
    """The verb's first log line says which device it got; a child that
    landed anywhere but the platform it was given fails here."""
    rec = next(
        (r for r in out.records() if r.get("verb") == verb and "platform" in r),
        None,
    )
    require(rec is not None, f"{out.name}: no `pio {verb}` start-up record")
    require(
        rec["platform"] == run.platform,
        f"{out.name}: ran on {rec['platform']!r}, not {run.platform!r}",
    )
    return rec


# ---------------------------------------------------------------------------
# HTTP for control requests (urllib; the load generator has its own client)


def http(
    method: str, url: str, body: dict | None = None, timeout: float = 60.0
) -> tuple[int, Any]:
    """(status, parsed JSON or text).  An HTTP error status is a result."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    text = raw.decode("utf-8", "replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


#: the served process's key: arms POST /debug/profile (obs/http.py refuses a
#: capture on a server that has no key at all)
ACCESS_KEY = "benchmark"


@contextlib.contextmanager
def deployed(
    run: Run, name: str, engine_json: Path, instance_id: str,
    ready_timeout: float = 300.0,
) -> Iterator[tuple[str, ChildOutput]]:
    """``pio deploy`` as a child; yields its base URL once it answers.
    Leaving the block stops it the way an operator would (``POST /stop``)
    and waits for the process to exit, so the chip is free again."""
    port = free_port()
    proc = run.spawn(
        name,
        CLI + [
            "deploy", "--engine-json", str(engine_json),
            "--engine-instance-id", instance_id,
            "--ip", "127.0.0.1", "--port", str(port),
            "--accesskey", ACCESS_KEY,
        ],
    )
    out = ChildOutput(run, name)
    base = f"http://127.0.0.1:{port}"
    t_end = time.monotonic() + ready_timeout
    try:
        while True:
            require(
                proc.poll() is None,
                f"{name} exited {proc.returncode} before serving:\n{out.tail()}",
            )
            require(time.monotonic() < t_end, f"{name} not serving in time")
            try:
                status, _ = http("GET", base + "/status.json", timeout=5)
            except (urllib.error.URLError, OSError):
                time.sleep(0.1)
                continue
            require(status == 200, f"{name}: /status.json answered {status}")
            break
        check_startup(run, out, "deploy")
        yield base, out
        status, _ = http("POST", f"{base}/stop?accessKey={ACCESS_KEY}")
        require(status == 200, f"{name}: POST /stop answered {status}")
        rc = proc.wait(timeout=60.0)
        if rc != 0:
            # every measurement is taken by now.  The server sometimes aborts
            # in interpreter shutdown (daemon threads inside the runtime:
            # "FATAL: exception not rethrown"); reported, not fatal
            print(f"[bench] {name} exited {rc} after /stop", flush=True)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def trained_instances(stdout: str) -> list[str]:
    """The engine instance ids the train verb printed."""
    marker = "Training completed. Engine instance: "
    return [
        line[len(marker):].strip()
        for line in stdout.splitlines()
        if line.startswith(marker)
    ]


def train_child(run: Run, name: str, engine_json: Path, timeout: float):
    """``pio train`` as a child -> (instance id, report from its log)."""
    out = run.run_child(
        name, CLI + ["train", "--engine-json", str(engine_json)], timeout
    )
    check_startup(run, out, "train")
    ids = trained_instances(out.stdout())
    require(ids, f"{name}: no engine instance id on stdout:\n{out.tail()}")
    stages = out.record_with("stages")["stages"]
    report = out.record_with("device_report")["device_report"]
    return ids[-1], {
        "wall_s": out.wall_s,
        "stages": stages,
        "compile_s": stages.get("jax_compile"),
        "peak_bytes_in_use": report["peak_bytes_in_use"],
    }
