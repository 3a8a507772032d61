"""Open-loop HTTP load from one thread: a schedule made from the seed, sent
on time whatever the server does, every request timed from when it was DUE.

One asyncio loop, callback protocols, keep-alive connections, no task and no
thread per request: the generator costs tens of microseconds a request, so at
the rates of these cells it is not what is measured — and ``lag`` (sent - due)
is recorded for every request so a starved generator shows.  The dispatcher
sleeps to within 1.5 ms of a due time and then polls the loop, which keeps
the lag far under a millisecond while the loop still reads answers.

The in-flight cap is the connection cap: with ``cap`` requests outstanding the
dispatcher waits for one to finish (an upstream with a bounded pool) and the
wait is lag.  A request that gets no answer within ``timeout_s`` fails; failed
and non-200 requests enter the latency list at the timeout.

(After ``replay/workload.py``'s OpenLoopRunner, which times from the start of
the send and so does not charge a stall to the requests behind it.)
"""

from __future__ import annotations

import asyncio
import gc
import re
import time
from dataclasses import dataclass, field

import numpy as np

_CONTENT_LENGTH = re.compile(rb"content-length:\s*(\d+)", re.I)
#: below this distance from a due time the dispatcher polls instead of sleeping
_SPIN_S = 0.0015


def make_schedule(rate_qps: float, seconds: float, n_keys: int, zipf_s: float, seed: int):
    """(offsets[N] in seconds, key ranks[N]) with N = rate * seconds exactly.

    Arrivals are Poisson in shape, fixed in amount: the N gaps are the N
    quantile midpoints of the exponential distribution, scaled to fill
    ``seconds`` and put in an order drawn from the seed — every seed sends the
    same number of requests with the same set of gaps.  Keys are Zipf(s) over
    ``n_keys`` ranks (inverse CDF), drawn from the seed."""
    n = int(rate_qps * seconds)
    if n < 1:
        raise ValueError("the schedule holds no request")
    rng = np.random.default_rng([seed, 2])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    weights = 1.0 / np.arange(1, n_keys + 1) ** zipf_s
    cdf = np.cumsum(weights / weights.sum())
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n)), n_keys - 1)
    return at, ranks


def http_post_bytes(host: str, port: int, path: str, body: bytes) -> bytes:
    return (
        f"POST {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
        "Content-Type: application/json\r\n"
        f"Content-Length: {len(body)}\r\n\r\n"
    ).encode() + body


@dataclass
class Outcome:
    """Per-request arrays of one run (seconds relative to the run's start)."""

    due: np.ndarray
    sent: np.ndarray
    done: np.ndarray
    status: np.ndarray  # 0 = no answer (timeout / connection lost)
    bodies: list = field(default_factory=list)
    timeout_s: float = 5.0
    wall_s: float = 0.0

    @property
    def ok(self) -> np.ndarray:
        return self.status == 200

    def latency_ms(self) -> np.ndarray:
        """From the due time; failures count at the client timeout."""
        lat = (self.done - self.due) * 1e3
        return np.where(self.ok, lat, self.timeout_s * 1e3)

    def lag_ms(self) -> np.ndarray:
        return (self.sent - self.due) * 1e3


class _Conn(asyncio.Protocol):
    def __init__(self, gen: "OpenLoop"):
        self.gen = gen
        self.buf = bytearray()
        self.transport = None
        self.req = -1
        self.body_at = -1
        self.end = -1
        self.status = 0

    def connection_made(self, transport) -> None:
        self.transport = transport

    def send(self, req: int, payload: bytes) -> None:
        self.req = req
        self.transport.write(payload)

    def data_received(self, data: bytes) -> None:
        self.buf += data
        if self.end < 0:
            head_end = self.buf.find(b"\r\n\r\n")
            if head_end < 0:
                return
            m = _CONTENT_LENGTH.search(self.buf, 0, head_end)
            self.status = int(self.buf[9:12])
            self.body_at = head_end + 4
            self.end = self.body_at + (int(m.group(1)) if m else 0)
        if len(self.buf) < self.end:
            return
        head = bytes(self.buf[: self.body_at])
        body = bytes(self.buf[self.body_at : self.end])
        del self.buf[: self.end]
        self.end = -1
        req, self.req = self.req, -1
        self.gen._finished(self, req, self.status, head, body)

    def connection_lost(self, exc) -> None:
        self.gen._lost(self)


class OpenLoop:
    def __init__(self, host: str, port: int, cap: int, timeout_s: float):
        self.host, self.port = host, port
        self.cap = int(cap)
        self.timeout_s = float(timeout_s)
        self._idle: list[_Conn] = []
        self._open = 0
        self._busy: set[_Conn] = set()
        self._freed: asyncio.Future | None = None
        self._out: Outcome | None = None
        self._t0 = 0.0
        self._left = 0
        self._all_done: asyncio.Future | None = None

    # -- connections ---------------------------------------------------------

    async def _connect(self) -> _Conn:
        loop = asyncio.get_running_loop()
        self._open += 1
        try:
            _, conn = await loop.create_connection(
                lambda: _Conn(self), self.host, self.port
            )
        except OSError:
            self._open -= 1
            raise
        return conn

    async def preopen(self, n: int) -> None:
        for _ in range(min(n, self.cap) - self._open):
            self._idle.append(await self._connect())

    async def _acquire(self) -> _Conn:
        while True:
            while self._idle:
                conn = self._idle.pop()
                if not conn.transport.is_closing():
                    return conn
            if self._open < self.cap:
                return await self._connect()
            self._freed = asyncio.get_running_loop().create_future()
            await self._freed

    def _release(self, conn: _Conn, reusable: bool) -> None:
        self._busy.discard(conn)
        if reusable:
            self._idle.append(conn)
        else:
            conn.transport.close()
        if self._freed is not None and not self._freed.done():
            self._freed.set_result(None)

    def _lost(self, conn: _Conn) -> None:
        self._open -= 1
        if conn in self._idle:
            self._idle.remove(conn)
        if conn.req >= 0:
            req, conn.req = conn.req, -1
            self._record(req, 0, b"")
            self._busy.discard(conn)
        if self._freed is not None and not self._freed.done():
            self._freed.set_result(None)

    # -- requests ------------------------------------------------------------

    def _record(self, req: int, status: int, body: bytes) -> None:
        out = self._out
        out.done[req] = time.perf_counter() - self._t0
        out.status[req] = status
        out.bodies[req] = body
        self._left -= 1
        if self._left == 0 and not self._all_done.done():
            self._all_done.set_result(None)

    def _finished(self, conn: _Conn, req: int, status: int, head: bytes, body: bytes) -> None:
        if req < 0:
            return  # an answer nobody waits for (timed out earlier)
        self._record(req, status, body)
        self._release(conn, reusable=b"connection: close" not in head.lower())

    async def _watchdog(self) -> None:
        """Fail requests that have waited ``timeout_s`` for their answer."""
        out = self._out
        while self._left > 0:
            await asyncio.sleep(0.05)
            now = time.perf_counter() - self._t0
            for conn in [c for c in self._busy if c.req >= 0]:
                if now - out.sent[conn.req] >= self.timeout_s:
                    req, conn.req = conn.req, -1
                    self._record(req, 0, b"")
                    self._release(conn, reusable=False)

    async def run(self, at: np.ndarray, payloads: list[bytes]) -> Outcome:
        """Send ``payloads[i]`` at ``at[i]`` seconds from now; returns when
        every request has its answer or has failed.  The cyclic collector is
        off meanwhile: a full collection over the harness's arrays stalls the
        one thread for ~0.1 s, which every request then due would be charged."""
        n = len(at)
        loop = asyncio.get_running_loop()
        out = self._out = Outcome(
            due=np.asarray(at, np.float64).copy(),
            sent=np.zeros(n), done=np.zeros(n),
            status=np.zeros(n, np.int64),
            bodies=[b""] * n, timeout_s=self.timeout_s,
        )
        self._left = n
        self._all_done = loop.create_future()
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            await self._dispatch(out, payloads)
        finally:
            gc.enable()
            gc.unfreeze()
        return out

    async def _dispatch(self, out: Outcome, payloads: list[bytes]) -> None:
        self._t0 = t0 = time.perf_counter()
        watchdog = asyncio.ensure_future(self._watchdog())
        for i in range(len(payloads)):
            due = t0 + out.due[i]
            while True:
                d = due - time.perf_counter()
                if d <= 0:
                    break
                await asyncio.sleep(d - _SPIN_S if d > _SPIN_S + 0.0005 else 0)
            try:
                conn = await self._acquire()
            except OSError:
                out.sent[i] = time.perf_counter() - t0
                self._record(i, 0, b"")
                continue
            out.sent[i] = time.perf_counter() - t0
            self._busy.add(conn)
            conn.send(i, payloads[i])
        await self._all_done
        watchdog.cancel()
        out.wall_s = time.perf_counter() - t0

    async def close(self) -> None:
        for conn in list(self._idle) + list(self._busy):
            conn.transport.close()
        self._idle.clear()
        await asyncio.sleep(0)


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile (a served request's own latency, no blend)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1)])
