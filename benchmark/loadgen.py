"""Load from one process, one event loop, over keep-alive connections.

Open loop: each request is due at a scheduled time and its latency runs
from that time to the last byte of its response, so a stall shows as
latency on the requests behind it, never as less offered load. One request
is outstanding per connection, as real clients speak HTTP/1.1; a request
that finds every connection busy waits, and that wait counts. Closed loop:
``callers`` callers each send their next request when the reply arrives.

(The arrival pacing and the from-due-time latency follow
``kmlserver_tpu/serving/replay.py``'s ``replay_async_http``; pipelining is
left out because it orders responses behind each other on a connection.)
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import socket
import time

import numpy as np

PATH = "/api/recommend/"


@dataclasses.dataclass
class Records:
    """One row per request offered in the window. Times are seconds from
    the window's start on this process's monotonic clock; NaN = never."""

    due: np.ndarray
    sent: np.ndarray
    done: np.ndarray
    status: np.ndarray  # int, 0 = never answered
    degraded: np.ndarray  # bool, X-KMLS-Degraded present
    bodies: list  # bytes | None
    t0_unix: float = 0.0  # wall clock at the window's start


def encode(names: list[str]) -> bytes:
    body = json.dumps({"songs": names}).encode()
    return (
        b"POST " + PATH.encode() + b" HTTP/1.1\r\nHost: bench\r\n"
        b"Content-Type: application/json\r\nContent-Length: "
        + str(len(body)).encode() + b"\r\n\r\n" + body
    )


async def _open(host: str, port: int):
    reader, writer = await asyncio.open_connection(host, port)
    sock = writer.get_extra_info("socket")
    if sock is not None:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return reader, writer


async def _exchange(conn, request: bytes):
    reader, writer = conn
    writer.write(request)
    head = await reader.readuntil(b"\r\n\r\n")
    lower = head.lower()
    clen = 0
    for line in lower.split(b"\r\n"):
        if line.startswith(b"content-length"):
            clen = int(line.split(b":", 1)[1])
    body = await reader.readexactly(clen)
    return int(head.split(b" ", 2)[1]), lower, body


class LoadGenerator:
    """Connections are opened by :meth:`connect` (set-up) and the window is
    driven by :meth:`run`; both on one loop owned by this object."""

    def __init__(self, host: str, port: int, connections: int):
        self.host, self.port, self.n_conns = host, port, connections
        self.loop = asyncio.new_event_loop()
        self.pool: asyncio.Queue | None = None
        self.conns: list = []

    def connect(self) -> None:
        async def go():
            self.pool = asyncio.Queue()
            for _ in range(self.n_conns):
                conn = await _open(self.host, self.port)
                self.conns.append(conn)
                self.pool.put_nowait(conn)
        self.loop.run_until_complete(go())

    def close(self) -> None:
        async def go():
            for _, writer in self.conns:
                writer.close()
        self.loop.run_until_complete(go())
        self.loop.close()

    def warm(self, requests: list[bytes]) -> list[tuple[int, bytes]]:
        """Send ``requests`` one after another (set-up) → (status, body)."""
        async def go():
            out = []
            for req in requests:
                conn = await self.pool.get()
                try:
                    status, _, body = await _exchange(conn, req)
                finally:
                    self.pool.put_nowait(conn)
                out.append((status, body))
            return out
        return self.loop.run_until_complete(go())

    def run(
        self, requests: list[bytes], due: np.ndarray, seconds: float,
        *, closed_callers: int = 0, grace_s: float = 60.0, hooks=(),
    ) -> Records:
        """Drive the window. ``hooks`` are ``(at_seconds, callable)`` run on
        the loop's clock in a thread (scrapes, the trace trigger)."""
        n = len(requests)
        rec = Records(
            due=np.asarray(due, dtype=np.float64).copy(),
            sent=np.full(n, np.nan), done=np.full(n, np.nan),
            status=np.zeros(n, dtype=np.int64),
            degraded=np.zeros(n, dtype=bool),
            bodies=[None] * n,
        )

        async def one(i: int, t0: float) -> None:
            conn = await self.pool.get()
            try:
                rec.sent[i] = time.perf_counter() - t0
                status, head, body = await _exchange(conn, requests[i])
                rec.done[i] = time.perf_counter() - t0
                rec.status[i] = status
                rec.degraded[i] = b"x-kmls-degraded" in head
                rec.bodies[i] = body
                self.pool.put_nowait(conn)
            except (OSError, asyncio.IncompleteReadError, asyncio.LimitOverrunError, ValueError):
                conn[1].close()
                try:
                    self.pool.put_nowait(await _open(self.host, self.port))
                except OSError:
                    pass  # server gone: the pool shrinks, requests fail

        async def hook(at: float, fn, t0: float) -> None:
            await asyncio.sleep(max(0.0, at - (time.perf_counter() - t0)))
            await asyncio.get_running_loop().run_in_executor(None, fn)

        async def open_loop(t0: float) -> list:
            tasks = []
            for i in range(n):
                wait = rec.due[i] - (time.perf_counter() - t0)
                if wait > 0:
                    await asyncio.sleep(wait)
                tasks.append(asyncio.ensure_future(one(i, t0)))
            return tasks

        async def closed_loop(t0: float) -> list:
            nxt = iter(range(n))

            async def caller() -> None:
                for i in nxt:
                    now = time.perf_counter() - t0
                    if now >= seconds:
                        return
                    rec.due[i] = now
                    await one(i, t0)

            return [asyncio.ensure_future(caller()) for _ in range(closed_callers)]

        async def go() -> None:
            rec.t0_unix = time.time()
            t0 = time.perf_counter()
            side = [asyncio.ensure_future(hook(at, fn, t0)) for at, fn in hooks]
            tasks = await (closed_loop(t0) if closed_callers else open_loop(t0))
            left = seconds + grace_s - (time.perf_counter() - t0)
            if tasks:
                _, pending = await asyncio.wait(tasks, timeout=max(left, 0.0))
                for t in pending:
                    t.cancel()
            await asyncio.gather(*side)

        self.loop.run_until_complete(go())
        return rec
