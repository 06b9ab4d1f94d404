"""Asyncio HTTP/1.1 transport for :class:`~.app.RecommendApp` — the
production serving front end.

Why not the stdlib ``ThreadingHTTPServer`` (kept in ``serving.server`` as
the ``KMLS_HTTP_IMPL=threaded`` fallback): thread-per-connection collapses
under concurrency on small pods — measured this round on a 2-core host,
``/healthz`` throughput FELL from ~800 QPS at 1 connection to ~300 at 32
(GIL convoy + context-switch storm), a ceiling far below the 1k-QPS
config-5 target before the engine does any work at all. A single-threaded
event loop holds ~700+ QPS flat at the same concurrency because each
request costs one parse + one dispatch, no thread handoffs.

The recommendation path never blocks the loop: ``app.submit_recommend``
first consults the epoch-keyed answer cache (a hit resolves inline on the
loop — no batcher, no executor, no thread handoff; concurrent identical
misses singleflight onto one shared future), then the micro-batcher's
non-blocking ``submit()`` (→ Future); the loop attaches a done-callback,
and the batcher's completion thread hands the finished result back via
``call_soon_threadsafe``. Every other route is sub-millisecond and runs
inline. One request is outstanding per connection (HTTP/1.1 without
pipelining — what real clients speak); further bytes buffer until the
response is written.

SIGTERM drain parity with the threaded transport (k8s rollout semantics):
on ``drain()`` the listener closes immediately (racing connects are
refused, not parked), every subsequent response carries ``Connection:
close`` so keep-alive clients migrate off the pod, shutdown settles
until in-flight requests hit zero (bounded by ``KMLS_DRAIN_SETTLE_S``),
and then the keep-alive connections still idling are closed so the
process exits — and gives up its device — whatever its clients do.
"""

from __future__ import annotations

import asyncio
import logging
import socket
import time

from .. import faults
from .app import RecommendApp

logger = logging.getLogger("kmlserver_tpu.serving")

_REASONS = {
    200: "OK", 307: "Temporary Redirect", 400: "Bad Request",
    403: "Forbidden", 404: "Not Found", 422: "Unprocessable Entity",
    429: "Too Many Requests", 500: "Internal Server Error",
    503: "Service Unavailable",
}
_MAX_HEAD = 32 * 1024
_MAX_BODY = 10 * 1024 * 1024
_RECOMMEND_PATHS = ("/api/recommend/", "/api/recommend")


class _ServerState:
    """Shared across connections: drain flag + in-flight accounting (the
    loop is single-threaded, so plain ints are safe)."""

    def __init__(self, app: RecommendApp):
        self.app = app
        self.draining = False
        self.inflight = 0
        self.idle = asyncio.Event()
        self.idle.set()
        # live connections, so shutdown can close the idle keep-alive
        # ones instead of waiting for their clients to hang up
        self.conns: set[_Conn] = set()
        self._engine_pool = None

    @property
    def engine_pool(self):
        """Small thread pool for the BATCHERLESS recommend path
        (KMLS_BATCH_WINDOW_MS=0): engine.recommend blocks until the
        device answers, and running it on the loop would freeze every
        connection for that long, health probes included. Lazy: the
        batched default never needs it."""
        if self._engine_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._engine_pool = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="kmls-aio-engine"
            )
        return self._engine_pool

    def enter(self) -> None:
        self.inflight += 1
        self.idle.clear()

    def leave(self) -> None:
        self.inflight -= 1
        if self.inflight <= 0:
            self.idle.set()


# bound on requests parsed-but-unanswered per connection: keeps a
# misbehaving pipeliner from queueing unbounded work
_MAX_PIPELINE = 128


class _Conn(asyncio.Protocol):
    """One HTTP/1.1 connection, with PIPELINING: every complete request in
    the buffer is dispatched immediately, responses are staged by sequence
    number, and every contiguous ready prefix goes out as ONE
    ``transport.write``. Syscalls are the dominant per-request cost in a
    sandboxed runtime (measured ~0.5 ms per ``recv``/``send`` here — a
    gVisor-style trap per call), so a client that bursts K requests per
    write costs this server ~2 syscalls per K requests instead of 2K;
    non-pipelining clients behave exactly as before."""

    def __init__(self, state: _ServerState):
        self.state = state
        self.buf = b""
        self.transport: asyncio.Transport | None = None
        self.peer_host: str | None = None
        self.closed = False
        self._next_seq = 0    # next request sequence number to assign
        self._next_write = 0  # next sequence number to write out
        self._staged: dict[int, tuple[tuple, bool, object]] = {}
        self._reading_paused = False

    # ---------- transport events ----------

    def connection_made(self, transport) -> None:
        self.transport = transport
        self.loop = asyncio.get_running_loop()
        sock = transport.get_extra_info("socket")
        if sock is not None:
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
        peer = transport.get_extra_info("peername")
        self.peer_host = peer[0] if peer else None
        self.state.conns.add(self)

    def connection_lost(self, exc) -> None:
        self.closed = True
        self.state.conns.discard(self)

    def data_received(self, data: bytes) -> None:
        self.buf += data
        self._process_buffer()
        self._update_read_flow()

    def _update_read_flow(self) -> None:
        """Backpressure the SOCKET, not just the parser: with parsing
        stopped at the pipeline cap, un-paused reads would still grow
        ``self.buf`` without bound for a client that keeps streaming."""
        if self.closed or self.transport is None:
            return
        backlogged = (
            self._next_seq - self._next_write >= _MAX_PIPELINE
            or len(self.buf) > _MAX_HEAD + _MAX_BODY
        )
        if backlogged and not self._reading_paused:
            try:
                self.transport.pause_reading()
                self._reading_paused = True
            except RuntimeError:
                pass
        elif not backlogged and self._reading_paused:
            try:
                self.transport.resume_reading()
                self._reading_paused = False
            except RuntimeError:
                pass

    # ---------- request framing ----------

    def _process_buffer(self) -> None:
        recorder = self.state.app.recorder
        while (
            not self.closed
            and self._next_seq - self._next_write < _MAX_PIPELINE
        ):
            # a traced request's root span starts where its parse does
            # (one attribute check while tracing is off)
            t_received = time.perf_counter() if recorder.active else None
            end = self.buf.find(b"\r\n\r\n")
            if end < 0:
                if len(self.buf) > _MAX_HEAD:
                    self._bad_request("headers too large")
                return
            head = self.buf[:end]
            try:
                request_line, _, header_block = head.partition(b"\r\n")
                method, path, _ = request_line.decode("latin1").split(" ", 2)
            except ValueError:
                self._bad_request("malformed request line")
                return
            content_length = 0
            close_after = False
            trace_header: str | None = None
            budget_header: str | None = None
            for line in header_block.split(b"\r\n"):
                key, _, value = line.partition(b":")
                lowered = key.strip().lower()
                if lowered == b"content-length":
                    try:
                        content_length = int(value.strip())
                    except ValueError:
                        self._bad_request("bad Content-Length")
                        return
                elif lowered == b"connection":
                    close_after = value.strip().lower() == b"close"
                elif lowered == b"x-kmls-trace":
                    # span-trace propagation (ISSUE 9): the raw value;
                    # the recorder validates the charset before any byte
                    # of it can reach JSON output
                    trace_header = value.strip().decode("latin1")
                elif lowered == b"x-kmls-deadline-budget":
                    # deadline propagation (ISSUE 18): remaining budget
                    # (ms) forwarded by an upstream hop; the app parses
                    # and ignores malformed values
                    budget_header = value.strip().decode("latin1")
            if content_length > _MAX_BODY:
                self._bad_request("body too large")
                return
            total = end + 4 + content_length
            if len(self.buf) < total:
                return  # body still arriving
            body = self.buf[end + 4: total] or None
            self.buf = self.buf[total:]
            self._dispatch(
                method, path, body, close_after, trace_header, budget_header,
                t_received,
            )

    def _bad_request(self, detail: str) -> None:
        seq = self._next_seq
        self._next_seq += 1
        self.buf = b""
        self._stage(
            seq,
            (400, {"Content-Type": "application/json"},
             b'{"detail": "' + detail.encode() + b'"}'),
            close_after=True,
        )

    # ---------- dispatch ----------

    def _dispatch(
        self, method: str, path: str, body: bytes | None, close_after: bool,
        trace_header: str | None = None, budget_header: str | None = None,
        t_received: float | None = None,
    ) -> None:
        state = self.state
        app = state.app
        state.enter()
        seq = self._next_seq
        self._next_seq += 1
        route = path.split("?", 1)[0]
        if method == "POST" and route in _RECOMMEND_PATHS:
            # gray-failure chaos site (ISSUE 18), loop-native form: an
            # armed per-replica stall delays THIS request on the loop
            # timer — pipelined neighbours and other connections keep
            # flowing, which is what a slow-but-alive replica looks
            # like from outside. fire()'s blocking sleep would stall
            # the whole loop and turn a per-request stall into a full
            # replica outage.
            try:
                delay = faults.take("fleet.peer", replica=app._fleet_index)
            except Exception:
                logger.exception("unhandled error for %s %s", method, path)
                app.metrics.record_error()
                self._stage(seq, (
                    500, {"Content-Type": "application/json"},
                    b'{"detail": "Internal Server Error"}',
                ), close_after)
                state.leave()
                return
            if delay > 0:
                self.loop.call_later(
                    delay, self._recommend, seq, path, body, close_after,
                    trace_header, budget_header, t_received,
                )
                return
            self._recommend(
                seq, path, body, close_after, trace_header, budget_header,
                t_received,
            )
            return
        try:
            response = app.handle(
                method, path, body, client_host=self.peer_host
            )
        except Exception:
            logger.exception("unhandled error for %s %s", method, path)
            app.metrics.record_error()
            response = (
                500, {"Content-Type": "application/json"},
                b'{"detail": "Internal Server Error"}',
            )
        self._stage(seq, response, close_after)
        state.leave()

    def _recommend(
        self, seq: int, path: str, body: bytes | None, close_after: bool,
        trace_header: str | None = None, budget_header: str | None = None,
        t_received: float | None = None,
    ) -> None:
        """The recommend-POST tail of :meth:`_dispatch`, split out so an
        armed fault stall can re-enter it from a loop timer with its
        response slot (``seq``) already reserved — pipelined responses
        still leave in request order through ``_stage``."""
        state = self.state
        app = state.app
        if self.closed:  # connection dropped during a fault stall
            state.leave()
            return
        trace = None
        try:
            if app.batcher is None:
                # batching disabled: the blocking engine call must
                # still stay off the loop
                # the fleet.peer stall was already take()n in _dispatch:
                # the handler must not fire the site a second time
                task = state.engine_pool.submit(
                    app.handle, "POST", path, body, self.peer_host,
                    trace_header, budget_header, False,
                )
                task.add_done_callback(
                    lambda f: self.loop.call_soon_threadsafe(
                        self._finish_handled, seq, f, close_after
                    )
                )
                return
            response, future, t0, trace = app.submit_recommend(
                body, trace_header, budget_header, t_received
            )
            if response is None:
                if isinstance(future, asyncio.Future):
                    # loop-native batcher: resolved ON the loop, the
                    # callback is already loop-scheduled
                    future.add_done_callback(
                        lambda f: self._finish_recommend(
                            seq, f, t0, close_after, trace
                        )
                    )
                else:
                    # threaded batcher: its completion thread fires
                    # the callback → hop back onto the loop
                    future.add_done_callback(
                        lambda f: self.loop.call_soon_threadsafe(
                            self._finish_recommend, seq, f, t0,
                            close_after, trace,
                        )
                    )
                return
        except Exception:
            logger.exception("unhandled error for POST %s", path)
            app.metrics.record_error()
            response = (
                500, {"Content-Type": "application/json"},
                b'{"detail": "Internal Server Error"}',
            )
        self._stage(seq, response, close_after, trace)
        state.leave()

    def _finish_recommend(
        self, seq: int, future, t0: float, close_after: bool, trace=None
    ) -> None:
        if not self.closed:
            response = self.state.app.finish_recommend(future, t0, trace=trace)
            self._stage(seq, response, close_after, trace)
        elif trace is not None:
            self.state.app.trace_written(trace)  # nobody left to write to
        self.state.leave()
        if not self.closed:
            self._process_buffer()  # pipeline slots freed — keep parsing
            self._update_read_flow()

    def _finish_handled(self, seq: int, task, close_after: bool) -> None:
        """Completion for the batcherless off-loop ``app.handle`` call."""
        if not self.closed:
            try:
                # kmls-verify: allow[loopblock] — this callback only runs
                # via call_soon_threadsafe AFTER the engine-pool task
                # completed, so result() returns immediately
                response = task.result()
            except Exception:
                logger.exception("engine-pool request failed")
                self.state.app.metrics.record_error()
                response = (
                    500, {"Content-Type": "application/json"},
                    b'{"detail": "Internal Server Error"}',
                )
            self._stage(seq, response, close_after)
        self.state.leave()
        if not self.closed:
            self._process_buffer()
            self._update_read_flow()

    # ---------- response writing ----------

    def _stage(
        self, seq: int, response, close_after: bool, trace=None,
    ) -> None:
        """Stage response ``seq``; flush the contiguous ready prefix as a
        single write (responses must leave in request order). ``trace``
        is the request's deferred trace (``app.submit_recommend`` with
        ``t_received``): the flush that carries its response records its
        ``write`` span — encode, join, ``transport.write`` returned — and
        closes it."""
        app = self.state.app
        if self.closed or self.transport is None:
            if trace is not None and trace.deferred:
                app.trace_written(trace)
            return
        self._staged[seq] = (response, close_after, trace)
        if seq != self._next_write:
            return
        chunks: list[bytes] = []
        traces = None  # the deferred traces this flush closes, if any
        closing = False
        t_write = time.perf_counter() if app.recorder.active else 0.0
        while self._next_write in self._staged:
            response, close_after, trace = self._staged.pop(self._next_write)
            self._next_write += 1
            if trace is not None and trace.deferred:
                traces = (traces or []) + [trace]
            closing = close_after or self.state.draining
            chunks.append(self._encode(response, closing))
            if closing:
                break
        self.transport.write(b"".join(chunks))
        if traces:
            t_written = time.perf_counter()
            for trace in traces:
                # t_write is 0.0 only where tracing went on mid-flush
                app.trace_written(trace, t_write or t_written, t_written)
        if closing:
            self.transport.close()
            self.closed = True

    def _encode(self, response, closing: bool) -> bytes:
        status, headers, payload = response
        reason = _REASONS.get(status, "OK")
        parts = [f"HTTP/1.1 {status} {reason}\r\nContent-Length: {len(payload)}\r\n"]
        for key, value in headers.items():
            parts.append(f"{key}: {value}\r\n")
        if closing:
            # during a SIGTERM drain keep-alive clients must re-connect
            # elsewhere — k8s endpoint removal only diverts NEW connections
            parts.append("Connection: close\r\n")
        parts.append("\r\n")
        return "".join(parts).encode("latin1") + payload


async def run_async(app: RecommendApp, port: int, ready=None) -> int:
    """Bind + serve until SIGTERM/SIGINT, then drain; → exit code.
    ``ready(port)`` is called once the socket is bound (tests use it)."""
    import os
    import signal

    loop = asyncio.get_running_loop()
    if app.batcher is None and app.cfg.batch_window_ms > 0:
        # the loop-native batcher (built here, where the loop exists):
        # admission/collection/resolution on the loop, compute in one
        # executor hop, one loop wakeup per batch
        from .batcher import AsyncMicroBatcher

        cfg = app.cfg
        app.batcher = AsyncMicroBatcher(
            app.engine, max_size=cfg.batch_max_size,
            window_ms=cfg.batch_window_ms,
            max_inflight=cfg.batch_max_inflight,
            adaptive=cfg.batch_adaptive_window,
            window_min_ms=cfg.batch_window_min_ms,
            shed_queue_budget_ms=cfg.shed_queue_budget_ms,
            shed_retry_after_s=cfg.shed_retry_after_s,
            shed_soft_ratio=cfg.shed_soft_ratio,
            shed_hard_ratio=cfg.shed_hard_ratio,
            shed_retry_jitter=cfg.shed_retry_jitter,
            eject_threshold=cfg.replica_eject_threshold,
            probe_interval_s=cfg.replica_probe_interval_s,
            redispatch_max=cfg.redispatch_max_retries,
            metrics=app.metrics,
            lag_monitor=app.loop_lag,
            forecaster=getattr(app, "forecaster", None),
            recorder=app.recorder,
        )
    if app.loop_lag is not None:
        # arm the drift tick on THIS loop: timer-due minus timer-ran is
        # the time something blocked the loop (kmls_loop_lag_ms at
        # /metrics, and the admission ladder's runtime-health term —
        # closing the PR 8 inline-path blind spot)
        app.loop_lag.start_on_loop(loop)
    state = _ServerState(app)
    server = await loop.create_server(
        lambda: _Conn(state), "0.0.0.0", port, backlog=256,
    )
    bound_port = server.sockets[0].getsockname()[1]
    logger.info(
        "serving on 0.0.0.0:%d (version %s, async)", bound_port, app.cfg.version
    )
    if ready is not None:
        ready(bound_port)

    stop = asyncio.Event()

    def _drain() -> None:
        logger.info("SIGTERM: draining in-flight requests, then exiting")
        state.draining = True
        stop.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, _drain)
        except (NotImplementedError, RuntimeError):
            pass  # non-main thread / exotic platform

    try:
        await stop.wait()
    finally:
        # listener closes NOW: racing connects get an instant refusal.
        # Not followed by wait_closed(): since Python 3.12 that blocks
        # until every accepted connection has closed, so one idle
        # keep-alive client would hold the process — and the device it
        # owns — forever.
        server.close()
    settle_s = float(os.getenv("KMLS_DRAIN_SETTLE_S") or 2.0)
    # floor before the zero-exit (threaded-transport parity): a
    # keep-alive client that raced the signal may still be writing its
    # request — give it a beat to land and be answered with
    # Connection: close before the idle check can end the settle
    await asyncio.sleep(min(0.5, settle_s))
    try:
        await asyncio.wait_for(state.idle.wait(), timeout=settle_s)
    except asyncio.TimeoutError:
        logger.warning(
            "drain settle expired after %.1fs with %d requests still "
            "in flight (raise KMLS_DRAIN_SETTLE_S to match "
            "terminationGracePeriodSeconds)", settle_s, state.inflight,
        )
    # whatever is still connected is an idle keep-alive client (or a
    # request past the settle): close it rather than wait on it
    for conn in list(state.conns):
        conn.transport.close()
    return 0
