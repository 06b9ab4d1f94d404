"""Container entrypoint for the online API.

Run as ``python -m kmlserver_tpu.serving.server`` — the rebuild's equivalent
of the reference API image's ``CMD fastapi run app/main.py --port 80``
(reference: rest_api/Dockerfile:28). Env-var configured
(kubernetes/deployment.yaml contract); logs to stdout with the same
timestamped format intent as the reference's logging setup
(rest_api/app/main.py:18-29).
"""

from __future__ import annotations

import logging
import signal
import sys
import threading
import time

from ..config import ServingConfig
from .app import RecommendApp, serve


def main() -> int:
    # the reference configures DEBUG-level stdout logging for ITS app
    # (rest_api/app/main.py:18-29). Scope DEBUG to this package's logger
    # only — putting the ROOT logger at DEBUG floods stdout with ~170 KB of
    # jax compile chatter per reload (and can block the process mid-warmup
    # when a log collector stops draining the pipe)
    logging.basicConfig(
        level=logging.INFO,
        stream=sys.stdout,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    logging.getLogger("kmlserver_tpu").setLevel(logging.DEBUG)
    cfg = ServingConfig.from_env()
    # persistent XLA compilation cache (utils/jaxcache.py): per-shape
    # warmup on every rollout/reload hits the cache instead of
    # recompiling the same serving-bucket kernels. Before any jit.
    from ..parallel.mesh import describe_devices
    from ..utils.jaxcache import enable_compilation_cache

    enable_compilation_cache()
    log = logging.getLogger("kmlserver_tpu.serving")
    log.info("devices: %s", describe_devices())
    # transport selection: the asyncio front end is the default (thread-
    # per-connection collapses under concurrency on small pods — see
    # serving/aioserver.py); the stdlib ThreadingHTTPServer stays as the
    # KMLS_HTTP_IMPL=threaded fallback.
    import os

    # GIL switch interval: tunable because thread-handoff latency vs
    # throughput is workload-dependent — measured here, LOWERING it from
    # the 5 ms default made a 2-core box thrash (881 → 415 QPS), so only
    # an explicit env value changes it.
    if os.environ.get("KMLS_GIL_SWITCH_S"):
        sys.setswitchinterval(float(os.environ["KMLS_GIL_SWITCH_S"]))
    use_async = (
        os.environ.get("KMLS_HTTP_IMPL", "async").strip().lower() != "threaded"
    )
    # defer_batcher under async: the transport installs its loop-native
    # AsyncMicroBatcher instead of the threaded pipeline
    app = RecommendApp(cfg, defer_batcher=use_async)
    app.engine.start_polling()
    if use_async:
        import asyncio

        from .aioserver import run_async

        return asyncio.run(run_async(app, cfg.port))
    if app.loop_lag is not None:
        # sleep-drift thread: the threaded transport's analogue of the
        # async drift tick — host-scheduling stalls (CPU starvation, GIL
        # convoy) surface as the same kmls_loop_lag_ms signal
        app.loop_lag.start_thread()
    server = serve(app)
    host, port = server.server_address[:2]
    log.info("serving on %s:%d (version %s)", host, port, cfg.version)

    # graceful drain on SIGTERM: a k8s rollout sends SIGTERM and waits
    # terminationGracePeriodSeconds before SIGKILL. The reference's uvicorn
    # drains in-flight requests on SIGTERM; the stdlib default would kill
    # them mid-response. Sequence: (1) the handler starts answering with
    # "Connection: close" so keep-alive clients migrate off the pod (k8s
    # endpoint removal only stops NEW connections — established flows keep
    # routing here); (2) shutdown() stops the accept loop and returns from
    # serve_forever (it must run OFF the serving thread or it deadlocks);
    # (3) server_close() immediately closes the LISTENING socket so racing
    # connects get an instant refusal (not a backlog-then-RST after the
    # settle); (4) a bounded settle lets in-flight responses finish —
    # handler threads are daemonic and idle keep-alive connections can
    # block forever, so joining them is not an option; instead the settle
    # polls the server's in-flight counter and exits the moment it reaches
    # zero, bounded by KMLS_DRAIN_SETTLE_S (set it to match the pod's
    # terminationGracePeriodSeconds minus a safety margin).
    draining = threading.Event()
    server.draining = draining  # handlers read this (app.make_handler)

    def _drain(signum, frame):
        log.info("SIGTERM: draining in-flight requests, then exiting")
        draining.set()
        threading.Thread(target=server.shutdown, daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _drain)
    except ValueError:
        pass  # not the main thread (embedded use); k8s path is main-thread
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()  # listening socket closed BEFORE the settle
        if draining.is_set():
            import os

            settle_s = float(os.getenv("KMLS_DRAIN_SETTLE_S") or 2.0)
            t_settle = time.monotonic()
            deadline = t_settle + settle_s
            # floor before the zero-exit: a connection accepted just before
            # shutdown has a handler thread that may not have reached the
            # counter increment yet — an instant first-poll zero would kill
            # it mid-parse (the floor covers accept→dispatch scheduling)
            floor = t_settle + min(0.5, settle_s)
            while time.monotonic() < deadline:
                with server.active_lock:
                    if server.active_requests == 0 and time.monotonic() >= floor:
                        break
                time.sleep(0.05)
            else:
                log.warning(
                    "drain settle expired after %.1fs with %d requests "
                    "still in flight (raise KMLS_DRAIN_SETTLE_S to match "
                    "terminationGracePeriodSeconds)",
                    settle_s, server.active_requests,
                )
    return 0


if __name__ == "__main__":
    sys.exit(main())
