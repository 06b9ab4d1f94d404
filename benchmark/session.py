"""One set-up, then one or more windows: the parts ``run.py`` is made of.

``run.py`` opens a session, measures one window and judges it. The sweep
and the control's readings reuse the same parts, so what they read is what
a run reads.
"""

from __future__ import annotations

import gc
import hashlib
import importlib
import json
import os
import shutil
import sys
import time

import numpy as np

from . import loadgen, manifest, readers, spans, traffic
from . import server as server_mod
from . import trace as trace_mod

GRACE_S = 60.0  # how long past the window's close an answer is waited for
KEPT_GENERATIONS = 8  # published generations kept in the checkout, newest first


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def percentile(sorted_values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile of an ascending array."""
    n = len(sorted_values)
    return float(sorted_values[min(n - 1, max(0, int(np.ceil(q * n)) - 1))])


class NoAccelerator(RuntimeError):
    pass


class Window:
    """What one window offered and what came back."""

    def __init__(self, rec, sets, seconds: float, prom_start, prom_end):
        self.rec, self.sets, self.seconds = rec, sets, seconds
        self.prom_start, self.prom_end = prom_start, prom_end
        self.offered = ~np.isnan(rec.due)  # a closed loop may not reach every request
        answered = rec.status != 0
        self.full = self.offered & (rec.status == 200) & ~rec.degraded
        fallback = 0.0
        for key, v in prom_end.items():
            if key[0] == "kmls_requests_by_source" and ("source", "fallback") in key[1]:
                fallback = v - prom_start.get(key, 0.0)
        self.attempted = int(self.offered.sum())
        # a fallback answer is a failed operation even where no header says so
        self.failed = int((self.offered & ~self.full).sum()) + max(
            0, int(fallback) - int((self.offered & rec.degraded).sum())
        )
        self.never = int((self.offered & ~answered).sum())
        # an answer's latency is its own whatever it says (the loss of a shed
        # or degraded one shows in served_rps and failed); one that never
        # came is as late as any can be
        self.latency_ms = np.sort(np.where(
            answered, (rec.done - rec.due) * 1e3, (seconds + GRACE_S) * 1e3
        )[self.offered])
        # the window closes as work dispatched ahead has to: nothing is sent
        # past its time, every answer is waited for, and the clock is read
        # after the wait, so all the answers count over all of that time
        self.served = int(self.full.sum())
        last = (seconds + GRACE_S) if self.never else float(np.nanmax(rec.done, initial=0.0))
        self.elapsed = max(seconds, last)
        sent = self.offered & ~np.isnan(rec.sent)
        self.late_ms = np.sort((rec.sent - rec.due)[sent] * 1e3)
        self.summary = (
            f"samples {self.attempted}, offered {self.attempted / seconds:.2f} requests/s, "
            f"generator late p95 {percentile(self.late_ms, 0.95):.3f} ms "
            f"(max {self.late_ms[-1]:.1f}), failed {self.failed} "
            f"(non-200 {int((answered & (rec.status != 200)).sum())}, degraded "
            f"{int(rec.degraded.sum())}, fallback {int(fallback)}, never answered {self.never}); "
            f"served {self.served} in {self.elapsed:.3f}s, p99 "
            f"{percentile(self.latency_ms, 0.99):.1f} ms, max {self.latency_ms[-1]:.1f} ms"
        )

    def end_to_end(self) -> dict:
        return {
            "recommend_p95_ms": percentile(self.latency_ms, 0.95),
            "recommend_p50_ms": percentile(self.latency_ms, 0.50),
            "served_rps": self.served / self.elapsed,
        }


class Session:
    def __init__(self, workload: str, seed: int, *, smoke: bool = False, trace: bool = False,
                 t_process: float | None = None):
        self.t_process = time.monotonic() if t_process is None else t_process
        self.cell = manifest.Cell(workload)
        self.cfg, self.mix, self.wl = self.cell.config, self.cell.mix, self.cell.workload
        self.seed, self.smoke, self.trace = seed, smoke, trace
        self.gen = importlib.import_module(self.cfg["generator"]["module"])
        self.params = dict(self.cfg["generator"]["params"])
        self.env_extra: dict = {}
        if smoke:
            self.params.update(self.cfg["generator"]["smoke_params"])
            self.env_extra.update(self.cfg["server"]["smoke_env"])
        self.work = os.path.join(manifest.ROOT, ".bench_work", self.cell.name)
        # a published generation is a function of the generator, its laws and
        # the seed alone: kept under that key, a seed's second run (the check
        # runs every seed twice and once more traced) finds it published
        key = json.dumps([self.cfg["generator"]["module"], self.params], sort_keys=True)
        self.pub = os.path.join(
            manifest.ROOT, ".bench_work", "generations",
            f"{hashlib.sha256(key.encode()).hexdigest()[:16]}-{seed}",
        )
        if trace:
            self.env_extra["KMLS_PROFILE_DIR"] = os.path.join(self.work, "profile")
        self.srv = self.lg = self.cat = None
        self.setup_s = float("nan")
        self.capture: dict = {}

    # ---- set-up: generate, publish, start, load, warm ----

    def start(self, n_window: int) -> None:
        """``n_window`` seed sets are drawn while the server loads."""
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        self.build()
        self.publish()
        self.srv = server_mod.Server(self.cfg["server"], self.pub, self.work, self.env_extra)
        warm_sets = self.draw(n_window)
        self.requests = [self.encode(s) for s in self.sets]
        platform, count = self.srv.wait_port()
        if not self.smoke and (platform in ("", "cpu") or count < self.cell.chips):
            raise NoAccelerator(
                f"no accelerator, or fewer chips than the cell's {self.cell.chips}: "
                f"platform={platform!r} count={count}"
            )
        self.srv.wait_ready()
        log(f"[setup] server ready {self.srv.ready_s:.1f}s after spawn, port {self.srv.port}")
        self.lg = loadgen.LoadGenerator("127.0.0.1", self.srv.port, int(self.wl["connections"]))
        self.lg.connect()
        for status, body in self.lg.warm([self.encode(s) for s in warm_sets]):
            if status != 200:
                raise server_mod.ServerFailed(f"warm-up request: HTTP {status} {body[:200]!r}")
        # the catalog is millions of objects: a full collection over them
        # stalls this process for hundreds of milliseconds, and a stalled
        # generator sends late and in bursts. Park them where the collector
        # does not look.
        gc.collect()
        gc.freeze()
        self.setup_s = time.monotonic() - self.t_process
        log(f"[setup] {self.setup_s:.1f}s in all")

    def build(self) -> None:
        t = time.monotonic()
        self.cat = self.gen.build(self.params, self.seed)
        log(f"[setup] catalog built in {time.monotonic() - t:.1f}s: {len(self.cat.names)} "
            f"tracks, {int(self.cat.live.sum())} live rules")

    def publish(self) -> None:
        if self.gen.published(self.pub):
            log(f"[setup] generation found published: {self.pub}")
        else:
            shutil.rmtree(self.pub, ignore_errors=True)
            self.gen.publish(self.cat, self.params, self.pub, log=log)
        os.utime(self.pub)
        kept = os.path.dirname(self.pub)
        by_age = sorted(
            (os.path.join(kept, d) for d in os.listdir(kept)), key=os.path.getmtime, reverse=True
        )
        for old in by_age[KEPT_GENERATIONS:]:
            shutil.rmtree(old, ignore_errors=True)

    def draw(self, n_window: int) -> list:
        """Draw the window's seed sets (``self.sets``) → the warm-up's."""
        warm_sets = traffic.seed_sets(
            self.cat, self.mix, int(self.wl["warm_requests"]), self.seed + 1
        )
        self.sets = traffic.seed_sets(self.cat, self.mix, n_window, self.seed, avoid=warm_sets)
        return warm_sets

    def encode(self, ids) -> bytes:
        return loadgen.encode([self.cat.names[i] for i in ids])

    # ---- a window ----

    def measure(self, rate: float, seconds: float, first: int = 0) -> Window:
        """Offer ``self.sets[first:first + n]`` at ``rate`` for ``seconds``."""
        closed = int(self.wl.get("closed_callers", 0))
        n = max(1, int(round(rate * seconds)))
        sets = self.sets[first:first + n]
        due = traffic.arrivals(self.mix, rate, seconds, self.seed)[:len(sets)]
        hooks = []
        if self.trace:
            capture_s = min(float(self.wl["trace_seconds"]), seconds / 2.0)
            self.capture = {"seconds": capture_s}

            def start_capture():
                status, body = self.srv.get(f"/debug/profile?seconds={capture_s}")
                self.capture["at_unix"] = time.time()
                log(f"[trace] capture asked for {capture_s}s: HTTP {status} {body[:120]!r}")

            hooks.append((max(0.0, (seconds - capture_s) / 2.0), start_capture))
        self.srv.get("/metrics/reset", method="POST")
        prom_start = readers.parse_prom(self.srv.metrics())
        gc.disable()  # no collector pause inside the window
        try:
            rec = self.lg.run(
                self.requests[first:first + n],
                due if not closed else np.full(len(sets), np.nan), seconds,
                closed_callers=closed, grace_s=GRACE_S, hooks=hooks,
            )
        finally:
            gc.enable()
        win = Window(rec, sets, seconds, prom_start, readers.parse_prom(self.srv.metrics()))
        log(f"[window] {win.summary}")
        return win

    def wait_capture(self, timeout: float = 150.0) -> str | None:
        """The capture's file, once the server has finished writing it and,
        after it, the spans beside it (its log then says closed, or that
        the spans were not written)."""
        deadline = time.monotonic() + timeout
        path, size = None, -1
        while time.monotonic() < deadline:
            path = trace_mod.find_capture(self.env_extra["KMLS_PROFILE_DIR"])
            now = os.path.getsize(path) if path else -1
            if path and now == size and spans.DONE_LINE.search(self.srv.output()):
                return path
            size = now
            time.sleep(1.0)
        return path

    def window_log(self, win: Window) -> str:
        """The server-log lines stamped inside the window (the server's
        clock is the wall clock, ``%Y-%m-%d %H:%M:%S,mmm``)."""
        keep = []
        t0 = win.rec.t0_unix
        for line in self.srv.output().splitlines():
            try:
                stamp = time.mktime(time.strptime(line[:19], "%Y-%m-%d %H:%M:%S"))
                stamp += int(line[20:23]) / 1e3
            except (ValueError, IndexError):
                continue
            if t0 <= stamp <= t0 + win.seconds + GRACE_S:
                keep.append(line)
        return "\n".join(keep)

    def close(self) -> dict | None:
        """Stop the load generator and the server → the child's device line."""
        if self.lg is not None:
            self.lg.close()
            self.lg = None
        if self.srv is not None:
            return self.srv.stop()
        return None

    # ---- correct ----

    def judge(self, win: Window, lowered=None) -> tuple[bool, dict]:
        """Compare the window's answers (all, or the configuration's sample)
        with the reference → (correct, {name: {value, limit}}). With
        ``lowered`` the reference's own low-precision answers stand in the
        program's place: the control."""
        t = time.monotonic()
        answer_cfg = self.cfg["answer"]
        ref = importlib.import_module(answer_cfg["reference"])
        judge = ref.Reference(self.cat, answer_cfg, int(self.cfg["max_seed_tracks"]))
        lengths = np.asarray([len(s) for s in win.sets])
        sample = ref.choose_sample(
            lengths, win.full, int(answer_cfg["check_sample"]), self.seed
        )
        if lowered is None:
            answers = {i: ref.parse_answer(win.rec.bodies[i], self.cat.name_to_id) for i in sample}
        else:
            low = ref.Reference(self.cat, answer_cfg, int(self.cfg["max_seed_tracks"]), lowered)
            answers = {i: low.answer(np.asarray(win.sets[i], dtype=np.int64)) for i in sample}
        numbers = ref.compare(judge, win.sets, answers, sample, answer_cfg["limits"])
        numbers["never_answered"] = win.never
        correct, checked = ref.verdict(numbers, answer_cfg["limits"])
        log(f"[check] {len(sample)} answers judged in {time.monotonic() - t:.1f}s "
            f"({int(lengths[sample].sum()) if len(sample) else 0} seeds, longest "
            f"{int(lengths[sample].max()) if len(sample) else 0}); only the rule family "
            f"could have chosen {numbers['rule_only_tracks']} of {numbers['served_tracks']} "
            f"served tracks, in {numbers['answers_with_rule_only']} answers")
        return correct and len(sample) > 0, checked
