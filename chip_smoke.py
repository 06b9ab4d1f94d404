#!/usr/bin/env python3
"""chip_smoke.py — does mine → publish → serve still start on the chip?

Drives the system's main path once through the entry points a user runs:

- *mine*    ``python -m kmlserver_tpu.mining.job`` on a seeded ds2-shaped CSV
            (2,246 playlists × 2,171 tracks, ``KMLS_EMBED_ENABLED=1``); the
            rule tensors must equal ``tests/oracle.py``'s brute-force answer
            on the same table and the count path must be a device path;
- *serve*   ``python -m kmlserver_tpu.serving.server`` on that PVC, default
            front end and layout, answer cache off: requests over ONE
            keep-alive connection (every seed-length bucket) plus a
            concurrent burst, judged by the source counters, the per-kernel
            device seconds and the compile counter in ``/metrics``, then a
            SIGTERM with the connection still open;
- *cache*   the same server started a second time must add nothing to the
            compile-cache directory; it runs with ``KMLS_HYBRID_MODE=rules``
            (same warm-up set) so its answers can be checked EXACTLY against
            a numpy max-merge/top-k over the published tensors;
- *kernels* the Pallas popcount kernel compiled with ``interpret=False`` at
            a grid with ≥ 2 steps on every axis, against the MXU path and
            ``x @ x.T``; the embedding lookup against float64 numpy;
- with ≥ 4 devices: the same mine under the default ``auto`` mesh (must
  shard, tensors equal), the same serve with ``KMLS_MODEL_LAYOUT=sharded``
  (answers identical, every device holds its slab), and every replica of the
  default layout dispatched under the burst.

The parent never imports JAX: a chip belongs to one process at a time, so it
runs children one after another, all sharing one compile cache
(``kmlserver_tpu/utils/jaxcache.py``). Without ``--tiny-cpu``, finding no TPU
is exit 3 and no result line — never a CPU run. ``--tiny-cpu`` runs the same
flow at a toy size on four virtual CPU devices with the kernel interpreted,
prints no timing, and is what tier-1 calls.

Last stdout line, exactly: ``{"ok": true, "device": {"platform": …, "kind":
…, "count": …}}`` — the device as the children's JAX reported it. The line
before it is ``[summary] platform=… {…, "claim": null}`` with the legs, count
path, sources, cache entries and set-up seconds. Exit 0 only if every leg
passed.
"""

from __future__ import annotations

import argparse
import atexit
import http.client
import json
import os
import pickle
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
EXIT_LEG_FAILED = 1
EXIT_NOT_A_CHECKOUT = 2
EXIT_NO_ACCELERATOR = 3

SEED = 21
TINY_SHAPE = dict(n_playlists=300, n_tracks=120, target_rows=6000)
MIN_SUPPORT = 0.05
K_BEST = 10  # ServingConfig default (K_BEST_TRACKS)
# bf16 MXU passes under a float32 matmul of unit vectors: 8 mantissa bits,
# |Σ aᵢbᵢ| ≤ 1, so a similarity is off by at most ~2⁻⁸; doubled for margin
SIM_TOL = 2.0 ** -7

# every child states its device through parallel.mesh.describe_devices()
_DEVICE_RE = re.compile(r"platform=(\S+) device_kind=(.+?) count=(\d+)\s*$", re.M)
_PROBE = (
    "from kmlserver_tpu.parallel.mesh import describe_devices; "
    "print(describe_devices())"
)


class LegFailed(Exception):
    pass


def _check(cond: bool, why: str) -> None:
    if not cond:
        raise LegFailed(why)


# ---------------------------------------------------------------- children

_LIVE: "set[subprocess.Popen]" = set()


def _spawn(argv: list[str], env: dict, log_path: str) -> subprocess.Popen:
    """Start a child in its own process group, output to ``log_path``."""
    log = open(log_path, "wb")
    try:
        proc = subprocess.Popen(
            argv, stdout=log, stderr=subprocess.STDOUT, env=env, cwd=REPO,
            start_new_session=True,
        )
    finally:
        log.close()  # the child holds its own descriptor
    _LIVE.add(proc)
    return proc


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
    _LIVE.discard(proc)


def _kill_all(*_args) -> None:
    for proc in list(_LIVE):
        _kill(proc)


def _on_signal(signum, _frame) -> None:
    _kill_all()
    sys.exit(128 + signum)


def _read(path: str) -> str:
    with open(path, "r", errors="replace") as f:
        return f.read()


def _tail(text: str, n: int = 25) -> str:
    return "\n".join(text.strip().splitlines()[-n:])


def _run(name: str, argv: list[str], env: dict, log_path: str,
         timeout: float) -> tuple[int, str]:
    """Run one child to its end → (exit code, its output)."""
    proc = _spawn(argv, env, log_path)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _kill(proc)
        raise LegFailed(
            f"{name}: no exit within {timeout:.0f}s\n{_tail(_read(log_path))}"
        ) from None
    _LIVE.discard(proc)
    return rc, _read(log_path)


def _device_of(text: str, who: str) -> dict:
    m = _DEVICE_RE.search(text)
    _check(m is not None, f"{who}: printed no device line\n{_tail(text)}")
    return {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}


def _fmt(device: dict) -> str:
    return (
        f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}"
    )


# ------------------------------------------------------------------ the run


class Smoke:
    def __init__(self, tiny: bool, logs: str | None = None):
        from kmlserver_tpu.utils import jaxcache

        self.tiny = tiny
        self.want_platform = "cpu" if tiny else "tpu"
        self.work = tempfile.mkdtemp(prefix="kmls_smoke_")  # the PVCs
        # child logs go where the caller says and stay there (a chip
        # machine is thrown away — its logs come back only from its output
        # directory); otherwise next to the PVCs, kept only on failure
        self.logs = logs or self.work
        os.makedirs(self.logs, exist_ok=True)
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = REPO + os.pathsep + self.env.get("PYTHONPATH", "")
        # one cache for every child; failing to create it is an error here
        self.env.update(jaxcache.child_env())
        self.cache_dir = self.env[jaxcache.ENV_VAR]
        if tiny:
            self.env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                # on a CPU backend the miner's default is its host twin;
                # the smoke is about the jitted device paths
                "KMLS_NATIVE_PAIR_COUNTS": "0",
                "KMLS_SERVE_DEVICES": "4",
                # toy warm-up grid: 2 length × 3 batch buckets
                "KMLS_BATCH_MAX_SIZE": "4",
                "KMLS_MAX_SEED_TRACKS": "8",
                # 3% of 120 tracks would leave nothing to seed from
                "TOP_TRACKS_SAVE_PERCENTILE": "0.2",
            })
        self.device: dict | None = None
        self.setup_s: dict[str, float] = {}
        self.summary: dict = {}
        self.passed: list[str] = []
        self.failed: list[str] = []

    # ---- plumbing

    def say(self, leg: str, device: dict, msg: str) -> None:
        print(f"[{leg}] {_fmt(device)} {msg}", flush=True)

    def check_device(self, device: dict, who: str) -> None:
        _check(
            device["platform"] == self.want_platform,
            f"{who} ran on {_fmt(device)}, wanted {self.want_platform}",
        )
        _check(
            device == self.device,
            f"{who} saw {_fmt(device)}, the probe saw {_fmt(self.device)}",
        )

    def log_path(self, name: str) -> str:
        return os.path.join(self.logs, f"{name}.log")

    def leg(self, name: str, fn) -> bool:
        try:
            fn()
        except LegFailed as exc:
            self.failed.append(name)
            print(f"[{name}] FAILED: {exc}", file=sys.stderr, flush=True)
            return False
        self.passed.append(name)
        return True

    # ---- probe

    def probe(self) -> None:
        """Exit 3 with nothing on stdout unless JAX finds the wanted
        platform — the smoke never continues on another device."""
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE], env=self.env, cwd=REPO,
            capture_output=True, text=True, timeout=600,
        )
        if proc.returncode != 0 or not _DEVICE_RE.search(proc.stdout):
            print(
                f"chip_smoke: JAX found no usable device (exit "
                f"{proc.returncode}):\n{_tail(proc.stderr)}", file=sys.stderr,
            )
            sys.exit(EXIT_NO_ACCELERATOR)
        device = _device_of(proc.stdout, "probe")
        if device["platform"] != self.want_platform:
            print(
                f"chip_smoke: JAX reports {_fmt(device)}; this run needs "
                f"platform={self.want_platform}"
                + ("" if self.tiny else " (use --tiny-cpu for the CPU dry run)"),
                file=sys.stderr,
            )
            sys.exit(EXIT_NO_ACCELERATOR)
        self.device = device

    # ---- mine

    def make_table(self):
        from kmlserver_tpu.data.synthetic import DS2_SHAPE, synthetic_table

        return synthetic_table(**(TINY_SHAPE if self.tiny else DS2_SHAPE), seed=SEED)

    def mine(self, name: str, table, extra_env: dict) -> dict:
        """Seed a PVC, run the job entry point → the published tensors."""
        from kmlserver_tpu.data.csv import write_tracks_csv

        base = os.path.join(self.work, f"pvc_{name}")
        os.makedirs(os.path.join(base, "datasets"))
        write_tracks_csv(
            os.path.join(base, "datasets", "2023_spotify_ds1.csv"), table
        )
        env = dict(
            self.env, BASE_DIR=base, DATASETS_DIR=os.path.join(base, "datasets"),
            MIN_SUPPORT=str(MIN_SUPPORT), KMLS_EMBED_ENABLED="1", **extra_env,
        )
        t0 = time.monotonic()
        rc, out = _run(
            name, [sys.executable, "-m", "kmlserver_tpu.mining.job"], env,
            self.log_path(name), timeout=900,
        )
        self.setup_s[f"{name}_job"] = time.monotonic() - t0
        _check(rc == 0, f"{name}: job exited {rc}\n{_tail(out)}")
        device = _device_of(out, name)
        self.check_device(device, name)
        m = re.search(r"^Pair-count path: (\S+)", out, re.M)
        _check(m is not None, f"{name}: no 'Pair-count path:' line\n{_tail(out)}")
        path = m.group(1)
        m = re.search(r"\(CSV loader: (\w+)\)", out)
        loader = m.group(1) if m else "unknown"
        _check(
            "ALS embeddings trained" in out,
            f"{name}: the ALS phase did not train\n{_tail(out)}",
        )
        pickles = os.path.join(base, "pickles")
        _check(
            os.path.exists(os.path.join(pickles, "embeddings.npz")),
            f"{name}: embeddings.npz was not published",
        )
        npz_path = os.path.join(pickles, "recommendations.pickle.tensors.npz")
        with np.load(npz_path, allow_pickle=True) as npz:
            tensors = {k: npz[k] for k in npz.files}
        return {
            "base": base, "device": device, "path": path, "loader": loader,
            "tensors": tensors,
        }

    def leg_mine(self) -> None:
        table = self.make_table()
        # the brute-force oracle is plain Python: run it here, on the FULL
        # table, while the job holds the chip
        oracle_box: dict = {}
        oracle_thread = threading.Thread(
            target=lambda: oracle_box.update(rules=_oracle_rules(table)),
            daemon=True,
        )
        oracle_thread.start()
        # with several devices the default mesh shards; the first mine is
        # the one-device program, the reference for the sharded one below
        many = self.device["count"] > 1
        try:
            mined = self.mine(
                "mine", table, {"KMLS_MESH_SHAPE": "1x1"} if many else {}
            )
        finally:
            # the mine's own limit again: no wait here is without one
            oracle_thread.join(timeout=900)
        _check(
            "rules" in oracle_box,
            "mine: the oracle thread died or ran past 900 s",
        )
        _check(
            mined["path"] == "dense-fused",
            f"mine: Pair-count path {mined['path']!r}, wanted the device "
            "path 'dense-fused' ('native-cpu' would be the host twin)",
        )
        n_rules = _compare_with_oracle(mined["tensors"], oracle_box["rules"])
        t = mined["tensors"]
        self.mined = mined
        self.summary["count_path"] = mined["path"]
        self.summary["csv_loader"] = mined["loader"]
        self.say(
            "mine", mined["device"],
            f"ok count_path={mined['path']} csv_loader={mined['loader']} "
            f"als=trained rule_tensors={t['rule_ids'].shape[0]}x"
            f"{t['rule_ids'].shape[1]} rules={n_rules} equal to "
            "tests/oracle.py reference_fast_rules on the full table "
            "(itemsets up to pairs: a larger itemset cannot raise a "
            "max-merged support)",
        )
        if self.device["count"] >= 4:
            sharded = self.mine("mine_mesh", table, {})
            _check(
                sharded["path"].startswith("sharded-"),
                f"mine_mesh: Pair-count path {sharded['path']!r} under the "
                "default auto mesh, wanted 'sharded-…'",
            )
            for key in ("vocab", "rule_ids", "rule_counts", "item_counts"):
                _check(
                    np.array_equal(sharded["tensors"][key], t[key]),
                    f"mine_mesh: {key} differs from the one-device run",
                )
            self.summary["count_path_mesh"] = sharded["path"]
            self.say(
                "mine_mesh", sharded["device"],
                f"ok count_path={sharded['path']} tensors equal to the "
                "one-device run",
            )

    # ---- serve

    def request_plan(self, base: str) -> list[list[str]]:
        """A few dozen seed sets from best_tracks.pickle, every
        seed-length bucket (1, 8, 32, 128) at least once."""
        with open(os.path.join(base, "pickles", "best_tracks.pickle"), "rb") as f:
            names = [row["track_name"] for row in pickle.load(f)]
        _check(len(names) >= 8, f"only {len(names)} best tracks to seed from")
        cap = 8 if self.tiny else 128
        lengths = [n for n in (1, 1, 1, 3, 5, 8, 12, 20, 32, 40, len(names))
                   if n <= min(cap, len(names))]
        plan = []
        for i in range(36):
            n = lengths[i % len(lengths)]
            plan.append([names[(i + j) % len(names)] for j in range(n)])
        return plan

    def leg_serve(self) -> None:
        base = self.mined["base"]
        plan = self.request_plan(base)
        ref = _Reference(base)
        many = self.device["count"] > 1
        entries_before = _cache_entries(self.cache_dir)

        srv = _Server(self, "serve", base, {})
        answers = srv.ask_all(plan)
        burst = srv.burst(plan, threads=16, rounds=4)
        metrics = srv.metrics()
        dispatch = _series(metrics, "kmls_device_dispatch_total")
        srv.sigterm()  # the keep-alive connection is still open
        self.setup_s["serve_cold_start"] = srv.ready_s

        sources = _series(metrics, "kmls_requests_by_source")
        n_req = len(plan) + burst
        _check(
            sources.get("hybrid", 0) + sources.get("rules", 0) == n_req
            and sources.get("fallback", 0) == 0,
            f"serve: sources {sources} for {n_req} requests (wanted all "
            "hybrid/rules, fallback 0)",
        )
        seconds = _series(metrics, "kmls_kernel_device_seconds")
        for kernel in ("serve_rules", "embed_topk"):
            _check(
                seconds.get(kernel, 0.0) > 0.0,
                f"serve: kmls_kernel_device_seconds{{{kernel}}} did not "
                f"move: {seconds}",
            )
        compiles = _series(metrics, "kmls_compiles_total")
        _check(
            bool(compiles) and all(v == 0 for v in compiles.values()),
            f"serve: compiles after warm-up {compiles}, wanted every "
            "watched kernel at 0",
        )
        for seeds, songs in zip(plan, answers):
            ref.check_hybrid(seeds, songs)
        if many:
            _check(
                len(dispatch) == self.device["count"]
                and all(v > 0 for v in dispatch.values()),
                f"serve: per-replica dispatches {dispatch}, wanted "
                f"{self.device['count']} replicas all > 0",
            )
        entries_first = _cache_entries(self.cache_dir)
        _check(entries_first, f"serve: {self.cache_dir} is empty after a start")
        self.serve_plan, self.serve_answers, self.ref = plan, answers, ref
        self.cache_before, self.cache_first = entries_before, entries_first
        self.summary.update(
            sources=sources, compiles_after_warmup=sum(compiles.values()),
        )
        self.say(
            "serve", srv.device,
            f"ok requests={n_req} all 200 sources={json.dumps(sources)} "
            f"kernels_moved=serve_rules,embed_topk compiles_after_warmup=0 "
            f"hybrid answers within bf16 tolerance of numpy "
            + (f"replica_dispatches={list(dispatch.values())} " if many else "")
            + "sigterm_exit=0 with a keep-alive client attached",
        )

    def leg_cache(self) -> None:
        """Second start of the same server: nothing new to compile. Runs
        rules-only so every answer has an exact numpy reference."""
        srv = _Server(self, "serve_again", self.mined["base"],
                      {"KMLS_HYBRID_MODE": "rules"})
        answers = srv.ask_all(self.serve_plan)
        metrics = srv.metrics()
        srv.sigterm()
        self.setup_s["serve_warm_start"] = srv.ready_s
        entries_second = _cache_entries(self.cache_dir)
        new = sorted(entries_second - self.cache_first)
        _check(
            not new,
            f"cache: the second start added {len(new)} entries to "
            f"{self.cache_dir}: {new[:5]}",
        )
        sources = _series(metrics, "kmls_requests_by_source")
        _check(
            sources.get("rules", 0) == len(self.serve_plan)
            and sum(sources.values()) == len(self.serve_plan),
            f"cache: sources {sources}, wanted {len(self.serve_plan)} rules",
        )
        tie_order = True
        for seeds, songs in zip(self.serve_plan, answers):
            tie_order &= self.ref.check_rules(seeds, songs)
        self.summary["cache_entries"] = {
            "before": len(self.cache_before), "after_first_start":
            len(self.cache_first), "after_second_start": len(entries_second),
        }
        self.say(
            "cache", srv.device,
            f"ok cache_dir={self.cache_dir} entries before/first/second="
            f"{len(self.cache_before)}/{len(self.cache_first)}/"
            f"{len(entries_second)} (second start added none) "
            f"rules-only answers={len(answers)} equal to numpy "
            f"max-merge/top-k (ties in index order: {tie_order})",
        )

    def leg_serve_sharded(self) -> None:
        n = self.device["count"]
        srv = _Server(self, "serve_sharded", self.mined["base"],
                      {"KMLS_MODEL_LAYOUT": "sharded"})
        answers = srv.ask_all(self.serve_plan)
        metrics = srv.metrics()
        srv.sigterm()
        _check(
            f"layout sharded ({n} shard(s))" in srv.output(),
            f"serve_sharded: the bundle did not publish sharded over {n}",
        )
        _check(
            answers == self.serve_answers,
            "serve_sharded: answers differ from the default layout's",
        )
        shard_hits = _series(metrics, "kmls_shard_dispatch_total")
        _check(
            len(shard_hits) == n and sum(shard_hits.values()) > 0,
            f"serve_sharded: shard dispatch counters {shard_hits}",
        )
        self.say(
            "serve_sharded", srv.device,
            f"ok shards={n} answers identical to the default layout "
            f"({len(answers)} requests) shard_seed_hits="
            f"{list(shard_hits.values())} sigterm_exit=0",
        )
        # placement is only visible from inside a process
        out = self.child("placement", self.mined["base"])
        self.say(
            "placement", out["device"],
            f"ok rule rows per device={out['rows_per_device']} on "
            f"{len(out['devices'])} distinct devices bytes_in_use="
            f"{out['bytes_in_use']}",
        )

    # ---- in-process children (this file, --child)

    def child(self, what: str, *args: str) -> dict:
        argv = [sys.executable, os.path.abspath(__file__), "--child", what, *args]
        if self.tiny:
            argv.append("--tiny-cpu")
        rc, out = _run(what, argv, self.env, self.log_path(what), timeout=900)
        _check(rc == 0, f"{what}: exit {rc}\n{_tail(out)}")
        m = re.search(r"^RESULT (\{.*\})$", out, re.M)
        _check(m is not None, f"{what}: printed no result\n{_tail(out)}")
        result = json.loads(m.group(1))
        result["device"] = _device_of(out, what)
        self.check_device(result["device"], what)
        return result

    def leg_kernels(self) -> None:
        out = self.child("kernels")
        self.summary["popcount"] = out["popcount"]
        self.say(
            "kernels", out["device"],
            f"ok popcount variant={out['popcount']['variant']} "
            f"interpret={out['popcount']['interpret']} grid="
            f"{out['popcount']['grid']} counts equal to "
            "mxu_pair_counts_padded and to x@x.T on a sub-block; "
            f"embed_topk {out['embed']['shape']} within {SIM_TOL:g} of numpy",
        )

    # ---- the flow

    def run(self) -> int:
        self.probe()
        if self.leg("mine", self.leg_mine):
            if self.leg("serve", self.leg_serve):
                self.leg("cache", self.leg_cache)
                if self.device["count"] >= 4:
                    self.leg("serve_sharded", self.leg_serve_sharded)
        self.leg("kernels", self.leg_kernels)
        ok = not self.failed
        summary = {"legs": self.passed}
        if self.failed:
            summary["failed"] = self.failed
        summary.update(self.summary)
        if self.tiny:
            summary["tiny_cpu"] = True
        else:
            # set-up time, not a metric: process start, compile, warm-up
            summary["setup_seconds"] = {
                k: round(v, 1) for k, v in self.setup_s.items()
            }
        summary["claim"] = None
        self.say("summary", self.device, json.dumps(summary))
        # the verdict line is an interface: these two keys and no others
        print(json.dumps({"ok": ok, "device": self.device}), flush=True)
        return 0 if ok else EXIT_LEG_FAILED


# ------------------------------------------------------------------- server


class _Server:
    """One run of the server entry point, driven over HTTP."""

    def __init__(self, smoke: Smoke, name: str, base: str, extra_env: dict):
        self.name = name
        self.log = smoke.log_path(name)
        env = dict(
            smoke.env, BASE_DIR=base, KMLS_PORT="0", KMLS_CACHE_ENABLED="0",
            POLLING_WAIT_IN_MINUTES="60", **extra_env,
        )
        t0 = time.monotonic()
        self.proc = _spawn(
            [sys.executable, "-m", "kmlserver_tpu.serving.server"], env,
            self.log,
        )
        self.port = self._await(
            r"serving on \S+?:(\d+)", 600, "did not bind a port"
        )
        self.device = _device_of(self.output(), name)
        smoke.check_device(self.device, name)
        self._await_ready(900)
        self.ready_s = time.monotonic() - t0
        self.conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)

    def output(self) -> str:
        return _read(self.log)

    def _await(self, pattern: str, timeout: float, why: str) -> int:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = re.search(pattern, self.output())
            if m:
                return int(m.group(1))
            if self.proc.poll() is not None:
                break
            time.sleep(0.2)
        _kill(self.proc)
        raise LegFailed(f"{self.name}: {why}\n{_tail(self.output())}")

    def _await_ready(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        status = None
        while time.monotonic() < deadline and self.proc.poll() is None:
            try:
                conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=10)
                conn.request("GET", "/readyz")
                resp = conn.getresponse()
                status = json.loads(resp.read()).get("status")
                conn.close()
                if resp.status == 200:
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        if status != "ready":
            # "degraded" is the server doing its job (last-good, rules-only)
            # — and exactly what a bring-up check must not wave through
            _kill(self.proc)
            raise LegFailed(
                f"{self.name}: /readyz says {status!r}, wanted 'ready'\n"
                f"{_tail(self.output())}"
            )

    @staticmethod
    def _post(conn: http.client.HTTPConnection, seeds: list[str]) -> list[str]:
        conn.request(
            "POST", "/api/recommend/", body=json.dumps({"songs": seeds}),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        body = resp.read()
        _check(resp.status == 200, f"HTTP {resp.status}: {body[:200]!r}")
        songs = json.loads(body)["songs"]
        _check(bool(songs), f"empty answer for {len(seeds)} seeds")
        return songs

    def ask_all(self, plan: list[list[str]]) -> list[list[str]]:
        """Every request over the ONE keep-alive connection."""
        try:
            return [self._post(self.conn, seeds) for seeds in plan]
        except (OSError, http.client.HTTPException, LegFailed) as exc:
            _kill(self.proc)
            raise LegFailed(f"{self.name}: {exc}\n{_tail(self.output())}") from None

    def burst(self, plan: list[list[str]], threads: int, rounds: int) -> int:
        """Concurrent requests, one connection per thread, so batches
        larger than one form and every replica gets work."""
        errors: list[str] = []

        def worker(k: int) -> None:
            conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
            try:
                for r in range(rounds):
                    self._post(conn, plan[(k * rounds + r) % len(plan)])
            except (OSError, http.client.HTTPException, LegFailed) as exc:
                errors.append(str(exc))
            finally:
                conn.close()

        pool = [threading.Thread(target=worker, args=(k,)) for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=300)
        if errors or any(t.is_alive() for t in pool):
            _kill(self.proc)
            raise LegFailed(f"{self.name}: burst failed: {errors[:3]}")
        return threads * rounds

    def metrics(self) -> str:
        self.conn.request("GET", "/metrics")
        resp = self.conn.getresponse()
        text = resp.read().decode()
        _check(resp.status == 200, f"{self.name}: /metrics HTTP {resp.status}")
        return text

    def sigterm(self) -> None:
        """SIGTERM with ``self.conn`` still open: the server must exit 0
        within the drain settle (2 s default) plus process teardown — one
        that lingers keeps the chip."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            _kill(self.proc)
            raise LegFailed(
                f"{self.name}: still running 60 s after SIGTERM with a "
                f"keep-alive client attached\n{_tail(self.output())}"
            ) from None
        finally:
            self.conn.close()
        _LIVE.discard(self.proc)
        _check(
            rc == 0,
            f"{self.name}: exit {rc} on SIGTERM\n{_tail(self.output())}",
        )


def _series(metrics: str, name: str) -> dict:
    """``name{label="x"} v`` lines → {x: v}."""
    out = {}
    for m in re.finditer(
        rf'^{name}\{{\w+="([^"]*)"\}} (\S+)$', metrics, re.M
    ):
        value = float(m.group(2))
        out[m.group(1)] = int(value) if value.is_integer() else value
    return out


def _cache_entries(path: str) -> set[str]:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


# --------------------------------------------------------------- references


def _oracle_rules(table) -> dict:
    from tests import oracle

    baskets: dict[int, list[str]] = {}
    for pid, name in zip(table.pid.tolist(), table.track_name.tolist()):
        baskets.setdefault(pid, []).append(name)
    return oracle.reference_fast_rules(
        list(baskets.values()), MIN_SUPPORT, max_len=2
    )


def _compare_with_oracle(tensors: dict, oracle_rules: dict) -> int:
    """Published rule tensors vs the brute-force rule dict → rule count.
    A row the K_max capacity truncated must hold the top of the oracle's
    row: same values, nothing kept below something dropped."""
    vocab = [str(s) for s in tensors["vocab"]]
    n_playlists = int(tensors["n_playlists"])
    ids, counts = tensors["rule_ids"], tensors["rule_counts"]
    k_max = ids.shape[1]
    keys = {
        vocab[i] for i, c in enumerate(tensors["item_counts"].tolist())
        if c / n_playlists >= MIN_SUPPORT
    }
    _check(
        keys == set(oracle_rules),
        f"mine: {len(keys)} rule keys, the oracle has {len(oracle_rules)}",
    )
    n_rules = 0
    for i, name in enumerate(vocab):
        if name not in keys:
            continue
        live = ids[i] >= 0
        row = {
            vocab[j]: c / n_playlists
            for j, c in zip(ids[i][live].tolist(), counts[i][live].tolist())
        }
        want = oracle_rules[name]
        _check(
            len(row) == min(len(want), k_max),
            f"mine: row {name!r} has {len(row)} rules, oracle {len(want)}",
        )
        for other, conf in row.items():
            _check(
                want.get(other) == conf,
                f"mine: {name!r}->{other!r} is {conf}, oracle {want.get(other)}",
            )
        dropped = [c for o, c in want.items() if o not in row]
        _check(
            not dropped or min(row.values()) >= max(dropped),
            f"mine: row {name!r} dropped a rule above one it kept",
        )
        n_rules += len(row)
    return n_rules


class _Reference:
    """Plain numpy answers from the published artifacts."""

    def __init__(self, base: str):
        from kmlserver_tpu.io import artifacts

        pickles = os.path.join(base, "pickles")
        with np.load(
            os.path.join(pickles, "recommendations.pickle.tensors.npz"),
            allow_pickle=True,
        ) as npz:
            self.vocab = [str(s) for s in npz["vocab"]]
            self.rule_ids = npz["rule_ids"]
            n_playlists = int(npz["n_playlists"])
            # ops.rules.derive_confs, support mode
            _check(str(npz["mode"]) == "support", "reference expects support mode")
            self.rule_confs = (
                npz["rule_counts"].astype(np.float64) / n_playlists
            ).astype(np.float32)
            self.known = npz["item_counts"] / n_playlists >= float(npz["min_support"])
        self.index = {n: i for i, n in enumerate(self.vocab)}
        emb = artifacts.load_embeddings(os.path.join(pickles, "embeddings.npz"))
        self.emb_vocab = emb["vocab"]
        self.emb_index = {n: i for i, n in enumerate(self.emb_vocab)}
        self.factors = emb["item_factors"].astype(np.float64)

    def rule_scores(self, seeds: list[str]):
        """Max-merge of the known seeds' rule rows → (V,) float32."""
        scores = np.zeros(len(self.vocab), np.float32)
        for s in seeds:
            i = self.index.get(s)
            if i is None or not self.known[i]:
                continue
            live = self.rule_ids[i] >= 0
            np.maximum.at(scores, self.rule_ids[i][live], self.rule_confs[i][live])
        return scores

    def check_rules(self, seeds: list[str], songs: list[str]) -> bool:
        """Exact: the answer's confidences are the top-k confidences.
        → whether ties also came back in index order (``lax.top_k``'s
        order on the backends seen so far)."""
        scores = self.rule_scores(seeds)
        order = np.argsort(-scores, kind="stable")[:K_BEST]
        order = order[scores[order] > 0]
        want = [self.vocab[i] for i in order]
        _check(
            len(songs) == len(want) and len(set(songs)) == len(songs)
            and all(s in self.index for s in songs),
            f"rules answer {songs} vs reference {want}",
        )
        got_scores = [float(scores[self.index[s]]) for s in songs]
        _check(
            got_scores == [float(scores[i]) for i in order],
            f"rules answer {songs} scores {got_scores}, reference {want}",
        )
        return songs == want

    def check_hybrid(self, seeds: list[str], songs: list[str]) -> None:
        """Every returned song is one of the rule top-k or within the bf16
        tolerance of the embedding top-k — the blend can only pick from
        those two lists."""
        _check(
            len(songs) == K_BEST and len(set(songs)) == K_BEST,
            f"hybrid answer has {len(songs)} songs: {songs}",
        )
        scores = self.rule_scores(seeds)
        order = np.argsort(-scores, kind="stable")[:K_BEST]
        allowed = {self.vocab[i] for i in order if scores[i] > 0}
        seed_ids = [self.emb_index[s] for s in seeds if s in self.emb_index]
        sims = (self.factors[seed_ids] @ self.factors.T).max(axis=0)
        sims[seed_ids] = -np.inf
        kth = np.sort(sims)[-K_BEST]
        allowed |= {
            self.emb_vocab[i] for i in np.flatnonzero(sims >= kth - 2 * SIM_TOL)
        }
        stray = [s for s in songs if s not in allowed]
        _check(not stray, f"hybrid answer holds {stray}: in neither top-k")


# ---------------------------------------------------- children that use JAX


def child_kernels(tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from kmlserver_tpu.ops import popcount
    from kmlserver_tpu.ops.embed import embed_topk, factor_table
    from kmlserver_tpu.utils.jaxcache import enable_compilation_cache

    enable_compilation_cache()
    # ---- the Pallas popcount kernel: default variant and tiles, a grid
    # with >= 2 steps on every axis (the accumulate-across-chunks branch)
    ti, tj, wk = popcount.resolve_tiles()
    variant, swar = popcount.resolve_kernel_opts(None, None)
    n_playlists, n_tracks = (2 * 32 * wk, 200) if tiny else (65536, 2171)
    v_pad, w_pad = popcount.padded_shape(n_tracks, n_playlists)
    grid = (v_pad // ti, v_pad // tj, w_pad // wk)
    if min(grid) < 2:
        raise SystemExit(f"grid {grid} has a one-step axis")
    rng = np.random.default_rng(SEED)
    bt_host = rng.integers(0, 2**32, size=(v_pad, w_pad), dtype=np.uint32)
    bt_host &= rng.integers(0, 2**32, size=(v_pad, w_pad), dtype=np.uint32)
    bt_host[n_tracks:] = 0
    bt = jnp.asarray(bt_host)
    interpret = tiny  # explicit either way: compiled on the chip
    got = np.asarray(popcount.popcount_pair_counts_padded(
        bt, interpret=interpret, variant=variant, swar=swar,
    ))
    want = np.asarray(popcount.mxu_pair_counts_padded(bt))
    if not np.array_equal(got, want):
        raise SystemExit(
            f"popcount {variant} differs from the MXU path in "
            f"{int((got != want).sum())} cells"
        )
    block = slice(0, 64)
    bits = np.unpackbits(
        bt_host[block].view(np.uint8), axis=1, bitorder="little"
    ).astype(np.int64)
    if not np.array_equal(got[block, block], bits @ bits.T):
        raise SystemExit("popcount differs from x @ x.T on the sub-block")

    # ---- the embedding lookup at the served width, against float64 numpy
    v, rank, b, length = (120, 8, 4, 8) if tiny else (2171, 32, 32, 8)
    factors = rng.standard_normal((v, rank)).astype(np.float32)
    factors /= np.linalg.norm(factors, axis=1, keepdims=True)
    seeds = rng.integers(0, v, size=(b, length)).astype(np.int32)
    seeds[:, length // 2:] = -1
    ids, sims = embed_topk(factor_table(factors), jnp.asarray(seeds), k_best=K_BEST)
    ids, sims = np.asarray(ids), np.asarray(sims)
    if not np.isfinite(sims).all() or ids.shape != (b, K_BEST):
        raise SystemExit(f"embed_topk returned {ids.shape}, finite={np.isfinite(sims).all()}")
    f64 = factors.astype(np.float64)
    for r in range(b):
        live = seeds[r][seeds[r] >= 0]
        ref = (f64[live] @ f64.T).max(axis=0)
        ref[live] = -np.inf
        kth = np.sort(ref)[-K_BEST]
        if (np.abs(sims[r] - ref[ids[r]]) > SIM_TOL).any() or (
            ref[ids[r]] < kth - 2 * SIM_TOL
        ).any():
            raise SystemExit(f"embed_topk row {r} outside {SIM_TOL:g} of numpy")
    return {
        "popcount": {
            "variant": variant, "swar": swar, "interpret": interpret,
            "tiles": [ti, tj, wk], "grid": list(grid),
            "shape": [n_playlists, n_tracks],
        },
        "embed": {"shape": [v, rank, b, length]},
    }


def child_placement(tiny: bool, base: str) -> dict:
    """Sharded layout seen from inside: where do the rule rows live?"""
    import dataclasses

    import jax

    from kmlserver_tpu.config import ServingConfig
    from kmlserver_tpu.serving.engine import RecommendEngine
    from kmlserver_tpu.utils.jaxcache import enable_compilation_cache

    enable_compilation_cache()
    os.environ["BASE_DIR"] = base
    cfg = dataclasses.replace(ServingConfig.from_env(), model_layout="sharded")
    engine = RecommendEngine(cfg)
    if not engine.load():
        raise SystemExit(f"engine.load() failed: {engine.last_load_error}")
    bundle = engine.bundle
    n = len(jax.devices())
    shards = bundle.rule_ids.addressable_shards
    rows = sorted({s.data.shape[0] for s in shards})
    devices = sorted({s.device.id for s in shards})
    v_pad = bundle.rule_ids.shape[0]
    if bundle.layout != "sharded" or rows != [v_pad // n] or len(devices) != n:
        raise SystemExit(
            f"layout {bundle.layout}: shard rows {rows} on devices {devices}, "
            f"wanted {v_pad // n} rows on each of {n}"
        )
    slab = (v_pad // n) * bundle.rule_ids.shape[1] * 8  # ids + confs
    in_use = []
    for dev in jax.devices():
        stats = dev.memory_stats()
        if stats is None:  # CPU: no allocator statistics to read
            in_use.append(None)
            continue
        in_use.append(int(stats["bytes_in_use"]))
        if in_use[-1] < slab:
            raise SystemExit(
                f"device {dev.id} holds {in_use[-1]} bytes, less than one "
                f"{slab}-byte slab"
            )
    return {"rows_per_device": rows[0], "devices": devices, "bytes_in_use": in_use}


def _child_main(what: str, args: list[str], tiny: bool) -> int:
    from kmlserver_tpu.parallel.mesh import describe_devices

    print(f"Devices: {describe_devices()}", flush=True)
    result = {"kernels": child_kernels, "placement": child_placement}[what](
        tiny, *args
    )
    print("RESULT " + json.dumps(result), flush=True)
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--tiny-cpu", action="store_true",
        help="the same flow at a toy size on virtual CPU devices, kernel "
        "interpreted, no timing (tier-1)",
    )
    parser.add_argument(
        "--logs", metavar="DIR",
        help="write the children's logs to DIR and keep them (e.g. "
        "chiprun_out/smoke_logs)",
    )
    parser.add_argument("--child", nargs="+", help=argparse.SUPPRESS)
    opts = parser.parse_args()
    sys.path.insert(0, REPO)
    try:
        import kmlserver_tpu
        from tests import oracle  # noqa: F401
    except ImportError as exc:
        print(
            f"chip_smoke: {REPO} is not a checkout of the repo ({exc})",
            file=sys.stderr,
        )
        return EXIT_NOT_A_CHECKOUT
    if os.path.dirname(os.path.abspath(kmlserver_tpu.__file__)) != os.path.join(
        REPO, "kmlserver_tpu"
    ):
        print(
            f"chip_smoke: kmlserver_tpu resolves to {kmlserver_tpu.__file__}, "
            f"not to the checkout at {REPO}", file=sys.stderr,
        )
        return EXIT_NOT_A_CHECKOUT
    if opts.child:
        return _child_main(opts.child[0], opts.child[1:], opts.tiny_cpu)
    atexit.register(_kill_all)
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)
    smoke = Smoke(opts.tiny_cpu, opts.logs)
    try:
        return smoke.run()
    finally:
        _kill_all()
        if smoke.failed and smoke.logs == smoke.work:
            print(f"chip_smoke: child logs kept in {smoke.work}", file=sys.stderr)
        else:
            shutil.rmtree(smoke.work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
