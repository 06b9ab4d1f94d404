"""Two live serving replicas off one PVC — the reference's production
topology (kubernetes/deployment.yaml:10 runs 3 API replicas against the
shared data volume). The multi-replica story
(shared invalidation token, independent hot-swap, identical static
fallback via the stable seed) was asserted piecewise; this exercises it
whole — two real server processes, one artifact dir, a mid-test re-mine,
zero downtime."""

import http.client
import json
import os
import re
import subprocess
import sys
import threading
import time

import pytest

from kmlserver_tpu.config import MiningConfig
from kmlserver_tpu.data.csv import write_tracks_csv
from kmlserver_tpu.io import artifacts
from kmlserver_tpu.mining.pipeline import run_mining_job

from .oracle import random_baskets
from .test_pipeline import table_with_metadata

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _start_replica(
    base_dir: str, extra_env: dict | None = None
) -> tuple[subprocess.Popen, int]:
    env = dict(
        os.environ, BASE_DIR=base_dir, KMLS_PORT="0",
        POLLING_WAIT_IN_MINUTES="0.005",  # ~0.3 s staleness poll
        # the admission ladder is not what these tests are about: beside
        # five other pytest workers a replica warming its kernels (every
        # start and every hot swap, now that CPU serves through the
        # jitted path) can be starved past the 250 ms default budget and
        # answer its next request 429. The benchmark's budget instead.
        KMLS_SHED_QUEUE_BUDGET_MS="10000",
    )
    if extra_env:
        env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable, "-m", "kmlserver_tpu.serving.server"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO,
    )
    # bounded port discovery: a reader thread drains stdout for the whole
    # replica lifetime (a full pipe would block the server); the main
    # thread waits on the port with a deadline and kills the child on
    # failure so a hung startup can't hang the test session
    port_holder: list[int] = []
    port_found = threading.Event()

    def _drain() -> None:
        for line in proc.stdout:  # type: ignore[union-attr]
            m = re.search(r"serving on \S+?:(\d+)", line)
            if m and not port_found.is_set():
                port_holder.append(int(m.group(1)))
                port_found.set()

    threading.Thread(target=_drain, daemon=True).start()
    if not port_found.wait(timeout=120) or not port_holder:
        proc.kill()
        raise AssertionError("replica never reported its port")
    return proc, port_holder[0]


def _get(port: int, path: str, timeout: float = 5.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _post(port: int, songs: list[str], timeout: float = 10.0) -> tuple[int, bytes]:
    body = json.dumps({"songs": songs}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/api/recommend/", body,
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _wait_ready(port: int, deadline_s: float = 120.0) -> None:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            if _get(port, "/readyz", timeout=3)[0] == 200:
                return
        except OSError:
            pass
        time.sleep(0.5)
    raise AssertionError(f"replica on :{port} never went ready")


def _reloads(port: int) -> int:
    text = _get(port, "/metrics")[1].decode()
    m = re.search(r"kmls_reloads_total (\d+)", text)
    return int(m.group(1)) if m else -1


class _DowntimeProber(threading.Thread):
    """Hammers one replica with the same request; any non-200, bad JSON,
    or connection error is downtime."""

    def __init__(self, port: int, songs: list[str]):
        super().__init__(daemon=True)
        self.port, self.songs = port, songs
        self.errors: list[str] = []
        self.n_ok = 0
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.is_set():
            try:
                status, payload = _post(self.port, self.songs)
                if status != 200:
                    self.errors.append(f"status {status}")
                else:
                    json.loads(payload)
                    self.n_ok += 1
            except (OSError, ValueError) as exc:
                self.errors.append(f"{type(exc).__name__}: {exc}")
            time.sleep(0.02)

    def stop(self) -> None:
        self._halt.set()


@pytest.fixture
def shared_pvc(tmp_path, rng):
    """One PVC, mined once; returns (base_dir, mining_cfg, rules_dict)."""
    ds_dir = tmp_path / "datasets"
    ds_dir.mkdir()
    baskets = random_baskets(rng, n_playlists=60, n_tracks=18, mean_len=5)
    write_tracks_csv(
        str(ds_dir / "2023_spotify_ds1.csv"), table_with_metadata(baskets)
    )
    mining_cfg = MiningConfig(
        base_dir=str(tmp_path), datasets_dir=str(ds_dir), min_support=0.08,
        k_max_consequents=32, top_tracks_save_percentile=0.5,
    )
    run_mining_job(mining_cfg)
    rules_dict = artifacts.load_pickle(
        str(tmp_path / "pickles" / "recommendations.pickle")
    )
    return str(tmp_path), mining_cfg, rules_dict


class TestTwoReplicas:
    def test_identical_serving_and_hot_swap_zero_downtime(self, shared_pvc):
        base_dir, mining_cfg, rules_dict = shared_pvc
        seeds_known = [s for s, row in rules_dict.items() if row][:2]
        assert seeds_known, "fixture must yield at least one ruled seed"
        seeds_unknown = ["never-mined-track-xyz", "another-unknown-abc"]

        a = b = None
        try:
            a, port_a = _start_replica(base_dir)
            b, port_b = _start_replica(base_dir)
            _wait_ready(port_a)
            _wait_ready(port_b)

            # identical answers replica-to-replica: the rules path, and the
            # static fallback (its stable blake2 seed is the documented fix
            # for process-salted hash() — two processes MUST agree)
            for songs in (seeds_known, seeds_unknown):
                ra, rb = _post(port_a, songs), _post(port_b, songs)
                assert ra[0] == rb[0] == 200, (ra, rb)
                assert json.loads(ra[1]) == json.loads(rb[1]), songs
            before = json.loads(_post(port_a, seeds_known)[1])
            base_reloads = (_reloads(port_a), _reloads(port_b))
            assert min(base_reloads) >= 1

            # hammer both replicas while the PVC is re-mined underneath
            probers = [
                _DowntimeProber(port_a, seeds_known),
                _DowntimeProber(port_b, seeds_known),
            ]
            for p in probers:
                p.start()
            run_mining_job(mining_cfg)  # rewrites artifacts, flips the token

            # both replicas hot-swap independently off the shared token
            deadline = time.time() + 60
            while time.time() < deadline:
                if (
                    _reloads(port_a) > base_reloads[0]
                    and _reloads(port_b) > base_reloads[1]
                ):
                    break
                time.sleep(0.2)
            else:
                raise AssertionError("a replica never reloaded the re-mine")
            time.sleep(1.0)  # swap settled; catch any post-swap wobble
            for p in probers:
                p.stop()
            for p in probers:
                p.join(timeout=10)

            # zero downtime: every request during the swap answered 200
            for p in probers:
                assert p.errors == [], p.errors
                assert p.n_ok > 0
            # same data re-mined → same rules → same answers, still
            # identical across replicas and unchanged vs pre-swap
            ra, rb = _post(port_a, seeds_known), _post(port_b, seeds_known)
            assert ra[0] == rb[0] == 200
            after_a, after_b = json.loads(ra[1]), json.loads(rb[1])
            assert after_a == after_b  # incl. model_date: same artifact
            # model_date moved (the proof a real swap occurred); the
            # recommendations themselves are unchanged
            assert after_a["model_date"] != before["model_date"]
            strip = lambda d: {k: v for k, v in d.items() if k != "model_date"}
            assert strip(after_a) == strip(before)
            fa, fb = _post(port_a, seeds_unknown), _post(port_b, seeds_unknown)
            assert json.loads(fa[1]) == json.loads(fb[1])
        finally:
            for proc in (a, b):
                if proc is not None and proc.poll() is None:
                    proc.kill()


class TestCacheAcrossReplicas:
    def test_cached_and_uncached_replicas_stay_answer_identical(
        self, shared_pvc
    ):
        """One replica with the answer cache on (default), one with
        KMLS_CACHE_ENABLED=0: every answer — cold, repeated (a cache hit
        on A), and post-re-mine — must be identical across the pair, and
        no post-swap answer may come from A's stale epoch."""
        base_dir, mining_cfg, rules_dict = shared_pvc
        seeds = [s for s, row in rules_dict.items() if row][:2]
        assert seeds
        a = b = None
        try:
            a, port_a = _start_replica(base_dir)
            b, port_b = _start_replica(
                base_dir, extra_env={"KMLS_CACHE_ENABLED": "0"}
            )
            _wait_ready(port_a)
            _wait_ready(port_b)
            # repeated queries: the second answer on A is served from its
            # cache; B computes every time — bytes must not diverge
            first = None
            for _ in range(3):
                ra, rb = _post(port_a, seeds), _post(port_b, seeds)
                assert ra[0] == rb[0] == 200
                assert json.loads(ra[1]) == json.loads(rb[1])
                first = first or json.loads(ra[1])
            metrics_a = _get(port_a, "/metrics")[1].decode()
            m = re.search(r"kmls_cache_hits_total (\d+)", metrics_a)
            assert m and int(m.group(1)) >= 2, "A never actually cached"
            metrics_b = _get(port_b, "/metrics")[1].decode()
            assert "kmls_cache_hits_total" not in metrics_b
            base_reloads = (_reloads(port_a), _reloads(port_b))

            # re-mine: the token flips, both replicas hot-swap; A's whole
            # cache is invalidated by the epoch key
            run_mining_job(mining_cfg)
            deadline = time.time() + 60
            while time.time() < deadline:
                if (
                    _reloads(port_a) > base_reloads[0]
                    and _reloads(port_b) > base_reloads[1]
                ):
                    break
                time.sleep(0.2)
            else:
                raise AssertionError("a replica never reloaded the re-mine")
            ra, rb = _post(port_a, seeds), _post(port_b, seeds)
            assert ra[0] == rb[0] == 200
            after_a, after_b = json.loads(ra[1]), json.loads(rb[1])
            # identical across the cached/uncached pair (incl. model_date
            # — proof both actually swapped); the stale-epoch
            # unreachability itself is pinned by the poison test in
            # tests/test_cache.py, this exercises it across real processes
            assert after_a == after_b
            assert after_a["model_date"] != first["model_date"]
            # same data re-mined → same rules → same songs as before
            assert after_a["songs"] == first["songs"]
        finally:
            for proc in (a, b):
                if proc is not None and proc.poll() is None:
                    proc.kill()
