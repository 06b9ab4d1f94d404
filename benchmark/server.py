"""Start, watch and stop the system under test as a child process.

(The spawn / wait-for-``/readyz`` / SIGTERM sequence follows
``chip_smoke.py``'s ``_Server``; copied, not imported.)
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ServerFailed(RuntimeError):
    pass


class Server:
    def __init__(self, server_cfg: dict, base_dir: str, work_dir: str, env_extra: dict):
        self.cfg = server_cfg
        self.log_path = os.path.join(work_dir, "server.log")
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        env.update(server_cfg["env"])
        env.update(env_extra)
        env["BASE_DIR"] = base_dir
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        # one compile cache at a fixed path inside the checkout, unless the
        # machine's owner placed one
        env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
        os.makedirs(env["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
        self.log = open(self.log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "server_child.py"), server_cfg["module"]],
            env=env, cwd=ROOT, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self.port = 0
        self.ready_s = float("nan")

    def output(self) -> str:
        with open(self.log_path, "r", errors="replace") as fh:
            return fh.read()

    def tail(self, n: int = 30) -> str:
        return "\n".join(self.output().splitlines()[-n:])

    def wait_port(self) -> tuple[str, int]:
        """Wait for the listener → (platform, device count) as the server's
        start-up line reports them."""
        deadline = self.t_spawn + float(self.cfg["ready_timeout_s"])
        while time.monotonic() < deadline and self.proc.poll() is None:
            text = self.output()
            m = re.search(r"serving on \S+?:(\d+)", text)
            if m:
                self.port = int(m.group(1))
                d = re.search(r"devices: platform=(\S+) device_kind=.+? count=(\d+)", text)
                return (d.group(1), int(d.group(2))) if d else ("", 0)
            time.sleep(0.25)
        raise ServerFailed(f"server bound no port (exit {self.proc.poll()})\n{self.tail()}")

    def wait_ready(self) -> None:
        deadline = self.t_spawn + float(self.cfg["ready_timeout_s"])
        while time.monotonic() < deadline and self.proc.poll() is None:
            try:
                status, body = self.get(self.cfg["ready_path"], timeout=10)
                if status == 200:
                    state = json.loads(body).get("status")
                    if state != "ready":
                        raise ServerFailed(f"{self.cfg['ready_path']} says {state!r}\n{self.tail()}")
                    self.ready_s = time.monotonic() - self.t_spawn
                    return
            except (OSError, ValueError, http.client.HTTPException):
                pass
            time.sleep(0.25)
        raise ServerFailed(f"server not ready (exit {self.proc.poll()})\n{self.tail()}")

    def get(self, path: str, timeout: float = 30, method: str = "GET") -> tuple[int, bytes]:
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=timeout)
        try:
            conn.request(method, path)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def metrics(self) -> str:
        status, body = self.get("/metrics")
        if status != 200:
            raise ServerFailed(f"/metrics HTTP {status}")
        return body.decode()

    def stop(self) -> dict | None:
        """SIGTERM, wait for the exit → the child's device line (None if it
        never printed one). Kills the whole process group if it lingers."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                pass
        self.kill()
        m = re.search(r"^BENCH_DEVICE (\{.*\})$", self.output(), re.M)
        return json.loads(m.group(1)) if m else None

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        if not self.log.closed:
            self.log.close()
