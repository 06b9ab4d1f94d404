"""Shared build-and-load scaffolding for the ``native/`` shared objects.

Both native modules (the CSV loader, ``data/native.py``, and the POPCNT
pair counter, ``ops/cpu_popcount.py``) need the same lifecycle: run
``make -C native`` on demand, load the .so via ctypes, verify its ABI,
honor the ``KMLS_NATIVE=0`` kill switch on EVERY call, and degrade
gracefully when the toolchain or .so is absent. This is the one copy of
that logic — the two modules previously duplicated it verbatim, and the
duplicate missed negative caching (a host with no toolchain re-spawned a
failing ``make`` on every call).

``make`` runs at most once per process: its file dependencies make a
second invocation a no-op anyway, and per-call subprocess spawns would
land inside latency-sensitive paths (the miner consults availability when
choosing its pair-count implementation).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Callable

logger = logging.getLogger("kmlserver_tpu.nativelib")

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)

_make_lock = threading.Lock()
_make_ran = False


def run_make_once(quiet: bool = True) -> None:
    """Invoke ``make -C native`` at most once per process (all targets
    build together). A failed build is logged, not raised — per-.so
    existence decides availability afterwards, and callers name the path
    they took instead (``CSV loader:``, ``Pair-count path:``)."""
    global _make_ran
    with _make_lock:
        if _make_ran:
            return
        _make_ran = True
        try:
            subprocess.run(
                ["make", "-C", NATIVE_DIR], check=True, capture_output=quiet
            )
        except FileNotFoundError:
            logger.warning("native build skipped: no `make` on PATH")
        except subprocess.CalledProcessError as exc:
            tail = (exc.stderr or b"").decode("utf-8", "replace")[-400:]
            logger.warning(
                "native build failed (exit %d): %s", exc.returncode, tail
            )


class NativeLib:
    """One .so's cached loader: ``bind`` receives the raw CDLL and must
    set up prototypes + verify the ABI version (raising OSError to
    reject); both success and failure are cached, while the kill switch
    stays live (checked before the cache on every call)."""

    def __init__(self, so_name: str, bind: Callable[[ctypes.CDLL], ctypes.CDLL]):
        self.so_path = os.path.join(NATIVE_DIR, so_name)
        self._bind = bind
        self._lib: ctypes.CDLL | None = None
        self._failed = False
        self._lock = threading.Lock()

    def load(self) -> ctypes.CDLL | None:
        if os.environ.get("KMLS_NATIVE", "1") == "0":
            return None
        with self._lock:
            if self._lib is not None:
                return self._lib
            if self._failed:
                return None
            run_make_once()
            if not os.path.exists(self.so_path):
                self._failed = True
                return None
            try:
                self._lib = self._bind(ctypes.CDLL(self.so_path))
            except OSError:
                self._failed = True
                return None
            return self._lib

    def available(self) -> bool:
        return self.load() is not None
