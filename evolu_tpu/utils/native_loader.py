"""Shared build-on-demand ctypes loader for the native/ libraries.

Both native bindings (`storage/native.py` over libevolu_host.so,
`sync/native_crypto.py` over libevolu_crypto.so) follow the same
contract: build the specific make target on first use (g++ and the
versioned system sonames are baked into the image), load via ctypes,
run the module's `configure` (argtypes + optional runtime probe), and
cache the result — including failure, so an unbuildable environment
costs one attempt, not one per call. Failure means "caller falls back
to its pure-Python path" (the test oracle), never an exception — but
never silently: every failed build, load or probe is logged and
counted in `evolu_native_load_failures_total{lib, reason}`, and
chip_smoke.py fails on any count.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
from typing import Callable, Dict, Optional

from evolu_tpu.obs import metrics
from evolu_tpu.utils.log import log

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)

_lock = threading.Lock()
_cache: Dict[str, Optional[ctypes.CDLL]] = {}  # so_name → lib (None = failed)


def load_native_library(
    so_name: str,
    configure: Callable[[ctypes.CDLL], Optional[ctypes.CDLL]],
) -> Optional[ctypes.CDLL]:
    """The shared library named `so_name` (also its make target),
    built on first use; None if unavailable. `configure` sets argtypes
    and may return None to veto (e.g. a failing runtime probe)."""
    with _lock:
        if so_name in _cache:
            return _cache[so_name]
        path = os.path.join(NATIVE_DIR, so_name)
        # Run make UNCONDITIONALLY (an up-to-date target is a ~50 ms
        # no-op): a stale binary from an older checkout would dlopen
        # fine but lack newly added symbols, and re-dlopen after a
        # rebuild returns the already-loaded stale handle — so the
        # rebuild must happen BEFORE the first load.
        try:
            # One build at a time across PROCESSES (pytest-xdist
            # workers, relay workers starting together): a concurrent
            # make races the linker — a sibling finds the target "up to
            # date" while ld is still writing it and dlopens a partial
            # file, which used to read as "native unavailable" on
            # whichever worker lost (9 silent skips under `-n 6`).
            with open(os.path.join(NATIVE_DIR, "Makefile")) as build_lock:
                fcntl.flock(build_lock, fcntl.LOCK_EX)
                subprocess.run(
                    ["make", "-s", so_name], cwd=NATIVE_DIR,
                    check=True, capture_output=True, timeout=120,
                )
        except (OSError, subprocess.SubprocessError) as e:
            _note_failure(so_name, "build", e)
            if not os.path.exists(path):
                _cache[so_name] = None
                return None
            # make unavailable but a binary exists: try it as-is.
        try:
            lib = configure(ctypes.CDLL(path))
            if lib is None:
                _note_failure(so_name, "probe", "configure vetoed the library")
        except (OSError, AttributeError) as e:
            # AttributeError = a symbol this build of the bindings
            # needs is missing (stale binary + no toolchain): fall
            # back to the pure-Python paths instead of crashing.
            _note_failure(so_name, "load", e)
            lib = None
        _cache[so_name] = lib
        return lib


def open_gil_held(so_name: str, probe: str) -> Optional[ctypes.PyDLL]:
    """An already-built library opened again through ctypes.PyDLL — the
    same mapping, but calls made through this handle KEEP the GIL, which
    every function that takes Python objects (native/pyabi.h) requires.
    Only after the library's `probe` (`py_abi_probe` under its prefix)
    has validated pyabi.h's self-declared object layout against a live
    str on THIS interpreter: any drift (debug build, free-threading, a
    future CPython), or a binary without the symbol, gives None and the
    caller keeps its Python path."""
    try:
        plib = ctypes.PyDLL(os.path.join(NATIVE_DIR, so_name))
        fn = getattr(plib, probe)
        fn.restype = ctypes.c_int
        fn.argtypes = [ctypes.py_object]
        return plib if fn("x") == 0 else None
    except (OSError, AttributeError):
        return None


def _note_failure(so_name: str, reason: str, error: object) -> None:
    stderr = getattr(error, "stderr", None)
    detail = stderr.decode("utf-8", "replace")[-400:] if stderr else repr(error)
    metrics.inc("evolu_native_load_failures_total", lib=so_name, reason=reason)
    log("dev", "native library unavailable: pure-Python fallback",
        lib=so_name, reason=reason, error=detail)
