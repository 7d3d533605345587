"""Native C++ IO engine — build-on-demand loader.

The engine (src/engine.cpp) runs epoll loops, tpu_std frame cutting and
vectored writes in C++ with the GIL released; Python is entered once per
complete message.  This is the framework's native-performance data plane
(SURVEY.md §2's "C++, not Python stand-ins" requirement); the pure-Python
transport remains the fallback and the full multi-protocol path.

``build()`` compiles ``_native.so`` with g++ (``make -C brpc_tpu/native``)
and raises when that fails.  ``load()`` builds on first use and returns
the module, or None when the build fails — callers that ask
(``available()``) treat None as "use the Python transport"; a caller
that REQUIRES the engine (``chip_smoke.py``) calls ``build()`` itself and
lets the error surface.

A binary is current only when the stamp beside it holds the hash of the
sources it was built from.  mtime says nothing here: ``_native.so`` is
git-ignored, so a checkout, an archive or a tree copy can deliver a
stale binary that is newer than ``engine.cpp``.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading
from typing import Optional

from ..butil.logging_util import LOG

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_module = None
_tried = False


def _source_hash() -> str:
    h = hashlib.sha256()
    for rel in ("src/engine.cpp", "Makefile"):
        with open(os.path.join(_DIR, rel), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def build(force: bool = False) -> str:
    """Make sure the engine binary is built from the sources on disk and
    return its path; raises if the build fails.  ``force`` rebuilds even
    when the stamp matches."""
    asan = os.environ.get("BRPC_TPU_NATIVE_ASAN") == "1"
    so = os.path.join(_DIR, "_native_asan.so" if asan else "_native.so")
    stamp = so + ".srchash"
    want = _source_hash()
    have = None
    if os.path.exists(so) and os.path.exists(stamp):
        with open(stamp) as f:
            have = f.read().strip()
    if force or have != want:
        LOG.info("building native engine (%s)...", os.path.basename(so))
        # built beside the target and renamed over it: a process that
        # already mapped the old binary, or a second builder, never
        # sees a half-written one.  -B: make's own staleness test is
        # the mtime this loader does not trust.
        tmp = f"{so}.build{os.getpid()}"
        try:
            subprocess.run(
                ["make", "-B", "-C", _DIR]
                + (["asan", f"ASAN_OUT={tmp}"] if asan
                   else [f"OUT={tmp}"]),
                check=True, capture_output=True, timeout=240)
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        with open(stamp, "w") as f:
            f.write(want)
    return so


def load() -> Optional[object]:
    """The compiled engine module, building it if needed (None if the
    build fails — callers fall back to the Python transport).

    With ``BRPC_TPU_NATIVE_ASAN=1`` in the environment the sanitizer-
    hardened build (``make asan`` → ``_native_asan.so``) is loaded
    instead — the host python must have libasan LD_PRELOADed (the
    sanitizer stress test's subprocess arranges this; see
    tests/asan_driver.py)."""
    global _module, _tried
    with _lock:
        if _module is not None or _tried:
            return _module
        _tried = True
        try:
            so = build()
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "brpc_tpu.native._native", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _module = mod
        except Exception as e:
            LOG.warning("native engine unavailable (%s); "
                        "using the Python transport", e)
            _module = None
        return _module


def available() -> bool:
    return load() is not None
