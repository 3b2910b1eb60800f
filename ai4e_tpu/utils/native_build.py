"""Shared build-if-stale compiler for the native cores (``native/*.cpp``).

Both ctypes bindings (``broker/native.py``, ``taskstore/native.py``) build
their shared object on demand through this one helper so compiler flags and
staleness rules can never drift between the cores. Honors ``CXX``/
``CXXFLAGS`` like ``native/Makefile``.
"""

from __future__ import annotations

import logging
import os
import shlex
import subprocess

log = logging.getLogger("ai4e_tpu.native_build")

NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native"))
DEFAULT_FLAGS = ["-O2", "-shared", "-fPIC", "-std=c++17"]


def build_native_library(src_name: str, so_name: str,
                         force: bool = False) -> str:
    """Compile ``native/{src_name}`` into ``native/{so_name}`` if the .so is
    missing or older than the source; returns the .so path."""
    src = os.path.join(NATIVE_DIR, src_name)
    out = os.path.join(NATIVE_DIR, so_name)
    # Said on every request, prebuilt or not: a process's log shows
    # whether it ran on a native core (chip_smoke.py asserts its path
    # loads none).
    log.info("native library requested: %s", so_name)
    if (not force and os.path.exists(out)
            and os.path.getmtime(out) >= os.path.getmtime(src)):
        return out
    cxx = os.environ.get("CXX", "g++")
    flags = (shlex.split(os.environ["CXXFLAGS"])
             if os.environ.get("CXXFLAGS") else DEFAULT_FLAGS)
    cmd = [cxx, *flags, src, "-o", out]
    log.info("building native core: %s", " ".join(cmd))
    subprocess.run(cmd, check=True, capture_output=True)
    return out


def load_native_function(src_name: str, so_name: str, fn_name: str,
                         restype, argtypes):
    """Build-if-stale + CDLL + bind ONE function, or None when the
    toolchain can't produce it (callers keep a pure-Python fallback) —
    the shared loader for the per-request codecs (``ops/yuv.py``,
    ``ops/dct.py``). CDLL releases the GIL during the foreign call, which
    is what makes these codecs cheap on a serving host's event loop."""
    try:
        import ctypes

        lib = ctypes.CDLL(build_native_library(src_name, so_name))
        fn = getattr(lib, fn_name)
        fn.restype = restype
        fn.argtypes = argtypes
        return fn
    except Exception:  # noqa: BLE001 — fallback keeps serving
        log.exception("native %s unavailable; caller falls back to numpy",
                      so_name)
        return None
