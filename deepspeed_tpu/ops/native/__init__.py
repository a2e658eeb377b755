"""Native op JIT builder.

Analog of the reference ``op_builder/builder.py`` which compiles torch
cpp-extensions on first use. Here: g++ compiles each C++ source set to a
shared library loaded via ctypes (no pybind11 in this image). Libraries are
cached under ``<repo>/build/native/`` keyed by a hash of the sources, the
flags and the CPU they were built on (``-march=native``), so a source edit
triggers recompilation — the same staleness contract as the reference's JIT
load path — and a library built on one machine is never loaded on another.
"""

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

from ...utils.logging import logger

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "csrc")
_BUILD_ROOT = os.environ.get(
    "DS_TPU_BUILD_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
                 "build", "native"))

_lock = threading.Lock()
_loaded = {}


class NativeBuildError(RuntimeError):
    pass


def _machine_tag():
    """What ``-march=native`` resolves against: the architecture plus the
    CPU's feature-flag line. A copied ``build/`` tree then misses on any CPU
    with a different instruction set instead of loading a library that may
    fault on it."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.machine() + platform.processor()


def _source_hash(paths, flags):
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    h.update(" ".join(flags).encode())
    h.update(_machine_tag().encode())
    return h.hexdigest()[:16]


def build_op(name, sources, extra_flags=()):
    """Compile (if stale) and load the shared library for ``name``.

    ``sources``: paths relative to ``ops/csrc``. Returns a ctypes.CDLL.
    """
    with _lock:
        if name in _loaded:
            return _loaded[name]
        srcs = [os.path.join(_CSRC, s) for s in sources]
        for s in srcs:
            if not os.path.isfile(s):
                raise NativeBuildError(f"missing source {s}")
        flags = ["-O3", "-std=c++17", "-fPIC", "-shared", "-pthread", "-march=native", *extra_flags]
        tag = _source_hash(srcs, flags)
        os.makedirs(_BUILD_ROOT, exist_ok=True)
        lib_path = os.path.join(_BUILD_ROOT, f"lib{name}-{tag}.so")
        if not os.path.isfile(lib_path):
            tmp = lib_path + f".tmp{os.getpid()}"
            cmd = ["g++", *flags, "-o", tmp, *srcs]
            logger.info(f"building native op '{name}': {' '.join(cmd)}")
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                raise NativeBuildError(f"g++ failed for op '{name}':\n{proc.stderr}")
            os.replace(tmp, lib_path)
        lib = ctypes.CDLL(lib_path)
        _loaded[name] = lib
        return lib


def is_available():
    """True when a host toolchain exists (ds_report compat matrix entry)."""
    try:
        return subprocess.run(["g++", "--version"], capture_output=True).returncode == 0
    except OSError:
        return False
