"""Loader for the native C++ runtime (``native/`` at the repo root).

The native layer plays the role SURVEY.md §2.9 assigns to native code in a
TPU stack: host-side serving bookkeeping (paged KV block allocator,
admission scheduler — ``native/runtime/gofr_runtime.cc``) and the PJRT
C-API binding (``native/pjrt/pjrt_dl.cc``). Python talks to it over a
plain C ABI via ctypes (no pybind11 in the image).

Build model: shared objects are compiled on first use with ``g++`` into
``native/_build/`` and re-used while their source hash matches (the
"compile-or-load executable cache" idea of SURVEY §5.4 applied to our own
native code). When the build fails the callers run the pure-Python twins
in :mod:`gofr_tpu.native.fallback` — loudly: the compiler's stderr is
logged once per library, and ``backend`` on every wrapper (surfaced in
the engine's health as ``scheduler_backend``) says which runtime is live.
"""

from __future__ import annotations

import ctypes
import hashlib
import importlib.util
import logging
import os
import subprocess
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_BUILD_DIR = os.path.join(_NATIVE_DIR, "_build")

_lock = threading.Lock()
_cache: dict[str, ctypes.CDLL | None] = {}
_log = logging.getLogger(__name__)

GOFR_OK = 0
GOFR_E_BADHANDLE = -1
GOFR_E_NOMEM = -2
GOFR_E_NOTFOUND = -3
GOFR_E_EXISTS = -4
GOFR_E_QUEUEFULL = -5
GOFR_E_ARG = -6
GOFR_E_CAP = -7

ERROR_NAMES = {
    GOFR_E_BADHANDLE: "bad handle",
    GOFR_E_NOMEM: "out of KV blocks",
    GOFR_E_NOTFOUND: "not found",
    GOFR_E_EXISTS: "already exists",
    GOFR_E_QUEUEFULL: "queue full",
    GOFR_E_ARG: "bad argument",
    GOFR_E_CAP: "buffer too small",
}


class NativeError(RuntimeError):
    def __init__(self, code: int, what: str = "") -> None:
        self.code = code
        super().__init__(f"{what}: {ERROR_NAMES.get(code, code)}" if what else str(code))


def _source_hash(*paths: str) -> str:
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def pjrt_include_dirs() -> list[str]:
    """Locate the PJRT C API headers (shipped in the installed
    tensorflow wheel). The package is located, never imported: only its
    header tree is wanted, and the import costs seconds and drags TF's
    runtime into the serving process. ``GOFR_PJRT_INCLUDE_DIRS``
    (colon-separated) overrides the lookup."""
    env = os.environ.get("GOFR_PJRT_INCLUDE_DIRS")
    if env:
        return [d for d in env.split(":") if d]
    spec = importlib.util.find_spec("tensorflow")
    roots = (spec.submodule_search_locations or []) if spec else []
    for root in roots:
        tf_inc = os.path.join(root, "include")
        if os.path.exists(os.path.join(tf_inc, "xla/pjrt/c/pjrt_c_api.h")):
            return [tf_inc]
    return []


def build_library(name: str, sources: list[str], extra_flags: list[str] | None = None,
                  libs: list[str] | None = None) -> str | None:
    """Compile `sources` (relative to native/) into _build/<name>-<hash>.so.

    Returns the path, or None if a source is missing, the toolchain is
    unavailable or the compile fails — each logged with its cause
    (callers then run the Python twins; ``_load`` memoizes, so once).
    """
    srcs = [os.path.join(_NATIVE_DIR, s) for s in sources]
    missing = [s for s in srcs if not os.path.exists(s)]
    if missing:
        _log.error("native build of %s: missing source %s", name, missing)
        return None
    # sanitizer tier (SURVEY §5.2): GOFR_NATIVE_EXTRA_CXXFLAGS joins the
    # build AND the cache tag, so asan and release artifacts never collide
    env_flags = os.environ.get("GOFR_NATIVE_EXTRA_CXXFLAGS", "").split()
    tag = _source_hash(*srcs)
    if env_flags:
        tag += "-" + hashlib.sha256(" ".join(env_flags).encode()).hexdigest()[:8]
    out = os.path.join(_BUILD_DIR, f"{name}-{tag}.so")
    if os.path.exists(out):
        return out
    os.makedirs(_BUILD_DIR, exist_ok=True)
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O2", "-fPIC", "-std=c++17", "-shared", "-fvisibility=hidden",
        *(extra_flags or []),
        *env_flags,
        "-o", out + ".tmp", *srcs, *(libs or []),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(out + ".tmp", out)
    except subprocess.CalledProcessError as exc:
        _log.error(
            "native build of %s failed (exit %s): %s\n%s", name,
            exc.returncode, " ".join(cmd),
            exc.stderr.decode("utf-8", "replace"),
        )
        return None
    except (OSError, subprocess.TimeoutExpired) as exc:
        _log.error("native build of %s did not run: %s", name, exc)
        return None
    return out


def _load(name: str, sources: list[str], extra_flags: list[str] | None = None,
          libs: list[str] | None = None) -> ctypes.CDLL | None:
    with _lock:
        if name in _cache:
            return _cache[name]
        path = build_library(name, sources, extra_flags, libs)
        lib = None
        if path is not None:
            try:
                lib = ctypes.CDLL(path)
            except OSError as exc:
                _log.error("native library %s did not load: %s", path, exc)
        _cache[name] = lib
        return lib


def load_runtime() -> ctypes.CDLL | None:
    """The serving runtime: block allocator + scheduler. None if unbuildable."""
    lib = _load("libgofr_runtime", ["runtime/gofr_runtime.cc"])
    if lib is not None and not getattr(lib, "_gofr_typed", False):
        _declare_runtime(lib)
        lib._gofr_typed = True
    return lib


def load_pjrt() -> ctypes.CDLL | None:
    """The PJRT C-API binding. None if headers/toolchain unavailable."""
    incs = pjrt_include_dirs()
    if not incs:
        return None
    flags = [f"-I{d}" for d in incs]
    lib = _load("libgofr_pjrt", ["pjrt/pjrt_dl.cc"], flags, ["-ldl"])
    if lib is not None and not getattr(lib, "_gofr_typed", False):
        _declare_pjrt(lib)
        lib._gofr_typed = True
    return lib


def build_stub_plugin() -> str | None:
    """Build the test-only stub PJRT plugin (SURVEY §4: fake PJRT rig)."""
    incs = pjrt_include_dirs()
    if not incs:
        return None
    return build_library(
        "libgofr_pjrt_stub", ["pjrt/stub_plugin.cc"], [f"-I{d}" for d in incs]
    )


def _declare_runtime(lib: ctypes.CDLL) -> None:
    i32, i64 = ctypes.c_int32, ctypes.c_int64
    p_i32, p_i64 = ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64)
    sig = {
        "gofr_ba_create": (i64, [i32, i32]),
        "gofr_ba_destroy": (i32, [i64]),
        "gofr_ba_alloc": (i32, [i64, i64, i64]),
        "gofr_ba_extend": (i32, [i64, i64, i64, p_i32, p_i32]),
        "gofr_ba_fork": (i64, [i64, i64, i64, i64]),
        "gofr_ba_free": (i32, [i64, i64]),
        "gofr_ba_block_table": (i32, [i64, i64, p_i32, i32]),
        "gofr_ba_seq_length": (i64, [i64, i64]),
        "gofr_ba_stats": (i32, [i64, p_i64]),
        "gofr_sched_create": (i64, [i32, i32, i32]),
        "gofr_sched_destroy": (i32, [i64]),
        "gofr_sched_submit": (i32, [i64, i64, i32, i32, i32]),
        "gofr_sched_submit_front": (i32, [i64, i64, i32, i32, i32]),
        "gofr_sched_cancel": (i32, [i64, i64]),
        "gofr_sched_admit": (i32, [i64, p_i64, p_i32, i32, p_i64, i32, p_i32]),
        "gofr_sched_release": (i32, [i64, i32]),
        "gofr_sched_stats": (i32, [i64, p_i64]),
        "gofr_runtime_version": (ctypes.c_char_p, []),
    }
    for fname, (res, args) in sig.items():
        fn = getattr(lib, fname)
        fn.restype = res
        fn.argtypes = args


def _declare_pjrt(lib: ctypes.CDLL) -> None:
    i32, i64 = ctypes.c_int32, ctypes.c_int64
    cp = ctypes.c_char_p
    p_i64 = ctypes.POINTER(ctypes.c_int64)
    vp = ctypes.c_void_p
    sig = {
        "gofr_pjrt_load": (i64, [cp]),
        "gofr_pjrt_api_version": (i32, [i64, ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]),
        "gofr_pjrt_client_create": (i64, [i64]),
        "gofr_pjrt_client_destroy": (i32, [i64]),
        "gofr_pjrt_platform_name": (i32, [i64, cp, i32]),
        "gofr_pjrt_device_count": (i32, [i64]),
        "gofr_pjrt_addressable_device_count": (i32, [i64]),
        "gofr_pjrt_device_ids": (i32, [i64, p_i64, i32]),
        "gofr_pjrt_compile": (i64, [i64, vp, i64, cp, vp, i64]),
        "gofr_pjrt_executable_destroy": (i32, [i64]),
        "gofr_pjrt_unload": (i32, [i64]),
        "gofr_pjrt_execute_f32": (
            i32,
            [i64, i64, ctypes.POINTER(ctypes.c_float), i64,
             ctypes.POINTER(ctypes.c_float), i64, p_i64],
        ),
        "gofr_pjrt_last_error": (cp, []),
    }
    for fname, (res, args) in sig.items():
        fn = getattr(lib, fname)
        fn.restype = res
        fn.argtypes = args


def native_available() -> bool:
    return load_runtime() is not None
