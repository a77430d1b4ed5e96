"""Native (C++) runtime components, built on demand with g++.

The reference implements its control plane, fusion engine and profiling in
C++ (horovod/common/*.cc); this package holds the rebuild's native
equivalents, compiled lazily into one shared library and bound via ctypes
(the reference binds its core the same way — ctypes over libhorovod,
horovod/common/basics.py:29).

Everything here has a pure-Python fallback in the rest of the package; the
native layer is the production path, the fallback keeps tests/CI alive on
machines without a toolchain.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
# repo layout first (editable installs), then the in-package copy that
# wheels/sdists ship (see setup.py build_py hook)
_CSRC_CANDIDATES = (
    os.path.abspath(os.path.join(_HERE, "..", "..", "csrc")),
    os.path.join(_HERE, "csrc"),
)
_CSRC = next((p for p in _CSRC_CANDIDATES if os.path.isdir(p)),
             _CSRC_CANDIDATES[0])


def _build_dir() -> str:
    """In-package _build when writable (repo checkouts), else a per-user
    cache (system-wide installs where site-packages is read-only)."""
    in_pkg = os.path.join(_HERE, "_build")
    try:
        os.makedirs(in_pkg, exist_ok=True)
        probe = os.path.join(in_pkg, ".w")
        with open(probe, "w"):
            pass
        os.unlink(probe)
        return in_pkg
    except OSError:  # resilience: exempt (build-cache probe, not wire IO)
        cache = os.path.join(
            os.environ.get("XDG_CACHE_HOME",
                           os.path.expanduser("~/.cache")),
            "horovod_tpu", "native_build")
        os.makedirs(cache, exist_ok=True)
        return cache


_BUILD_DIR = _build_dir()

_lock = threading.Lock()
_lib = None
_lib_error = None


def _sources():
    if not os.path.isdir(_CSRC):
        return []
    return sorted(
        os.path.join(_CSRC, f) for f in os.listdir(_CSRC) if f.endswith(".cc"))


_EXTRA_LINK_FLAGS = (
    # shm_open/shm_unlink live in librt until glibc 2.34; harmless (empty
    # stub library) on newer systems
    "-lrt",
)


def _fingerprint(sources):
    h = hashlib.sha256()
    for s in sources:
        h.update(s.encode())
        with open(s, "rb") as f:
            h.update(f.read())
    # flags participate so a flag change invalidates cached builds
    h.update(" ".join(_EXTRA_LINK_FLAGS).encode())
    return h.hexdigest()[:16]


def build(force: bool = False) -> str:
    """Compile csrc/*.cc into libhvd_native.so (cached by source hash)."""
    sources = _sources()
    if not sources:
        raise RuntimeError(f"no C++ sources found under {_CSRC}")
    os.makedirs(_BUILD_DIR, exist_ok=True)
    so_path = os.path.join(_BUILD_DIR,
                           f"libhvd_native-{_fingerprint(sources)}.so")
    if os.path.exists(so_path) and not force:
        return so_path
    cmd = [
        "g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-o", so_path + ".tmp", *sources, *_EXTRA_LINK_FLAGS,
    ]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(so_path + ".tmp", so_path)
    # prune stale builds
    for f in os.listdir(_BUILD_DIR):
        p = os.path.join(_BUILD_DIR, f)
        if p != so_path and f.startswith("libhvd_native-"):
            try:
                os.unlink(p)
            except OSError:  # resilience: exempt (stale-build prune,
                pass         # not wire IO)
    return so_path


def _declare(lib):
    c = ctypes
    u8p = c.POINTER(c.c_uint8)
    sigs = {
        "hvd_store_server_create": (c.c_void_p, [c.c_int]),
        "hvd_store_server_port": (c.c_int, [c.c_void_p]),
        "hvd_store_server_destroy": (None, [c.c_void_p]),
        "hvd_client_create": (c.c_void_p, [c.c_char_p, c.c_int]),
        "hvd_client_destroy": (None, [c.c_void_p]),
        "hvd_client_reconnect": (c.c_int, [c.c_void_p]),
        "hvd_client_set": (c.c_int, [c.c_void_p, c.c_char_p, u8p, c.c_uint32]),
        "hvd_client_get": (c.c_int, [c.c_void_p, c.c_char_p, c.c_double,
                                     c.c_int, c.c_uint64, u8p, c.c_uint32,
                                     c.POINTER(c.c_uint32)]),
        "hvd_client_del": (c.c_int, [c.c_void_p, c.c_char_p]),
        "hvd_client_gather": (c.c_int, [c.c_void_p, c.c_char_p, c.c_double,
                                        c.c_int, c.c_int, c.c_uint64, u8p,
                                        c.c_uint32, u8p, c.c_uint32,
                                        c.POINTER(c.c_uint32)]),
        "hvd_client_reduce": (c.c_int, [c.c_void_p, c.c_char_p, c.c_double,
                                        c.c_int, c.c_int, c.c_int,
                                        c.c_uint64, u8p, c.c_uint32, u8p,
                                        c.c_uint32,
                                        c.POINTER(c.c_uint32)]),
        "hvd_client_stat": (c.c_int, [c.c_void_p, u8p, c.c_uint32,
                                      c.POINTER(c.c_uint32)]),
        "hvd_client_take_pending": (c.c_int, [c.c_void_p, u8p, c.c_uint32,
                                              c.POINTER(c.c_uint32)]),
        "hvd_coord_create": (c.c_void_p, [c.c_char_p, c.c_int, c.c_int,
                                          c.c_int]),
        "hvd_coord_destroy": (None, [c.c_void_p]),
        "hvd_coord_reconnect": (c.c_int, [c.c_void_p]),
        "hvd_coord_barrier": (c.c_int, [c.c_void_p, c.c_char_p, c.c_double]),
        "hvd_coord_allgather": (c.c_int, [c.c_void_p, c.c_char_p, u8p,
                                          c.c_uint32, c.c_double, u8p,
                                          c.c_uint32,
                                          c.POINTER(c.c_uint32)]),
        "hvd_coord_bcast": (c.c_int, [c.c_void_p, c.c_char_p, c.c_int, u8p,
                                      c.c_uint32, c.c_double, u8p, c.c_uint32,
                                      c.POINTER(c.c_uint32)]),
        "hvd_coord_bitand": (c.c_int, [c.c_void_p, c.c_char_p, u8p,
                                       c.c_uint32, c.c_double]),
        "hvd_coord_bitor": (c.c_int, [c.c_void_p, c.c_char_p, u8p, c.c_uint32,
                                      c.c_double]),
        "hvd_timeline_create": (c.c_void_p, [c.c_char_p]),
        "hvd_timeline_destroy": (None, [c.c_void_p]),
        "hvd_timeline_emit": (None, [c.c_void_p, c.c_char_p, c.c_char_p,
                                     c.c_char, c.c_int64, c.c_int, c.c_int64,
                                     c.c_char_p]),
        "hvd_shm_create": (c.c_void_p, [c.c_char_p, c.c_int, c.c_int,
                                        c.c_uint64, c.c_uint64, c.c_double]),
        "hvd_shm_destroy": (None, [c.c_void_p]),
        "hvd_shm_barrier": (c.c_int, [c.c_void_p, c.c_double]),
        "hvd_shm_allreduce": (c.c_int, [c.c_void_p, c.c_void_p, c.c_uint64,
                                        c.c_int, c.c_int, c.c_double]),
        "hvd_shm_allgather": (c.c_int, [c.c_void_p, c.c_void_p, c.c_uint64,
                                        c.c_void_p, c.c_double]),
        "hvd_shm_broadcast": (c.c_int, [c.c_void_p, c.c_void_p, c.c_uint64,
                                        c.c_int, c.c_double]),
        "hvd_shm_reducescatter": (c.c_int, [c.c_void_p, c.c_void_p,
                                            c.c_void_p, c.c_uint64, c.c_int,
                                            c.c_int, c.c_double]),
    }
    for name, (res, args) in sigs.items():
        fn = getattr(lib, name)
        fn.restype = res
        fn.argtypes = args
    return lib


def lib():
    """Load (building if needed) the native library; raises on failure."""
    global _lib, _lib_error
    with _lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise _lib_error
        try:
            _lib = _declare(ctypes.CDLL(build()))
            return _lib
        except Exception as e:  # noqa: BLE001 — cache failure, don't retry
            _lib_error = RuntimeError(f"native build failed: {e}")
            raise _lib_error from e


def loaded() -> bool:
    """True once :func:`lib` has loaded the shared library in this
    process. Unlike :func:`available` this never triggers a build."""
    return _lib is not None


def available() -> bool:
    try:
        lib()
        return True
    except Exception:  # noqa: BLE001
        return False
