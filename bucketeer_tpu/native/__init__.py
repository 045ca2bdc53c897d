"""ctypes bindings for the native Tier-1 coder (t1.cpp).

The production entropy-coding path: batches of code-blocks are encoded in
C++ across a thread pool (cores-1 threads by default, mirroring the
reference's uploader-pool sizing, reference:
verticles/MainVerticle.java:64-77). Falls back to the pure Python coder
when the shared library is missing and cannot be built (e.g. no
compiler in the deployment image) — the analog of the reference's
Kakadu-to-OpenJPEG degradation
(reference: converters/ConverterFactory.java:37-47). Every block that
takes that path is counted in ``codec.t1_batch.python_coded_blocks``.

The read path decodes through the same library: ``t1_decode_blocks``
decodes one tile's code-blocks per call (``codec/decode/t1_dec.py``);
blocks that fall back to the Python decoder count in the
``decode.python_blocks`` metric.

Set ``BUCKETEER_NO_NATIVE=1`` to force the Python path.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from pathlib import Path

LOG = logging.getLogger(__name__)

_DIR = Path(__file__).resolve().parent
_LIB_PATH = _DIR / "libbucketeer_t1.so"
_ABI_VERSION = 6     # must match t1_abi_version() in t1.cpp
_lib = None
_tried = False
# Held across the first load and its build: the host Tier-1 pool calls
# load() from several threads at once, and a caller that found the
# build in progress used to take the pure-Python coder for its chunk
# (a 4096² encode's first chunks on a fresh checkout, PR 21).
_LOAD_LOCK = threading.Lock()


class NativeABIError(RuntimeError):
    """The loaded libbucketeer_t1.so speaks a different ABI than these
    bindings expect. Calling into it anyway would misread the argument
    layout, so the loader refuses it."""

    def __init__(self, found: int, expected: int, lib_path: Path):
        self.found = found
        self.expected = expected
        self.lib_path = Path(lib_path)
        super().__init__(
            f"{self.lib_path.name}: t1_abi_version() returned {found}, "
            f"these bindings expect {expected} "
            "(the symbol is absent entirely when -1). Remediation: "
            f"delete {self.lib_path} so it is rebuilt from t1.cpp, or "
            "set BUCKETEER_NO_NATIVE=1 to force the pure-Python coder.")


def _check_abi(lib: ctypes.CDLL) -> None:
    """Raise :class:`NativeABIError` unless ``lib`` matches
    ``_ABI_VERSION`` (the single ABI guard; every load path funnels
    through here)."""
    try:
        lib.t1_abi_version.restype = ctypes.c_int32
        found = int(lib.t1_abi_version())
    except AttributeError:
        found = -1
    if found != _ABI_VERSION:
        raise NativeABIError(found, _ABI_VERSION, _LIB_PATH)


def _build(out: Path | None = None) -> bool:
    src = _DIR / "t1.cpp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
             "-o", str(out or _LIB_PATH), str(src)],
            check=True, capture_output=True, timeout=300)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def load():
    """Load (building if needed) the native library; None if unavailable."""
    if _lib is not None:
        return _lib
    with _LOAD_LOCK:
        return _load_locked()


def _load_locked():
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("BUCKETEER_NO_NATIVE"):
        return None
    src = _DIR / "t1.cpp"
    try:
        stale = (not _LIB_PATH.exists()
                 or _LIB_PATH.stat().st_mtime < src.stat().st_mtime)
    except OSError:
        stale = False        # source pruned from deployment; use the .so
    if stale and not _build():
        return None
    try:
        lib = ctypes.CDLL(str(_LIB_PATH))
    except OSError:
        return None
    # ABI guard: a prebuilt .so from an older tree (deployment images
    # prune t1.cpp, defeating the mtime staleness check) must not be
    # called with a newer argument layout. Rebuild if possible, else
    # fall back to the pure-Python coder.
    try:
        _check_abi(lib)
    except NativeABIError as exc:
        # dlopen dedupes by pathname, so rebuilding in place and
        # re-CDLL'ing _LIB_PATH would hand back the stale mapping (and
        # g++ truncating a currently-mapped .so risks SIGBUS). Build to
        # a distinct path, load that, then rename it over _LIB_PATH
        # (atomic, new inode) so future processes load it directly.
        rebuilt = _LIB_PATH.with_suffix(f".v{_ABI_VERSION}.so")
        if not (src.exists() and _build(rebuilt)):
            LOG.warning("%s; no source to rebuild from — falling back "
                        "to the pure-Python Tier-1 coder", exc)
            return None
        try:
            lib = ctypes.CDLL(str(rebuilt))
            _check_abi(lib)
        except (OSError, NativeABIError) as exc2:
            LOG.warning("%s after rebuild — falling back to the "
                        "pure-Python Tier-1 coder", exc2)
            return None
        try:
            os.replace(rebuilt, _LIB_PATH)
        except OSError:
            LOG.warning("could not move rebuilt %s over %s; the stale "
                        "library remains on disk", rebuilt, _LIB_PATH)
    lib.t1_encode_blocks.restype = ctypes.c_void_p
    lib.t1_encode_blocks.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int]
    lib.t1_encode_packed.restype = ctypes.c_void_p
    lib.t1_encode_packed.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int]
    lib.t1_encode_cxd.restype = ctypes.c_void_p
    lib.t1_encode_cxd.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int]
    lib.t1_decode_blocks.restype = None
    lib.t1_decode_blocks.argtypes = [ctypes.c_int] + \
        [ctypes.c_void_p] * 11 + [ctypes.c_int]
    lib.t1_block_sizes.restype = None
    lib.t1_block_sizes.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 3
    lib.t1_result_copy.restype = None
    lib.t1_result_copy.argtypes = [ctypes.c_void_p] * 6
    lib.t1_result_free.restype = None
    lib.t1_result_free.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def available() -> bool:
    return load() is not None
