"""ctypes bindings for the native host-table kernels (native/tablebuilder.cc).

``libminisched_native.so`` lives in this package directory and is git-
ignored, so every checkout builds its own: on import the library is loaded
only if ``libminisched_native.so.sha256`` beside it records the digest of
the CURRENT ``native/tablebuilder.cc``; otherwise it is (re)built with g++
first.  A stale or foreign ``.so`` is never loaded.  Every entry point has
a NumPy fallback (``HAVE_NATIVE`` False) so the package works without a
toolchain — but taking it is one loud stderr line, never silence: the
fallback is the slow host build.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
from typing import List, Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libminisched_native.so")
_SRC = os.path.join(
    os.path.dirname(os.path.dirname(_HERE)), "native", "tablebuilder.cc"
)

HAVE_NATIVE = False
_lib: Optional[ctypes.CDLL] = None


def _digest_path(so: str) -> str:
    return so + ".sha256"


def _fallback(why: str) -> None:
    print(
        f"minisched_tpu.native: {why}; host table builds take the slow "
        "NumPy path",
        file=sys.stderr,
        flush=True,
    )


def _build(so: str, src: str, digest: str) -> bool:
    """Compile ``src`` into ``so`` and record ``digest`` beside it.  Both
    land by atomic rename (concurrent importers — test workers, HA engine
    children — may all build at once), the digest last: a crash in between
    leaves a mismatch, i.e. a rebuild, never a stale load."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so)
        with open(tmp, "w") as f:
            f.write(digest + "\n")
        os.replace(tmp, _digest_path(so))
        return True
    except (OSError, subprocess.SubprocessError) as err:
        detail = getattr(err, "stderr", b"") or b""
        _fallback(
            f"g++ build of {src} failed ({type(err).__name__}: "
            f"{detail.decode(errors='replace').strip()[-200:] or err})"
        )
        if os.path.exists(tmp):
            os.unlink(tmp)
        return False


def _current(so: str, digest: str) -> bool:
    try:
        with open(_digest_path(so)) as f:
            return os.path.exists(so) and f.read().strip() == digest
    except OSError:
        return False


def _load(so: str = _SO, src: str = _SRC) -> None:
    global _lib, HAVE_NATIVE
    _lib, HAVE_NATIVE = None, False
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
    except OSError as err:
        _fallback(f"source {src} unreadable ({err})")
        return
    if not _current(so, digest) and not _build(so, src, digest):
        return
    try:
        lib = ctypes.CDLL(so)
    except OSError as err:
        _fallback(f"cannot load {so} ({err})")
        return
    c_char_p = ctypes.c_char_p
    i64_p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32_p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    u32_p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    for name, out_t in (
        ("fnv1a32_batch", i32_p),
        ("name_suffix_batch", i32_p),
        ("pod_seed_batch", u32_p),
    ):
        fn = getattr(lib, name)
        fn.argtypes = [c_char_p, i64_p, ctypes.c_int64, out_t]
        fn.restype = None
    _lib = lib
    HAVE_NATIVE = True


_load()


def pack_strings(strings: Sequence[str]) -> Tuple[bytes, np.ndarray]:
    """Arrow-style packing: (joined UTF-8 buffer, int64 offsets[n+1])."""
    encoded: List[bytes] = [s.encode("utf-8") for s in strings]
    offsets = np.zeros(len(encoded) + 1, np.int64)
    np.cumsum([len(b) for b in encoded], out=offsets[1:])
    return b"".join(encoded), offsets


def fnv1a32_batch(strings: Sequence[str]) -> np.ndarray:
    """Signed-int32 FNV-1a hash per string (== tables.fnv1a32)."""
    n = len(strings)
    out = np.empty(n, np.int32)
    if HAVE_NATIVE and n:
        buf, offsets = pack_strings(strings)
        _lib.fnv1a32_batch(buf, offsets, n, out)
        return out
    from minisched_tpu.models.tables import fnv1a32  # canonical scalar form

    for i, s in enumerate(strings):
        out[i] = fnv1a32(s)
    return out


def name_suffix_batch(strings: Sequence[str]) -> np.ndarray:
    """Trailing ASCII digit per name, -1 if absent (== tables._name_suffix)."""
    n = len(strings)
    out = np.empty(n, np.int32)
    if HAVE_NATIVE and n:
        buf, offsets = pack_strings(strings)
        _lib.name_suffix_batch(buf, offsets, n, out)
        return out
    from minisched_tpu.models.tables import _name_suffix

    for i, s in enumerate(strings):
        out[i] = _name_suffix(s)
    return out


def pod_seed_batch(strings: Sequence[str]) -> np.ndarray:
    """uint32 tie-break seed per uid (== tables.pod_seed)."""
    n = len(strings)
    out = np.empty(n, np.uint32)
    if HAVE_NATIVE and n:
        buf, offsets = pack_strings(strings)
        _lib.pod_seed_batch(buf, offsets, n, out)
        return out
    from minisched_tpu.models.tables import pod_seed

    for i, s in enumerate(strings):
        out[i] = pod_seed(s)
    return out
