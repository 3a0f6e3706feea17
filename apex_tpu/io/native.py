"""ctypes loader for the native runtime library.

Builds ``native/apex_tpu_native.cpp`` with g++ on first use and exposes
flatten/unflatten/gather_rows.  The library file is named by a hash of
the source (``native/build/libapex_tpu_native-<sha>.so``), so what gets
loaded is always the source git has — never a stale or foreign ``.so``
that happened to be on disk.  A failed build raises: the trainer's
batch assembly and the checkpointer run on this library, and a silent
drop to Python loops would change what a run measures without a word.
"""

import contextlib
import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import List

import numpy as np


@contextlib.contextmanager
def atomic_output(path):
    """THE atomic write/rename helper for checkpoint bytes: yields a
    binary file open on ``<path>.tmp``; on clean exit the data is
    fsync'd, renamed onto ``path``, and the directory entry fsync'd —
    a crash or power loss mid-write can never leave a truncated file
    under the final name, and the published bytes are durable.  On any
    exception the temp file is unlinked and nothing is published.

    Every checkpoint-path write in the tree must route through here (or
    a wrapper of it): analyzer rule APX104 flags direct
    ``open(..., "wb")`` calls on checkpoint paths, because a direct
    write IS the torn-file class ``io.validate_checkpoint`` exists to
    detect after the fact."""
    tmp = str(path) + ".tmp"
    f = open(tmp, "wb")
    try:
        yield f
        f.flush()
        os.fsync(f.fileno())  # data durable before the rename publishes it
        f.close()
        os.replace(tmp, str(path))
        dfd = os.open(os.path.dirname(str(path)) or ".", os.O_RDONLY)
        try:
            os.fsync(dfd)  # the rename itself durable
        finally:
            os.close(dfd)
    except BaseException:
        try:
            f.close()
        except OSError:
            pass
        try:
            if os.path.exists(tmp):
                os.unlink(tmp)
        except OSError:
            pass
        raise

_REPO = Path(__file__).resolve().parents[2]
_SRC = _REPO / "native" / "apex_tpu_native.cpp"

_lock = threading.Lock()
_lib = None

DEFAULT_THREADS = min(8, os.cpu_count() or 1)


def _so_path() -> Path:
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _REPO / "native" / "build" / f"libapex_tpu_native-{digest}.so"


def _build(so: Path) -> None:
    so.parent.mkdir(parents=True, exist_ok=True)
    # build beside the target and rename: a concurrent process (the
    # supervisor's child, a second replica) never loads a half-written file
    tmp = so.with_name(f"{so.name}.{os.getpid()}.tmp")
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        str(_SRC), "-o", str(tmp),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120,
                       text=True)
    except (OSError, subprocess.SubprocessError) as e:
        tmp.unlink(missing_ok=True)
        raise RuntimeError(
            f"building the native library failed ({' '.join(cmd)}): {e}\n"
            f"{getattr(e, 'stderr', '') or ''}") from e
    os.replace(tmp, so)


def get_lib() -> ctypes.CDLL:
    """The loaded library, built from the current source if its hash-named
    file is not there yet.  Raises when it cannot be built or loaded."""
    global _lib
    with _lock:
        if _lib is None:
            so = _so_path()
            if not so.exists():
                _build(so)
            lib = ctypes.CDLL(str(so))
            lib.apex_tpu_native_abi_version.restype = ctypes.c_int
            abi = lib.apex_tpu_native_abi_version()
            if abi != 1:
                raise RuntimeError(
                    f"{so} reports ABI version {abi}; this loader speaks 1")
            _lib = lib
        return _lib


def available() -> bool:
    """True once the library is built and loaded (raises if it cannot be)."""
    get_lib()
    return True


def flatten(arrays: List[np.ndarray], threads: int = DEFAULT_THREADS) -> np.ndarray:
    """Concatenate arbitrary-dtype arrays into one byte buffer
    (apex_C.flatten, csrc/flatten_unflatten.cpp:16)."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    sizes = np.array([a.nbytes for a in arrays], np.int64)
    total = int(sizes.sum())
    out = np.empty(total, np.uint8)
    lib = get_lib()
    srcs = (ctypes.c_void_p * len(arrays))(
        *[a.ctypes.data_as(ctypes.c_void_p) for a in arrays]
    )
    lib.apex_tpu_flatten(
        srcs,
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(len(arrays)),
        out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int(threads),
    )
    return out


def unflatten(buf: np.ndarray, shapes, dtypes, threads: int = DEFAULT_THREADS) -> List[np.ndarray]:
    """Split a flat byte buffer back into arrays (apex_C.unflatten)."""
    outs = [np.empty(s, d) for s, d in zip(shapes, dtypes)]
    sizes = np.array([o.nbytes for o in outs], np.int64)
    lib = get_lib()
    buf = np.ascontiguousarray(buf)
    dsts = (ctypes.c_void_p * len(outs))(
        *[o.ctypes.data_as(ctypes.c_void_p) for o in outs]
    )
    lib.apex_tpu_unflatten(
        buf.ctypes.data_as(ctypes.c_void_p),
        dsts,
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(len(outs)),
        ctypes.c_int(threads),
    )
    return outs


def gather_rows(src: np.ndarray, indices: np.ndarray, threads: int = DEFAULT_THREADS) -> np.ndarray:
    """dst[i] = src[indices[i]] — batch assembly for input pipelines."""
    src = np.ascontiguousarray(src)
    indices = np.ascontiguousarray(indices.astype(np.int64))
    n = len(indices)
    out = np.empty((n,) + src.shape[1:], src.dtype)
    lib = get_lib()
    row_bytes = src[0].nbytes if src.shape[0] else 0
    lib.apex_tpu_gather_rows(
        src.ctypes.data_as(ctypes.c_void_p),
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(n),
        ctypes.c_int64(row_bytes),
        out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int(threads),
    )
    return out
