"""Loader + ctypes bindings for the native runtime library.

The C++ library (``native/`` at the repo root) provides the runtime
components the reference keeps native-adjacent (its embedded etcd is a
Go-wrapped C-lineage storage engine; pkg/etcd/etcd.go): a durable WAL
storage engine and the object-encoding hot loop. Python is the
orchestration layer; anything that runs per-mutation or per-object goes
through here when the library is available.

The library is built on demand with ``make`` (toolchain is expected in
the image); if building or loading fails, ``load()`` logs the error once
at WARNING, returns ``None``, and every caller takes the pure-Python
path — the native layer is an accelerator, never a requirement, but its
absence is never silent (:func:`status` says which it was). Set
``KCP_TPU_NO_NATIVE=1`` to force the Python path (used by differential
tests).
"""

from __future__ import annotations

import ctypes
import fcntl
import logging
import os
import subprocess
import threading
from typing import Iterator

from ..analysis.sanitize import make_lock

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_NAME = "libkcpnative.so"

log = logging.getLogger(__name__)

_lock = make_lock("native.load")
_lib: ctypes.CDLL | None = None
_load_attempted = False
_built_now = False  # this process ran make for the library
_load_error = ""  # why load() gave None (make's stderr or the dlopen error)


def _ensure_built(target: str, *make_args: str) -> bool:
    """Build ``native/<target>`` if it is missing or older than its
    sources; True when this process ran make. The check and the build
    sit under an inter-process lock (``flock`` on ``native/.build.lock``):
    several processes starting on a fresh tree — pytest-xdist workers,
    the shards of a deployment — would otherwise run make at once over
    the same object files, and the losers would find a half-written
    library and report it unavailable. A process that finds a build in
    progress waits for it, then finds the target up to date."""
    path = os.path.join(_NATIVE_DIR, target)
    try:
        lock_fh = open(os.path.join(_NATIVE_DIR, ".build.lock"), "w")
    except OSError:  # a read-only tree builds nothing: no one to wait for
        lock_fh = open(os.devnull, "w")
    with lock_fh:
        fcntl.flock(lock_fh, fcntl.LOCK_EX)  # released when the file closes
        if os.path.exists(path) and not _sources_newer_than_lib(path):
            return False
        try:
            subprocess.run(["make", "-s", "-C", _NATIVE_DIR, *make_args],
                           check=True, capture_output=True, timeout=120)
        except subprocess.CalledProcessError as e:
            out = (e.stderr or e.stdout or b"").decode(errors="replace").strip()
            raise RuntimeError(
                f"make failed (rc={e.returncode}): {out[-2000:]}") from e
        return True


def _sources_newer_than_lib(lib_path: str) -> bool:
    lib_mtime = os.path.getmtime(lib_path)
    for fn in os.listdir(_NATIVE_DIR):
        if fn.endswith((".cc", ".h")) and os.path.getmtime(os.path.join(_NATIVE_DIR, fn)) > lib_mtime:
            return True
    return False


def _declare(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u32p = ctypes.POINTER(ctypes.c_uint32)

    lib.ws_open.restype = ctypes.c_void_p
    lib.ws_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.ws_close.argtypes = [ctypes.c_void_p]
    lib.ws_last_error.restype = ctypes.c_char_p
    lib.ws_last_error.argtypes = [ctypes.c_void_p]
    lib.ws_put.restype = ctypes.c_int
    lib.ws_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
                           ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64]
    lib.ws_del.restype = ctypes.c_int
    lib.ws_del.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64]
    lib.ws_get.restype = ctypes.c_int
    lib.ws_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
                           ctypes.POINTER(u8p), u32p]
    lib.ws_rv.restype = ctypes.c_uint64
    lib.ws_rv.argtypes = [ctypes.c_void_p]
    lib.ws_count.restype = ctypes.c_uint64
    lib.ws_count.argtypes = [ctypes.c_void_p]
    lib.ws_flush.restype = ctypes.c_int
    lib.ws_flush.argtypes = [ctypes.c_void_p]
    lib.ws_batch_begin.restype = ctypes.c_int
    lib.ws_batch_begin.argtypes = [ctypes.c_void_p]
    lib.ws_batch_commit.restype = ctypes.c_int
    lib.ws_batch_commit.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ws_batch_abort.restype = ctypes.c_int
    lib.ws_batch_abort.argtypes = [ctypes.c_void_p]
    lib.ws_epoch.restype = ctypes.c_uint64
    lib.ws_epoch.argtypes = [ctypes.c_void_p]
    lib.ws_set_epoch.restype = ctypes.c_int
    lib.ws_set_epoch.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.ws_set_rv.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.ws_snapshot.restype = ctypes.c_int
    lib.ws_snapshot.argtypes = [ctypes.c_void_p]
    lib.ws_snapshot_begin.restype = ctypes.c_int
    lib.ws_snapshot_begin.argtypes = [ctypes.c_void_p]
    lib.ws_snapshot_add.restype = ctypes.c_int
    lib.ws_snapshot_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
                                    ctypes.c_char_p, ctypes.c_uint32]
    lib.ws_snapshot_commit.restype = ctypes.c_int
    lib.ws_snapshot_commit.argtypes = [ctypes.c_void_p]
    lib.ws_index_release.argtypes = [ctypes.c_void_p]
    lib.ws_scan.restype = ctypes.c_void_p
    lib.ws_scan.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.ws_scan_next.restype = ctypes.c_int
    lib.ws_scan_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(u8p), u32p,
                                 ctypes.POINTER(u8p), u32p]
    lib.ws_scan_free.argtypes = [ctypes.c_void_p]

    lib.enc_bucket_new.restype = ctypes.c_void_p
    lib.enc_bucket_new.argtypes = [ctypes.c_uint32]
    lib.enc_bucket_free.argtypes = [ctypes.c_void_p]
    lib.enc_bucket_encode.restype = ctypes.c_int
    lib.enc_bucket_encode.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t, u32p]
    lib.enc_bucket_nslots.restype = ctypes.c_uint32
    lib.enc_bucket_nslots.argtypes = [ctypes.c_void_p]
    lib.enc_bucket_path.restype = ctypes.c_int
    lib.enc_bucket_path.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                    ctypes.POINTER(ctypes.c_char_p), u32p]
    lib.enc_bucket_add_path.restype = ctypes.c_int
    lib.enc_bucket_add_path.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.enc_hash_value.restype = ctypes.c_uint32
    lib.enc_hash_value.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.enc_fnv1a.restype = ctypes.c_uint32
    lib.enc_fnv1a.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32]
    lib.enc_hash_pair.restype = ctypes.c_uint32
    lib.enc_hash_pair.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
                                  ctypes.c_size_t]
    lib.enc_tokenize_schemas.restype = ctypes.c_int
    lib.enc_tokenize_schemas.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint32,
        ctypes.c_uint32, u32p]


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library, or None."""
    global _lib, _load_attempted, _built_now, _load_error
    if os.environ.get("KCP_TPU_NO_NATIVE"):
        return None
    with _lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        lib_path = os.path.join(_NATIVE_DIR, _LIB_NAME)
        try:
            _built_now = _ensure_built(_LIB_NAME)
            lib = ctypes.CDLL(lib_path)
            _declare(lib)
            _lib = lib
        except Exception as e:  # noqa: BLE001 — the Python paths serve
            _lib = None
            _load_error = f"{type(e).__name__}: {e}"
            log.warning("native library unavailable, serving the pure-Python "
                        "paths: %s", _load_error)
        return _lib


def status() -> tuple[str, str]:
    """How :func:`load` went: ``("disabled" | "built" | "loaded" |
    "unavailable", detail)`` — ``built`` means this process ran ``make``
    just now, ``unavailable`` carries the build or load error."""
    if os.environ.get("KCP_TPU_NO_NATIVE"):
        return "disabled", "KCP_TPU_NO_NATIVE is set"
    if load() is None:
        return "unavailable", _load_error
    return ("built" if _built_now else "loaded"), os.path.join(
        os.path.abspath(_NATIVE_DIR), _LIB_NAME)


def available() -> bool:
    return load() is not None


class WalEngine:
    """Durable WAL storage engine handle (native walstore.cc).

    Keys and values are bytes; the store layers its
    ``/<resource>/<cluster>/<ns>/<name>`` scheme on top with NUL-joined
    key tuples so prefix scans follow the etcd range-scan idiom
    (docs/investigations/logical-clusters.md:70-74 in the reference).
    """

    def __init__(self, path: str, sync_every: int = 256):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.ws_open(path.encode(), sync_every)
        if not self._h:
            raise OSError(f"ws_open({path!r}) failed")

    def put(self, key: bytes, val: bytes, rv: int) -> None:
        if self._lib.ws_put(self._h, key, len(key), val, len(val), rv) != 0:
            raise OSError(self._lib.ws_last_error(self._h).decode())

    def delete(self, key: bytes, rv: int) -> None:
        if self._lib.ws_del(self._h, key, len(key), rv) != 0:
            raise OSError(self._lib.ws_last_error(self._h).decode())

    def get(self, key: bytes) -> bytes | None:
        val = ctypes.POINTER(ctypes.c_uint8)()
        vlen = ctypes.c_uint32()
        if self._lib.ws_get(self._h, key, len(key), ctypes.byref(val), ctypes.byref(vlen)):
            return ctypes.string_at(val, vlen.value)
        return None

    @property
    def rv(self) -> int:
        return self._lib.ws_rv(self._h)

    @property
    def epoch(self) -> int:
        """Replication epoch persisted in the log (0 = never stamped)."""
        return self._lib.ws_epoch(self._h)

    def set_epoch(self, epoch: int) -> None:
        """Durably stamp a replication epoch (fsynced before return —
        fences and promotions must not be lost to a crash)."""
        if self._lib.ws_set_epoch(self._h, epoch) != 0:
            raise OSError(self._lib.ws_last_error(self._h).decode())

    def set_rv(self, rv: int) -> None:
        """Advance the RV watermark without a mutation record (snapshot
        resync: objects arrive with their own RVs, the barrier carries
        the authoritative watermark)."""
        self._lib.ws_set_rv(self._h, rv)

    def __len__(self) -> int:
        return self._lib.ws_count(self._h)

    def append_batch(self, ops, fsync: bool = False) -> None:
        """Append one group-commit window of records as ONE buffered
        write + at most one fsync. ``ops`` is an iterable of
        ``(key, val, rv)`` tuples — ``val is None`` means delete. With
        ``fsync=False`` the engine's ``sync_every`` batching still
        applies (the KCP_WAL_SYNC=flush policy); a failed commit leaves
        NONE of the window's records in the log."""
        lib = self._lib
        if lib.ws_batch_begin(self._h) != 0:
            raise OSError(lib.ws_last_error(self._h).decode())
        try:
            for key, val, rv in ops:
                if val is None:
                    self.delete(key, rv)
                else:
                    self.put(key, val, rv)
        except BaseException:
            lib.ws_batch_abort(self._h)
            raise
        if lib.ws_batch_commit(self._h, 1 if fsync else 0) != 0:
            raise OSError(lib.ws_last_error(self._h).decode())

    def flush(self) -> None:
        if self._lib.ws_flush(self._h) != 0:
            raise OSError("fsync failed")

    def snapshot(self) -> None:
        if self._lib.ws_snapshot(self._h) != 0:
            raise OSError("snapshot failed")

    def snapshot_stream(self, items) -> None:
        """Compact by streaming (key, value) pairs from the caller —
        used in journal-only mode where the engine keeps no value copy."""
        if self._lib.ws_snapshot_begin(self._h) != 0:
            raise OSError("snapshot begin failed")
        for key, val in items:
            if self._lib.ws_snapshot_add(self._h, key, len(key), val, len(val)) != 0:
                raise OSError("snapshot add failed")
        if self._lib.ws_snapshot_commit(self._h) != 0:
            raise OSError("snapshot commit failed")

    def release_index(self) -> None:
        """Switch to journal-only mode: drop the engine's in-memory copy
        (the host holds the authoritative objects; get/scan go dark)."""
        self._lib.ws_index_release(self._h)

    def scan(self, prefix: bytes = b"") -> Iterator[tuple[bytes, bytes]]:
        cur = self._lib.ws_scan(self._h, prefix, len(prefix))
        try:
            key = ctypes.POINTER(ctypes.c_uint8)()
            val = ctypes.POINTER(ctypes.c_uint8)()
            klen = ctypes.c_uint32()
            vlen = ctypes.c_uint32()
            while self._lib.ws_scan_next(cur, ctypes.byref(key), ctypes.byref(klen),
                                         ctypes.byref(val), ctypes.byref(vlen)):
                yield ctypes.string_at(key, klen.value), ctypes.string_at(val, vlen.value)
        finally:
            self._lib.ws_scan_free(cur)

    def close(self) -> None:
        if self._h:
            self._lib.ws_close(self._h)
            self._h = None


class NativeBucket:
    """Native slot-vocabulary encoder (twin of ops.encode.BucketEncoder)."""

    OVERFLOW = -1

    def __init__(self, capacity: int):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.capacity = capacity
        self._h = lib.enc_bucket_new(capacity)

    def encode_json(self, json_bytes: bytes, out) -> int:
        """Encode one object's JSON into out (uint32[capacity] numpy).

        Returns 0 ok, -1 overflow, -2/-3 parse errors.
        """
        import numpy as np

        if out.size < self.capacity:
            raise ValueError(
                f"out has {out.size} elements; bucket capacity is {self.capacity}"
            )
        direct = out.flags["C_CONTIGUOUS"] and out.dtype == np.uint32
        buf = out if direct else np.zeros(self.capacity, dtype=np.uint32)
        ptr = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))
        rc = self._lib.enc_bucket_encode(self._h, json_bytes, len(json_bytes), ptr)
        if not direct and rc == 0:
            out[: self.capacity] = buf
        return rc

    @property
    def nslots(self) -> int:
        return self._lib.enc_bucket_nslots(self._h)

    def slot_paths(self) -> list[str]:
        out = []
        path = ctypes.c_char_p()
        plen = ctypes.c_uint32()
        for slot in range(self.nslots):
            if self._lib.enc_bucket_path(self._h, slot, ctypes.byref(path), ctypes.byref(plen)):
                out.append(path.value[:plen.value].decode())
        return out

    def add_path(self, path: str) -> int:
        return self._lib.enc_bucket_add_path(self._h, path.encode(), len(path.encode()))

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.enc_bucket_free(self._h)
        except Exception:
            pass


def hash_value_native(json_bytes: bytes) -> int:
    lib = load()
    assert lib is not None
    return lib.enc_hash_value(json_bytes, len(json_bytes))


def fnv1a_native(data: bytes, seed: int = 0x811C9DC5) -> int:
    lib = load()
    assert lib is not None
    return lib.enc_fnv1a(data, len(data), seed)


_tok_mod = None
_tok_tried = False


def load_tokenizer():
    """Load (building if needed) the kcptok CPython extension, or None.

    Separate from :func:`load` because the extension needs Python dev
    headers at build time; its absence must not disable the main
    library. Same fallback contract: None means callers use the next
    tier down (the JSON-blob native path, then the Python walk).
    """
    global _tok_mod, _tok_tried
    if os.environ.get("KCP_TPU_NO_NATIVE"):
        return None
    with _lock:
        if _tok_tried:
            return _tok_mod
        _tok_tried = True
        path = os.path.join(_NATIVE_DIR, "kcptok.so")
        try:
            import sysconfig

            # compile against THIS interpreter's headers — the
            # Makefile's PATH-python3 default could be a different
            # Python whose ABI would segfault on dlopen
            _ensure_built("kcptok.so", "kcptok.so",
                          f"PYINC={sysconfig.get_paths()['include']}")
            import importlib.machinery
            import importlib.util

            loader = importlib.machinery.ExtensionFileLoader("kcptok", path)
            spec = importlib.util.spec_from_loader("kcptok", loader)
            mod = importlib.util.module_from_spec(spec)
            loader.exec_module(mod)
            _tok_mod = mod
        except Exception as e:  # noqa: BLE001 — next tier down serves
            _tok_mod = None
            log.warning("native tokenizer unavailable: %s: %s",
                        type(e).__name__, e)
        return _tok_mod


def tokenize_schemas_native(blobs: list[bytes], max_tokens: int):
    """Tokenize a batch of canonical-JSON schemas in one native call.

    Returns a ``[len(blobs), max_tokens]`` uint32 numpy array, or None
    when the library is unavailable or any blob fails to parse (callers
    fall back to the Python walk — same contract as the other native
    accelerators here).
    """
    lib = load()
    if lib is None:
        return None
    import numpy as np

    n = len(blobs)
    if n == 0:
        return np.zeros((0, max_tokens), dtype=np.uint32)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    lengths = np.fromiter((len(b) for b in blobs), dtype=np.uint64, count=n)
    np.cumsum(lengths, out=offsets[1:])
    data = b"".join(blobs)
    out = np.empty((n, max_tokens), dtype=np.uint32)
    rc = lib.enc_tokenize_schemas(
        data,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n,
        max_tokens,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    return out if rc == 0 else None
