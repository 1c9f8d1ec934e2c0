"""ctypes bindings for the native runtime core (csrc/).

The reference's native layer binds through Cython (ref:
python/ray/_raylet.pyx); this image has no pybind11, so the C ABI +
ctypes is the binding (zero build-time Python deps). `ensure_built()`
compiles csrc/ on first use when a toolchain is present; every native
feature has a pure-Python fallback, so the framework still works where
there is no compiler — but a failed build is never silent: it is logged
once with the compiler's output, `build_error()` returns it, and
`chip_smoke.py` fails on it.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
# RTPU_NATIVE_SO selects an alternate build of the native core — the
# sanitizer tier sets librtpu_asan.so (`make -C csrc asan`) so the same
# Python tests drive the store/sched/dataio under ASan+UBSan
_SO = os.path.join(_HERE, os.environ.get("RTPU_NATIVE_SO",
                                         "librtpu.so"))
_CSRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "csrc")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False
_build_error: Optional[str] = None


def _stale() -> bool:
    if not os.path.exists(_SO):
        return True
    so_mtime = os.path.getmtime(_SO)
    for name in os.listdir(_CSRC):
        if name.endswith(".cc"):
            if os.path.getmtime(os.path.join(_CSRC, name)) > so_mtime:
                return True
    return False


def ensure_built() -> bool:
    """Build librtpu.so if missing/stale. Returns availability.

    Safe across processes: the check and the build run under an exclusive
    file lock, and the compiler writes to a temporary name that is renamed
    into place. A fresh checkout has no .so (it is git-ignored), and the
    test workers, the worker factory and every cluster worker all come here
    at once: without the lock one of them loads a half-written library
    ("file too short")."""
    global _build_failed, _build_error
    import fcntl

    with _lock, open(os.path.join(_HERE, ".build.lock"), "w") as lock_file:
        fcntl.flock(lock_file, fcntl.LOCK_EX)
        if os.path.exists(_SO) and not _stale():
            return True
        if _build_failed:
            return False
        tmp = f"{_SO}.tmp"
        try:
            asan = _SO.endswith("_asan.so")
            subprocess.run(
                ["make", "-C", _CSRC, *(["asan"] if asan else []),
                 f"{'ASAN_OUT' if asan else 'OUT'}={tmp}"],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, _SO)
            return True
        except Exception as e:  # noqa: BLE001 — any failure = pure-Python store
            _build_failed = True
            out = getattr(e, "stderr", None) or b""
            _build_error = (f"{e!r}\n{out.decode(errors='replace')}"
                            .strip())
            import logging

            logging.getLogger(__name__).warning(
                "native runtime core did not build; running the "
                "pure-Python store: %s", _build_error)
            return False


def build_error() -> Optional[str]:
    """The failed build's error and compiler output (None: no build has
    failed in this process)."""
    return _build_error


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, or None when unavailable (no toolchain)."""
    global _lib
    if _lib is not None:
        return _lib
    if os.environ.get("RTPU_NATIVE", "1") == "0":
        return None
    if not ensure_built():
        return None
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(_SO)
            lib.rtpu_pool_create.restype = ctypes.c_int
            lib.rtpu_pool_create.argtypes = [ctypes.c_char_p,
                                             ctypes.c_uint64,
                                             ctypes.c_uint64]
            lib.rtpu_pool_open.restype = ctypes.c_void_p
            lib.rtpu_pool_open.argtypes = [ctypes.c_char_p]
            lib.rtpu_pool_close.argtypes = [ctypes.c_void_p]
            lib.rtpu_pool_base.restype = ctypes.POINTER(ctypes.c_ubyte)
            lib.rtpu_pool_base.argtypes = [ctypes.c_void_p]
            lib.rtpu_store_create.restype = ctypes.c_int64
            lib.rtpu_store_create.argtypes = [ctypes.c_void_p,
                                              ctypes.c_char_p,
                                              ctypes.c_uint64]
            lib.rtpu_store_seal.restype = ctypes.c_int
            lib.rtpu_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.rtpu_store_get.restype = ctypes.c_int64
            lib.rtpu_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           ctypes.POINTER(ctypes.c_uint64)]
            lib.rtpu_store_release.restype = ctypes.c_int
            lib.rtpu_store_release.argtypes = [ctypes.c_void_p,
                                               ctypes.c_char_p]
            lib.rtpu_store_delete.restype = ctypes.c_int
            lib.rtpu_store_delete.argtypes = [ctypes.c_void_p,
                                              ctypes.c_char_p]
            lib.rtpu_store_contains.restype = ctypes.c_int
            lib.rtpu_store_contains.argtypes = [ctypes.c_void_p,
                                                ctypes.c_char_p]
            lib.rtpu_store_stats.argtypes = [ctypes.c_void_p,
                                             ctypes.POINTER(
                                                 ctypes.c_uint64 * 4)]
            lib.rtpu_hash_combine_i64.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
            lib.rtpu_hash_combine_bytes.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p]
            lib.rtpu_hash_combine_bytes_varlen.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p]
            lib.rtpu_hash_to_partition.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_void_p]
            lib.rtpu_sched_pick.restype = ctypes.c_int
            lib.rtpu_sched_pick.argtypes = [
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_double), ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_double), ctypes.c_int, ctypes.c_int,
                ctypes.c_double, ctypes.c_uint32]
            _lib = lib
    return _lib


class OutOfMemory(Exception):
    pass


class NativePool:
    """One mmap'd object pool shared by all processes of a session
    (plasma-store equivalent; see csrc/store.cc)."""

    KEY_LEN = 20

    def __init__(self, path: str, capacity: int = 256 << 20,
                 nbuckets: int = 4096):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._path = path
        creator = not os.path.exists(path)
        rc = lib.rtpu_pool_create(path.encode(), capacity, nbuckets)
        if rc != 0:
            raise OSError(f"pool create failed: {rc}")
        self._handle = lib.rtpu_pool_open(path.encode())
        if not self._handle:
            raise OSError("pool open failed")
        base = lib.rtpu_pool_base(self._handle)
        # view over the whole pool for zero-copy reads
        stats = (ctypes.c_uint64 * 4)()
        lib.rtpu_store_stats(self._handle, ctypes.byref(stats))
        self._pool_size = stats[1]
        base_addr = ctypes.addressof(base.contents)
        arr = (ctypes.c_ubyte * self._pool_size).from_address(base_addr)
        self._mem = memoryview(arr).cast("B")
        if creator:
            # creator-only: openers fault their page tables lazily (the
            # physical pages are already committed), and thousands of
            # workers must not each sweep the whole range
            self._prefault_async(base_addr, self._pool_size)

    @staticmethod
    def _prefault_async(addr: int, size: int) -> None:
        """Fault the pool's pages in off the critical path. First-touch
        faults on fresh /dev/shm pages throttle a large put to ~0.8 GB/s
        (kernel page allocation + zeroing inside the copy loop); a
        populated pool copies at memcpy speed. MADV_POPULATE_WRITE
        allocates without altering contents, so re-opening a live pool
        is safe. Best-effort: older kernels return EINVAL, and the put
        path works either way."""
        import threading

        def run():
            try:
                libc = ctypes.CDLL(None, use_errno=True)
                MADV_POPULATE_WRITE = 23
                libc.madvise(ctypes.c_void_p(addr),
                             ctypes.c_size_t(size), MADV_POPULATE_WRITE)
            except Exception:  # rtpulint: ignore[RTPU006] — madvise prefault is a droppable optimization; the pool works unpopulated
                pass

        threading.Thread(target=run, daemon=True,
                         name="rtpu-pool-prefault").start()

    def _key(self, key: bytes) -> bytes:
        assert len(key) == self.KEY_LEN, key
        return key

    def create(self, key: bytes, size: int) -> memoryview:
        off = self._lib.rtpu_store_create(self._handle, self._key(key), size)
        if off == -1:
            raise FileExistsError(key.hex())
        if off == -2:
            raise OutOfMemory(f"pool full allocating {size} bytes")
        return self._mem[off:off + size]

    def seal(self, key: bytes) -> None:
        self._lib.rtpu_store_seal(self._handle, self._key(key))

    def get(self, key: bytes) -> Optional[memoryview]:
        """Zero-copy view; pairs with release()."""
        raw = self.get_raw(key)
        if raw is None:
            return None
        off, size = raw
        return self._mem[off:off + size]

    def get_raw(self, key: bytes):
        """(file_offset, size) with the refcount bumped, or None. Callers
        that hand out zero-copy views should map their own window over the
        pool file at this offset so alias liveness is detectable at
        close() time (buffer exports root at the mmap object)."""
        size = ctypes.c_uint64()
        off = self._lib.rtpu_store_get(self._handle, self._key(key),
                                       ctypes.byref(size))
        if off < 0:
            return None
        return int(off), int(size.value)

    def release(self, key: bytes) -> None:
        self._lib.rtpu_store_release(self._handle, self._key(key))

    def delete(self, key: bytes) -> None:
        self._lib.rtpu_store_delete(self._handle, self._key(key))

    def contains(self, key: bytes) -> bool:
        return bool(self._lib.rtpu_store_contains(self._handle,
                                                  self._key(key)))

    def stats(self) -> dict:
        raw = (ctypes.c_uint64 * 4)()
        self._lib.rtpu_store_stats(self._handle, ctypes.byref(raw))
        return {"used_bytes": raw[0], "capacity": raw[1],
                "num_objects": raw[2], "evictions": raw[3]}

    def close(self) -> None:
        if self._handle:
            self._lib.rtpu_pool_close(self._handle)
            self._handle = None


STRATEGY_CODES = {"HYBRID": 0, "SPREAD": 1, "RANDOM": 2}


def native_pick(avail, total, req, strategy: str, local_index: int = -1,
                hybrid_threshold: float = 0.5, seed: int = 1):
    """avail/total: list of per-node resource lists (n x k); req: k floats.
    Returns node index or None. Falls back to None when unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(avail)
    k = len(req)
    if n == 0:
        return -1
    import numpy as np

    flat_a = np.ascontiguousarray(avail, dtype=np.float64)
    flat_t = np.ascontiguousarray(total, dtype=np.float64)
    flat_r = np.ascontiguousarray(req, dtype=np.float64)
    dptr = ctypes.POINTER(ctypes.c_double)
    idx = lib.rtpu_sched_pick(
        flat_a.ctypes.data_as(dptr), flat_t.ctypes.data_as(dptr), n, k,
        flat_r.ctypes.data_as(dptr),
        STRATEGY_CODES.get(strategy, 0), local_index, hybrid_threshold,
        seed)
    return idx


# ---------------------------------------------------------------- dataio
def hash_partition(columns, num_parts: int):
    """Vectorized hash-partition of rows by key columns -> int32 partition
    ids (csrc/dataio.cc; numpy fallback computes the SAME hashes, so
    mixed native/fallback workers agree on the partitioning).

    Accepts numpy columns: integers/bools (cast i64), floats (bit-cast),
    and bytes/str (fixed-width encode).
    """
    import numpy as np

    n = len(columns[0])
    acc = np.zeros(n, np.uint64)
    lib = get_lib()
    prepped = []
    for col in columns:
        col = np.asarray(col)
        if col.dtype.kind in "iub":
            prepped.append(("i64", np.ascontiguousarray(col, np.int64)))
        elif col.dtype.kind == "f":
            prepped.append(("i64", np.ascontiguousarray(
                col.astype(np.float64)).view(np.int64)))
        else:  # strings / bytes -> fixed-width bytes + actual lengths
            if col.dtype.kind == "U":
                # utf-8 so non-ascii strings stay on the vectorized path
                col = np.char.encode(col, "utf-8")
            as_bytes = np.ascontiguousarray(np.asarray(col, dtype="S"))
            # hash only each row's real bytes: the 'S' width (and its NUL
            # padding) is block-local, and padding in the hash would
            # partition the same key differently across blocks
            width = as_bytes.dtype.itemsize
            raw = as_bytes.view(np.uint8).reshape(n, width)
            nonzero = raw != 0
            lens = np.where(
                nonzero.any(axis=1),
                width - np.argmax(nonzero[:, ::-1], axis=1), 0).astype(np.int64)
            prepped.append(("bytes", (as_bytes, np.ascontiguousarray(lens))))
    if lib is not None:
        import ctypes

        for kind, arr in prepped:
            if kind == "i64":
                lib.rtpu_hash_combine_i64(
                    arr.ctypes.data_as(ctypes.c_void_p), n,
                    acc.ctypes.data_as(ctypes.c_void_p))
            else:
                data, lens = arr
                lib.rtpu_hash_combine_bytes_varlen(
                    data.ctypes.data_as(ctypes.c_void_p), n,
                    data.dtype.itemsize,
                    lens.ctypes.data_as(ctypes.c_void_p),
                    acc.ctypes.data_as(ctypes.c_void_p))
        out = np.empty(n, np.int32)
        lib.rtpu_hash_to_partition(
            acc.ctypes.data_as(ctypes.c_void_p), n, num_parts,
            out.ctypes.data_as(ctypes.c_void_p))
        return out
    # numpy fallback: identical algorithm, vectorized uint64 wraparound
    def _splitmix64(x):
        x = (x + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(0xFFFFFFFFFFFFFFFF)
        x = ((x ^ (x >> np.uint64(30)))
             * np.uint64(0xBF58476D1CE4E5B9)) & np.uint64(0xFFFFFFFFFFFFFFFF)
        x = ((x ^ (x >> np.uint64(27)))
             * np.uint64(0x94D049BB133111EB)) & np.uint64(0xFFFFFFFFFFFFFFFF)
        return x ^ (x >> np.uint64(31))

    def _combine(a, h):
        return a ^ ((h + np.uint64(0x9E3779B97F4A7C15)
                     + ((a << np.uint64(6)) & np.uint64(0xFFFFFFFFFFFFFFFF))
                     + (a >> np.uint64(2))) & np.uint64(0xFFFFFFFFFFFFFFFF))

    with np.errstate(over="ignore"):
        for kind, arr in prepped:
            if kind == "i64":
                acc = _combine(acc, _splitmix64(arr.view(np.uint64)))
            else:
                data, lens = arr
                fnv = np.full(n, np.uint64(1469598103934665603))
                width = data.dtype.itemsize
                raw = data.view(np.uint8).reshape(n, width)
                for j in range(width):
                    live = lens > j  # mirror varlen: stop at each row's len
                    step = ((fnv ^ raw[:, j])
                            * np.uint64(1099511628211)) & np.uint64(0xFFFFFFFFFFFFFFFF)
                    fnv = np.where(live, step, fnv)
                acc = _combine(acc, fnv)
        return (_splitmix64(acc) % np.uint64(num_parts)).astype(np.int32)
