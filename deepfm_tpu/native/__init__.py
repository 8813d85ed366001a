"""Native (C++) data plane: streaming TFRecord reader + fused CTR decoder.

This package owns the framework's native-runtime surface for ingest — the
capability the reference inherits from tf.data's C++ runtime and the
``sagemaker_tensorflow`` PipeModeDataset C++ op (SURVEY.md §2b; reference
ps:147,150, hvd:136).  The shared library is compiled from
``src/tfrecord_reader.cc`` with the system ``g++`` on first use and cached
next to the source; set ``DEEPFM_NO_NATIVE=1`` to force the pure-Python
fallback (``deepfm_tpu.data.tfrecord`` / ``example_proto``).

The hot entry point is :class:`NativeCtrReader`, which streams whole decoded
numpy batches out of C++ — framing, CRC32C (SSE4.2 when available), record
sharding, and Example-proto parsing all happen without touching the Python
interpreter per record.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Iterable, Iterator, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [
    os.path.join(_HERE, "src", "tfrecord_reader.cc"),
    os.path.join(_HERE, "src", "criteo_encoder.cc"),
]
_LIB_DIR = os.path.join(_HERE, "_build")
_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-fno-exceptions",
        "-Wall"]

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_error: str | None = None


def _lib_path() -> str:
    """The library is named by the hash of what it is built from (sources,
    compiler command, machine), so a tree copied from elsewhere — stale
    ``.so`` and fresh mtimes included — rebuilds exactly when its sources
    differ from the ones the library was compiled from."""
    h = hashlib.sha256(" ".join(_CXX + [platform.machine()]).encode())
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_LIB_DIR, f"libdeepfm_native.{h.hexdigest()[:16]}.so")


def _build(lib: str) -> None:
    os.makedirs(_LIB_DIR, exist_ok=True)
    tmp = f"{lib}.{os.getpid()}.tmp"  # unique per builder: concurrent
    # processes each compile their own file; os.replace publishes whichever
    # finishes last, atomically
    proc = subprocess.run([*_CXX, *_SRCS, "-o", tmp],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed:\n{proc.stderr}")
    os.replace(tmp, lib)


def _load() -> ctypes.CDLL:
    global _lib, _build_error
    if _lib is not None:       # loaded: no lock, no source hashing
        return _lib
    path = _lib_path()         # reads the sources — outside the lock
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            raise RuntimeError(_build_error)
        try:
            if not os.path.exists(path):
                # da:allow[blocking-under-lock] build-once lazy init: the lock exists to make the slow compile happen exactly once; callers blocking behind it is the design
                _build(path)
            lib = ctypes.CDLL(path)
        except Exception as e:  # remember failure; don't retry per call
            _build_error = f"{type(e).__name__}: {e}"
            raise
        lib.dfm_reader_open.restype = ctypes.c_void_p
        lib.dfm_reader_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.dfm_reader_close.argtypes = [ctypes.c_void_p]
        lib.dfm_reader_error.restype = ctypes.c_char_p
        lib.dfm_reader_error.argtypes = [ctypes.c_void_p]
        lib.dfm_reader_next_record.restype = ctypes.c_int64
        lib.dfm_reader_next_record.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ]
        lib.dfm_reader_next_ctr_batch.restype = ctypes.c_int64
        lib.dfm_reader_next_ctr_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
        ]
        lib.dfm_masked_crc32c.restype = ctypes.c_uint32
        lib.dfm_masked_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.dfm_have_hw_crc.restype = ctypes.c_int
        lib.dfm_blake2b64.restype = ctypes.c_uint64
        lib.dfm_blake2b64.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.dfm_criteo_hash_encode.restype = ctypes.c_int64
        lib.dfm_criteo_hash_encode.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
        ]
        _lib = lib
        return lib


def available() -> bool:
    """True when the native library is usable (builds it on first call).
    A failed build or load is logged ONCE with the compiler's stderr — the
    pure-Python reader then serves a host without a compiler, but never
    silently."""
    if os.environ.get("DEEPFM_NO_NATIVE"):
        return False
    first_failure = _build_error is None
    try:
        _load()
        return True
    except (RuntimeError, OSError) as e:
        if first_failure:
            logging.getLogger(__name__).warning(
                "native reader unavailable, using the pure-Python reader: %s",
                e,
            )
        return False


def have_hw_crc() -> bool:
    return bool(_load().dfm_have_hw_crc())


def masked_crc32c(data: bytes) -> int:
    return _load().dfm_masked_crc32c(data, len(data))


def blake2b64(data: bytes) -> int:
    """8-byte unkeyed BLAKE2b as a little-endian int — the criteo hash
    (== int.from_bytes(hashlib.blake2b(data, digest_size=8).digest(),
    'little'))."""
    return _load().dfm_blake2b64(data, len(data))


def criteo_hash_encode_file(
    input_path: str | os.PathLike,
    output_dir: str | os.PathLike,
    *,
    feature_size: int,
    records_per_shard: int = 1_000_000,
    prefix: str = "tr",
) -> int:
    """Native drop-in for ``data.criteo.convert_criteo_to_tfrecords`` with a
    ``CriteoHashEncoder`` — byte-identical shards (same hash, proto bytes,
    framing, shard naming), interpreter-free per line.  Returns records
    written; raises ValueError if any line was malformed (the Python
    encoder raises on the first one; here the count is reported after the
    well-formed lines were written)."""
    os.makedirs(output_dir, exist_ok=True)
    err = ctypes.create_string_buffer(256)
    n = _load().dfm_criteo_hash_encode(
        os.fsencode(os.fspath(input_path)),
        os.fsencode(os.fspath(output_dir)),
        prefix.encode(),
        feature_size,
        records_per_shard,
        err,
        len(err),
    )
    if n < 0:
        raise NativeReaderError(err.value.decode(errors="replace"))
    if err.value:
        raise ValueError(err.value.decode(errors="replace"))
    return int(n)


def _pack_paths(paths: Sequence[str | os.PathLike]) -> bytes:
    out = b""
    for p in paths:
        out += os.fsencode(os.fspath(p)) + b"\x00"
    return out + b"\x00"


class NativeReaderError(IOError):
    pass


class _Handle:
    """RAII wrapper over a dfm_reader handle."""

    def __init__(self, paths, verify: bool, shard_n: int, shard_i: int):
        self._lib = _load()
        self._h = self._lib.dfm_reader_open(
            _pack_paths(paths), 1 if verify else 0, shard_n, shard_i
        )
        if not self._h:
            raise NativeReaderError("dfm_reader_open failed")

    def error(self) -> str:
        return self._lib.dfm_reader_error(self._h).decode(errors="replace")

    def close(self) -> None:
        if self._h:
            self._lib.dfm_reader_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        # da:allow[swallowed-exception] finalizer: interpreter teardown may have dropped the lib handle
        except Exception:
            pass


def read_records(
    paths: str | os.PathLike | Sequence[str | os.PathLike],
    *,
    verify: bool = True,
    shard_n: int = 1,
    shard_i: int = 0,
) -> Iterator[bytes]:
    """Yield raw record payloads (this shard) from the native reader.

    Drop-in analog of ``deepfm_tpu.data.tfrecord.read_records`` but over a
    *list* of sources with sharding pushed into C++.
    """
    if isinstance(paths, (str, os.PathLike)):
        paths = [paths]
    h = _Handle(paths, verify, shard_n, shard_i)
    lib = h._lib
    ptr = ctypes.POINTER(ctypes.c_uint8)()
    try:
        while True:
            n = lib.dfm_reader_next_record(h._h, ctypes.byref(ptr))
            if n == -1:
                return
            if n < 0:
                raise NativeReaderError(h.error())
            yield ctypes.string_at(ptr, n)
    finally:
        h.close()


class NativeCtrReader:
    """Stream decoded CTR batches out of the C++ reader.

    Yields ``{"feat_ids": i64 [B,F], "feat_vals": f32 [B,F], "label": f32 [B]}``
    exactly like ``data.pipeline.batched_ctr_batches`` — but the whole
    record→batch path (framing, CRC, shard filter, proto decode) runs native.
    """

    def __init__(
        self,
        paths: Sequence[str | os.PathLike],
        *,
        batch_size: int,
        field_size: int,
        shard_n: int = 1,
        shard_i: int = 0,
        drop_remainder: bool = True,
        verify: bool = True,
        skip_counter: list[int] | None = None,
    ):
        self._paths = list(paths)
        self._batch = batch_size
        self._fields = field_size
        self._shard = (shard_n, shard_i)
        self._drop = drop_remainder
        self._verify = verify
        self._skip_counter = skip_counter

    def __iter__(self) -> Iterator[dict]:
        h = _Handle(self._paths, self._verify, *self._shard)
        lib = h._lib
        B, F = self._batch, self._fields
        try:
            # input-position resume: fast-forward whole batches at the raw-
            # record level (framing+CRC only, no Example decode, no copies).
            # The shared counter lets the caller spread a skip across epochs;
            # a partial tail doesn't decrement it (drop_remainder parity).
            ptr = ctypes.POINTER(ctypes.c_uint8)()
            while self._skip_counter and self._skip_counter[0] > 0:
                pulled = 0
                while pulled < B:
                    n = lib.dfm_reader_next_record(h._h, ctypes.byref(ptr))
                    if n == -1:
                        # stream ended mid-skip: with remainders kept the
                        # partial tail counts as one skipped step
                        if pulled and not self._drop:
                            self._skip_counter[0] -= 1
                        return
                    if n < 0:
                        raise NativeReaderError(h.error())
                    pulled += 1
                self._skip_counter[0] -= 1
            while True:
                ids = np.empty((B, F), np.int64)
                vals = np.empty((B, F), np.float32)
                labels = np.empty((B,), np.float32)
                n = lib.dfm_reader_next_ctr_batch(
                    h._h, B, F,
                    ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                    vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                    labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                )
                if n < 0:
                    raise NativeReaderError(h.error())
                if n == B:
                    yield {"feat_ids": ids, "feat_vals": vals, "label": labels}
                    continue
                if n > 0 and not self._drop:
                    yield {
                        "feat_ids": ids[:n],
                        "feat_vals": vals[:n],
                        "label": labels[:n],
                    }
                return
        finally:
            h.close()
