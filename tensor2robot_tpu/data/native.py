"""ctypes loader for the native data-path library, with auto-build.

Consumers call `get_native()`; None means "use the pure-Python path"
(missing compiler, missing libjpeg, or build failure — all non-fatal).
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import Optional, Tuple

import numpy as np

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_native: Optional["NativeData"] = None
_load_attempted = False

# Set T2R_DISABLE_NATIVE=1 to force the pure-Python data path.
_DISABLE_ENV = "T2R_DISABLE_NATIVE"


class NativeData:
  """Typed wrappers over libt2rnative.so."""

  def __init__(self, lib: ctypes.CDLL):
    self._lib = lib
    lib.t2r_masked_crc32c.restype = ctypes.c_uint32
    lib.t2r_masked_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.t2r_tfrecord_index.restype = ctypes.c_int64
    lib.t2r_tfrecord_index.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint64, ctypes.c_int32]
    lib.t2r_jpeg_info.restype = ctypes.c_int32
    lib.t2r_jpeg_info.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32)]
    lib.t2r_jpeg_decode.restype = ctypes.c_int32
    lib.t2r_jpeg_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32]
    lib.t2r_jpeg_decode_batch.restype = ctypes.c_int32
    lib.t2r_jpeg_decode_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32)]
    lib.t2r_example_batch_dense.restype = ctypes.c_int32
    lib.t2r_example_batch_dense.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_int32, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
    lib.t2r_example_batch_bytes.restype = ctypes.c_int32
    lib.t2r_example_batch_bytes.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_int32, ctypes.c_char_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_int64)]

  def masked_crc32c(self, data: bytes) -> int:
    return self._lib.t2r_masked_crc32c(data, len(data))

  def tfrecord_index(self, buf: bytes, verify_crc: bool = True
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (offsets, lengths) of record payloads in `buf`.

    Whole-buffer indexing: memory is O(len(buf)). For large shards
    prefer the streaming tfrecord.read_tfrecords (which uses the native
    CRC but O(record) memory)."""
    # Worst-case record size 16 bytes (empty payload) → bound the index.
    max_records = max(len(buf) // 16, 1)
    offsets = (ctypes.c_uint64 * max_records)()
    lengths = (ctypes.c_uint64 * max_records)()
    n = self._lib.t2r_tfrecord_index(
        buf, len(buf), offsets, lengths, max_records, int(verify_crc))
    if n < 0:
      reasons = {-1: "truncated record", -2: "length CRC mismatch",
                 -3: "data CRC mismatch", -4: "index overflow"}
      raise ValueError(
          f"Corrupt TFRecord buffer: {reasons.get(n, n)}")
    # as_array derives shape from the ctypes array type (max_records);
    # slice down to the actual record count.
    return (np.ctypeslib.as_array(offsets)[:n].copy(),
            np.ctypeslib.as_array(lengths)[:n].copy())

  def jpeg_decode(self, data: bytes,
                  channels: Optional[int] = None) -> np.ndarray:
    """Decodes a JPEG to (H, W, C) uint8 (C = 1 or 3)."""
    w = ctypes.c_int32()
    h = ctypes.c_int32()
    c = ctypes.c_int32()
    if self._lib.t2r_jpeg_info(data, len(data),
                               ctypes.byref(w), ctypes.byref(h),
                               ctypes.byref(c)) != 0:
      raise ValueError("Invalid JPEG data")
    out_channels = channels or (1 if c.value == 1 else 3)
    if out_channels not in (1, 3):
      raise ValueError(f"channels must be 1 or 3, got {out_channels}")
    out = np.empty((h.value, w.value, out_channels), np.uint8)
    rc = self._lib.t2r_jpeg_decode(
        data, len(data),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), out_channels)
    if rc != 0:
      raise ValueError("JPEG decode failed")
    return out

  def jpeg_decode_batch(
      self,
      images: "list[bytes]",
      height: int,
      width: int,
      channels: int = 3,
      num_threads: int = 0,
  ) -> Tuple[np.ndarray, np.ndarray]:
    """Decodes a batch concurrently in C++ (GIL released for the whole
    batch — one call saturates all cores regardless of Python threads).

    Every image must decode to exactly (height, width); failures leave
    their output slot zeroed.

    Returns:
      ((N, H, W, C) uint8 array, (N,) int32 statuses — 0 ok, -1 decode
      error, -2 dimension mismatch, -3 corrupt-but-recoverable data
      such as truncated entropy segments).
    """
    if channels not in (1, 3):
      raise ValueError(f"channels must be 1 or 3, got {channels}")
    n = len(images)
    # np.empty, not np.zeros: the memset of the (N, H, W, C) output is
    # measurable on a 1-core host (~6% of the whole pipeline at 472²,
    # 2026-07-31 profile). The zeroed-failed-slot contract is enforced
    # inside the C++ worker (every failure path memsets its slot), not
    # by pre-zeroing the whole batch.
    out = np.empty((n, height, width, channels), np.uint8)
    statuses = np.zeros((n,), np.int32)
    if n == 0:
      return out, statuses
    datas = (ctypes.c_char_p * n)(*images)
    lens = (ctypes.c_uint64 * n)(*(len(im) for im in images))
    if num_threads <= 0:
      num_threads = min(n, os.cpu_count() or 1)
    self._lib.t2r_jpeg_decode_batch(
        datas, lens,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        height, width, channels, n, num_threads,
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out, statuses


  # --- tf.Example parsing ---------------------------------------------------

  def example_batch_dense(self, records: "list[bytes]", name: str,
                          kind: int, elems: int) -> Optional[np.ndarray]:
    """Parses feature `name` from every record into a (N, elems) array
    (kind 2 → float32 FloatList, 3 → int64 Int64List), entirely in C++.

    Returns None when the records don't match the request (missing
    feature / different wire kind / count mismatch) — callers fall back
    to the Python codec, which produces the precise error if the data is
    genuinely wrong. Raises on malformed protos (corrupt data is never
    silently skipped).
    """
    n = len(records)
    dtype = np.float32 if kind == 2 else np.int64
    out = np.empty((n, elems), dtype)
    if n == 0:
      return out
    datas = (ctypes.c_char_p * n)(*records)
    lens = (ctypes.c_uint64 * n)(*(len(r) for r in records))
    err_index = ctypes.c_int64(-1)
    rc = self._lib.t2r_example_batch_dense(
        datas, lens, n, name.encode("utf-8"), len(name.encode("utf-8")),
        kind, elems, out.ctypes.data_as(ctypes.c_void_p),
        ctypes.byref(err_index))
    if rc == 0:
      return out
    if rc == -4:
      raise ValueError(
          f"Malformed tf.Example proto at record {err_index.value} "
          f"(feature {name!r})")
    return None

  def example_batch_bytes(self, records: "list[bytes]",
                          name: str) -> Optional["list[bytes]"]:
    """Extracts the (first) bytes value of feature `name` per record.

    Same None-fallback / raise-on-malformed contract as
    example_batch_dense.
    """
    n = len(records)
    if n == 0:
      return []
    datas = (ctypes.c_char_p * n)(*records)
    lens = (ctypes.c_uint64 * n)(*(len(r) for r in records))
    ptrs = (ctypes.c_void_p * n)()
    out_lens = (ctypes.c_uint64 * n)()
    err_index = ctypes.c_int64(-1)
    rc = self._lib.t2r_example_batch_bytes(
        datas, lens, n, name.encode("utf-8"), len(name.encode("utf-8")),
        ptrs, out_lens, ctypes.byref(err_index))
    if rc == 0:
      # Copy out while `records` (the backing buffers) are alive.
      return [ctypes.string_at(ptrs[i], out_lens[i]) for i in range(n)]
    if rc == -4:
      raise ValueError(
          f"Malformed tf.Example proto at record {err_index.value} "
          f"(feature {name!r})")
    return None


def reset_cache() -> None:
  """Forgets the cached load decision so the next get_native() re-reads
  T2R_DISABLE_NATIVE — for tests/benchmarks toggling the native path
  within one process."""
  global _native, _load_attempted
  with _lock:
    _native = None
    _load_attempted = False


def get_native() -> Optional[NativeData]:
  """The loaded native library, building it on first use; None if
  unavailable."""
  global _native, _load_attempted
  with _lock:
    if _native is not None or _load_attempted:
      return _native
    _load_attempted = True
    if os.environ.get(_DISABLE_ENV) == "1":
      return None
    from tensor2robot_tpu.data import build_native
    try:
      # The .so is loaded only if its recorded hash matches the source
      # and build command on disk; anything else is rebuilt here first.
      if not build_native.library_is_current():
        build_native.build(verbose=False)
      _native = NativeData(ctypes.CDLL(build_native.LIBRARY))
    except Exception as e:  # missing toolchain/libjpeg → Python path
      _log.info("Native data path unavailable (%s); using pure Python.", e)
      _native = None
    return _native
