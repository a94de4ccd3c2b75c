"""Native host runtime bindings (C++ via ctypes).

The runtime around the JAX compute path is native where the reference's is
(SURVEY.md §2 [NATIVE-EQ] items): fixed-bit pack/unpack of dictId arrays,
refcounted mmap buffers, file CRC, and varint posting lists live in
``native/pinot_native.cpp``, compiled with g++ on first use and bound
through ctypes (no pybind11 in the image). The library is always built
from that tracked source — a ``.so`` without it is not loaded. Every entry
point has a numpy fallback so the framework still runs where no compiler
exists; ``available()`` says which one is in use.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import zlib

from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "pinot_native.cpp")
_LIB_DIR = os.path.join(_REPO_ROOT, "native", "build")
_LIB = os.path.join(_LIB_DIR, "libpinot_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _build() -> bool:
    os.makedirs(_LIB_DIR, exist_ok=True)
    # compile beside the target and rename: processes that race to the
    # first use (spawned segment builders) never load a half-written file
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, _SRC]
    try:
        r = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build failed to run: %s", e)
        return False
    if r.returncode != 0:
        log.warning("native build failed:\n%s", r.stderr.decode()[-2000:])
        return False
    os.replace(tmp, _LIB)
    return True


def load() -> Optional[ctypes.CDLL]:
    """The bound library, building it on first use; None -> numpy fallback."""
    global _lib, _load_attempted
    with _lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        if not os.path.isfile(_SRC):
            log.warning("native source %s is missing; numpy fallback", _SRC)
            return None
        if (not os.path.isfile(_LIB)
                or os.path.getmtime(_LIB) < os.path.getmtime(_SRC)):
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError as e:
            log.warning("native library load failed: %s", e)
            return None
        _declare(lib)
        _lib = lib
        return _lib


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.pn_packed_size.restype = c.c_int64
    lib.pn_packed_size.argtypes = [c.c_int64, c.c_int32]
    lib.pn_bitpack_i32.restype = c.c_int64
    lib.pn_bitpack_i32.argtypes = [c.c_void_p, c.c_int64, c.c_int32,
                                   c.c_void_p, c.c_int64]
    lib.pn_bitunpack_i32.restype = c.c_int64
    lib.pn_bitunpack_i32.argtypes = [c.c_void_p, c.c_int64, c.c_int64,
                                     c.c_int32, c.c_void_p]
    lib.pn_mmap_open.restype = c.c_int64
    lib.pn_mmap_open.argtypes = [c.c_char_p]
    lib.pn_mmap_addr.restype = c.c_void_p
    lib.pn_mmap_addr.argtypes = [c.c_int64]
    lib.pn_mmap_size.restype = c.c_int64
    lib.pn_mmap_size.argtypes = [c.c_int64]
    lib.pn_mmap_acquire.restype = c.c_int32
    lib.pn_mmap_acquire.argtypes = [c.c_int64]
    lib.pn_mmap_release.restype = c.c_int32
    lib.pn_mmap_release.argtypes = [c.c_int64]
    lib.pn_mmap_open_count.restype = c.c_int64
    lib.pn_crc32_file.restype = c.c_int64
    lib.pn_crc32_file.argtypes = [c.c_char_p, c.c_uint32]
    lib.pn_varint_encode.restype = c.c_int64
    lib.pn_varint_encode.argtypes = [c.c_void_p, c.c_int64, c.c_void_p,
                                     c.c_int64]
    lib.pn_varint_decode.restype = c.c_int64
    lib.pn_varint_decode.argtypes = [c.c_void_p, c.c_int64, c.c_void_p,
                                     c.c_int64]
    lib.pn_varint_encode_lists.restype = c.c_int64
    lib.pn_varint_encode_lists.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                           c.c_void_p, c.c_int64, c.c_void_p]


def available() -> bool:
    return load() is not None


# --------------------------------------------------------------------------
# fixed-bit packing
# --------------------------------------------------------------------------

def bits_needed(cardinality: int) -> int:
    """Bits per dictId (ref: PinotDataBitSet.getNumBitsPerValue)."""
    return max(1, int(cardinality - 1).bit_length())


def bitpack(values: np.ndarray, bits: int) -> bytes:
    """int32 array -> packed bytes."""
    values = np.ascontiguousarray(values, dtype=np.int32)
    lib = load()
    if lib is not None:
        n = values.shape[0]
        cap = lib.pn_packed_size(n, bits)
        out = np.empty(cap, dtype=np.uint8)
        wrote = lib.pn_bitpack_i32(
            values.ctypes.data, n, bits, out.ctypes.data, cap)
        if wrote < 0:
            raise ValueError(f"bitpack failed (bits={bits})")
        return out[:wrote].tobytes()
    # numpy fallback: expand to a bit matrix, pack into 64-bit words
    n = values.shape[0]
    total_words = (n * bits + 63) // 64
    bit_idx = (np.arange(n, dtype=np.int64)[:, None] * bits
               + np.arange(bits, dtype=np.int64)[None, :]).ravel()
    bit_vals = ((values.astype(np.uint64)[:, None]
                 >> np.arange(bits, dtype=np.uint64)[None, :]) & 1).ravel()
    words = np.zeros(total_words, dtype=np.uint64)
    np.bitwise_or.at(words, bit_idx >> 6,
                     bit_vals.astype(np.uint64) << (bit_idx & 63).astype(np.uint64))
    return words.tobytes()


def bitunpack(buf: bytes, n: int, bits: int) -> np.ndarray:
    """packed bytes -> int32 array of n values."""
    lib = load()
    if lib is not None:
        src = np.frombuffer(buf, dtype=np.uint8)
        out = np.empty(n, dtype=np.int32)
        got = lib.pn_bitunpack_i32(src.ctypes.data, src.shape[0], n, bits,
                                   out.ctypes.data)
        if got != n:
            raise ValueError(f"bitunpack failed (n={n}, bits={bits})")
        return out
    pad = (-len(buf)) % 8
    words = np.frombuffer(buf + b"\x00" * pad, dtype=np.uint64)
    bit_idx = (np.arange(n, dtype=np.int64)[:, None] * bits
               + np.arange(bits, dtype=np.int64)[None, :])
    bit_vals = (words[bit_idx >> 6] >> (bit_idx & 63).astype(np.uint64)) & 1
    weights = (1 << np.arange(bits, dtype=np.uint64))
    return (bit_vals * weights[None, :]).sum(axis=1).astype(np.int32)


# --------------------------------------------------------------------------
# mmap buffers
# --------------------------------------------------------------------------

class MmapBuffer:
    """Refcounted read-only mapping (ref: PinotDataBuffer.mapFile). Use
    ``as_array`` for a zero-copy numpy view; hold the buffer while views
    are alive (release unmaps at refcount zero)."""

    def __init__(self, path: str):
        lib = load()
        self._lib = lib
        self._handle = 0
        self._mm = None
        if lib is not None:
            h = lib.pn_mmap_open(path.encode())
            if h > 0:
                self._handle = h
                self.size = lib.pn_mmap_size(h)
                self._addr = lib.pn_mmap_addr(h)
                return
        # fallback: python mmap
        import mmap as _pymmap

        f = open(path, "rb")
        try:
            self._mm = _pymmap.mmap(f.fileno(), 0, access=_pymmap.ACCESS_READ)
        finally:
            f.close()
        self.size = len(self._mm)

    def as_array(self, dtype, count: int = -1, offset: int = 0) -> np.ndarray:
        if self._handle:
            raw = (ctypes.c_uint8 * (self.size - offset)).from_address(
                self._addr + offset)
            arr = np.frombuffer(raw, dtype=dtype)
        else:
            arr = np.frombuffer(self._mm, dtype=dtype,
                                offset=offset)
        return arr[:count] if count >= 0 else arr

    def read(self) -> bytes:
        return self.as_array(np.uint8).tobytes()

    _local_refs = 1  # references THIS object holds on the mapping

    def acquire(self) -> bool:
        if self._handle:
            if not self._lib.pn_mmap_acquire(self._handle):
                return False
            self._local_refs += 1
        return True

    def release(self) -> None:
        """Give back one of this object's references; never touches other
        holders' refcounts (a double release beyond what was acquired is a
        no-op, so __del__ cannot unmap memory someone else pinned)."""
        if self._handle and self._local_refs > 0:
            self._local_refs -= 1
            rc = self._lib.pn_mmap_release(self._handle)
            if rc == 0 or self._local_refs == 0:
                self._handle = 0

    def __del__(self):
        try:
            while self._handle and self._local_refs > 0:
                self.release()
        except Exception:
            pass


# --------------------------------------------------------------------------
# CRC + varint
# --------------------------------------------------------------------------

def crc32_file(path: str, seed: int = 0) -> int:
    lib = load()
    if lib is not None:
        v = lib.pn_crc32_file(path.encode(), seed & 0xFFFFFFFF)
        if v >= 0:
            return int(v) & 0xFFFFFFFF
    crc = seed
    with open(path, "rb") as f:
        while chunk := f.read(1 << 20):
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def varint_encode(doc_ids: np.ndarray) -> bytes:
    """Sorted int32 doc ids -> delta+varint bytes (posting list storage)."""
    doc_ids = np.ascontiguousarray(doc_ids, dtype=np.int32)
    lib = load()
    if lib is not None:
        cap = doc_ids.shape[0] * 5 + 16
        out = np.empty(cap, dtype=np.uint8)
        wrote = lib.pn_varint_encode(doc_ids.ctypes.data, doc_ids.shape[0],
                                     out.ctypes.data, cap)
        if wrote < 0:
            raise ValueError("varint encode overflow")
        return out[:wrote].tobytes()
    out_b = bytearray()
    prev = 0
    for v in doc_ids.tolist():
        d = v - prev
        prev = v
        while d >= 0x80:
            out_b.append((d & 0x7F) | 0x80)
            d >>= 7
        out_b.append(d)
    return bytes(out_b)


def varint_encode_lists(docs: np.ndarray,
                        offsets: np.ndarray) -> tuple:
    """Encode posting lists docs[offsets[i]:offsets[i+1]] in one pass.
    Returns (blob bytes, byte_offsets int64[num_lists+1])."""
    docs = np.ascontiguousarray(docs, dtype=np.int32)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    num_lists = offsets.shape[0] - 1
    lib = load()
    if lib is not None:
        cap = docs.shape[0] * 5 + 16
        out = np.empty(cap, dtype=np.uint8)
        byte_offsets = np.empty(num_lists + 1, dtype=np.int64)
        wrote = lib.pn_varint_encode_lists(
            docs.ctypes.data, offsets.ctypes.data, num_lists,
            out.ctypes.data, cap, byte_offsets.ctypes.data)
        if wrote < 0:
            raise ValueError("varint encode overflow")
        return out[:wrote].tobytes(), byte_offsets
    blobs = []
    byte_offsets = np.zeros(num_lists + 1, dtype=np.int64)
    for i in range(num_lists):
        enc = varint_encode(docs[offsets[i]:offsets[i + 1]])
        blobs.append(enc)
        byte_offsets[i + 1] = byte_offsets[i] + len(enc)
    return b"".join(blobs), byte_offsets


def varint_decode(buf: bytes, n: int) -> np.ndarray:
    lib = load()
    if lib is not None:
        src = np.frombuffer(buf, dtype=np.uint8)
        out = np.empty(n, dtype=np.int32)
        got = lib.pn_varint_decode(src.ctypes.data, src.shape[0],
                                   out.ctypes.data, n)
        if got != n:
            raise ValueError("varint decode failed")
        return out
    out_l = []
    prev = 0
    i = 0
    for _ in range(n):
        d = 0
        shift = 0
        while True:
            b = buf[i]
            i += 1
            d |= (b & 0x7F) << shift
            if not (b & 0x80):
                break
            shift += 7
        prev += d
        out_l.append(prev)
    return np.asarray(out_l, dtype=np.int32)


def mmap_buffer_count() -> int:
    """Currently-mapped native buffers (0 with the numpy fallback) —
    the MmapDebugResource accounting hook."""
    lib = load()
    if lib is None:
        return 0
    try:
        return int(lib.pn_mmap_open_count())
    except Exception:
        return 0
