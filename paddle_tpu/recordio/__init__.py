"""RecordIO: chunked record container (writer + fault-tolerant scanner).

Parity: reference paddle/fluid/recordio/ (C++ chunk/header/writer/scanner)
and its recordio Python bindings.  The hot path is the C++ implementation
(recordio.cc, built lazily with g++ and loaded over ctypes); a pure-Python
codec of the SAME on-disk format is the fallback and the cross-check —
files written by either implementation are readable by both.

Format (little-endian; see recordio.cc header comment):
  chunk  := magic:u32 compressor:u32 num_records:u32
            uncompressed_len:u32 stored_len:u32 crc32:u32 payload
  payload (zlib per chunk by default) := { len:u32 bytes } * num_records
Corrupt or truncated chunks are skipped on read (the reference's
fault-tolerant scanner behavior, recordio/README.md).
"""
from __future__ import annotations

import ctypes
import os
import struct
import subprocess
import zlib

__all__ = ["Writer", "Scanner", "write_records", "read_records",
           "native_available"]

MAGIC = 0x54505231
NO_COMPRESS = 0
ZLIB = 2

_HEADER = struct.Struct("<6I")

_lib = None
_lib_tried = False


def _load_native():
    """Build (once) and load librecordio.so; None if no toolchain."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "recordio.cc")
    so = os.path.join(here, "librecordio.so")
    try:
        if (not os.path.exists(so) or
                os.path.getmtime(so) < os.path.getmtime(src)):
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-o", so + ".tmp", src,
                 "-lz"], check=True, capture_output=True)
            os.replace(so + ".tmp", so)
        lib = ctypes.CDLL(so)
        lib.rio_writer_open.restype = ctypes.c_void_p
        lib.rio_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                        ctypes.c_uint32]
        lib.rio_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_uint32]
        lib.rio_writer_close.argtypes = [ctypes.c_void_p]
        lib.rio_scanner_open.restype = ctypes.c_void_p
        lib.rio_scanner_open.argtypes = [ctypes.c_char_p]
        lib.rio_next.restype = ctypes.c_int64
        lib.rio_next.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_char_p)]
        lib.rio_scanner_close.argtypes = [ctypes.c_void_p]
        _lib = lib
    except Exception as e:
        # the pure-Python codec below reads and writes the same format,
        # only slower: say so once rather than degrade quietly (an
        # input-pipeline measurement must know which codec it timed)
        import warnings
        detail = getattr(e, "stderr", b"") or b""
        warnings.warn(
            "recordio: native codec unavailable (%s: %s %s) — using the "
            "pure-Python codec" % (type(e).__name__, e,
                                   detail.decode(errors="replace")[-300:]))
        _lib = None
    return _lib


def native_available():
    return _load_native() is not None


class Writer:
    """Append records to a recordio file; chunks flush every
    ``max_chunk_records`` records (or ~1MB) and on close."""

    def __init__(self, path, compressor=ZLIB, max_chunk_records=1000,
                 use_native=True):
        self._native = _load_native() if use_native else None
        self._path = path
        self._compressor = compressor
        self._max = max_chunk_records
        if self._native is not None:
            self._h = self._native.rio_writer_open(
                os.fsencode(path), compressor, max_chunk_records)
            if not self._h:
                raise IOError("cannot open %s for writing" % path)
        else:
            self._f = open(path, "wb")
            self._buf = []
            self._buf_bytes = 0

    def write(self, record):
        if not isinstance(record, (bytes, bytearray)):
            raise TypeError("record must be bytes, got %s" % type(record))
        if self._native is not None:
            self._native.rio_write(self._h, bytes(record), len(record))
            return
        self._buf.append(bytes(record))
        self._buf_bytes += len(record) + 4
        if len(self._buf) >= self._max or self._buf_bytes >= (1 << 20):
            self._flush()

    def _flush(self):
        if not self._buf:
            return
        raw = b"".join(struct.pack("<I", len(r)) + r for r in self._buf)
        stored = zlib.compress(raw) if self._compressor == ZLIB else raw
        crc = zlib.crc32(stored) & 0xFFFFFFFF
        self._f.write(_HEADER.pack(MAGIC, self._compressor, len(self._buf),
                                   len(raw), len(stored), crc))
        self._f.write(stored)
        self._buf = []
        self._buf_bytes = 0

    def close(self):
        if self._native is not None:
            if self._h:
                self._native.rio_writer_close(self._h)
                self._h = None
        else:
            self._flush()
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class Scanner:
    """Iterate records; corrupt/truncated chunks are skipped."""

    def __init__(self, path, use_native=True):
        self._native = _load_native() if use_native else None
        if self._native is not None:
            self._h = self._native.rio_scanner_open(os.fsencode(path))
            if not self._h:
                raise IOError("cannot open %s" % path)
        else:
            self._f = open(path, "rb")
            self._chunk_iter = None

    def __iter__(self):
        if self._native is not None:
            out = ctypes.c_char_p()
            while True:
                n = self._native.rio_next(self._h, ctypes.byref(out))
                if n < 0:
                    return
                yield ctypes.string_at(out, n)
        else:
            while True:
                head = self._f.read(_HEADER.size)
                if len(head) < _HEADER.size:
                    return
                magic, comp, nrec, raw_len, stored_len, crc = \
                    _HEADER.unpack(head)
                if magic != MAGIC:
                    return  # out of sync: stop
                stored = self._f.read(stored_len)
                if len(stored) < stored_len:
                    return  # truncated tail
                if (zlib.crc32(stored) & 0xFFFFFFFF) != crc:
                    continue  # corrupt chunk: skip
                raw = zlib.decompress(stored) if comp == ZLIB else stored
                pos = 0
                for _ in range(nrec):
                    if pos + 4 > len(raw):
                        break
                    (ln,) = struct.unpack_from("<I", raw, pos)
                    pos += 4
                    yield raw[pos:pos + ln]
                    pos += ln

    def close(self):
        if self._native is not None:
            if self._h:
                self._native.rio_scanner_close(self._h)
                self._h = None
        else:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def write_records(path, records, **kwargs):
    with Writer(path, **kwargs) as w:
        for r in records:
            w.write(r)


def read_records(path, **kwargs):
    with Scanner(path, **kwargs) as s:
        for r in s:
            yield r
