"""RecordIO: chunked record file format for fast reader pipelines.

Capability parity with the reference's C++ recordio library (reference:
paddle/fluid/recordio/ — kMagicNumber header.h:23, Compressor enum
header.h:25, Chunk::Write chunk.h:36, Scanner, writer.cc; python writer
bound via pybind recordio.cc).

Layout per chunk (all u32 little-endian, matching the reference header
fields): MAGIC, num_records, checksum (crc32 of the payload), compressor,
payload_size, then the payload = concatenated [u32 length | bytes]
records. Compressor 0 = none, 1 = snappy (pure-python codec in
snappy_codec.py: real greedy-match encoder + framed-stream layer matching
the reference's snappystream format, header CRC over the compressed bytes
as chunk.cc places it), 2 = gzip (zlib).
The byte-level hot paths (checksums, record splitting, and the snappy
match/replay loops) run in a small C++ library (native.cc) compiled
lazily with g++; pure-python fallbacks keep the format usable without a
toolchain."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import struct
import subprocess
import tempfile
import zlib
from typing import Iterator, List, Optional

logger = logging.getLogger(__name__)

MAGIC = 0x01020304
NO_COMPRESS = 0
SNAPPY = 1      # reference vendored C snappy; here snappy_codec.py
GZIP = 2

_HDR = struct.Struct("<IIIII")   # magic, num_records, checksum, comp, size


# -- native fast path -------------------------------------------------------

_native = None


def _load_native():
    global _native
    if _native is not None:
        return _native
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "native.cc")
    try:
        # built inside the checkout (git-ignored) and named by the
        # source's content: a library from another checkout or an older
        # native.cc can never be picked up in its place
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        cache = os.path.join(here, "_build", f"librecordio-{digest}.so")
        if not os.path.exists(cache):
            os.makedirs(os.path.dirname(cache), exist_ok=True)
            tmp = f"{cache}.{os.getpid()}.tmp"
            subprocess.run(["g++", "-O2", "-fPIC", "-shared", "-o", tmp,
                            src], check=True, capture_output=True)
            os.replace(tmp, cache)
        lib = ctypes.CDLL(cache)
        lib.rio_crc32.restype = ctypes.c_uint32
        lib.rio_crc32.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.rio_split_records.restype = ctypes.c_long
        lib.rio_split_records.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t]
        if hasattr(lib, "rio_snappy_compress"):  # round-5 additions
            lib.rio_crc32c.restype = ctypes.c_uint32
            lib.rio_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
            for fn in (lib.rio_snappy_compress, lib.rio_snappy_decompress):
                fn.restype = ctypes.c_long
                fn.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                               ctypes.c_char_p, ctypes.c_size_t]
        _native = lib
    except Exception as e:  # no g++ / sandbox: python fallback
        logger.info("recordio: native library unavailable (%s); using "
                    "python fallback", e)
        _native = False
    return _native


def _crc32(data: bytes) -> int:
    lib = _load_native()
    if lib:
        return lib.rio_crc32(data, len(data))
    return zlib.crc32(data) & 0xFFFFFFFF


def _split_records(payload: bytes) -> List[bytes]:
    lib = _load_native()
    if lib:
        cap = max(16, len(payload) // 4)
        offs = (ctypes.c_uint32 * cap)()
        lens = (ctypes.c_uint32 * cap)()
        n = lib.rio_split_records(payload, len(payload), offs, lens, cap)
        if n == -1:
            raise IOError("recordio: malformed chunk payload")
        if n >= 0:
            return [payload[offs[i]:offs[i] + lens[i]] for i in range(n)]
        # n == -2: more records than cap (all empty records) — fall through
    out = []
    pos, n = 0, len(payload)
    while pos < n:
        if pos + 4 > n:
            raise IOError("recordio: malformed chunk payload")
        (ln,) = struct.unpack_from("<I", payload, pos)
        pos += 4
        if pos + ln > n:
            raise IOError("recordio: malformed chunk payload")
        out.append(payload[pos:pos + ln])
        pos += ln
    return out


# -- chunk ------------------------------------------------------------------

def _write_chunk(fo, records: List[bytes], compressor: int):
    payload = b"".join(struct.pack("<I", len(r)) + r for r in records)
    if compressor == GZIP:
        checksum = _crc32(payload)
        payload = zlib.compress(payload)
    elif compressor == SNAPPY:
        # reference format: snappystream FRAMED payload, header CRC over
        # the COMPRESSED bytes (chunk.cc Crc32Stream after compression)
        from . import snappy_codec
        payload = snappy_codec.compress_framed(payload)
        checksum = _crc32(payload)
    elif compressor == NO_COMPRESS:
        checksum = _crc32(payload)
    else:
        raise ValueError(f"unsupported compressor {compressor}")
    fo.write(_HDR.pack(MAGIC, len(records), checksum, compressor,
                       len(payload)))
    fo.write(payload)


def _read_chunk(fi) -> Optional[List[bytes]]:
    hdr = fi.read(_HDR.size)
    if not hdr:
        return None
    if len(hdr) < _HDR.size:
        raise IOError("recordio: truncated chunk header")
    magic, num, checksum, comp, size = _HDR.unpack(hdr)
    if magic != MAGIC:
        raise IOError(f"recordio: bad magic {magic:#x}")
    payload = fi.read(size)
    if len(payload) < size:
        raise IOError("recordio: truncated chunk payload")
    if comp == GZIP:
        payload = zlib.decompress(payload)
        if _crc32(payload) != checksum:
            raise IOError("recordio: checksum mismatch")
    elif comp == SNAPPY:
        from . import snappy_codec
        wire = payload
        payload = (snappy_codec.decompress_framed(wire)
                   if snappy_codec.is_framed(wire)
                   else snappy_codec.decompress(wire))
        # reference placement: CRC over the compressed stream; rounds 3-4
        # of this repo wrote raw-snappy payloads with CRC over the
        # DEcompressed bytes — accept either, exact match required
        if _crc32(wire) != checksum and _crc32(payload) != checksum:
            raise IOError("recordio: checksum mismatch")
    elif comp == NO_COMPRESS:
        if _crc32(payload) != checksum:
            raise IOError("recordio: checksum mismatch")
    else:
        raise IOError(f"recordio: unsupported compressor {comp}")
    records = _split_records(payload)
    if len(records) != num:
        raise IOError(f"recordio: header claims {num} records, "
                      f"found {len(records)}")
    return records


# -- public API (reference writer.h / scanner.h shapes) ---------------------

class Writer:
    """reference recordio::Writer: buffer records, flush a chunk every
    max_num_records (or max_chunk_size bytes)."""

    def __init__(self, path_or_file, max_num_records: int = 1000,
                 max_chunk_size: int = 8 << 20, compressor: int = NO_COMPRESS):
        self._own = isinstance(path_or_file, (str, os.PathLike))
        self._f = open(path_or_file, "wb") if self._own else path_or_file
        self.max_num_records = max_num_records
        self.max_chunk_size = max_chunk_size
        self.compressor = compressor
        self._records: List[bytes] = []
        self._nbytes = 0

    def write(self, record: bytes):
        if isinstance(record, str):
            record = record.encode()
        self._records.append(bytes(record))
        self._nbytes += len(record)
        if (len(self._records) >= self.max_num_records
                or self._nbytes >= self.max_chunk_size):
            self.flush()

    def flush(self):
        if self._records:
            _write_chunk(self._f, self._records, self.compressor)
            self._records, self._nbytes = [], 0

    def close(self):
        self.flush()
        if self._own:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class Scanner:
    """reference recordio::Scanner: iterate records across chunks."""

    def __init__(self, path_or_file):
        self._own = isinstance(path_or_file, (str, os.PathLike))
        self._f = open(path_or_file, "rb") if self._own else path_or_file

    def __iter__(self) -> Iterator[bytes]:
        while True:
            records = _read_chunk(self._f)
            if records is None:
                return
            yield from records

    def close(self):
        if self._own:
            self._f.close()


def write_file(path, record_iter, **kw):
    """Convenience: dump an iterable of byte records to `path`."""
    with Writer(path, **kw) as w:
        n = 0
        for r in record_iter:
            w.write(r)
            n += 1
    return n


def reader(path):
    """Reader-creator over a RecordIO file (fits paddle_tpu.reader
    decorators)."""
    def _r():
        s = Scanner(path)
        try:
            yield from iter(s)
        finally:
            s.close()
    return _r
