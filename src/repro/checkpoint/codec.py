"""Checkpoint codec: pytree <-> chunked byte records.

Layout (append-only stream, written through the plain file API so NVCache
can boost it transparently):

    [record 0][record 1]...[record N-1][index][footer]

Each record is one row-chunk of one leaf:  ``msgpack header || payload``.
Chunking along axis 0 is what makes *resharded restore* possible: a reader
assembling any slice of a leaf touches only the chunks that overlap it —
the elastic-scaling path re-slices checkpoints to a new device count
without ever materializing the full array on one host.

Payload encodings: raw | zstd | int8 group-quantized (+f32 scales, zstd'd)
| zlib — the quantized mode shrinks NVMM log entries, pushing the paper's
Fig.-5 log-saturation point out by ~4x for checkpoint traffic.

``zstandard`` is an *optional* dependency: when absent, compressed writes
transparently downgrade to zlib (recorded per record in its header, so a
reader on any host decodes correctly), and only streams that were actually
written with zstd require the package to read.

``Writer`` encodes records on a small pool of threads (zstandard, zlib and
NumPy release the interpreter lock while they work) ahead of the writes,
and writes the finished records in order on the calling thread: the file
and the sequence of file-API calls are those of a serial writer.
"""
from __future__ import annotations

import collections
import os
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import msgpack
import numpy as np

from repro import obs

try:
    import zstandard
except ImportError:                       # optional dependency (see docstring)
    zstandard = None

MAGIC = b"RPCKPT01"
_FOOT = struct.Struct("<QQI")       # index_off, index_len, index_crc

ENC_RAW, ENC_ZSTD, ENC_INT8, ENC_ZLIB = 0, 1, 2, 3


def _compress(raw, *, force_zlib: bool = False) -> tuple[bytes, bool]:
    """Compress the bytes-like ``raw`` with zstd when available (and not
    overridden), zlib otherwise.

    Returns ``(payload, used_zlib)``.
    """
    if not force_zlib and zstandard is not None:
        return zstandard.compress(raw, 3), False
    return zlib.compress(raw, 6), True


def _decompress(payload: bytes, used_zlib: bool) -> bytes:
    if used_zlib:
        return zlib.decompress(payload)
    if zstandard is None:
        raise ImportError(
            "checkpoint record is zstd-compressed but `zstandard` is not "
            "installed; install it or re-write the checkpoint")
    return zstandard.decompress(payload)


def _quant_np(x: np.ndarray, group: int = 256):
    flat = x.astype(np.float32).reshape(-1)
    pad = (-flat.size) % group
    if pad:
        flat = np.pad(flat, (0, pad))
    g = flat.reshape(-1, group)
    amax = np.abs(g).max(axis=1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(g / scale[:, None]), -127, 127).astype(np.int8)
    return q.reshape(-1), scale, pad


def _dequant_np(q: np.ndarray, scale: np.ndarray, pad: int, group: int = 256):
    g = q.reshape(-1, group).astype(np.float32) * scale[:, None]
    flat = g.reshape(-1)
    return flat[:flat.size - pad] if pad else flat


# Encode threads: at most four, and the host's cores less those the writing
# thread, the NVCache drain and JAX's host threads keep busy during a save.
# On a TPU v5e host (13 cores) with an 8 GiB log, two threads left the save
# waiting on the encode for 19% of its time and the durable commit 6% later
# than four did, which keep that wait near 5%.
_RESERVED_CPUS = 3
_MAX_WORKERS = 4


def encode_workers() -> int:
    """The number of threads a ``Writer`` encodes records on."""
    return max(1, min(_MAX_WORKERS, (os.cpu_count() or 1) - _RESERVED_CPUS))


class Writer:
    """Streams records through an FS (see repro.storage.fsapi).

    Records are encoded on ``workers`` threads, at most ``2 * workers`` in
    flight, while the calling thread writes the finished ones in index
    order: every call into the FS happens on the calling thread, and the
    file is byte for byte the one a serial writer makes.  A leaf must not
    change between ``put_leaf`` and ``finish``.  An error in an encode or a
    write stops the pool's threads and propagates from ``put_leaf`` or
    ``finish``; a writer abandoned after ``put_leaf`` writes nothing more:
    its threads finish the records in flight, then exit once the writer is
    garbage-collected.
    """

    def __init__(self, fs, path: str, *, encoding: int = ENC_ZSTD,
                 chunk_bytes: int = 4 << 20, close_on_finish: bool = True):
        self.fs = fs
        self.fd = fs.open(path)
        self.off = 0
        self.encoding = encoding
        self.chunk_bytes = chunk_bytes
        self.close_on_finish = close_on_finish
        self.index = []
        self._w(MAGIC)
        self.workers = encode_workers()
        # a thread starts only for a record that finds none idle, so a file
        # of fewer records than workers starts fewer threads
        self._pool = ThreadPoolExecutor(self.workers, thread_name_prefix="ckpt-encode")
        self._pending = collections.deque()     # (path, start, end, future)

    def _w(self, data: bytes):
        with obs.span("ckpt.write_us", bytes=len(data)):
            self.fs.pwrite(self.fd, data, self.off)
        self.off += len(data)

    def put_leaf(self, path: str, arr) -> None:
        a = np.asarray(arr)
        rows = max(1, a.shape[0]) if a.ndim else 1
        row_bytes = max(1, a.nbytes // rows)
        rows_per_chunk = max(1, self.chunk_bytes // row_bytes)
        if a.ndim == 0:
            chunks = [(0, 1, a.reshape(1))]
        else:
            chunks = [(s, min(s + rows_per_chunk, a.shape[0]),
                       a[s:min(s + rows_per_chunk, a.shape[0])])
                      for s in range(0, a.shape[0], rows_per_chunk)]
        try:
            for start, end, part in chunks:
                if len(self._pending) >= 2 * self.workers:
                    self._write_next()
                fut = self._pool.submit(self._encode, path, a, start, end, part)
                self._pending.append((path, start, end, fut))
        except BaseException:
            self._abort()
            raise

    def _encode(self, path, a, start, end, part) -> bytes:
        """One record, on a pool thread: contiguous copy, compression or
        quantisation, header and framing."""
        with obs.span("ckpt.encode_us", bytes=part.nbytes) as sp:
            raw = np.ascontiguousarray(part)
            rows = raw.reshape(-1).view(np.uint8)   # the rows' bytes, no copy
            meta = {"p": path, "dt": str(a.dtype), "gs": list(a.shape),
                    "s": start, "e": end, "enc": self.encoding}
            if self.encoding == ENC_INT8 and raw.dtype.kind == "f" and raw.size >= 256:
                q, scale, pad = _quant_np(raw.view(raw.dtype))
                payload, used_zlib = _compress(q.tobytes() + scale.tobytes())
                meta["pad"] = pad
                meta["nsc"] = scale.size
                if used_zlib:
                    meta["zc"] = 1          # int8 payload compressed with zlib
            elif self.encoding in (ENC_ZSTD, ENC_ZLIB):
                # ENC_ZLIB is an explicit request for the portable codec — honour
                # it even when zstandard is installed
                payload, used_zlib = _compress(rows,
                                               force_zlib=self.encoding == ENC_ZLIB)
                meta["enc"] = ENC_ZLIB if used_zlib else ENC_ZSTD
            else:
                meta["enc"] = ENC_RAW
                payload = rows
            hdr = msgpack.packb(meta)
            sp.set(out_bytes=len(payload))
            return b"".join((struct.pack("<II", len(hdr), len(payload)), hdr, payload))

    def _write_next(self) -> None:
        """Wait for the oldest record's encode and write it."""
        path, start, end, fut = self._pending.popleft()
        with obs.span("ckpt.encode_wait_us"):
            rec = fut.result()
        self.index.append((path, int(start), int(end), self.off, len(rec)))
        self._w(rec)

    def _abort(self) -> None:
        """Drop the records not yet written and stop the pool's threads."""
        self._pending.clear()
        self._pool.shutdown(wait=True, cancel_futures=True)

    def finish(self) -> dict:
        try:
            while self._pending:
                self._write_next()
        except BaseException:
            self._abort()
            raise
        self._pool.shutdown()
        idx = msgpack.packb(self.index)
        idx_off = self.off
        self._w(idx)
        self._w(_FOOT.pack(idx_off, len(idx), zlib.crc32(idx)))
        size = self.off
        if self.close_on_finish:
            self.fs.close(self.fd)      # close() drains (paper semantics)
            self.fd = None
        return {"size": size, "index_off": idx_off}


class Reader:
    def __init__(self, fs, path: str):
        self.fs = fs
        self.fd = fs.open_ro(path) if hasattr(fs, "open_ro") else fs.open(path)
        size = fs.size(self.fd)
        foot = fs.pread(self.fd, _FOOT.size, size - _FOOT.size)
        idx_off, idx_len, crc = _FOOT.unpack(foot)
        idx = fs.pread(self.fd, idx_len, idx_off)
        if zlib.crc32(idx) != crc:
            raise IOError("checkpoint index corrupt")
        self.index = msgpack.unpackb(idx)
        assert fs.pread(self.fd, len(MAGIC), 0) == MAGIC

    def leaf_paths(self):
        return sorted({e[0] for e in self.index})

    def read_leaf(self, path: str, *, rows: Optional[tuple] = None) -> np.ndarray:
        entries = sorted((e for e in self.index if e[0] == path),
                         key=lambda e: e[1])
        if not entries:
            raise KeyError(path)
        parts, meta0 = [], None
        for _p, start, end, off, ln in entries:
            if rows is not None and (end <= rows[0] or start >= rows[1]):
                continue
            with obs.span("ckpt.read_us", bytes=ln):
                rec = self.fs.pread(self.fd, ln, off)
            with obs.span("ckpt.decode_us") as sp:
                hlen, plen = struct.unpack("<II", rec[:8])
                meta = msgpack.unpackb(rec[8:8 + hlen])
                payload = rec[8 + hlen:8 + hlen + plen]
                arr = self._decode(meta, payload, start, end)
                sp.set(bytes=arr.nbytes)
            if rows is not None:
                lo = max(rows[0], start) - start
                hi = min(rows[1], end) - start
                arr = arr[lo:hi]
            parts.append(arr)
            meta0 = meta
        gs = meta0["gs"]
        out = np.concatenate(parts, axis=0) if gs else parts[0].reshape(())
        if rows is None and gs:
            out = out.reshape(gs)
        return out

    def _decode(self, meta, payload, start, end):
        dt = np.dtype(meta["dt"])
        shape = [end - start] + meta["gs"][1:] if meta["gs"] else [1]
        if meta["enc"] == ENC_INT8:
            blob = _decompress(payload, bool(meta.get("zc")))
            n = int(np.prod(shape))
            pad = meta["pad"]
            q = np.frombuffer(blob[:n + pad], np.int8)
            scale = np.frombuffer(blob[n + pad:], np.float32)
            return _dequant_np(q, scale, pad).astype(dt).reshape(shape)
        if meta["enc"] in (ENC_ZSTD, ENC_ZLIB):
            blob = _decompress(payload, meta["enc"] == ENC_ZLIB)
            return np.frombuffer(blob, dt).reshape(shape)
        return np.frombuffer(payload, dt).reshape(shape)

    def close(self):
        self.fs.close(self.fd)
