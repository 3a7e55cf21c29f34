"""Wire integrity: content checksums for host-path messages.

The compressed wires are lossless *given intact bits* — a single flipped
bit in a packed plane decodes to silently wrong weights (the XOR-delta
wire is the worst case: corruption XORs straight into the receiver's
base).  Every host-path shipment therefore carries a cheap CRC-32 over
its payload, computed at encode time and re-verified by the receiver
BEFORE anything is applied (``sync.engine.verify_update``,
``serve.kv_transfer.unpack_cache``).  Mismatch means reject-and-
renegotiate, never apply: the sender escalates delta -> full -> raw
under the fleet's bounded retry protocol (``sync/fleet.py``).

The checksum covers the *payload* (packed planes, exception lists, raw
arrays, bucket schedule strings), not the (version, epoch, base)
envelope: envelope fields are self-protecting — the receiver fences them
against its own state (``docs/ARCHITECTURE.md``, "Failure model &
recovery").

CRC-32 (zlib) is deliberate: integrity here defends against *transport
corruption* (the fault model injects bit flips), not adversaries, and
the checksum must stay far cheaper than the encode it protects.

The checksum of an array is the CRC-32, chained from the running value,
of its dtype's ``str``, then the ``repr`` of its shape (a 0-d array
counts as shape ``(1,)``), then its bytes in C order.  ``zlib.crc32``
reads those bytes from the array's own memory through a flat ``uint8``
view; only an array that is not C-contiguous is first copied, once, into
C order (``wire_crc_bytes_total`` counts each array's bytes under
``path="view"`` or ``path="copy"``).

An array of two or more whole ``_CRC_CHUNK``-byte chunks is hashed on a
small thread pool (``zlib.crc32`` releases the GIL over large buffers):
each whole chunk's CRC is taken from 0 in parallel, the chunk CRCs are
folded into the running value in order, and the ragged tail is hashed
from the folded value.  The value is the single call's, bit for bit:
CRC-32 is affine, ``crc(B, c) = M_len(B)(c) ^ crc(B, 0)``, where
``M_L`` is the linear map of appending ``L`` zero bytes, applied here
through four 256-entry tables built once per chunk size.  The pool has
as many threads as the process may run on, at most ``_POOL_CAP``; the
chunks are views of the array, never copies
(``wire_crc_pooled_bytes_total`` counts the bytes hashed on it).  Smaller
arrays, strings, scalars and ``bytes`` take one call, as before.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro import obs


class WireIntegrityError(ValueError):
    """A shipped payload failed its content checksum (or exhausted the
    bounded integrity-retry budget).  Receivers raise it BEFORE applying
    anything — corruption is detected, never installed."""


def crc32_bytes(data: bytes, seed: int = 0) -> int:
    return zlib.crc32(data, seed & 0xFFFFFFFF)


_CRC_CHUNK = 4 << 20  # bytes of one pooled chunk
_POOL_CAP = 8  # most threads the pool ever starts
_POOL = ThreadPoolExecutor(
    max_workers=min(_POOL_CAP, len(os.sched_getaffinity(0))),
    thread_name_prefix="wire-crc")


def _gf2_times(mat, v: int) -> int:
    """The 32x32 GF(2) matrix ``mat`` (a list of 32 columns) times ``v``."""
    out = 0
    for col in mat:
        if v & 1:
            out ^= col
        v >>= 1
    return out


@functools.lru_cache(maxsize=None)
def _zeros_tables(nbytes: int):
    """``M_nbytes``, the map of appending ``nbytes`` zero bytes to a
    CRC-32, as four 256-entry tables, one per byte of the CRC.  The map of
    one zero byte comes from zlib itself; its ``nbytes``-th power is taken
    by squaring."""
    op = [zlib.crc32(b"\0", 1 << i) ^ zlib.crc32(b"\0") for i in range(32)]
    m = [1 << i for i in range(32)]
    while nbytes:
        if nbytes & 1:
            m = [_gf2_times(op, col) for col in m]
        nbytes >>= 1
        if nbytes:
            op = [_gf2_times(op, col) for col in op]
    tables = []
    for k in range(4):
        t = [0] * 256
        for b in range(1, 256):
            low = b & -b
            t[b] = t[b ^ low] ^ m[8 * k + low.bit_length() - 1]
        tables.append(t)
    return tables


def _append_zeros(c: int, nbytes: int) -> int:
    """``M_nbytes(c)``: ``zlib.crc32(B, c) == _append_zeros(c, len(B)) ^
    zlib.crc32(B)`` for every ``B``."""
    t0, t1, t2, t3 = _zeros_tables(nbytes)
    return t0[c & 255] ^ t1[c >> 8 & 255] ^ t2[c >> 16 & 255] ^ t3[c >> 24]


def _crc32_flat(flat: np.ndarray, c: int) -> int:
    """``zlib.crc32(flat, c)`` of a flat ``uint8`` array; two or more whole
    chunks are hashed on the pool and folded in order."""
    chunk = _CRC_CHUNK
    whole = flat.size // chunk * chunk
    if whole < 2 * chunk:
        return zlib.crc32(flat, c)
    for crc in _POOL.map(lambda i: zlib.crc32(flat[i:i + chunk]),
                         range(0, whole, chunk)):
        c = _append_zeros(c, chunk) ^ crc
    obs.metric("wire_crc_pooled_bytes_total").inc(whole)
    return zlib.crc32(flat[whole:], c)


def crc32_tree(obj, seed: int = 0) -> int:
    """CRC-32 over every array/scalar reachable from ``obj``.

    Walks tuples/lists/dicts/dataclasses natively (the host wire's
    message types — ``packing.CompressedMessage``/``DeltaMessage``,
    ``p2p.engine.Message`` — are dataclasses, registered as pytrees or
    not), hashing each ndarray's dtype+shape+bytes and each scalar/str's
    repr.  Deterministic for a given payload, so sender and receiver
    agree iff the bits agree."""
    c = seed & 0xFFFFFFFF

    def visit(o):
        nonlocal c
        if o is None or isinstance(o, (bool, int, float, str)):
            c = zlib.crc32(repr(o).encode(), c)
        elif isinstance(o, bytes):
            c = zlib.crc32(o, c)
        elif isinstance(o, (list, tuple)):
            for x in o:
                visit(x)
        elif isinstance(o, dict):
            for k in sorted(o, key=repr):
                visit(k)
                visit(o[k])
        elif hasattr(o, "shape") and hasattr(o, "dtype"):
            arr = np.asarray(o)  # device -> host
            path = "view" if arr.flags.c_contiguous else "copy"
            arr = np.ascontiguousarray(arr)
            c = zlib.crc32(str(arr.dtype).encode(), c)
            c = zlib.crc32(repr(arr.shape).encode(), c)
            c = _crc32_flat(arr.reshape(-1).view(np.uint8), c)
            obs.metric("wire_crc_bytes_total").inc(arr.nbytes, path=path)
        elif dataclasses.is_dataclass(o) and not isinstance(o, type):
            for f in dataclasses.fields(o):
                visit(getattr(o, f.name))
        else:
            c = zlib.crc32(repr(o).encode(), c)

    visit(obj)
    return c


def flip_bit(arr: np.ndarray, bit_index: int) -> np.ndarray:
    """A copy of ``arr`` with one bit flipped in its raw byte stream —
    the fault injector's corruption primitive (``runtime/faults.py``).
    Never mutates the input (encoded updates are memoized and shared)."""
    src = np.ascontiguousarray(np.asarray(arr))
    raw = bytearray(src.tobytes())
    if not raw:
        return src
    bit_index %= len(raw) * 8
    raw[bit_index // 8] ^= 1 << (bit_index % 8)
    return np.frombuffer(bytes(raw), dtype=src.dtype).reshape(src.shape)
