"""On-demand build + ctypes bindings for the native runtime.

The image has a C++ toolchain but no pybind11 (and nothing may be pip
installed), so the native layer is a plain C ABI compiled with g++ on
first use and loaded via ctypes.  The compiled object is cached next to
the source keyed by a content hash, so rebuilds only happen when
``dat_native.cpp`` changes.  Everything degrades gracefully: callers use
:func:`get_lib` and fall back to pure Python when it returns ``None``
(no toolchain, read-only filesystem, ...).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np

from ..obs.events import emit as _emit
from ..obs.metrics import OBS as _OBS
from ..obs.metrics import counter as _counter

# host-engine digest traffic (device-telemetry catalog): bytes hashed by
# the native C pass — the host-side counterpart of device.h2d.bytes
_M_NATIVE_HASH_BYTES = _counter("device.native.hash.bytes")

_SRC = Path(__file__).resolve().parent.parent / "native" / "dat_native.cpp"
# location config, not behavior gating: where build products land may
# freeze at import  # datlint: disable=env-cache-policy
_BUILD_DIR = Path(
    os.environ.get(
        "DAT_NATIVE_BUILD_DIR",
        Path(__file__).resolve().parent.parent / "native" / "_build",
    )
)

ERR_TRUNCATED = -1
ERR_CAPACITY = -2
ERR_BAD_VARINT = -3
ERR_BAD_RECORD = -4
ERR_NOMEM = -5

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_I64P = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")
_U32P = np.ctypeslib.ndpointer(dtype=np.uint32, flags="C_CONTIGUOUS")
_U64P = np.ctypeslib.ndpointer(dtype=np.uint64, flags="C_CONTIGUOUS")
_U8P = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")


def _build() -> Path | None:
    digest = hashlib.blake2b(_SRC.read_bytes(), digest_size=8).hexdigest()
    so = _BUILD_DIR / f"dat_native-{digest}.so"
    if so.exists():
        return so
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    tmp = so.with_suffix(f".tmp{os.getpid()}.so")
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        str(_SRC), "-o", str(tmp),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        print(f"dat_native build failed ({e}); using Python fallbacks",
              file=sys.stderr)
        return None
    os.replace(tmp, so)  # atomic: concurrent builders race benignly
    return so


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.dat_split_frames.restype = ctypes.c_int64
    lib.dat_split_frames.argtypes = [
        _U8P, ctypes.c_int64, _I64P, _I64P, _U8P, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.dat_greedy_select.restype = ctypes.c_int64
    lib.dat_greedy_select.argtypes = [
        _I64P, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, _I64P, ctypes.c_int64,
    ]
    lib.dat_decode_changes.restype = ctypes.c_int64
    lib.dat_decode_changes.argtypes = [
        _U8P, _I64P, _I64P, ctypes.c_int64,
        _U32P, _U32P, _U32P,
        _I64P, _I64P, _I64P, _I64P, _I64P, _I64P,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.dat_encode_changes.restype = ctypes.c_int64
    lib.dat_encode_changes.argtypes = [
        _U8P, ctypes.c_int64,
        _U32P, _U32P, _U32P,
        _I64P, _I64P, _I64P, _I64P, _I64P, _I64P,
        _U8P, ctypes.c_int64,
    ]
    lib.dat_encode_changes_mt.restype = ctypes.c_int64
    lib.dat_encode_changes_mt.argtypes = [
        _U8P, ctypes.c_int64,
        _U32P, _U32P, _U32P,
        _I64P, _I64P, _I64P, _I64P, _I64P, _I64P,
        _U8P, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.dat_decode_changes_mt.restype = ctypes.c_int64
    lib.dat_decode_changes_mt.argtypes = [
        _U8P, _I64P, _I64P, ctypes.c_int64,
        _U32P, _U32P, _U32P,
        _I64P, _I64P, _I64P, _I64P, _I64P, _I64P,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
    ]
    lib.dat_encode_change_batch.restype = ctypes.c_int64
    lib.dat_encode_change_batch.argtypes = [
        _U8P, ctypes.c_int64,
        _U32P, _U32P, _U32P,
        _I64P, _I64P, _I64P, _I64P, _I64P, _I64P,
        _U8P, ctypes.c_int64,
    ]
    lib.dat_gear_candidates.restype = ctypes.c_int64
    lib.dat_gear_candidates.argtypes = [
        _U8P, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        _I64P, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.dat_blake2b_many.restype = ctypes.c_int64
    lib.dat_blake2b_many.argtypes = [
        _U8P, _I64P, _I64P, ctypes.c_int64, _U8P, ctypes.c_int64,
    ]
    # pointer-array twin: payload ADDRESSES ride a dedicated parameter
    # (an int64 address array on the Python side) instead of being
    # smuggled through the offset column (ADVICE r5 low)
    lib.dat_blake2b_many_ptrs.restype = ctypes.c_int64
    lib.dat_blake2b_many_ptrs.argtypes = [
        _I64P, _I64P, ctypes.c_int64, _U8P, ctypes.c_int64,
    ]
    lib.dat_cdc_hash.restype = ctypes.c_int64
    lib.dat_cdc_hash.argtypes = [
        _U8P, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, _I64P, _U8P, ctypes.c_int64,
        ctypes.c_int64,
    ]
    lib.dat_sketch.restype = ctypes.c_int64
    lib.dat_sketch.argtypes = [
        _U8P, _I64P, _I64P, _I64P, _I64P,
        ctypes.c_int64, ctypes.c_int64, _U32P, _U32P, ctypes.c_int64,
    ]
    lib.dat_rateless_build.restype = ctypes.c_int64
    lib.dat_rateless_build.argtypes = [
        _U8P, ctypes.c_int64, _U64P, _U64P,
        ctypes.c_int64, ctypes.c_int64, _U32P, ctypes.c_int64,
    ]
    lib.dat_rateless_build_w.restype = ctypes.c_int64
    lib.dat_rateless_build_w.argtypes = [
        _U8P, _I64P, ctypes.c_int64, _U64P, _U64P,
        ctypes.c_int64, ctypes.c_int64, _U32P, ctypes.c_int64,
    ]
    # transport pump (ISSUE 14): batched-syscall socket loops
    lib.dat_pump_probe.restype = ctypes.c_int64
    lib.dat_pump_probe.argtypes = []
    lib.dat_pump_recv_scan.restype = ctypes.c_int64
    lib.dat_pump_recv_scan.argtypes = [
        ctypes.c_int64, _U8P, ctypes.c_int64, ctypes.c_int64,
        _I64P, _I64P, _U8P, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), _I64P,
    ]
    lib.dat_pump_send.restype = ctypes.c_int64
    lib.dat_pump_send.argtypes = [
        _I64P, _I64P, ctypes.c_int64, ctypes.c_int64, _I64P,
    ]
    lib.dat_pump_send_nb.restype = ctypes.c_int64
    lib.dat_pump_send_nb.argtypes = [
        _I64P, _I64P, ctypes.c_int64, ctypes.c_int64, _I64P,
    ]
    return lib


def get_lib() -> ctypes.CDLL | None:
    """The bound native library, or None (callers fall back to Python).

    Same gating policy as :func:`runtime.fastpath.get` (the shared
    env-cache policy datlint's env-cache-policy rule enforces): the
    DISABLE env var is re-read every call, only the build+load is
    cached, and a call made while disabled does not poison the cache.
    """
    if os.environ.get("DAT_NATIVE_DISABLE"):
        return None
    if _tried:  # lock-free hot path: _lib is set before _tried
        return _lib
    return _load_once()


def _load_once() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        lib = None
        # the ONE-TIME toolchain build runs under the load lock by
        # design: every caller needs its result, and serializing here
        # is what makes the load a process-wide once — audited escape:
        # datlint: allow-blocking-under-lock
        so = _build()
        if so is not None:
            try:
                lib = _bind(ctypes.CDLL(str(so)))
            except OSError as e:
                print(f"dat_native load failed ({e}); using Python fallbacks",
                      file=sys.stderr)
                lib = None
        _lib = lib
        _tried = True
    if _OBS.on:
        # once per process (only the winning builder reaches here —
        # emitted AFTER the lock releases, the sink can block): which
        # engine tier this host actually has, the first question when
        # a number moves between hosts
        _emit("device.native.load", ok=lib is not None)
    return _lib


def reset_for_tests() -> None:
    """Drop the cached load so the next :func:`get_lib` re-decides (disk
    build cache untouched); the fastpath twin is
    :func:`runtime.fastpath.reset_for_tests`."""
    global _lib, _tried
    with _lock:
        _lib = None
        _tried = False


def available() -> bool:
    return get_lib() is not None


def _nthreads() -> int:
    return int(os.environ.get("DAT_NTHREADS", "0"))  # 0 = auto (hw cap)


def encode_change_batch(buf, n: int, change, from_, to, key_off, key_len,
                        sub_off, sub_len, val_off, val_len) -> bytes | None:
    """One columnar ``ChangeBatch`` payload from record spans over
    ``buf`` (the ChangeColumns layout; -1 lens = absent optionals), or
    ``None`` when the native library is unavailable (callers fall back
    to the Python codec in ``wire/batch_codec.py``).  The C pass owns
    the dictionary dedup — the only per-row work numpy cannot
    vectorize."""
    lib = get_lib()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    key_len = np.ascontiguousarray(key_len, dtype=np.int64)
    sub_len = np.ascontiguousarray(sub_len, dtype=np.int64)
    val_len = np.ascontiguousarray(val_len, dtype=np.int64)
    # capacity: header + worst-case dictionaries (every span unique) +
    # fixed columns at max widths + value heap
    heap = int(key_len.sum()) \
        + int(np.where(sub_len > 0, sub_len, 0).sum()) \
        + int(np.where(val_len > 0, val_len, 0).sum())
    cap = 64 + 32 * n + heap
    dst = np.empty(cap, np.uint8)
    w = lib.dat_encode_change_batch(
        buf, n,
        np.ascontiguousarray(change, np.uint32),
        np.ascontiguousarray(from_, np.uint32),
        np.ascontiguousarray(to, np.uint32),
        np.ascontiguousarray(key_off, np.int64), key_len,
        np.ascontiguousarray(sub_off, np.int64), sub_len,
        np.ascontiguousarray(val_off, np.int64), val_len,
        dst, cap,
    )
    if w < 0:
        if w == ERR_NOMEM:
            return None  # degrade to the Python codec
        if w == ERR_BAD_RECORD:
            # same contract (and failure class) as _pick_width's raise
            raise ValueError(
                "value exceeds ChangeBatch width ladder")
        raise RuntimeError(f"native batch encode failed (code {w})")
    return dst[:w].tobytes()


def hash_many_list(payloads: list) -> np.ndarray | None:
    """BLAKE2b-256 of a list of ``bytes`` payloads -> (n, 32) uint8, or
    ``None`` when unavailable (callers join + :func:`hash_many`).

    Zero-copy: the C engine reads each payload in place via
    (address, length) spans filled by the dat_fastpath extension,
    passed through ``dat_blake2b_many_ptrs``'s dedicated pointer-array
    parameter (ADVICE r5: the earlier detour through the offset column
    relative to a 1-byte dummy base was out-of-object pointer
    arithmetic — UB, and brittle against any future bounds check in the
    engine).  The ``b"".join`` this path replaces was ~25% of the
    routed host-hash path at digest-pipeline batch shapes.
    """
    lib = get_lib()
    if lib is None or not payloads:
        return None
    from . import fastpath

    fp = fastpath.get()
    if fp is None:
        return None
    n = len(payloads)
    addrs = np.empty(n, dtype=np.int64)
    lens = np.empty(n, dtype=np.int64)
    if not fp.bytes_spans(payloads, addrs, lens):
        return None  # non-bytes entries: caller falls back to the join
    out = np.empty((n, 32), dtype=np.uint8)
    # `payloads` stays referenced (and its bytes pinned) for the call
    rc = lib.dat_blake2b_many_ptrs(addrs, lens, n, out.reshape(-1),
                                   _nthreads())
    if rc != 0:
        return None
    if _OBS.on:
        _M_NATIVE_HASH_BYTES.inc(int(lens.sum()))
    return out


def hash_many(buf: np.ndarray, offs: np.ndarray, lens: np.ndarray):
    """BLAKE2b-256 of ``n`` extents of ``buf`` -> (n, 32) uint8 array, or
    ``None`` when the native library is unavailable (callers fall back).

    Thread-parallel C loop: no per-record interpreter cost, no device
    transfer — the host engine for digesting host-born bytes.
    """
    lib = get_lib()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    offs = np.ascontiguousarray(offs, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int64)
    n = len(offs)
    out = np.empty((n, 32), dtype=np.uint8)
    rc = lib.dat_blake2b_many(buf, offs, lens, n, out.reshape(-1), _nthreads())
    if rc != 0:  # only allocation failure today
        return None
    if _OBS.on:
        _M_NATIVE_HASH_BYTES.inc(int(lens.sum()))
    return out


def sketch(buf: np.ndarray, rec_offs, rec_lens, key_offs, key_lens,
           log2_slots: int):
    """One-pass reconciliation sketch (see ops/reconcile.py): returns
    ``(table, slots)`` as numpy arrays, or ``None`` if unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    rec_offs = np.ascontiguousarray(rec_offs, dtype=np.int64)
    rec_lens = np.ascontiguousarray(rec_lens, dtype=np.int64)
    key_offs = np.ascontiguousarray(key_offs, dtype=np.int64)
    key_lens = np.ascontiguousarray(key_lens, dtype=np.int64)
    n = len(rec_offs)
    table = np.zeros(((1 << log2_slots), 8), dtype=np.uint32)
    slots = np.empty(n, dtype=np.uint32)
    rc = lib.dat_sketch(buf, rec_offs, rec_lens, key_offs, key_lens, n,
                        log2_slots, table.reshape(-1), slots, _nthreads())
    if rc != 0:
        return None
    return table, slots


def hash_many_fallback(buf: np.ndarray, offs: np.ndarray,
                       lens: np.ndarray) -> np.ndarray:
    """:func:`hash_many`, degrading to a hashlib loop on toolchain-less
    hosts — the ONE owner of that fallback shape (consumers previously
    each carried a copy; the digest convention must have one home)."""
    out = hash_many(buf, offs, lens)
    if out is not None:
        return out
    import hashlib

    data = np.ascontiguousarray(buf, dtype=np.uint8).tobytes()
    out = np.empty((len(offs), 32), dtype=np.uint8)
    for i, (o, ln) in enumerate(zip(np.asarray(offs).tolist(),
                                    np.asarray(lens).tolist())):
        out[i] = np.frombuffer(
            hashlib.blake2b(data[o:o + ln], digest_size=32).digest(),
            np.uint8)
    return out


def rateless_build(digests: np.ndarray, state: np.ndarray,
                   next_idx: np.ndarray, m: int, base: int = 0):
    """Rateless coded-symbol build (see ops/rateless.py): advance the
    per-element cursors ``state`` / ``next_idx`` (IN PLACE — the same
    postcondition as ``IndexCursor.advance``) and return the
    ``(m - base, 11)`` u32 cell block for indices ``[base, m)``, or
    ``None`` when the native library is unavailable (callers fall back
    to the numpy reference — byte-identical by construction)."""
    lib = get_lib()
    if lib is None:
        return None
    digests = np.ascontiguousarray(digests, dtype=np.uint8)
    cells = np.zeros((m - base, 11), dtype=np.uint32)
    rc = lib.dat_rateless_build(digests.reshape(-1), len(state), state,
                                next_idx, base, m, cells.reshape(-1),
                                _nthreads())
    if rc != 0:
        return None
    return cells


def rateless_build_w(digests: np.ndarray, lens: np.ndarray,
                     state: np.ndarray, next_idx: np.ndarray,
                     m: int, base: int = 0):
    """Weighted coded-symbol build over (digest, length) elements (see
    ops/rateless.py's variable-size extension): same INOUT cursor
    contract as :func:`rateless_build`, 12-word cells, or ``None`` when
    the native library is unavailable (callers fall back to the numpy
    reference — byte-identical by construction)."""
    lib = get_lib()
    if lib is None:
        return None
    digests = np.ascontiguousarray(digests, dtype=np.uint8)
    lens = np.ascontiguousarray(lens, dtype=np.int64)
    cells = np.zeros((m - base, 12), dtype=np.uint32)
    rc = lib.dat_rateless_build_w(digests.reshape(-1), lens, len(state),
                                  state, next_idx, base, m,
                                  cells.reshape(-1), _nthreads())
    if rc != 0:
        return None
    return cells


def cdc_hash(buf: np.ndarray, avg_bits: int, thin_bits: int,
             min_size: int, max_size: int):
    """Fused single-pass content addressing: chunk cuts AND per-chunk
    BLAKE2b-256 digests in ONE sweep over ``buf`` (the ``fused1p``
    route's host engine).  Returns ``(cuts, digests)`` — cuts as int64
    end-offsets (exclusive, last == len), digests (nchunks, 32) uint8 —
    or ``None`` when the native library is unavailable or the shape is
    out of the fused kernel's range (``thin_bits`` outside [5, 31]):
    callers fall back to the two-pass route, which is byte-identical.
    """
    if not 5 <= thin_bits <= 31:
        return None
    lib = get_lib()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    n = len(buf)
    cap = n // max(min_size, 1) + 2
    cuts = np.empty(cap, dtype=np.int64)
    digests = np.empty((cap, 32), dtype=np.uint8)
    rc = lib.dat_cdc_hash(buf, n, avg_bits, thin_bits, min_size, max_size,
                          cuts, digests.reshape(-1), cap, _nthreads())
    if rc < 0:
        return None  # parameter out of range: two-pass route serves it
    if _OBS.on:
        _M_NATIVE_HASH_BYTES.inc(n)
    return cuts[:rc], digests[:rc]


def gear_candidates(buf: np.ndarray, avg_bits: int, thin_bits: int = -1,
                    serial_reference: bool = False):
    """Host gear CDC candidate scan (seeded-stream definition); sorted
    absolute positions as int64, or None when unavailable.

    ``serial_reference=True`` forces the independently-implemented
    single-chain route (tests compare the 4-chain machinery against it;
    never faster, only simpler)."""
    if not 1 <= avg_bits <= 31:
        raise ValueError("avg_bits must be in [1, 31]")
    if thin_bits > 31:
        raise ValueError("thin_bits must be < 32")
    lib = get_lib()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    n = len(buf)
    cap = max(256, (n >> max(avg_bits - 2, 0)) + 16)
    if thin_bits >= 0:
        cap = min(cap, (n >> thin_bits) + 16)
    while True:
        out = np.empty(cap, dtype=np.int64)
        rc = lib.dat_gear_candidates(buf, n, avg_bits, thin_bits, out, cap,
                                     -2 if serial_reference else _nthreads())
        if rc == ERR_CAPACITY:
            cap *= 4
            continue
        if rc < 0:
            return None
        return out[:rc]


# -- transport pump (ISSUE 14) ----------------------------------------------
# Thin ctypes fronts for the batched-syscall socket loops; the policy
# layer (route selection, decoder feeding, flow control, telemetry)
# lives in session/pump.py.  All of these return ``None`` when the
# native library is unavailable — callers take the Python pumps.


def pump_probe() -> int | None:
    """Bitmask of batched syscalls this kernel serves (bit 0 recvmmsg,
    bit 1 sendmmsg), or ``None`` without the native library.  The pump
    itself degrades per call (ENOSYS/ENOTSOCK fall back to plain
    read/writev batches); this probe only feeds telemetry."""
    lib = get_lib()
    if lib is None:
        return None
    return int(lib.dat_pump_probe())


def pump_recv_scan(fd: int, buf: np.ndarray, slice_bytes: int,
                   starts: np.ndarray, lens: np.ndarray, ids: np.ndarray,
                   stats: np.ndarray):
    """One batched receive into ``buf`` plus a native frame index over
    the received prefix (``dat_pump_recv_scan``): returns
    ``(nbytes, nframes, consumed, err)`` — ``nbytes`` 0 at EOF,
    negative ``-errno`` on a transport error; ``nframes``/``consumed``
    are ``dat_split_frames``' outputs (the decoder's bulk-index input).
    ``stats`` (int64[2]) receives [syscalls, messages] for the call.
    ``None`` when the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    nf = ctypes.c_int64(0)
    consumed = ctypes.c_int64(0)
    err = ctypes.c_int64(0)
    n = lib.dat_pump_recv_scan(fd, buf, len(buf), slice_bytes,
                               starts, lens, ids, len(starts),
                               ctypes.byref(nf), ctypes.byref(consumed),
                               ctypes.byref(err), stats)
    return int(n), int(nf.value), int(consumed.value), int(err.value)


def pump_send_spans(fd: int, addrs: np.ndarray, lens: np.ndarray,
                    n: int, stats: np.ndarray, nonblocking: bool = False):
    """Gather-send ``n`` (address, length) spans (``dat_pump_send`` /
    ``_nb``): the whole batch goes through sendmmsg/writev loops with
    the GIL released; returns bytes the kernel accepted (the full sum
    on a blocking fd) or ``-errno``.  The caller owns keeping every
    span's backing buffer alive across the call.  ``None`` when the
    native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    fn = lib.dat_pump_send_nb if nonblocking else lib.dat_pump_send
    return int(fn(addrs, lens, n, fd, stats))
