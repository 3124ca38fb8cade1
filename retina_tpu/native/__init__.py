"""Native component loader: compile-on-demand + ctypes bindings.

Reference analog: pkg/loader/compile.go — the reference shells out to
clang at plugin-reconcile time to build its eBPF objects; here the loader
invokes ``make`` (g++) once per checkout and caches the shared library
next to the sources. Every consumer degrades gracefully to the pure
Python/numpy implementation when the toolchain is unavailable
(``native_available()`` gates the fast paths).

Exposes:
- :func:`decode_pcap_native` — C++ pcap→records decoder (decoder.cpp),
  bit-identical to sources/pcapdecode.decode_pcap_bytes.
- :class:`NativeRing` — shared-memory SPSC record ring (ring.cpp) usable
  across processes via an mmap'd file.
"""

from __future__ import annotations

import ctypes
import mmap
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from retina_tpu.events.schema import NUM_FIELDS
from retina_tpu.log import logger

_log = logger("native")
_dir = os.path.dirname(os.path.abspath(__file__))
_so_path = os.path.join(_dir, "libretina_native.so")
_lib: Optional[ctypes.CDLL] = None
_lock = threading.Lock()
_build_failed = False

# Expected ABI of libretina_native.so (decoder.cpp rt_abi_version — the
# single source of truth on the C++ side). The loader refuses a library
# reporting anything else: a stale prebuilt .so (wrong checkout, wrong
# arch cache) would otherwise misparse the dense wire bitstream or call
# an entry point it no longer has. Bump BOTH sides together.
NATIVE_ABI_VERSION = 4


def _build(force: bool = False) -> bool:
    try:
        cmd = ["make", "-C", _dir, "-s"]
        if force:
            cmd.append("-B")
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as e:
        detail = getattr(e, "stderr", b"") or b""
        _log.warning("native build failed (%s); using Python fallbacks: %s",
                     e, detail.decode(errors="replace")[:500])
        return False


def _loaded_abi(lib: ctypes.CDLL) -> int:
    """ABI version a loaded library reports (0 = pre-versioning v1-era
    binary with no rt_abi_version export)."""
    try:
        fn = lib.rt_abi_version
    except AttributeError:
        return 0
    fn.restype = ctypes.c_uint32
    fn.argtypes = []
    return int(fn())


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library, or None."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        src_mtime = max(
            os.path.getmtime(os.path.join(_dir, f))
            for f in ("decoder.cpp", "ring.cpp", "combine.cpp",
                      "afpacket.cpp", "flowdict.cpp", "pack.cpp")
        )
        if (not os.path.exists(_so_path)
                or os.path.getmtime(_so_path) < src_mtime):
            if not _build():
                _build_failed = True
                return None
        try:
            lib = ctypes.CDLL(_so_path)
        except OSError as e:
            _log.warning("native library load failed: %s", e)
            _build_failed = True
            return None
        # ABI gate: an .so that predates (or postdates) this checkout's
        # bindings gets one forced rebuild from source; if the toolchain
        # can't produce a matching binary, fall back to Python rather
        # than call through a mismatched ABI.
        abi = _loaded_abi(lib)
        if abi != NATIVE_ABI_VERSION:
            _log.warning(
                "native library ABI %d != expected %d; rebuilding",
                abi, NATIVE_ABI_VERSION,
            )
            if not _build(force=True):
                _build_failed = True
                return None
            lib = ctypes.CDLL(_so_path)
            abi = _loaded_abi(lib)
            if abi != NATIVE_ABI_VERSION:
                _log.warning(
                    "native library ABI still %d after rebuild; "
                    "using Python fallbacks", abi,
                )
                _build_failed = True
                return None
        lib.rt_decode_pcap.restype = ctypes.c_long
        lib.rt_decode_pcap.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.rt_combine.restype = ctypes.c_long
        lib.rt_combine.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.rt_combine_hint.restype = ctypes.c_long
        lib.rt_combine_hint.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
        ]
        lib.rt_combine_multi.restype = ctypes.c_long
        lib.rt_combine_multi.argtypes = [
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint32)),
            ctypes.POINTER(ctypes.c_size_t), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
        ]
        lib.rt_flowdict_new.restype = ctypes.c_void_p
        lib.rt_flowdict_new.argtypes = [ctypes.c_uint32]
        lib.rt_flowdict_free.restype = None
        lib.rt_flowdict_free.argtypes = [ctypes.c_void_p]
        lib.rt_flowdict_clear.restype = None
        lib.rt_flowdict_clear.argtypes = [ctypes.c_void_p]
        lib.rt_flowdict_len.restype = ctypes.c_uint32
        lib.rt_flowdict_len.argtypes = [ctypes.c_void_p]
        lib.rt_flowdict_generation.restype = ctypes.c_uint32
        lib.rt_flowdict_generation.argtypes = [ctypes.c_void_p]
        lib.rt_flowdict_assign.restype = ctypes.c_uint32
        lib.rt_flowdict_assign.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.rt_ts_base.restype = ctypes.c_uint64
        lib.rt_ts_base.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
        ]
        lib.rt_pack.restype = None
        lib.rt_pack.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.rt_flowwire_dense.restype = ctypes.c_long
        lib.rt_flowwire_dense.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.rt_afp_open.restype = ctypes.c_void_p
        lib.rt_afp_open.argtypes = [
            ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint32,
        ]
        lib.rt_afp_poll.restype = ctypes.c_long
        lib.rt_afp_poll.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.rt_afp_drops.restype = ctypes.c_uint64
        lib.rt_afp_drops.argtypes = [ctypes.c_void_p]
        lib.rt_afp_close.restype = None
        lib.rt_afp_close.argtypes = [ctypes.c_void_p]
        lib.rt_ring_bytes.restype = ctypes.c_size_t
        lib.rt_ring_bytes.argtypes = [ctypes.c_uint64, ctypes.c_uint32]
        lib.rt_ring_init.restype = ctypes.c_int
        lib.rt_ring_init.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                     ctypes.c_uint32]
        lib.rt_ring_check.restype = ctypes.c_int
        lib.rt_ring_check.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        for fn, nargs in (("rt_ring_push", 3), ("rt_ring_pop", 3),
                          ("rt_ring_size", 1), ("rt_ring_dropped", 1)):
            f = getattr(lib, fn)
            f.restype = ctypes.c_uint64
            f.argtypes = [ctypes.c_void_p] + (
                [ctypes.POINTER(ctypes.c_uint32), ctypes.c_uint64]
                if nargs == 3 else []
            )
        _lib = lib
        _log.info("native library loaded: %s", _so_path)
        return _lib


def native_available() -> bool:
    return get_lib() is not None


def decode_pcap_native(data: bytes, obs_point: int = 2) -> Optional[tuple]:
    """C++ decode. Returns (records (N,16) u32, n_packets_total) or None
    when the library is unavailable. DNS names are NOT extracted here
    (strings stay host-Python; see sources/pcapdecode for the name pass)
    but DNS qtype/rcode/qname-hash fields are filled identically."""
    lib = get_lib()
    if lib is None:
        return None
    # Generous upper bound: every record is ≥ 16B header + 54B packet.
    max_records = max(len(data) // 70 + 64, 1024)
    while True:
        out = np.zeros((max_records, NUM_FIELDS), np.uint32)
        total = ctypes.c_size_t(0)
        n = lib.rt_decode_pcap(
            data, len(data), obs_point,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            max_records, ctypes.byref(total),
        )
        if n == -1:
            raise ValueError("not a pcap file")
        if n == -2:
            max_records *= 2
            continue
        return out[:n], int(total.value)


# Distinct-group count of the previous combine: flush-over-flush flow
# diversity is stable, so sizing the next probe table from it keeps the
# table cache-resident (combine.cpp rt_combine_hint grows it when the
# hint undershoots — identical results either way). Plain int store:
# the feed workers each write it after their own flush's combine, and a
# stale or another worker's value only costs a suboptimal table size.
_combine_hint_groups = 0


def combine_native(records: np.ndarray) -> Optional[np.ndarray]:
    """C++ descriptor-RLE combine (combine.cpp). Returns the combined
    (G, 16) array, or None when the library is unavailable. Semantics
    match parallel.combine.combine_records_numpy; the ctypes call
    releases the GIL, so combining overlaps device transfers running on
    another thread."""
    global _combine_hint_groups
    lib = get_lib()
    if lib is None:
        return None
    n = len(records)
    if n <= 1:
        return records
    if not records.flags.c_contiguous:
        records = np.ascontiguousarray(records)
    out = np.empty_like(records)
    # Target load factor <= 0.25 at the remembered group count so the
    # common case never pays the grow-and-rehash.
    g = lib.rt_combine_hint(
        records.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        4 * _combine_hint_groups,
    )
    if g < 0:
        return None
    _combine_hint_groups = int(g)
    if g == n:
        return records
    return out[:g]


def combine_native_blocks(
    blocks: list,
) -> Optional[np.ndarray]:
    """C++ multi-block combine (combine.cpp rt_combine_multi): one pass
    over a LIST of (n_i, 16) u32 blocks, skipping the concatenation
    copy the single-array path needs (~40% of the combine stage at
    production quanta). Output is bit-identical to
    ``combine_native(np.concatenate(blocks))``. Returns None when the
    library is unavailable or any block isn't a plain (N, 16) u32
    array — callers fall back to concat + combine."""
    global _combine_hint_groups
    lib = get_lib()
    if lib is None or not blocks:
        return None
    total = 0
    for b in blocks:
        if (b.ndim != 2 or b.shape[1] != 16 or b.dtype != np.uint32
                or not b.flags.c_contiguous):
            return None
        total += len(b)
    if total == 0:
        return blocks[0][:0]
    ptrs = (ctypes.POINTER(ctypes.c_uint32) * len(blocks))(
        *[b.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))
          for b in blocks]
    )
    ns = (ctypes.c_size_t * len(blocks))(*[len(b) for b in blocks])
    out = np.empty((total, 16), np.uint32)
    g = lib.rt_combine_multi(
        ptrs, ns, len(blocks),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        4 * _combine_hint_groups,
    )
    if g < 0:
        return None
    _combine_hint_groups = int(g)
    return out[:g]


def native_abi_version() -> Optional[int]:
    """ABI version of the loaded native library (None when unavailable).
    get_lib() already enforces == NATIVE_ABI_VERSION; this exists for
    the tier-1 ABI check and diagnostics."""
    lib = get_lib()
    if lib is None:
        return None
    return _loaded_abi(lib)


def flowwire_dense_native(
    rows: np.ndarray, ids: np.ndarray, sel_new: np.ndarray,
    base: int, id_bits: int, pk_bits: int, by_bits: int,
    new_out: np.ndarray, known_words: np.ndarray,
) -> Optional[int]:
    """C++ dense flow-dict wire build (pack.cpp rt_flowwire_dense): one
    pass splits ``rows`` by ``sel_new`` into the new wire (id + 12
    packed lanes, written to ``new_out``) and the known wire, the
    ZEROED 1-D ``known_words`` bitstream at (id_bits + pk_bits +
    by_bits) bits per row (parallel/wire.py dense_known_rows is the
    numpy twin). Returns the new-row count, or None when the library
    is unavailable / the inputs don't match the fast-path layout
    (caller falls back to the numpy build). Semantics are cross-checked
    against the numpy path by tests/test_wire.py."""
    lib = get_lib()
    n = len(rows)
    row_bits = int(id_bits) + int(pk_bits) + int(by_bits)
    if (lib is None or row_bits > 64
            or rows.ndim != 2 or rows.shape[1] != NUM_FIELDS
            or rows.dtype != np.uint32 or not rows.flags.c_contiguous
            or ids.dtype != np.uint32 or not ids.flags.c_contiguous
            or sel_new.dtype != np.uint8
            or not sel_new.flags.c_contiguous
            or len(ids) != n or len(sel_new) != n
            or new_out.dtype != np.uint32
            or known_words.dtype != np.uint32
            or not new_out.flags.c_contiguous
            or not known_words.flags.c_contiguous
            or new_out.ndim != 2 or new_out.shape[1] != 13
            or known_words.ndim != 1):
        return None
    # Capacity guard: n_new*13 words on the new side, the dense stream
    # plus one pad word on the known side — undersized must fall back,
    # not corrupt.
    n_sel = int(sel_new.sum())
    need = ((n - n_sel) * row_bits + 31) // 32 + 1
    if len(new_out) < n_sel or len(known_words) < need:
        return None
    u32p = ctypes.POINTER(ctypes.c_uint32)
    return int(lib.rt_flowwire_dense(
        rows.ctypes.data_as(u32p), n, ids.ctypes.data_as(u32p),
        sel_new.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_uint64(int(base)), ctypes.c_uint32(int(id_bits)),
        ctypes.c_uint32(int(pk_bits)), ctypes.c_uint32(int(by_bits)),
        new_out.ctypes.data_as(u32p), known_words.ctypes.data_as(u32p),
    ))


def pack_native(
    records: np.ndarray, base: Optional[int] = None
) -> Optional[tuple]:
    """C++ wire packer (pack.cpp): (n, 16) u32 -> ((n, 12) u32, base).
    Returns None when the native library is unavailable or the input is
    not a 2-D schema array (callers fall back to the numpy path).
    Semantics match parallel.wire.pack_records — cross-checked by
    tests/test_native.py."""
    lib = get_lib()
    if (lib is None or records.ndim != 2 or records.dtype != np.uint32
            or records.shape[1] != NUM_FIELDS):
        return None
    if not records.flags.c_contiguous:
        records = np.ascontiguousarray(records)
    n = len(records)
    rows = records.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))
    if base is None:
        base = int(lib.rt_ts_base(rows, n)) if n else 0
    out = np.empty((n, 12), np.uint32)
    if n:
        lib.rt_pack(
            rows, n, ctypes.c_uint64(base),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        )
    return out, base


class NativeFlowDict:
    """Persistent descriptor->id dictionary (flowdict.cpp) — the
    GIL-released twin of parallel.flowdict.HostFlowDict (same contract,
    cross-checked by tests). Raises RuntimeError if the native library
    is unavailable; callers fall back to the Python dict."""

    def __init__(self, capacity: int = 1 << 18):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.capacity = int(capacity)
        self._h = lib.rt_flowdict_new(self.capacity)
        if not self._h:
            raise RuntimeError("flowdict allocation failed")

    @property
    def generation(self) -> int:
        return int(self._lib.rt_flowdict_generation(self._h))

    def __len__(self) -> int:
        return int(self._lib.rt_flowdict_len(self._h))

    def clear(self) -> None:
        self._lib.rt_flowdict_clear(self._h)

    def lookup_or_assign(self, records: np.ndarray):
        n = len(records)
        ids = np.zeros(n, np.uint32)
        is_new = np.zeros(n, np.uint8)
        if n:
            # Same contract as HostFlowDict: accept (N, >=16) of any int
            # dtype — rt_flowdict_assign reads row-major (n,16) u32, so
            # anything wider/non-u32 must be sliced+cast first or the C++
            # side would misread the rows.
            if records.ndim != 2 or records.shape[1] < NUM_FIELDS:
                raise ValueError(
                    f"expected (N, >={NUM_FIELDS}) records, got "
                    f"{records.shape}"
                )
            if (records.dtype != np.uint32
                    or records.shape[1] != NUM_FIELDS):
                records = records[:, :NUM_FIELDS].astype(np.uint32)
            if not records.flags.c_contiguous:
                records = np.ascontiguousarray(records)
            self._lib.rt_flowdict_assign(
                self._h,
                records.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                n,
                ids.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                is_new.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            )
        return ids, is_new.astype(bool)

    def close(self) -> None:
        if self._h:
            self._lib.rt_flowdict_free(self._h)
            self._h = None

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:  # noqa: RT101 — __del__ must never raise; close() is the real API
            pass


class AfPacketRing:
    """TPACKET_V3 live capture (afpacket.cpp) — the perf-ring analog.

    ``poll(timeout_ms)`` returns ((N, 16) records, frames_seen); kernel
    drops surface via ``drops()`` as a monotonic counter. Raises
    RuntimeError when the ring cannot open (no CAP_NET_RAW, non-Linux,
    unknown interface) — callers fall back to the Python socket loop.
    """

    # A 1 MiB TPACKET_V3 block holds at most ~11k minimum-size frames;
    # polling with capacity for two full blocks means the mid-block
    # resume path is the exception, not the rule.
    POLL_RECORDS = 1 << 15

    DNS_BUF_BYTES = 1 << 16

    def __init__(self, iface: str = "", block_size: int = 1 << 20,
                 block_nr: int = 32, obs_point: int = 2):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.obs_point = obs_point
        self._h = lib.rt_afp_open(iface.encode(), block_size, block_nr)
        if not self._h:
            raise RuntimeError(
                f"AF_PACKET TPACKET_V3 ring open failed (iface={iface!r}; "
                "needs Linux + CAP_NET_RAW)"
            )
        self._buf = np.empty((self.POLL_RECORDS, NUM_FIELDS), np.uint32)
        self._dns_buf = (ctypes.c_uint8 * self.DNS_BUF_BYTES)()

    def poll(self, timeout_ms: int = 100):
        """Returns (records (N, 16), frames_seen, dns_frames bytes) —
        dns_frames is a [u16 len][frame] blob of the DNS packets in this
        batch, for the host-side qname string pass."""
        if self._h is None:
            raise RuntimeError("AF_PACKET ring is closed")
        seen = ctypes.c_uint64(0)
        dns_used = ctypes.c_size_t(0)
        n = self._lib.rt_afp_poll(
            self._h, timeout_ms, self.obs_point,
            self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            self.POLL_RECORDS, ctypes.byref(seen),
            self._dns_buf, self.DNS_BUF_BYTES, ctypes.byref(dns_used),
        )
        if n < 0:
            raise RuntimeError("AF_PACKET poll failed")
        return (
            self._buf[:n].copy(),
            int(seen.value),
            bytes(self._dns_buf[: dns_used.value]),
        )

    def drops(self) -> int:
        if self._h is None:
            raise RuntimeError("AF_PACKET ring is closed")
        return int(self._lib.rt_afp_drops(self._h))

    def close(self) -> None:
        if self._h:
            self._lib.rt_afp_close(self._h)
            self._h = None


class NativeRing:
    """SPSC record ring over private memory or an mmap'd shm file."""

    def __init__(self, capacity: int = 1 << 14,
                 path: Optional[str] = None, create: bool = True):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.capacity = capacity
        nbytes = lib.rt_ring_bytes(capacity, NUM_FIELDS)
        self._file = None
        if path is None:
            self._mm = mmap.mmap(-1, nbytes)
        else:
            mode = "r+b" if (os.path.exists(path) and not create) else "w+b"
            self._file = open(path, mode)
            if create or os.path.getsize(path) < nbytes:
                self._file.truncate(nbytes)
            self._mm = mmap.mmap(self._file.fileno(), nbytes)
        self._buf = ctypes.c_char.from_buffer(self._mm)
        self._addr = ctypes.addressof(self._buf)
        if create:
            if lib.rt_ring_init(self._addr, capacity, NUM_FIELDS) != 0:
                raise ValueError("capacity must be a power of two")
        elif lib.rt_ring_check(self._addr, NUM_FIELDS) != 0:
            raise ValueError(f"not a retina ring: {path}")

    def push(self, records: np.ndarray) -> int:
        rec = np.ascontiguousarray(records, np.uint32)
        assert rec.ndim == 2 and rec.shape[1] == NUM_FIELDS
        return int(self._lib.rt_ring_push(
            self._addr,
            rec.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            len(rec),
        ))

    def pop(self, max_records: int = 8192) -> np.ndarray:
        out = np.empty((max_records, NUM_FIELDS), np.uint32)
        n = int(self._lib.rt_ring_pop(
            self._addr,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            max_records,
        ))
        return out[:n]

    def __len__(self) -> int:
        return int(self._lib.rt_ring_size(self._addr))

    @property
    def dropped(self) -> int:
        return int(self._lib.rt_ring_dropped(self._addr))

    def close(self) -> None:
        # Release the exported buffer before closing the mmap.
        del self._buf
        self._mm.close()
        if self._file is not None:
            self._file.close()
