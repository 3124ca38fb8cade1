"""Packed host->device wire format for event records.

The host->device link (PCIe) is the system's scarcest bandwidth, so
records cross it packed: 12 uint32 lanes instead of the schema's 16
(events/schema.py), unpacked back to the full 16-lane layout ON DEVICE where HBM bandwidth
makes the expansion free. Together with descriptor combining
(parallel/combine.py) and power-of-two transfer buckets
(parallel/partition.py), wire bytes per represented event drop from 64 to
~48/combine_ratio.

Layout (indices into the packed minor axis):

==  =========  ========================================================
ix  name       contents
==  =========  ========================================================
0   TS_REL     1 + nanoseconds since the batch base timestamp (u32;
               spreads beyond ~4.29 s saturate — harmless: the device
               consumes per-row time only for apiserver RTT matching).
               0 means "no timestamp": a source that never stamps
               round-trips to ts 0 exactly instead of inheriting the
               batch base (which would feed phantom values into the
               apiserver RTT latency matcher)
1   SRC_IP     = schema F.SRC_IP
2   DST_IP     = schema F.DST_IP
3   PORTS      = schema F.PORTS
4   META       = schema F.META
5   BYTES      = schema F.BYTES
6   PACKETS    = schema F.PACKETS
7   MISC       VERDICT(3b) << 29 | DROP_REASON(8b) << 21 |
               EVENT_TYPE(4b) << 17 | IFINDEX(17b)   (each saturating)
8   TSVAL      = schema F.TSVAL
9   TSECR      = schema F.TSECR
10  DNS        = schema F.DNS
11  DNS_QHASH  = schema F.DNS_QHASH
==  =========  ========================================================

The batch base timestamp travels as two u32 scalars (lo, hi) beside the
array. Saturation bounds (verdict 7, reason 255, event type 15, ifindex
131071) exceed every value the reference emits (flow.Verdict <= 5, drop
reason ids < 200, EV_* < 8; pkg/utils/flow_utils.go).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from retina_tpu.events.schema import F, NUM_FIELDS

PACKED_FIELDS = 12

_U32 = np.uint64(0xFFFFFFFF)


def batch_ts_base(records: np.ndarray) -> np.uint64:
    """Minimum nonzero 64-bit timestamp of the batch (0 if none) — the
    TS_REL base shared by every wire array cut from one flush."""
    ts = (records[..., F.TS_HI].astype(np.uint64) << np.uint64(32)) | records[
        ..., F.TS_LO
    ].astype(np.uint64)
    nz = ts[ts > 0]
    return np.uint64(nz.min()) if len(nz) else np.uint64(0)


def ts_rel(records: np.ndarray, base: np.uint64) -> np.ndarray:
    """Biased relative timestamps: 1 + ns since ``base`` (saturating),
    0 for unstamped rows — the TS_REL lane encoding."""
    ts = (records[..., F.TS_HI].astype(np.uint64) << np.uint64(32)) | records[
        ..., F.TS_LO
    ].astype(np.uint64)
    return np.where(
        ts > 0,
        np.minimum(ts - base, _U32 - np.uint64(1)) + np.uint64(1),
        0,
    ).astype(np.uint32)


# -- dense known-row bitstream ----------------------------------------
#
# Under the flow dictionary a known row needs its id, PACKETS and BYTES
# only, and BYTES almost never needs 32 bits. Each known row is packed
# as (id_bits + DENSE_PK_BITS + DENSE_BY_BITS) CONTIGUOUS bits —
# ``id | packets << id_bits | bytes << (id_bits + DENSE_PK_BITS)`` —
# streamed into one u32 word array: 50 bits = 6.25 B/row at id_bits=18,
# and the row narrows further for smaller dictionaries. Rows whose
# PACKETS or BYTES overflow their lane escalate to the full 13-word
# new-row side (engine._dispatch_flowdict's mask), so the stream stores
# every surviving row exactly. The +1 pad word keeps the device
# unpack's two-word gather in bounds for the final row.
#
# Three implementations, cross-checked bit-for-bit by
# tests/test_wire.py: native/pack.cpp rt_flowwire_dense (the fast
# path), dense_known_rows below (numpy fallback), and
# dense_known_unpack_device (the device-side reader).

DENSE_PK_BITS = 10
DENSE_BY_BITS = 22


def dense_row_bits(id_bits: int) -> int:
    """Bits per dense known row. <= 64 for every legal dictionary size
    (id_bits <= 32)."""
    return int(id_bits) + DENSE_PK_BITS + DENSE_BY_BITS


def dense_words(n_rows: int, id_bits: int) -> int:
    """u32 words needed for ``n_rows`` dense known rows, including the
    pad word the device unpack's two-word gather requires."""
    return (int(n_rows) * dense_row_bits(id_bits) + 31) // 32 + 1


def dense_known_rows(
    rows: np.ndarray, ids: np.ndarray, id_bits: int, out: np.ndarray
) -> None:
    """Numpy twin of native rt_flowwire_dense's known side: OR the
    dense bit rows into the ZEROED 1-D u32 ``out`` stream in row order.
    Caller guarantees packets < 2**DENSE_PK_BITS and bytes <
    2**DENSE_BY_BITS (the escalation mask's job)."""
    k = len(rows)
    if k == 0:
        return
    rb = dense_row_bits(id_bits)
    v = (
        ids.astype(np.uint64)
        | (rows[:, F.PACKETS].astype(np.uint64) << np.uint64(id_bits))
        | (rows[:, F.BYTES].astype(np.uint64)
           << np.uint64(id_bits + DENSE_PK_BITS))
    )
    p = np.arange(k, dtype=np.uint64) * np.uint64(rb)
    wi = (p >> np.uint64(5)).astype(np.int64)
    sh = p & np.uint64(31)
    # A <=64-bit value shifted by <=31 spans <=3 words; split explicitly
    # (v << sh would overflow u64 for sh > 64 - rb).
    lo = ((v & _U32) << sh) & _U32
    mid = (v >> (np.uint64(32) - sh)) & _U32  # sh==0 -> v >> 32: word 1
    hi_sh = np.where(sh > 0, np.uint64(64) - sh, np.uint64(63))
    hi = np.where(sh > 0, v >> hi_sh, np.uint64(0))
    np.bitwise_or.at(out, wi, lo.astype(np.uint32))
    np.bitwise_or.at(out, wi + 1, mid.astype(np.uint32))
    np.bitwise_or.at(out, wi + 2, hi.astype(np.uint32))


def dense_known_unpack_device(words, n_rows: int, id_bits: int):
    """jax: dense known stream -> (ids, packets, bytes), each (..., n).

    ``words`` is (..., W) u32 (per-device streams stack on the leading
    axis); gathers two words per field and shifts them together — every
    field is <= 32 bits wide, so two words always suffice. Runs inside
    the engine's known-ingest jit.
    """
    rb = dense_row_bits(id_bits)
    i = jnp.arange(n_rows, dtype=jnp.uint32)

    def field(off: int, width: int):
        p = i * np.uint32(rb) + np.uint32(off)
        wi = (p >> np.uint32(5)).astype(jnp.int32)
        sh = p & np.uint32(31)
        lo = words[..., wi] >> sh
        up = words[..., wi + 1]
        # sh==0 would shift by 32 (undefined); (32-sh)&31 makes it a
        # shift by 0 and the where() discards the lane.
        up = jnp.where(
            sh > 0, up << ((np.uint32(32) - sh) & np.uint32(31)), 0
        )
        return (lo | up) & np.uint32((1 << width) - 1)

    return (
        field(0, id_bits),
        field(id_bits, DENSE_PK_BITS),
        field(id_bits + DENSE_PK_BITS, DENSE_BY_BITS),
    )


def dense_known_unpack_numpy(
    words: np.ndarray, n_rows: int, id_bits: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host mirror of dense_known_unpack_device (tests)."""
    rb = dense_row_bits(id_bits)
    i = np.arange(n_rows, dtype=np.uint32)

    def field(off: int, width: int) -> np.ndarray:
        p = i * np.uint32(rb) + np.uint32(off)
        wi = (p >> np.uint32(5)).astype(np.int64)
        sh = p & np.uint32(31)
        lo = words[..., wi] >> sh
        up = words[..., wi + 1]
        up = np.where(
            sh > 0, up << ((np.uint32(32) - sh) & np.uint32(31)), 0
        ).astype(np.uint32)
        return (lo | up) & np.uint32((1 << width) - 1)

    return (
        field(0, id_bits),
        field(id_bits, DENSE_PK_BITS),
        field(id_bits + DENSE_PK_BITS, DENSE_BY_BITS),
    )


def pack_records(
    records: np.ndarray, base: np.uint64 | None = None
) -> tuple[np.ndarray, np.uint32, np.uint32]:
    """(..., 16) u32 -> ((..., 12) u32, base_lo, base_hi).

    Works on (N, 16) host batches and (D, B, 16) sharded batches alike;
    padding rows (all zeros) pack to all-zero rows given base handling
    below. The base defaults to the minimum valid timestamp of THIS
    array; pass one explicitly when several wire arrays cut from one
    flush must share it. Zero-timestamp rows (padding or sources that
    never stamp) keep TS_REL 0.
    """
    if records.ndim == 2:
        # Native single pass (native/pack.cpp) when available: packing
        # sits on the flush critical path, and the strided column
        # copies + u64 timestamp math below are ~19% of the host feed
        # cost at production quanta.
        try:
            from retina_tpu.native import pack_native
        except ImportError:
            got = None
        else:
            # Binding errors must surface, not silently fall back to
            # the slow path on every flush.
            got = pack_native(
                records, None if base is None else int(base)
            )
        if got is not None:
            out, nbase = got
            nbase = np.uint64(nbase)
            return (
                out,
                np.uint32(nbase & _U32),
                np.uint32(nbase >> np.uint64(32)),
            )
    if base is None:
        base = batch_ts_base(records)
    rel = ts_rel(records, base)
    out = np.empty(records.shape[:-1] + (PACKED_FIELDS,), np.uint32)
    out[..., 0] = rel
    out[..., 1] = records[..., F.SRC_IP]
    out[..., 2] = records[..., F.DST_IP]
    out[..., 3] = records[..., F.PORTS]
    out[..., 4] = records[..., F.META]
    out[..., 5] = records[..., F.BYTES]
    out[..., 6] = records[..., F.PACKETS]
    out[..., 7] = (
        (np.minimum(records[..., F.VERDICT], 7) << np.uint32(29))
        | (np.minimum(records[..., F.DROP_REASON], 255) << np.uint32(21))
        | (np.minimum(records[..., F.EVENT_TYPE], 15) << np.uint32(17))
        | np.minimum(records[..., F.IFINDEX], 0x1FFFF)
    )
    out[..., 8] = records[..., F.TSVAL]
    out[..., 9] = records[..., F.TSECR]
    out[..., 10] = records[..., F.DNS]
    out[..., 11] = records[..., F.DNS_QHASH]
    return (
        out,
        np.uint32(base & _U32),
        np.uint32(base >> np.uint64(32)),
    )


def unpack_records_device(packed, base_lo, base_hi):
    """jax: (..., 12) u32 + base scalars -> (..., 16) u32 (schema layout).

    Runs inside the engine's per-bucket unpack-pad jit; XLA fuses the bit
    surgery with the zero-extension to the step's static shape.
    """
    rel = packed[..., 0]
    relm1 = rel - np.uint32(1)  # wraps for rel==0; masked below
    ts_lo = base_lo + relm1
    carry = (ts_lo < relm1).astype(jnp.uint32)
    stamped = rel > 0
    misc = packed[..., 7]
    cols = [None] * NUM_FIELDS
    cols[F.TS_LO] = jnp.where(stamped, ts_lo, 0)
    cols[F.TS_HI] = jnp.where(stamped, base_hi + carry, 0)
    cols[F.SRC_IP] = packed[..., 1]
    cols[F.DST_IP] = packed[..., 2]
    cols[F.PORTS] = packed[..., 3]
    cols[F.META] = packed[..., 4]
    cols[F.BYTES] = packed[..., 5]
    cols[F.PACKETS] = packed[..., 6]
    cols[F.VERDICT] = misc >> 29
    cols[F.DROP_REASON] = (misc >> 21) & np.uint32(0xFF)
    cols[F.EVENT_TYPE] = (misc >> 17) & np.uint32(0xF)
    cols[F.IFINDEX] = misc & np.uint32(0x1FFFF)
    cols[F.TSVAL] = packed[..., 8]
    cols[F.TSECR] = packed[..., 9]
    cols[F.DNS] = packed[..., 10]
    cols[F.DNS_QHASH] = packed[..., 11]
    return jnp.stack(cols, axis=-1)


def unpack_records_numpy(packed: np.ndarray, base_lo, base_hi) -> np.ndarray:
    """Host mirror of unpack_records_device (tests)."""
    rel = packed[..., 0]
    relm1 = (rel - np.uint32(1)).astype(np.uint32)  # wraps for rel==0
    ts_lo = (np.uint32(base_lo) + relm1).astype(np.uint32)
    carry = (ts_lo < relm1).astype(np.uint32)
    stamped = rel > 0
    misc = packed[..., 7]
    out = np.empty(packed.shape[:-1] + (NUM_FIELDS,), np.uint32)
    out[..., F.TS_LO] = np.where(stamped, ts_lo, 0)
    out[..., F.TS_HI] = np.where(stamped, np.uint32(base_hi) + carry, 0)
    out[..., F.SRC_IP] = packed[..., 1]
    out[..., F.DST_IP] = packed[..., 2]
    out[..., F.PORTS] = packed[..., 3]
    out[..., F.META] = packed[..., 4]
    out[..., F.BYTES] = packed[..., 5]
    out[..., F.PACKETS] = packed[..., 6]
    out[..., F.VERDICT] = misc >> 29
    out[..., F.DROP_REASON] = (misc >> 21) & np.uint32(0xFF)
    out[..., F.EVENT_TYPE] = (misc >> 17) & np.uint32(0xF)
    out[..., F.IFINDEX] = misc & np.uint32(0x1FFFF)
    out[..., F.TSVAL] = packed[..., 8]
    out[..., F.TSECR] = packed[..., 9]
    out[..., F.DNS] = packed[..., 10]
    out[..., F.DNS_QHASH] = packed[..., 11]
    return out
