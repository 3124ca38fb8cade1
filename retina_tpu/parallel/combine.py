"""Host-side record combining: the eBPF-map pre-aggregation analog.

The reference never ships the per-packet firehose to userspace raw: its
kernel programs aggregate in eBPF maps first (packetforward sums per-
direction counters in a map, `pkg/plugin/packetforward/packetforward_linux.go`
reads totals; conntrack accumulates per-connection packet/byte counts in
its LRU map and emits per-connection reports, `_cprog/conntrack.c`). The
TPU analog of "the kernel map" is this combiner: before records cross the
host->device link (PCIe, the system's scarcest bandwidth), identical flow
descriptors within a flush interval are run-length encoded into one record carrying summed
PACKETS/BYTES and the latest timestamp.

Losslessness contract: every device-side aggregator weights by F.PACKETS
(models/pipeline.py), so feeding ``combine_records(batch)`` produces
EXACTLY the same device state as feeding ``batch`` row by row — the group
key is every column except the weight columns (BYTES, PACKETS) and the
timestamps. Two packets that differ in ANY descriptor bit (tcp flags, drop
reason, DNS rcode, interface, TSval...) stay separate rows, so nothing a
per-event aggregator could distinguish is merged away.

The compression ratio is the packets-per-distinct-descriptor factor of the
traffic — the same factor the reference's kernel maps exploit (flows are
few, packets are many). Worst case (every descriptor unique) the combiner
returns the input unchanged, minus the sort cost.
"""

from __future__ import annotations

import numpy as np

from retina_tpu.events.schema import F, NUM_FIELDS
from retina_tpu.parallel.partition import hash_cols_np

# Group key: every column EXCEPT the accumulated weights and timestamps.
# TSVAL/TSECR stay IN the key: latency matching (pipeline.py apiserver RTT)
# needs exact TSval/TSecr values, and same-TSval packets (ms granularity)
# still combine.
KEY_COLS = (
    F.SRC_IP,
    F.DST_IP,
    F.PORTS,
    F.META,
    F.VERDICT,
    F.DROP_REASON,
    F.TSVAL,
    F.TSECR,
    F.DNS,
    F.DNS_QHASH,
    F.EVENT_TYPE,
    F.IFINDEX,
)

_U32_MAX = np.uint64(0xFFFFFFFF)


def combine_records_numpy(records: np.ndarray) -> np.ndarray:
    """Pure-numpy combine: sort by descriptor hash + segmented reduce.

    Aggregation: PACKETS/BYTES sum (saturating at u32 max), timestamp is
    the group's latest. Returns the input array itself (no copy) when
    nothing merges. Row order of the output is arbitrary (hash order).
    """
    n = len(records)
    if n <= 1:
        return records
    assert records.shape[1] == NUM_FIELDS
    h = hash_cols_np([records[:, c] for c in KEY_COLS], seed=0xC0B1)
    order = np.argsort(h, kind="stable")
    r = records[order]
    # Group boundary = any key column differs from the previous sorted
    # row. Equal keys hash equally so they are adjacent (stable sort keeps
    # equal-hash rows in input order, so a hash collision between two
    # interleaved descriptors can only SPLIT a group — never merge one).
    bounds = np.empty(n, bool)
    bounds[0] = True
    acc = np.zeros(n - 1, bool)
    for c in KEY_COLS:
        col = r[:, c]
        acc |= col[1:] != col[:-1]
    bounds[1:] = acc
    starts = np.flatnonzero(bounds)
    if len(starts) == n:
        return records
    out = r[starts].copy()
    pkts = np.add.reduceat(r[:, F.PACKETS].astype(np.uint64), starts)
    byts = np.add.reduceat(r[:, F.BYTES].astype(np.uint64), starts)
    out[:, F.PACKETS] = np.minimum(pkts, _U32_MAX).astype(np.uint32)
    out[:, F.BYTES] = np.minimum(byts, _U32_MAX).astype(np.uint32)
    ts = (r[:, F.TS_HI].astype(np.uint64) << np.uint64(32)) | r[
        :, F.TS_LO
    ].astype(np.uint64)
    tmax = np.maximum.reduceat(ts, starts)
    out[:, F.TS_LO] = (tmax & _U32_MAX).astype(np.uint32)
    out[:, F.TS_HI] = (tmax >> np.uint64(32)).astype(np.uint32)
    return out


def combine_records(records: np.ndarray) -> np.ndarray:
    """(N, NUM_FIELDS) -> (G, NUM_FIELDS) with identical descriptors merged.

    Dispatches to the C++ single-pass hash combiner (native/combine.cpp —
    releases the GIL, so it overlaps device transfers) and falls back to
    the numpy sort-based path when the native library is unavailable.
    """
    from retina_tpu.native import combine_native

    out = combine_native(records)
    if out is not None:
        return out
    return combine_records_numpy(records)


def combine_blocks(blocks: list[np.ndarray]) -> np.ndarray:
    """Combine a LIST of record blocks (a feed worker's flush) without
    concatenating them first — the concat alone costs a full row-copy
    pass at production quanta (~40% of the stage on a 1-core host).
    One single-threaded native pass on the calling thread, at every
    size: the feed pool's workers are the parallelism, each combining
    its own flush with the GIL released in the ctypes call. The output
    is identical to ``combine_records(np.concatenate(blocks))``, row
    order included (first appearance). Falls back to concat + combine
    when the native library is unavailable."""
    from retina_tpu.native import combine_native_blocks

    if len(blocks) == 1:
        return combine_records(blocks[0])
    out = combine_native_blocks(blocks)
    if out is not None:
        return out
    return combine_records(np.concatenate(blocks, axis=0))
