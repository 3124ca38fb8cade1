"""Host-side event partitioning across mesh devices.

Connection-consistent sharding: both directions of a connection must land
on the same device, or per-device conntrack tables (ops/conntrack.py) would
see half-connections and double-report. The partition key is therefore the
same canonical (sorted-endpoint) key conntrack uses — mirroring how the
reference's kernel conntrack keys the 5-tuple after reverse-key lookup
(conntrack.c ct_process_packet :344).

This is the numpy mirror of ops/hashing.py (host batcher must not touch
the device), plus the bucketing that turns one (N, F) host batch into a
(D, B, F) sharded batch with per-device validity counts and drop accounting
(the reference never blocks, it counts losses — packetparser_linux.go:692-697).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from retina_tpu.events.schema import F, NUM_FIELDS

_PHI32 = np.uint32(0x9E3779B9)


def fmix32_np(x: np.ndarray) -> np.ndarray:
    """Host mirror of ops.hashing.fmix32 (must stay bit-identical)."""
    x = x.astype(np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def hash_cols_np(cols: list[np.ndarray], seed: int) -> np.ndarray:
    """Host mirror of ops.hashing.hash_cols."""
    h0 = (int(seed) * 0x9E3779B9) & 0xFFFFFFFF
    h = np.full(cols[0].shape, h0, np.uint32)
    for c in cols:
        c = c.astype(np.uint32)
        h = fmix32_np(h ^ (c + _PHI32 + (h << np.uint32(6)) + (h >> np.uint32(2))))
    return h


def canonical_conn_hash(records: np.ndarray, seed: int = 0x5A) -> np.ndarray:
    """(N, F) records -> (N,) direction-independent connection hashes."""
    src, dst = records[:, F.SRC_IP], records[:, F.DST_IP]
    ports = records[:, F.PORTS]
    proto = records[:, F.META] >> np.uint32(24)
    sp, dp = ports >> np.uint32(16), ports & np.uint32(0xFFFF)
    fwd = (src < dst) | ((src == dst) & (sp <= dp))
    a_ip = np.where(fwd, src, dst).astype(np.uint32)
    b_ip = np.where(fwd, dst, src).astype(np.uint32)
    a_pt = np.where(fwd, sp, dp).astype(np.uint32)
    b_pt = np.where(fwd, dp, sp).astype(np.uint32)
    return hash_cols_np([a_ip, b_ip, (a_pt << np.uint32(16)) | b_pt, proto], seed)


@dataclasses.dataclass
class ShardedBatch:
    """One host batch split across D devices."""

    records: np.ndarray  # (D, B, NUM_FIELDS) uint32
    n_valid: np.ndarray  # (D,) uint32
    lost: int  # EVENTS dropped because a shard overflowed (sum of the
    # dropped rows' F.PACKETS weights — a combined row stands for many
    # events, parallel/combine.py)
    events: int = 0  # EVENTS the kept rows stand for (same packet
    # weighting as ``lost``) — what to count if this batch is dropped
    # downstream instead of reaching the device
    sample_k: int = 1  # overload 1-in-k applied before partitioning
    # (runtime/overload.py): the device step rescales non-exempt rows
    # by this factor so packet-weighted estimates stay unbiased; 1 =
    # unsampled


def _next_bucket(n: int) -> int:
    """Smallest m * 2^k >= n with mantissa m in {4,6}: transfer shapes
    quantize to within 50% of the payload (vs up to 100% for pure
    powers of two) while keeping the distinct-shape count — and thus
    the engine's per-shape ingest jits — small. Two shapes per octave
    (was four, mantissa {4,5,6,7}): each grid key costs seconds of
    trace+lower on the device-proxy thread at boot warm, and halving
    the grid halved that for a bounded ~17% average padding cost on a
    wire that is already <0.5 B/event."""
    if n <= 4:
        return max(n, 1)
    k = (n - 1).bit_length() - 3  # so that 4*2^k <= n-1 < 8*2^k
    step = 1 << (k + 1)  # multiples of 2^(k+1): mantissa 4 or 6
    return ((n + step - 1) // step) * step


def _bucket_for(n_max: int, capacity: int, min_bucket: int | None) -> int:
    """Minor batch dim for ``n_max`` rows on the fullest device: the
    full capacity without ``min_bucket``, else the smallest bucket that
    holds them, at least ``min_bucket`` and at most ``capacity``."""
    if min_bucket is None:
        return capacity
    return min(_next_bucket(max(n_max, min_bucket)), capacity)


def partition_events(
    records: np.ndarray,
    n_devices: int,
    capacity: int,
    min_bucket: int | None = None,
) -> ShardedBatch:
    """Split (N, F) valid records into a (D, B', F) sharded batch.

    Overflowing rows are dropped and counted, never blocked on (the
    reference's universal backpressure rule, SURVEY.md §3.2).

    ``min_bucket=None`` emits the full (D, capacity, F) shape. With an
    integer, the minor batch dim B' is the smallest bucket (see
    ``_next_bucket``) >= max(shard fill, min_bucket), capped at capacity —
    so a lightly-filled batch crosses the host->device link at its own
    size and is padded to the step's static (D, capacity, F) shape ON
    DEVICE (engine ingest jit), where HBM bandwidth makes the padding
    free. Quantized buckets keep the number of distinct transfer shapes
    (and ingest-kernel compiles) logarithmic.

    ALIASING CONTRACT: for ``n_devices == 1`` with a bucket-full
    contiguous batch, ``records`` is returned as a zero-copy VIEW —
    consume the ShardedBatch (e.g. ``jax.device_put``, as the engine
    does) before reusing the input buffer. Multi-device output is always
    a fresh array.

    Hashing and loss weighting use schema columns only; trailing
    columns beyond NUM_FIELDS (none in-tree today) would ride along
    untouched.
    """
    assert records.ndim == 2 and records.shape[1] >= NUM_FIELDS
    width = records.shape[1]

    def bucket_for(n_max: int) -> int:
        return _bucket_for(n_max, capacity, min_bucket)

    if n_devices == 1:
        # Fast path: one shard takes everything — no connection hashing,
        # and a full batch is a zero-copy reshape (the hash pass cost
        # ~22 ms per 131k-event batch, dominating the host feed loop).
        n = min(len(records), capacity)
        lost = int(records[n:, F.PACKETS].astype(np.uint64).sum())
        kept = int(records[:n, F.PACKETS].astype(np.uint64).sum())
        b = bucket_for(n)
        if n == b:
            out = np.ascontiguousarray(records[:n], np.uint32)
            out = out.reshape(1, b, width)
        else:
            out = np.zeros((1, b, width), np.uint32)
            out[0, :n] = records[:n]
        return ShardedBatch(records=out, n_valid=np.array([n], np.uint32),
                            lost=lost, events=kept)
    n_valid = np.zeros((n_devices,), np.uint32)
    lost = 0
    kept = 0
    if len(records):
        dev = canonical_conn_hash(records) % np.uint32(n_devices)
        counts = np.bincount(dev, minlength=n_devices)
        b = bucket_for(int(min(counts.max(), capacity)))
        out = np.zeros((n_devices, b, width), np.uint32)
        total = int(records[:, F.PACKETS].astype(np.uint64).sum())
        for d in range(n_devices):
            rows = records[dev == d]
            n = min(len(rows), capacity)
            out[d, :n] = rows[:n]
            n_valid[d] = n
            lost += int(rows[n:, F.PACKETS].astype(np.uint64).sum())
        kept = total - lost
    else:
        out = np.zeros((n_devices, bucket_for(0), width), np.uint32)
    return ShardedBatch(records=out, n_valid=n_valid, lost=lost, events=kept)


def fold_batches(
    batches: list[ShardedBatch],
    capacity: int,
    min_bucket: int | None = None,
) -> tuple[ShardedBatch, int]:
    """Fold the longest prefix of ``batches`` that fits one transfer
    (``capacity`` rows a device, one sampling factor) into one
    ShardedBatch: per device, the valid rows of each batch in order.
    Returns the folded batch and how many batches it took (at least
    one: a single batch is handed back as it is).

    Rows are not combined again: a key that two batches carry crosses
    as two rows, which every consumer of a batch already sums (an
    uncombined feed hands them the same)."""
    first = batches[0]
    n_valid = first.n_valid.astype(np.int64)
    took = 1
    for sb in batches[1:]:
        if sb.sample_k != first.sample_k:
            break
        total = n_valid + sb.n_valid
        if int(total.max()) > capacity:
            break
        n_valid = total
        took += 1
    if took == 1:
        return first, 1
    n_devices, _, width = first.records.shape
    bucket = _bucket_for(int(n_valid.max()), capacity, min_bucket)
    out = np.zeros((n_devices, bucket, width), np.uint32)
    at = np.zeros((n_devices,), np.int64)
    for sb in batches[:took]:
        for d in range(n_devices):
            n = int(sb.n_valid[d])
            out[d, at[d]:at[d] + n] = sb.records[d, :n]
            at[d] += n
    return ShardedBatch(
        records=out,
        n_valid=n_valid.astype(np.uint32),
        lost=sum(sb.lost for sb in batches[:took]),
        events=sum(sb.events for sb in batches[:took]),
        sample_k=first.sample_k,
    ), took
