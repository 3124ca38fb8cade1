"""One combine pass per flush + sharded-feed algebra.

A flush is combined by ONE single-threaded native pass on the thread
that flushes it (a feed worker), at every size: the feed pool's workers
are the parallelism. Contract: the key -> (packets, bytes, latest-ts)
map equals the plain numpy combine, each key once, and no thread is
started for it. (Until PR 39 flushes of 65,536 rows or more went to
three extra stripe threads that each scanned every row: ≈ 4.5 times the
CPU of the one pass at 65,536 rows, slower in wall time too.)

The mesh-sharding half checks the algebra the multi-chip feed rests on
("Sketchy With a Chance of Adoption": mergeability makes per-device
shards + one associative merge exact): hash-partitioned per-shard
combines union to exactly the unsharded combine.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from retina_tpu.events.schema import F
from retina_tpu.events.synthetic import TrafficGen
from retina_tpu.parallel.combine import (
    KEY_COLS,
    combine_blocks,
    combine_records,
    combine_records_numpy,
)

native = pytest.importorskip("retina_tpu.native")

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native toolchain unavailable"
)


def _as_map(arr: np.ndarray) -> dict:
    return {
        tuple(int(x) for x in r[list(KEY_COLS)]): (
            int(r[F.PACKETS]),
            int(r[F.BYTES]),
            (int(r[F.TS_HI]) << 32) | int(r[F.TS_LO]),
        )
        for r in arr
    }


def _folded(arr: np.ndarray) -> dict:
    """The key map of a combine that may leave a key in several rows:
    the numpy reference splits a group where two descriptors' 32-bit
    hashes collide and interleave (combine_records_numpy), which a
    million rows of 70,000 flows meet."""
    out: dict = {}
    for key, (p, b, ts) in (
        (tuple(int(x) for x in r[list(KEY_COLS)]),
         (int(r[F.PACKETS]), int(r[F.BYTES]),
          (int(r[F.TS_HI]) << 32) | int(r[F.TS_LO])))
        for r in arr
    ):
        p0, b0, ts0 = out.get(key, (0, 0, 0))
        out[key] = (p0 + p, b0 + b, max(ts0, ts))
    return out


# Block lists shaped like the cells' flushes: a ring's hand-over on one
# chip (16,384) and on four (65,536), one block a second (262,144), six
# hand-overs held into one flush, a saturated feed's quantum (1 << 20),
# and an empty block beside a full one.
FLUSHES = {
    "one-16384": [16384],
    "one-65536": [65536],
    "one-262144": [262144],
    "six-16384": [16384] * 6,
    "one-1048576": [1 << 20],
    "empty-beside-full": [0, 65536],
}


@pytest.mark.parametrize("sizes", FLUSHES.values(), ids=FLUSHES.keys())
def test_one_pass_on_the_calling_thread_equals_numpy(sizes, monkeypatch):
    gen = TrafficGen(n_flows=50_000, n_pods=64, seed=39 + len(sizes))
    blocks = [gen.batch(max(n, 1))[:n] for n in sizes]
    ref = _folded(combine_records_numpy(np.concatenate(blocks)))

    def no_thread(self):
        raise AssertionError(f"combine_blocks started thread {self.name}")

    monkeypatch.setattr(threading.Thread, "start", no_thread)
    out = combine_blocks(blocks)
    monkeypatch.undo()
    got = _as_map(out)
    assert got == ref
    assert len(out) == len(got)  # each key exactly once


def test_mesh_shard_sums_equal_unsharded_combine():
    """Per-device feed shards, combined independently, must union to
    EXACTLY the unsharded combine: hash partitioning is key-consistent
    (identical descriptors land on one shard), so the per-shard maps
    are disjoint and their union — the one associative merge at window
    close — loses nothing and double-counts nothing."""
    from retina_tpu.parallel.partition import partition_events

    rec = TrafficGen(n_flows=1500, n_pods=64, seed=51).batch(1 << 15)
    full = _as_map(combine_records(rec))
    n_dev = 4
    sb = partition_events(rec, n_dev, capacity=len(rec), min_bucket=64)
    assert sb.lost == 0
    union: dict = {}
    for d in range(n_dev):
        shard = combine_records(
            np.ascontiguousarray(sb.records[d, : int(sb.n_valid[d])])
        )
        m = _as_map(shard)
        assert not (set(m) & set(union)), "shards share a descriptor"
        union.update(m)
    assert union == full
    # The scalar sums the device merge reduces over agree too.
    tot = np.concatenate(
        [sb.records[d, : int(sb.n_valid[d])] for d in range(n_dev)]
    )
    assert (
        tot[:, F.PACKETS].astype(np.uint64).sum()
        == rec[:, F.PACKETS].astype(np.uint64).sum()
    )
    assert (
        tot[:, F.BYTES].astype(np.uint64).sum()
        == rec[:, F.BYTES].astype(np.uint64).sum()
    )
