"""Host-side flow-descriptor dictionary of the wire format.

The combiner (parallel/combine.py) already collapses a flush quantum to
its distinct flow descriptors — but across quanta the SAME descriptors
recur (flows are long-lived; the reference's kernel maps bank on exactly
that). This dictionary closes the loop: every distinct descriptor gets a
stable id once, the descriptor's 12 packed lanes cross the host->device
link once (a "new" row), and every later occurrence crosses as a
dense ``id | packets | bytes`` bit-row (parallel/wire.py dense layer)
against the device-resident descriptor table (engine ingest gathers the
lanes back in HBM, where the bandwidth is ~3 orders of magnitude above
the link). Packet or byte counts beyond their narrow lanes escalate to
a full-row re-upload (idempotent), keeping exact counters exact.

Reference analog: the eBPF map key set — pkg/plugin/conntrack and
packetforward keep per-flow keys resident kernel-side and move only
counters per read interval. Here the "map" spans the host/device link.

Capacity contract: ids are slots in the device table. When the table
fills, the dictionary CLEARS and bumps its generation — every flow is
"new" again and re-uploads its descriptor (a one-quantum burst, not an
error; the engine counts it in ``tpu_flow_dict_clears_counter``, and
the rows a full table had no slot for as
``tpu_wire_rows_counter{kind="tableless"}``). The engine never
references an id the current generation did not assign, so the device
table needs no generation tag: slots are always rewritten by a new-row
upload before a known-row references them (proxy FIFO order).
"""

from __future__ import annotations

import numpy as np

from retina_tpu.parallel.combine import KEY_COLS

_KEY_COLS = np.asarray(KEY_COLS, np.int64)


class HostFlowDict:
    """descriptor bytes -> stable device-table slot id."""

    def __init__(self, capacity: int = 1 << 20):
        self.capacity = int(capacity)
        self.generation = 0
        self._ids: dict[bytes, int] = {}

    def __len__(self) -> int:
        return len(self._ids)

    def clear(self) -> None:
        self._ids.clear()
        self.generation += 1

    def lookup_or_assign(
        self, records: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """(N, >=16) records -> (ids (N,) u32, is_new (N,) bool).

        Assigns fresh ids to unseen descriptors in row order. If the
        batch would overflow capacity, the dictionary clears first
        (generation bump) and every row in this batch is "new"; a batch
        with more distinct descriptors than capacity gets ids only for
        the first ``capacity`` rows — the rest return id 0 with
        ``is_new`` True, which the engine ships as plain full rows that
        never enter the table (id slot 0 is sacrificed for this
        sentinel; the dictionary never assigns it).
        """
        n = len(records)
        ids = np.zeros(n, np.uint32)
        is_new = np.zeros(n, bool)
        if n == 0:
            return ids, is_new
        descs = np.ascontiguousarray(
            records[:, _KEY_COLS].astype(np.uint32, copy=False)
        )
        keys = descs.view(
            np.dtype((np.void, descs.shape[1] * 4))
        ).ravel()
        table = self._ids
        # Pessimistic overflow check: clearing mid-batch would violate
        # the "never reference an id this generation didn't assign"
        # contract for rows already marked known.
        if len(table) + n > self.capacity:
            fresh = set(keys.tolist()) - table.keys()
            if len(table) + len(fresh) > self.capacity:
                self.clear()
                table = self._ids
        next_id = len(table) + 1  # slot 0 reserved as overflow sentinel
        for i, k in enumerate(keys.tolist()):
            got = table.get(k)
            if got is None:
                is_new[i] = True
                if next_id < self.capacity:
                    table[k] = next_id
                    ids[i] = next_id
                    next_id += 1
                # else: id stays 0 — ships as a table-less full row
            else:
                ids[i] = got
        return ids, is_new


def flow_dict_stats(fd) -> dict:
    """Residency summary for debug vars / bench JSON. Duck-typed over
    both implementations (capacity / __len__ / generation); ``fd`` may
    be None when packed wire or the flow dict is disabled."""
    if fd is None:
        return {"enabled": False}
    return {
        "enabled": True,
        "entries": len(fd),
        "capacity": int(fd.capacity),
        "generation": int(fd.generation),
    }


def make_flow_dict(capacity: int):
    """Native (GIL-released single pass, native/flowdict.cpp) when the
    library is available, else the Python dict. Same contract either
    way — tests cross-check them on random batches."""
    try:
        from retina_tpu.native import NativeFlowDict

        return NativeFlowDict(capacity)
    except Exception:
        return HostFlowDict(capacity)
