"""Least work of the fused step, from the configuration's shapes.

The step folds one batch into the device-resident state. Whatever
implements it, the algorithm has to read the batch once and read and
write once every table the configuration's step updates; it has next to
no arithmetic (hashes, compares, adds on 32-bit integers), so memory
bandwidth bounds it. The tables and their shapes are the state's, listed
in the configuration's file under ``step_shapes``; all are 4-byte lanes.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
LANE = 4  # bytes: every table is uint32 or float32
RECORD_LANES = 16


def state_tables(s: dict, conntrack: bool) -> dict[str, int]:
    """Bytes of every state table the step touches."""
    p = s["n_pods"]
    hh = lambda key_cols: (  # Count-Min rows + candidate slots (keys, counts)
        s["cms_depth"] * s["cms_width"] + s["topk_slots"] * (key_cols + 1))
    lanes = {
        "pod_forward": p * 2 * 2,
        "pod_drop": p * s["n_drop_reasons"] * 2,
        "pod_tcpflags": p * 8,
        "pod_dns": p * s["n_dns_qtypes"] * 2,
        "pod_retrans": p,
        "node_counters_totals": 4 + 8 + 4,
        "flow_hh": hh(4), "svc_hh": hh(2), "dns_hh": hh(1),
        "hll_flows": 1 << s["hll_precision"],
        "hll_src_per_reason": s["n_drop_reasons"] << s["hll_precision"],
        "hll_src_per_pod": p << s["hll_pod_precision"],
        "entropy_anomaly": 3 * s["entropy_buckets"] + 9,
        "invertible_placeholders": 2 * (160 + 1),
        "latency": 2 * s["latency_slots"] + s["latency_buckets"],
    }
    if conntrack:
        lanes["conntrack"] = s["conntrack_slots"] * (2 + 4)
    return {k: v * LANE for k, v in lanes.items()}


def step_bytes(config: dict) -> int:
    """Least bytes one execution of the step moves: every touched table
    read and written once, the batch read once."""
    s = config["step_shapes"]
    tables = state_tables(
        s, bool(config["agent"].get("enable_conntrack_metrics", False)))
    batch = config["agent"]["batch_capacity"] * RECORD_LANES * LANE
    return 2 * sum(tables.values()) + batch


def peak(device_kind: str) -> dict:
    """The published peaks of the chip. A device that is not in the
    table is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"add it to benchmarks/peaks.json with its source")
    return table[device_kind]


def least_step_ms(config: dict, device_kind: str) -> float:
    return step_bytes(config) / peak(device_kind)["hbm_bytes_per_s"] * 1e3
