#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the agent's place
with one of the configuration's guarantees broken. It has to come out
as not correct, or the comparison decides nothing.

The system runs no model and states no precision, so the control breaks
a guarantee the configuration states (``guarantees`` in its file):

``lost_block``  one block of the traffic's ``block_rows`` rows is dropped
                on the way and counted nowhere (every event accepted or
                counted lost: broken);
``sampled``     1 row in 8 is kept and counted eight times, which is what
                the agent's own overload sampler does in DEGRADED (pod
                counters exact: broken);
``sketch_unchanged``  the per-pod counters are sound, but the sketches and
                the conntrack table are as the boot left them: no
                heavy-hitter series, no distinct flows, no packets
                carried by conntrack reports (documented sketch
                accuracy and conntrack accounting: broken);
``sketch_half`` the sketches and conntrack see only the first half of
                every block, the counters all of it.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 \
        [--seconds 20] [--rehearse]

It needs no chip: the answer it forges is a ``/metrics`` body, compared
by the same code that compares the agent's. One JSON object per seed and
control on standard output. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

CONTROLS = ("lost_block", "sampled", "sketch_unchanged", "sketch_half")


def walked(pool: np.ndarray, total_rows: int) -> np.ndarray:
    """Index into the pool of every row offered, in order."""
    return np.arange(total_rows, dtype=np.int64) % len(pool)


def exposition(counts: reference.Counts, distinct: int,
               top: list[tuple], ct_packets: int) -> str:
    """A ``/metrics`` body that says what ``counts`` says."""
    p = reference.PREFIX
    fwd, drop = counts.pod_series()
    out = []
    for (pod, direction, lane), v in fwd.items():
        out.append(f'{p}adv_forward_{lane}{{podname="{pod}",'
                   f'direction="{direction}"}} {float(v)}')
    for (pod, reason, lane), v in drop.items():
        out.append(f'{p}adv_drop_{lane}{{podname="{pod}",'
                   f'reason="{reason}"}} {float(v)}')
    out.append(f"{p}sketch_distinct_flows {float(distinct)}")
    out.append(f'{p}conntrack_packets{{direction="total"}} '
               f"{float(ct_packets)}")
    for src, dst, sport, dport, proto in top:
        out.append(f'{p}sketch_heavy_hitter_flow_packets{{src_ip="{src}",'
                   f'dst_ip="{dst}",src_port="{sport}",dst_port="{dport}",'
                   f'protocol="{proto}"}} 1.0')
    return "\n".join(out) + "\n"


def forged_scrape(pool: np.ndarray, total_rows: int, n_endpoints: int,
                  block_rows: int, control: str | None,
                  rng: np.random.Generator) -> reference.Scrape:
    """What a scrape would show had the reference stood in the agent's
    place, sound (``control`` None) or with one guarantee broken."""
    idx = walked(pool, total_rows)
    counts = reference.Counts(n_endpoints)
    if control == "lost_block":
        at = int(rng.integers(0, max(1, total_rows - block_rows)))
        idx = np.concatenate([idx[:at], idx[at + block_rows:]])
    elif control == "sampled":
        idx = idx[rng.random(len(idx)) < 1.0 / 8.0]
    for a in range(0, len(idx), 1 << 21):
        counts.add(pool[idx[a:a + (1 << 21)]], 8 if control == "sampled"
                   else 1)
    if control == "sketch_unchanged":
        distinct, top, ct_packets = 0, [], 0
    elif control == "sketch_half":
        # The first half of every block, lap after lap: the rows the
        # sketches saw, as a pool of their own.
        half = np.concatenate([
            pool[a:a + block_rows // 2]
            for a in range(0, len(pool), block_rows)])
        distinct, top = reference.flows(half, total_rows // 2, n_endpoints,
                                        50)
        ct_packets = counts.events // 2
    else:
        distinct, top = reference.flows(pool, total_rows, n_endpoints, 50)
        ct_packets = counts.events
    return reference.Scrape(exposition(counts, distinct, top, ct_packets))


def read(pool: np.ndarray, total_rows: int, mix: traffic.Mix, held: dict,
         control: str | None, seed: int) -> reference.Verdict:
    v = reference.Verdict()
    scrape = forged_scrape(pool, total_rows, mix.n_endpoints, mix.block_rows,
                           control, np.random.default_rng(seed))
    reference.compare(scrape, pool, total_rows, mix.n_endpoints, held, v)
    return v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config = harness.load_cell(bench, args.workload)
    mix = traffic.load_mix(cell["traffic"], args.rehearse)
    seconds = args.seconds or bench["run_seconds"]
    sched = traffic.Schedule.of(mix, config["agent"]["window_seconds"],
                                seconds)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = traffic.make_pool(mix, seed)
        for control in (None,) + CONTROLS:
            v = read(pool, sched.total_rows, mix, config["held"], control,
                     seed)
            print(json.dumps({"seed": seed, "control": control,
                              "correct": v.correct,
                              "failed": v.failed_names(),
                              "compared": v.as_dict()}), flush=True)
            ok &= v.correct == (control is None)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
