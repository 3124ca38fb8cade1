#!/usr/bin/env python3
"""Find, once, the highest rate a cell's agent holds: a ladder of rates
in one process after one boot.

    python3 benchmarks/ladder.py --workload <name> --seed <n> \
        [--rungs rate:ticks_per_s:block_rows,...] [--seconds 12]

Each rung offers load open loop as ``run.py`` does (warm-up, then
``--seconds``), and holds when nothing was lost or sampled, the overload
controller stayed NOMINAL with no transition, every window closed and
became visible, and the generator's own lateness stayed under one tick
at its 95th percentile. The ladder stops at the first rung that does not
hold. One JSON object per rung on standard output; the cell's rate (the
rung below the highest that held) is written into the traffic file by
hand, as a number. Not a benchmark run: it prints no result line.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rungs", required=True,
                    help="rate[:ticks_per_s[:block_rows]],... ; what is "
                         "left out is the traffic file's")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)

    import harness
    import measure
    import reference
    import traffic
    from agent import BenchFailure, device_identity

    bench_doc = harness.load_benchmark()
    cell, config = harness.load_cell(bench_doc, args.workload)
    device = device_identity()
    if device["platform"] != "tpu":
        print(f"not a TPU: {json.dumps(device)}", file=sys.stderr)
        return 2
    mix = traffic.load_mix(cell["traffic"])
    bench = harness.Bench(config, mix, args.seed, args.workload, False,
                          ["tpu_windows_closed", "tpu_windows_deferred",
                           "tpu_wire_rows_counter"], _T0)
    code = 0
    try:
        bench.setup(device)
        for spec in args.rungs.split(","):
            part = [int(x) for x in spec.split(":")]
            rate = part[0]
            rung_mix = dataclasses.replace(
                mix, rate_events_per_s=rate,
                ticks_per_s=part[1] if len(part) > 1 else mix.ticks_per_s,
                block_rows=part[2] if len(part) > 2 else mix.block_rows)
            load = bench.offer(rung_mix, args.seconds)
            scrape, dvars = bench.final_scrape()
            counted = int(scrape.total("adv_forward_count")
                          + scrape.total("adv_drop_count"))
            ov = dvars.get("overload", {})
            late = load.window_block_late_s
            fresh = load.freshness_s()
            trips = load.round_trips_s()
            bad = reference.health_nonzero(scrape)
            rung = {
                "rate": rate, "ticks_per_s": rung_mix.ticks_per_s,
                "block_rows": rung_mix.block_rows, "seconds": args.seconds,
                "offered_since_boot": load.offered_since_boot,
                "accepted": load.accepted_rows, "counted": counted,
                "unaccounted": load.offered_since_boot - counted,
                "overload_state": ov.get("state"),
                "overload_transitions": ov.get("transitions"),
                "health_nonzero": bad,
                "windows_closed": load.counter_delta("tpu_windows_closed"),
                "windows_deferred": load.counter_delta(
                    "tpu_windows_deferred"),
                "combine_ratio": load.total_rows / max(
                    load.counter_delta("tpu_wire_rows_counter"), 1),
                "settle_s": load.settle_s,
                "generator_late_p95_ms": measure.percentile(late, 95) * 1e3,
                "generator_late_max_ms": max(late) * 1e3,
                "freshness_p50_ms": measure.percentile(fresh, 50) * 1e3,
                "freshness_p95_ms": measure.percentile(fresh, 95) * 1e3,
                "scrape_p95_ms": measure.percentile(trips, 95) * 1e3,
                "host_cpu_us_per_event":
                    load.cpu_s * 1e6 / load.sched.window_rows,
                "cpu_cores_busy": load.cpu_s / args.seconds,
            }
            rung["held"] = (
                rung["unaccounted"] == 0
                and load.accepted_rows == load.offered_since_boot
                and not bad and ov.get("transitions") == 0
                and rung["generator_late_p95_ms"] <= 1e3 * load.sched.tick_s
                and all(f != float("inf") for f in fresh)
            )
            print(json.dumps(rung), flush=True)
            if not rung["held"]:
                break
    except BenchFailure as e:
        print(f"failed: {e}", file=sys.stderr, flush=True)
        code = 1
    finally:
        if bench.poller is not None:
            bench.poller.stop()
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
