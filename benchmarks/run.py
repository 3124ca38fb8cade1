#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process, which owns the chip. Device check first: on any platform
but ``tpu``, or with fewer chips than the cell asks for, it exits
nonzero in seconds and prints no result. Then: the cell's configuration
from its file; the agent booted through its normal entry (``Daemon``)
with the benchmark's seeded source where packetparser stands; endpoints
registered; the event pool made from ``--seed``; the whole background
warm waited for; load offered open loop for a warm-up and then for
``--seconds``; the agent settled; scraped; compared with the plain
reference. The last line of standard output is the contract's JSON
object; each number compared is on standard error beside its limit.

``--rate`` overrides the traffic file's rate (the ladder uses it).
``--rehearse`` runs tiny sizes off the chip; it always ends
``correct: false`` with a nonzero exit, because the platform is not a
TPU.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # process start, as near as Python can see it

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

# The driver's 360 s is for a run whose programs are cached; a first run
# in a checkout compiles and may take 1200 s.
WATCHDOG_S = 1150.0


def result_line(correct, attempted, failed, metrics, device, breakdown,
                compared) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = compared  # last, as the record keeps the line's end
    return json.dumps(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=int, default=None,
                    help="offered events/s instead of the traffic file's")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes off the chip; ends correct: false")
    args = ap.parse_args(argv)

    import harness
    from agent import BenchFailure, device_identity

    bench_doc = harness.load_benchmark()
    cell, config = harness.load_cell(bench_doc, args.workload)

    # The device check comes first: off the chip nothing is generated,
    # compiled or printed as a result.
    try:
        device = device_identity()
    except Exception as e:  # noqa: BLE001 — no backend at all
        print(f"no device: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"not a TPU: {json.dumps(device)}; no result",
              file=sys.stderr)
        return 2
    if on_tpu and device["count"] < cell["chips"]:
        print(f"cell needs {cell['chips']} chips, found "
              f"{json.dumps(device)}; no result", file=sys.stderr)
        return 2
    device["count"] = min(device["count"], cell["chips"])

    import traffic

    mix = traffic.load_mix(cell["traffic"], args.rehearse, args.rate)
    e2e = harness.metrics_of(bench_doc, "end_to_end", args.workload)
    layers = harness.metrics_of(bench_doc, "per_layer", args.workload)
    readers = {m["name"]: harness.load_reader(m["name"]) for m in layers}
    counters = sorted({c for r in readers.values()
                       for c in getattr(r, "COUNTERS", ())})

    bench = harness.Bench(config, mix, args.seed, args.workload,
                          args.rehearse, counters, _T0)

    def watchdog() -> None:
        print(f"not finished after {WATCHDOG_S:.0f}s; giving up",
              file=sys.stderr, flush=True)
        if bench.poller is not None:
            bench.poller.proc.kill()
        os._exit(3)

    timer = threading.Timer(WATCHDOG_S, watchdog)
    timer.daemon = True
    timer.start()
    try:
        harness.log(phase="start", workload=args.workload, seed=args.seed,
                    seconds=args.seconds, trace=args.trace, device=device,
                    rate=mix.rate_events_per_s, rehearse=args.rehearse)
        bench.setup(device)
        load = bench.offer(mix, args.seconds, trace=bool(args.trace))
        setup_s = load.t_open - _T0
        scrape, dvars = bench.final_scrape()
        peak = bench.memory_peak_bytes()
        bench.close()  # the agent's state is freed before the reference
        t_ref = time.monotonic()
        verdict = bench.judge(load, scrape, dvars, on_tpu)
        ref_s = time.monotonic() - t_ref
    except BenchFailure as e:
        print(f"failed: {e}", file=sys.stderr, flush=True)
        _kill_poller(bench)
        return 1
    except BaseException:
        _kill_poller(bench)
        raise
    finally:
        timer.cancel()

    device["memory_peak_bytes"] = peak
    if args.trace:
        values = {name: r.read(load) for name, r in readers.items()}
        units = {m["name"]: m["unit"] for m in layers}
        device["busy_s"] = load.trace.busy_s
        device["window_s"] = load.trace.window_s
        breakdown = {"device_ops": load.trace.device_ops(10),
                     "idle_gaps": load.trace.idle_gaps(10)}
    else:
        values = load.end_to_end(setup_s)
        values = {m["name"]: values[m["name"]] for m in e2e}
        units = {m["name"]: m["unit"] for m in e2e}
        breakdown = None
    # A reader that found nothing to read is left out of the line.
    metrics = {n: {"value": v, "unit": units[n]}
               for n, v in values.items()
               if v is not None and math.isfinite(v)}
    counted = verdict.notes["scraped_events"]
    failed = max(0, load.offered_since_boot - min(
        counted, load.accepted_rows))
    stale, trips = load.staleness_s(), load.round_trips_s()
    fresh = load.freshness_s()
    harness.log(
        phase="done", setup_s=round(setup_s, 2),
        settle_s=round(load.settle_s, 2), reference_s=round(ref_s, 2),
        total_s=round(time.monotonic() - _T0, 2),
        scrapes_in_window=len(trips),
        staleness_p50_ms=_pct(stale, 50),
        staleness_mean_ms=round(1e3 * sum(stale) / max(len(stale), 1), 1),
        staleness_p90_ms=_pct(stale, 90),
        scrape_p50_ms=_pct(trips, 50),
        scrape_p90_ms=_pct(trips, 90),
        tick_freshness_p50_ms=_pct(fresh, 50),
        tick_freshness_p95_ms=_pct(fresh, 95),
        cpu_cores_busy=round(load.cpu_s / args.seconds, 3),
        ticks=load.sched.window_ticks, notes=verdict.notes,
        programs=load.trace.programs() if load.trace else None)
    for name, value, op, limit in verdict.rows:
        print(f"compared {name} = {value} (must be {op} {limit})",
              file=sys.stderr)
    sys.stderr.flush()
    print(result_line(verdict.correct, load.offered_since_boot, failed,
                      metrics, device, breakdown, verdict.as_dict()),
          flush=True)
    return 0 if verdict.correct else 1


def _pct(values, q):
    import measure

    return round(measure.percentile(values, q) * 1e3, 1) if values else None


def _kill_poller(bench) -> None:
    if bench.poller is not None and bench.poller.proc.poll() is None:
        bench.poller.proc.kill()
        bench.poller.proc.wait()


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Skip interpreter teardown: daemon threads (device proxy, HTTP
    # server, watchers) may still sit inside runtime calls, and the exit
    # code must say what the checks said.
    os._exit(code)
