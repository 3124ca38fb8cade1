#!/usr/bin/env python3
"""Drive the harness at the rehearsal size with the timed path broken
underneath, for ``test_benchmark.py``: everything ``run.py`` does after its
look for a chip, in a process of its own (counters of the program's
metrics registry live as long as the process).

    python3 benchmarks/tests/drive_fault.py <fault> <workload>

Prints the verdict as one JSON object. ``none`` is the sound run.
"""

from __future__ import annotations

import json
import os
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import traffic  # noqa: E402


def plant(fault: str, bench) -> None:
    eng, src = bench.agent.engine, bench.agent.source
    if fault == "state_unchanged":
        # A step that returns its state unchanged.
        eng.sharded.step = lambda st, *a, **k: (st, None)
    elif fault == "sketch_unchanged":
        # The step updates the counters and leaves every sketch and the
        # conntrack table as they were.
        import dataclasses

        import jax
        import jax.numpy as jnp

        step = eng.sharded.step
        counters = {"pod_forward", "pod_drop", "pod_tcpflags", "pod_dns",
                    "pod_retrans", "node_counters", "totals"}

        def skipped(st, *a, **k):
            old = {f.name: jax.tree.map(jnp.copy, getattr(st, f.name))
                   for f in dataclasses.fields(st)
                   if f.name not in counters}
            new, out = step(st, *a, **k)
            return dataclasses.replace(new, **old), out

        eng.sharded.step = skipped
    elif fault == "half_batch":
        # Half of every block left out, the rest reported as the whole.
        write = src.sink.write_records

        def half(records, plugin):
            write(records[: len(records) // 2], plugin)
            return len(records)

        src.sink.write_records = half
    elif fault == "answer_altered":
        # One counter altered where the answer is produced.
        snapshot = eng.snapshot

        def altered(*a, **k):
            snap = dict(snapshot(*a, **k))
            pf = snap["pod_forward"].copy()
            pf[1, 0, 0] += 1
            snap["pod_forward"] = pf
            return snap

        eng.snapshot = altered
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    fault, workload = sys.argv[1], sys.argv[2]
    doc = harness.load_benchmark()
    cell, config = harness.load_cell(doc, workload)
    mix = traffic.load_mix(cell["traffic"], rehearse=True)
    bench = harness.Bench(config, mix, 11, f"fault-{fault}", True, [], T0)
    # Conntrack's accounting pass comes every 15 s: the runs that have
    # to show it wait for one.
    bench.settle_deadline_s = 20.0 if fault in (
        "none", "sketch_unchanged") else 4.0
    try:
        bench.setup({"platform": "cpu", "kind": "cpu", "count": 1})
        plant(fault, bench)
        load = bench.offer(mix, 3.0)
        scrape, dvars = bench.final_scrape()
        verdict = bench.judge(load, scrape, dvars, on_tpu=True)
    finally:
        if bench.poller is not None:
            bench.poller.stop()
    print(json.dumps({"correct": verdict.correct,
                      "failed": verdict.failed_names(),
                      "compared": verdict.as_dict()}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
