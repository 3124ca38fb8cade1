"""From a profiler trace (``.xplane.pb``) to device times.

Read with ``jax.profiler.ProfileData``, which needs nothing but JAX. A
TPU trace has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA
Modules`` holds one event per executed program (``jit_local_step(…)``)
and whose line ``XLA Ops`` holds one event per operation inside them.
Busy time is the union of the operation intervals; a program's device
time is the duration of its module events.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


_OP = re.compile(r"^(%[\w.\-]+) = (?:\()?(\w+\[[\d,]*\])?")


def op_name(event_name: str) -> str:
    """An operation's event carries its whole HLO line; keep its name
    and the shape of its result: ``%fusion.46 u32[524288]``."""
    m = _OP.match(event_name)
    if not m:
        return event_name[:80]
    return " ".join(g for g in m.groups() if g)


def program_name(event_name: str) -> str:
    """``jit_local_step(123456789)`` → ``jit_local_step``: the name the
    program was compiled under, without the fingerprint."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


@dataclasses.dataclass
class Chip:
    index: int
    modules: list[tuple[str, int, int]]  # (program, start_ns, duration_ns)
    ops: list[tuple[str, int, int]]

    def busy_ns(self) -> int:
        """Union of the intervals in which an operation ran."""
        spans = sorted((s, s + d) for _, s, d in (self.ops or self.modules))
        busy, end = 0, -1
        for a, b in spans:
            if b > end:
                busy += b - max(a, end)
                end = b
        return busy


@dataclasses.dataclass
class Trace:
    chips: list[Chip]
    window_s: float  # the traced span on the host's clock

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(c.busy_ns() for c in self.chips) / len(self.chips) / 1e9

    def program_ms(self, pattern: str) -> list[float]:
        """Device milliseconds of every execution of the programs whose
        name matches ``pattern``, over all chips."""
        rx = re.compile(pattern)
        return [d / 1e6 for c in self.chips for name, _, d in c.modules
                if rx.search(name)]

    def device_ops(self, top: int = 10) -> list[list]:
        """The operations that took most device time, summed by name."""
        total: dict[str, float] = {}
        for c in self.chips:
            for name, _, d in c.ops:
                total[name] = total.get(name, 0.0) + d / 1e9
        return [[n, s] for n, s in sorted(
            total.items(), key=lambda kv: -kv[1])[:top]]

    def programs(self) -> dict[str, dict]:
        out: dict[str, dict] = {}
        for c in self.chips:
            for name, _, d in c.modules:
                e = out.setdefault(name, {"executions": 0, "seconds": 0.0})
                e["executions"] += 1
                e["seconds"] += d / 1e9
        return out

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Idle seconds of the first chip, attributed to the program
        that ended each gap: what the device was waiting to be given.
        (The program writes no host spans into the profiler's trace
        yet, so a gap cannot be named by what the host was doing.)"""
        if not self.chips:
            return []
        mods = sorted(self.chips[0].modules, key=lambda m: m[1])
        total: dict[str, float] = {}
        end = None
        for name, s, d in mods:
            if end is not None and s > end:
                key = f"before {name}"
                total[key] = total.get(key, 0.0) + (s - end) / 1e9
            end = max(end or 0, s + d)
        return [[n, s] for n, s in sorted(
            total.items(), key=lambda kv: -kv[1])[:top]]


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce(path: str, window_s: float) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = [(program_name(e.name), int(e.start_ns),
                            int(e.duration_ns)) for e in line.events]
            elif line.name == OPS_LINE:
                ops = [(op_name(e.name), int(e.start_ns),
                        int(e.duration_ns)) for e in line.events]
        chips.append(Chip(int(m.group(1)), modules, ops))
    return Trace(sorted(chips, key=lambda c: c.index), window_s)
