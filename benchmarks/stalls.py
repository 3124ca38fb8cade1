"""What the stall readers share: the program's own record of every
stall of seconds (``retina_tpu/runtime/supervisor.py``).

The watchdog's scan, which wakes twice a second, writes a ``stall``
span into the flight recorder once a stall is over: cause ``paused``
(the scan woke late and the process had burnt hardly any CPU: nobody
ran) or ``held`` (it woke late and somebody had run: ``top_thread``
names who), both the whole process's, or ``thread`` (one thread
mid-work and silent for over a second, with its ``kind`` where it is the
device proxy). A reader takes the stalls that overlap the measured
window, whichever end lies outside it, and reports the longest of its
causes: 0.0 on a run without one, which is a reading (the program
looked and found nothing), and nothing where the program has no such
stage (a parent commit).

Once a traced run, the first reader asked logs one ``stalls`` line on
standard error, beside ``step_scopes``: every stall of the window with
its arguments and the closed spans that straddle it (open for nine
tenths of it or more: what every thread was in the middle of; a
thread's own stall begins at its beat, a hair before the span of the
call it is stuck in opens).
"""

import host_spans

STAGE = "stall"
PROCESS = ("paused", "held")
THREAD = ("thread",)
STRADDLE_SHARE = 0.9


def has_stage() -> bool:
    try:
        from retina_tpu.utils import metric_names
    except Exception:  # noqa: BLE001 — no program: nothing to read
        return False
    return STAGE in getattr(metric_names, "STAGES", ())


def _spans() -> list[dict]:
    try:
        from retina_tpu.obs.recorder import get_recorder

        return get_recorder().spans()
    except Exception:  # noqa: BLE001 — no recorder: nothing to read
        return []


def overlapping(spans: list[dict], t_open: float,
                t_close: float) -> list[dict]:
    """The ``stall`` spans any part of which lies in [t_open, t_close)."""
    return [s for s in spans if s.get("stage") == STAGE
            and s["t0"] < t_close and s["t1"] > t_open]


def straddling(spans: list[dict], stall: dict) -> list[dict]:
    return [{"stage": s["stage"], "thread": s.get("thread"),
             "began_before_s": round(stall["t0"] - s["t0"], 4),
             "ended_after_s": round(s["t1"] - stall["t1"], 4),
             "args": s.get("args", {})}
            for s in spans if s is not stall
            and min(s["t1"], stall["t1"]) - max(s["t0"], stall["t0"])
            >= STRADDLE_SHARE * (stall["t1"] - stall["t0"])]


def window_stalls(run) -> list[dict] | None:
    """The window's stalls, oldest first; None where the program has
    no ``stall`` stage. Logs the ``stalls`` line once a run."""
    if not has_stage():
        return None
    spans = _spans()
    found = overlapping(spans, run.t_open, run.t_close)
    if not getattr(run, "stalls_logged", False):
        run.stalls_logged = True  # on the run: an id may be used again
        host_spans.log(phase="stalls", count=len(found), stalls=[
            {"at_s": round(s["t0"] - run.t_open, 3),
             "thread_of_record": s.get("thread"), **s.get("args", {}),
             "straddling": straddling(spans, s)} for s in found])
    return found


def longest_ms(run, causes: tuple[str, ...]) -> float | None:
    found = window_stalls(run)
    if found is None:
        return None
    return 1e3 * max((s["t1"] - s["t0"] for s in found
                      if s.get("args", {}).get("cause") in causes),
                     default=0.0)
