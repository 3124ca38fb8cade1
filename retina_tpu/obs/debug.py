"""Debug endpoints over the flight recorder.

- ``GET /debug/trace?last=N`` — dump the recorder's retained spans as
  Chrome trace-event JSON (load the body straight into Perfetto or
  chrome://tracing). ``&epoch=E`` keeps the spans of one window epoch
  (the trace id) and adds that epoch's ``stageReport``.
- ``POST /debug/profile?seconds=S`` — on-demand deep profiling: one
  single-flight ``jax.profiler`` trace session plus an all-thread
  Python stack dump, written to a bounded artifact directory. Safe
  under load the same way the timetravel query service is: a session
  already in flight answers 503 busy, a cooldown bounds back-to-back
  sessions, and overload SHEDDING (and above) refuses new sessions
  outright — deep profiling is the first diagnostic to shed.

Both ride the agent HTTP server (`server.py`); `attach()` registers
the routes. Runbook: docs/observability.md.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time
import traceback
from typing import Any

from retina_tpu.log import logger
from retina_tpu.obs.recorder import FlightRecorder, get_recorder
from retina_tpu.runtime.overload import SHEDDING

_JSON = "application/json"


def _reply(code: int, doc: dict) -> tuple[int, bytes, str]:
    return code, json.dumps(doc, default=str).encode(), _JSON


def thread_stacks() -> dict[str, list[str]]:
    """Formatted stacks of every live Python thread."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: dict[str, list[str]] = {}
    for ident, frame in sys._current_frames().items():
        name = names.get(ident, f"tid-{ident}")
        out[name] = traceback.format_stack(frame)
    return out


class DebugObservability:
    """One per daemon/bench process; owns the profile artifact dir."""

    def __init__(
        self,
        cfg,
        recorder: FlightRecorder | None = None,
        overload=None,  # OverloadController (state read only)
    ) -> None:
        self.cfg = cfg
        self.log = logger("obs.debug")
        self.recorder = recorder or get_recorder()
        self._overload = overload
        self._flight = threading.Lock()
        self._last_done = 0.0  # monotonic end of the last session
        self.sessions = 0

    # -- wiring --------------------------------------------------------
    def attach(self, server) -> None:
        server.register_route("/debug/trace", self.handle_trace)
        server.register_post_route("/debug/profile", self.handle_profile)
        server.expose_var("obs", self.recorder.stats)

    # -- GET /debug/trace (handler threads) ----------------------------
    def handle_trace(self, q: dict) -> tuple[int, bytes, str]:
        try:
            last = None
            if "last" in q:
                last = max(0, int(q["last"][0]))
            epoch = int(q["epoch"][0]) if "epoch" in q else None
        except (ValueError, IndexError):
            return _reply(
                400, {"error": "last and epoch must be integers"}
            )
        doc = self.recorder.chrome_trace(last, trace_id=epoch)
        if epoch is not None:
            doc["stageReport"] = self.recorder.stage_report(
                last, trace_id=epoch
            )
        return 200, json.dumps(doc, default=str).encode(), _JSON

    # -- POST /debug/profile (handler threads; single-flight) ----------
    def handle_profile(self, q: dict) -> tuple[int, bytes, str]:
        try:
            seconds = float(q.get("seconds", ["2"])[0])
        except (ValueError, IndexError):
            return _reply(400, {"error": "seconds must be a number"})
        seconds = min(max(seconds, 0.1),
                      float(self.cfg.profile_max_seconds))
        ov = self._overload
        if ov is not None and ov.state >= SHEDDING:
            # The agent is already shedding enrichment work to protect
            # the datapath; a profiler session would add host load at
            # the worst moment.
            return _reply(503, {"error": "shedding", "retry": True})
        cooldown = float(self.cfg.profile_cooldown_s)
        since = time.monotonic() - self._last_done
        if self._last_done and since < cooldown:
            return _reply(503, {
                "error": "cooldown",
                "retry_after_s": round(cooldown - since, 1),
            })
        if not self._flight.acquire(blocking=False):
            return _reply(503, {"error": "busy", "retry": True})
        try:
            doc = self._run_session(seconds)
            return _reply(200, doc)
        except Exception as e:
            self.log.exception("profile session failed")
            return _reply(500, {"error": f"{type(e).__name__}: {e}"})
        finally:
            self._last_done = time.monotonic()
            self._flight.release()

    def _run_session(self, seconds: float) -> dict[str, Any]:
        outdir = os.path.join(
            self.cfg.profile_artifact_dir,
            f"profile-{int(time.time())}-{os.getpid()}",
        )
        os.makedirs(outdir, exist_ok=True)
        jax_ok = True
        try:
            import jax

            from retina_tpu.parallel.telemetry import write_op_scopes

            jax.profiler.start_trace(outdir)
            time.sleep(seconds)
            jax.profiler.stop_trace()
            # Device events carry no operator scope: the map from each
            # program's instructions to scopes goes beside the trace.
            write_op_scopes(os.path.join(outdir, "op_scopes.json"))
        except Exception as e:
            # The stack dump below still lands: a host-side hang is
            # diagnosable even when the device profiler is unavailable.
            jax_ok = False
            self.log.warning("jax.profiler session failed: %s: %s",
                             type(e).__name__, e)
        stacks = thread_stacks()
        with open(os.path.join(outdir, "threads.txt"), "w") as fh:
            for name, frames in sorted(stacks.items()):
                fh.write(f"=== {name} ===\n")
                fh.writelines(frames)
                fh.write("\n")
        self._prune_artifacts()
        self.sessions += 1
        return {
            "artifact_dir": outdir,
            "seconds": seconds,
            "jax_trace": jax_ok,
            "threads": sorted(stacks),
        }

    def _prune_artifacts(self) -> None:
        """Bound the artifact dir: keep the newest
        ``profile_max_artifacts`` session dirs, delete the rest."""
        root = self.cfg.profile_artifact_dir
        keep = max(1, int(self.cfg.profile_max_artifacts))
        try:
            entries = sorted(
                e for e in os.listdir(root) if e.startswith("profile-")
            )
        except OSError:
            return
        for stale in entries[:-keep]:
            shutil.rmtree(os.path.join(root, stale), ignore_errors=True)
