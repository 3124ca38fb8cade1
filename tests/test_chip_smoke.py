"""chip_smoke.py's own logic, on the CPU at the rehearsal size.

The script is the quickest proof that the agent still starts on the
chip; these tests are the proof that the script itself can fail: its
exact comparison sees an off-by-one, a degraded or lossy agent flips its
verdict, and off a TPU it ends with ``"ok": false`` and a nonzero exit
whatever else passed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from retina_tpu.events.schema import (  # noqa: E402
    DIR_EGRESS, DIR_INGRESS, F, NUM_FIELDS, VERDICT_DROPPED,
    VERDICT_FORWARDED,
)
from retina_tpu.events.synthetic import TrafficGen  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_verdict(monkeypatch):
    monkeypatch.setattr(chip_smoke, "FAILED", [])


def _event(src_pod, dst_pod, direction, verdict, reason=0, nbytes=100):
    rec = np.zeros((1, NUM_FIELDS), np.uint32)
    rec[0, F.SRC_IP] = chip_smoke.POD_NET + src_pod
    rec[0, F.DST_IP] = chip_smoke.POD_NET + dst_pod
    rec[0, F.PORTS] = (40000 << 16) | 80
    rec[0, F.META] = (6 << 24) | (direction << 4)
    rec[0, F.BYTES] = nbytes
    rec[0, F.PACKETS] = 1
    rec[0, F.VERDICT] = verdict
    rec[0, F.DROP_REASON] = reason
    return rec


def test_reference_counts_by_local_pod():
    """Three events by hand: ingress counts at the destination pod,
    egress at the source pod, a drop under its reason's name; an event
    between two unregistered addresses counts nowhere."""
    ref = chip_smoke.Reference(n_endpoints=8)
    ref.add(np.concatenate([
        _event(1, 2, DIR_INGRESS, VERDICT_FORWARDED, nbytes=100),
        _event(1, 2, DIR_EGRESS, VERDICT_FORWARDED, nbytes=60),
        _event(3, 4, DIR_INGRESS, VERDICT_DROPPED, reason=2, nbytes=70),
        _event(100, 200, DIR_INGRESS, VERDICT_FORWARDED),
    ]))
    fwd, drop = ref.pod_series()
    assert fwd == {
        ("pod-2", "ingress", "count"): 1, ("pod-2", "ingress", "bytes"): 100,
        ("pod-1", "egress", "count"): 1, ("pod-1", "egress", "bytes"): 60,
    }
    assert drop == {
        ("pod-4", "iptable_nat_drop", "count"): 1,
        ("pod-4", "iptable_nat_drop", "bytes"): 70,
    }
    assert ref.events == 3
    distinct, top = ref.flows(2)
    assert distinct == 2
    assert top[0] == ("10.0.0.1", "10.0.0.2", "40000", "80", "TCP")


def test_exact_comparison_catches_planted_off_by_one(capsys):
    ref = chip_smoke.Reference(n_endpoints=64)
    ref.add(TrafficGen(n_flows=500, n_pods=64, seed=3).batch(4096))
    want, _ = ref.pod_series()
    assert chip_smoke.compare_exact("same", dict(want), want)
    assert chip_smoke.FAILED == []
    off = dict(want)
    key = sorted(off)[len(off) // 2]
    off[key] += 1
    assert not chip_smoke.compare_exact("planted", off, want)
    assert chip_smoke.FAILED == ["planted"]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["mismatched"] == 1
    assert line["first_mismatches"][0]["key"] == list(key)
    # A series one side lacks is a mismatch too.
    del off[key]
    assert not chip_smoke.compare_exact("missing", off, want)


@pytest.mark.parametrize("force, series", [
    (lambda m: m.degraded_mode.set(1), "tpu_degraded_mode"),
    (lambda m: m.lost_events.labels(
        stage="device", plugin="engine").inc(5), "lost_events_counter"),
    (lambda m: m.engine_errors.labels(site="device_step").inc(),
     "engine_errors_counter"),
    (lambda m: m.overload_state.set(1), "tpu_overload_state"),
])
def test_health_verdict_flips_on_forced_failure(force, series):
    """What production survives by design (drop and count, rebuild,
    sample) is fatal to the smoke: it would hide a dead device."""
    from retina_tpu.exporter import get_exporter
    from retina_tpu.metrics import get_metrics

    m = get_metrics()
    m.windows_closed.inc(3)  # a healthy counter moving is not a finding
    clean = chip_smoke.Scrape(get_exporter().gather_text().decode())
    assert chip_smoke.health_verdict(clean) == {}
    force(m)
    bad = chip_smoke.health_verdict(
        chip_smoke.Scrape(get_exporter().gather_text().decode())
    )
    assert list(bad) and all(k.startswith(series) for k in bad)


def test_rehearsal_off_a_tpu_ends_not_ok(tmp_path):
    """The whole script at the rehearsal size on the CPU backend: every
    phase runs and passes, and the verdict is still "ok": false with a
    nonzero exit, because the platform is not a TPU."""
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
    }
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--rehearse"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=420,
    )
    lines = [json.loads(x) for x in res.stdout.strip().splitlines()]
    assert res.returncode != 0, res.stderr[-2000:]
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    summary = lines[-2]
    # Only the platform check failed: the path itself works end to end.
    assert summary["failed"] == ["platform"], (summary, res.stderr[-2000:])
    checks = {x["check"]: x["ok"] for x in lines if "check" in x}
    for name in ("boot1_pod_forward_exact", "boot2_pod_forward_exact",
                 "boot2_total_events_exact",
                 "second_boot_recompiled_nothing_persisted"):
        assert checks[name] is True
    # The caller placed the caches: nothing compiled lands elsewhere.
    assert os.listdir(tmp_path / "cache" / "aot")


def test_not_a_tpu_fails_before_anything_is_built(tmp_path):
    """Without the rehearsal switch the device check is the whole run."""
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
    )
    lines = [json.loads(x) for x in res.stdout.strip().splitlines()]
    assert res.returncode != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert not any("phase" in x for x in lines)
