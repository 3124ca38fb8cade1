"""The host-CPU account (obs/cpuaccount.py, ISSUE 36): the role table
holds every thread the tree spawns; the sampler adds increases and only
increases; threads that account for themselves are counted once; the
roles sum to the process."""

import ast
import os
import pathlib
import threading
import time

import pytest

from retina_tpu.metrics import get_metrics
from retina_tpu.obs import cpuaccount
from retina_tpu.obs.cpuaccount import CpuAccount
from retina_tpu.utils import metric_names as mn

ROOT = pathlib.Path(__file__).resolve().parent.parent
# Drives and load generators that live in the package: what they spawn
# stands where a benchmark's pacer stands, and ``foreign`` is its role.
DRIVES = {
    "retina_tpu/soak/runner.py", "retina_tpu/e2e/perf.py",
    "retina_tpu/e2e/steps.py", "retina_tpu/timetravel/dryrun.py",
    "retina_tpu/fleetquery/dryrun.py", "retina_tpu/fleet/dryrun.py",
    "retina_tpu/fleet/churn.py",
}
# supervisor.spawn() names its thread after its argument: the names
# are its callers', which the walk finds.
PASSED_ON = {("retina_tpu/runtime/supervisor.py", "name")}


def _literal_prefix(node: ast.expr | None) -> str | None:
    """The constant head of a thread's name: a string, or an f-string
    up to its first field."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        return _literal_prefix(node.values[0])
    return None


def _callee(call: ast.Call) -> str:
    f = call.func
    return f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", "")


def _kw(call: ast.Call, key: str) -> ast.expr | None:
    return next((k.value for k in call.keywords if k.arg == key), None)


def spawned_thread_names():
    """(file, line, name prefix or None) of every thread the package
    spawns: ``Thread(name=)``, ``Thread.__init__(name=)`` of a subclass,
    ``supervisor.spawn(name, ...)``, an executor's
    ``thread_name_prefix`` and a ``Timer``'s ``.name`` set beside it."""
    for path in sorted((ROOT / "retina_tpu").rglob("*.py")):
        rel = path.relative_to(ROOT).as_posix()
        if rel in DRIVES:
            continue
        tree = ast.parse(path.read_text())
        timer_names = {
            n.targets[0].value.attr
            if isinstance(n.targets[0].value, ast.Attribute)
            else getattr(n.targets[0].value, "id", None):
            _literal_prefix(n.value)
            for n in ast.walk(tree)
            if isinstance(n, ast.Assign) and len(n.targets) == 1
            and isinstance(n.targets[0], ast.Attribute)
            and n.targets[0].attr == "name"
        }
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call) and _callee(node.value) == "Timer":
                t = node.targets[0]
                var = t.attr if isinstance(t, ast.Attribute) else t.id
                yield rel, node.lineno, timer_names.get(var)
            if not isinstance(node, ast.Call):
                continue
            what = _callee(node)
            if what == "Thread":
                name = _kw(node, "name")
            elif what == "__init__" and _kw(node, "daemon") is not None:
                name = _kw(node, "name")  # a Thread subclass's super()
            elif what == "spawn" and node.args and _literal_prefix(
                    node.args[0]) is not None:
                name = node.args[0]
            elif what == "ThreadPoolExecutor":
                name = _kw(node, "thread_name_prefix")
            else:
                continue
            if isinstance(name, ast.Name) and (rel, name.id) in PASSED_ON:
                continue
            yield rel, node.lineno, _literal_prefix(name)


def test_the_role_table_holds_every_thread_the_tree_spawns():
    spawned = list(spawned_thread_names())
    assert len(spawned) >= 30
    unnamed = [(f, n) for f, n, name in spawned if not name]
    assert not unnamed, f"threads spawned without a name: {unnamed}"
    foreign = [(f, n, name) for f, n, name in spawned
               if mn.thread_role(name) == mn.ROLE_FOREIGN]
    assert not foreign, f"thread names the role table lacks: {foreign}"
    # The names the issue lists, to their roles.
    for name, role in (
            ("engine", mn.ROLE_FEED), ("feed-worker-3", mn.ROLE_FEED),
            ("plugin-seededsource", mn.ROLE_FEED),
            ("engine-dispatch", mn.ROLE_DISPATCH),
            ("device-proxy", mn.ROLE_PROXY),
            ("device-completion", mn.ROLE_PROXY),
            ("window-harvest", mn.ROLE_HARVEST),
            ("checkpointer", mn.ROLE_HARVEST),
            ("engine-bucket-warm", mn.ROLE_HARVEST),
            ("metricsmodule", mn.ROLE_PUBLISH),
            ("http-server", mn.ROLE_SERVE), ("http-handler", mn.ROLE_SERVE),
            ("metrics-render", mn.ROLE_SERVE), ("watchdog", mn.ROLE_CONTROL),
            ("monitoragent", mn.ROLE_HUBBLE),
            ("cpu-account", mn.ROLE_ACCOUNT),
            # Not the program's: a benchmark's threads, the main thread.
            ("pacer", mn.ROLE_FOREIGN), ("agent", mn.ROLE_FOREIGN),
            ("MainThread", mn.ROLE_FOREIGN),
            ("Thread-7 (process_request_thread)", mn.ROLE_FOREIGN)):
        assert mn.thread_role(name) == role, name
    roles = {r for _, r in mn.THREAD_ROLE_PREFIXES}
    assert roles | {mn.ROLE_FOREIGN} == set(mn.THREAD_ROLES)
    for prefix in mn.SELF_ACCOUNTING_PREFIXES:
        assert mn.thread_role(prefix) != mn.ROLE_FOREIGN


# -- the sampler on a fake /proc, by an injected clock -------------------
class FakeProc:
    """A ``/proc/self/task`` under ``tmp_path``: each thread a
    directory with the file the kernel would give (``schedstat`` in
    nanoseconds, or ``stat`` in ticks of 10 ms) and ``comm``; the
    Python threads the interpreter would list; the process's clock."""

    def __init__(self, root, source):
        self.root, self.source = root, source
        self.threads, self.process_s = [], 0.0
        self.account = CpuAccount(
            task_dir=str(root), process_cpu_s=lambda: self.process_s,
            threads=lambda: list(self.threads))

    def thread(self, tid, cpu_s, name=None, comm="python3"):
        """``tid`` has burnt ``cpu_s`` so far; ``name`` makes it a
        Python thread."""
        d = self.root / str(tid)
        d.mkdir(exist_ok=True)
        if self.source == cpuaccount.SCHEDSTAT:
            (d / "schedstat").write_text(f"{int(cpu_s * 1e9)} 12345 67\n")
        else:
            ticks = round(cpu_s * os.sysconf("SC_CLK_TCK"))
            user = ticks // 3
            (d / "stat").write_text(
                f"{tid} ({comm}) S 1 1 1 0 -1 4194304 10 0 0 0 "
                f"{user} {ticks - user} 0 0 20 0 9 0 100 1 1\n")
        (d / "comm").write_text(comm + "\n")
        self.threads = [t for t in self.threads if t.native_id != tid]
        if name is not None:
            self.threads.append(threading.Thread(name=name))
            self.threads[-1]._native_id = tid

    def gone(self, tid):
        for f in (self.root / str(tid)).iterdir():
            f.unlink()
        (self.root / str(tid)).rmdir()
        self.threads = [t for t in self.threads if t.native_id != tid]


def role_s(role):
    return get_metrics().thread_cpu_seconds.labels(role=role)._value.get()


def process_s():
    return get_metrics().process_cpu_seconds._value.get()


@pytest.fixture(params=[cpuaccount.SCHEDSTAT, cpuaccount.STAT])
def proc(request, tmp_path):
    fake = FakeProc(tmp_path, request.param)
    yield fake
    fake.account.close()


def test_two_samples_add_only_the_increase(proc):
    proc.thread(100, 3.0, "MainThread")
    proc.thread(101, 1.5, "engine")
    proc.thread(102, 0.5, "feed-worker-0")
    proc.thread(103, 0.25, "engine-dispatch")
    proc.thread(104, 2.0, comm="tfrt (a) b)")  # the runtime's, oddly named
    proc.process_s = 7.5  # a thread that died unseen burnt 0.25
    proc.account.sample()
    assert proc.account.source == proc.source
    assert role_s(mn.ROLE_FEED) == pytest.approx(2.0)
    assert role_s(mn.ROLE_DISPATCH) == pytest.approx(0.25)
    assert role_s(mn.ROLE_RUNTIME) == pytest.approx(2.0)
    assert role_s(mn.ROLE_FOREIGN) == pytest.approx(3.0)
    assert process_s() == pytest.approx(7.5)
    proc.thread(101, 1.75, "engine")
    proc.thread(104, 2.5, comm="tfrt (a) b)")
    proc.process_s = 8.25
    proc.account.sample()
    assert role_s(mn.ROLE_FEED) == pytest.approx(2.25)
    assert role_s(mn.ROLE_DISPATCH) == pytest.approx(0.25)
    assert role_s(mn.ROLE_RUNTIME) == pytest.approx(2.5)
    assert role_s(mn.ROLE_FOREIGN) == pytest.approx(3.0)
    assert process_s() == pytest.approx(8.25)
    proc.account.sample()  # nothing ran: nothing is added
    assert process_s() == pytest.approx(8.25)
    assert sum(role_s(r) for r in mn.THREAD_ROLES) == pytest.approx(8.0)
    stats = proc.account.stats()
    assert stats["source"] == proc.source and stats["samples"] == 3
    assert stats["unnamed_s"] == pytest.approx(0.25)
    assert stats["process_cpu_s"] == pytest.approx(8.25)
    assert stats["roles"][mn.ROLE_FEED] == pytest.approx(2.25)
    assert stats["threads"][0] == {
        "name": "MainThread", "tid": 100, "role": mn.ROLE_FOREIGN,
        "cpu_s": 3.0}
    assert {"name": "tfrt (a) b)", "tid": 104, "role": mn.ROLE_RUNTIME,
            "cpu_s": 2.5} in stats["threads"]
    assert len(stats["threads"]) == 5


def test_a_stall_asks_who_ran_since_the_sample_before(proc, monkeypatch):
    """``sample_top``: what the watchdog's scan asks at a ``held``
    stall. One sample more, and the role and the thread that burnt the
    most since the one before; the runtime's thread by its ``comm``."""
    proc.thread(100, 3.0, "MainThread")
    proc.thread(101, 1.5, "engine")
    proc.thread(104, 2.0, comm="tfrt_worker")
    proc.process_s = 6.5
    proc.account.sample()
    monkeypatch.setattr(proc.account, "_sampled_at", float("-inf"))
    proc.thread(100, 5.0, "MainThread")  # held the lock for 2 s
    proc.thread(101, 1.75, "engine")
    proc.process_s = 8.75
    top = proc.account.sample_top()
    assert top == {"top_role": mn.ROLE_FOREIGN, "top_role_cpu_s": 2.0,
                   "top_thread": "MainThread", "top_thread_cpu_s": 2.0}
    assert proc.account.samples == 2
    assert role_s(mn.ROLE_FOREIGN) == pytest.approx(5.0)
    # Asked again at once: the two samples count as one, so who ran
    # over the hole still reads, with what has been burnt since.
    proc.thread(104, 2.5, comm="tfrt_worker")
    again = proc.account.sample_top()
    assert again["top_thread"] == "MainThread"
    assert again["top_thread_cpu_s"] == 2.0
    assert again["top_role_cpu_s"] == 2.0
    assert proc.account.samples == 3
    assert role_s(mn.ROLE_RUNTIME) == pytest.approx(2.5)
    monkeypatch.setattr(proc.account, "_sampled_at", float("-inf"))
    proc.thread(104, 3.25, comm="tfrt_worker")
    top = proc.account.sample_top()
    assert (top["top_role"], top["top_thread"]) == (
        mn.ROLE_RUNTIME, "tfrt_worker")
    assert top["top_thread_cpu_s"] == pytest.approx(0.75)
    assert proc.account.samples == 4


def test_a_stall_reads_the_quiet_threads_too(proc, monkeypatch):
    """A thread that has hardly ever run is read every eighth sample by
    the account's own thread; the one that held the lock for seconds may
    have slept until it did (a timer's thread: the chip's control did),
    so a stall's sample reads every thread."""
    proc.thread(100, 3.0, "MainThread")
    proc.thread(301, 0.0, "stall-control")
    proc.process_s = 3.0
    for _ in range(cpuaccount.IDLE_AFTER + 2):
        proc.account.sample()
    assert (proc.account.samples + 301) % cpuaccount.IDLE_STRIDE
    proc.thread(301, 2.0, "stall-control")
    proc.process_s = 5.0
    proc.thread(100, 3.25, "MainThread")
    proc.process_s = 5.25
    monkeypatch.setattr(proc.account, "_sampled_at", float("-inf"))
    # The account's own thread wakes from the hole first: not the quiet
    # thread's turn, so its sample misses who held (as on the chip).
    proc.account.sample()
    assert role_s(mn.ROLE_FOREIGN) == pytest.approx(3.25)
    assert (proc.account.samples + 301) % cpuaccount.IDLE_STRIDE
    top = proc.account.sample_top()  # a moment later: one with it
    assert (top["top_thread"], top["top_thread_cpu_s"]) == (
        "stall-control", 2.0)
    assert (top["top_role"], top["top_role_cpu_s"]) == (
        mn.ROLE_FOREIGN, 2.25)
    assert role_s(mn.ROLE_FOREIGN) == pytest.approx(5.25)


def test_the_sampler_and_the_scan_may_sample_at_once(proc):
    """The account's own thread and the watchdog's scan both sample:
    neither adds an increase twice."""
    for tid in range(200, 240):
        proc.thread(tid, 1.0, f"feed-worker-{tid}")
    proc.process_s = 40.0
    threads = [threading.Thread(target=fn) for fn in
               (proc.account.sample, proc.account.sample_top) * 4]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert role_s(mn.ROLE_FEED) == pytest.approx(40.0)
    assert process_s() == pytest.approx(40.0)


def test_a_thread_that_accounts_for_itself_adds_nothing_through_the_sampler(
        proc):
    proc.thread(200, 0.5, "http-server")
    proc.thread(201, 0.75, "http-handler")
    proc.process_s = 1.25
    proc.account.sample()
    proc.thread(201, 1.25, "http-handler")
    proc.account.sample()
    assert role_s(mn.ROLE_SERVE) == pytest.approx(0.5)
    assert role_s(mn.ROLE_FEED) == 0.0
    assert [r["name"] for r in proc.account.stats()["threads"]] == [
        "http-server"]


def test_a_vanished_tid_loses_nothing_already_counted(proc):
    proc.thread(300, 1.0, "window-harvest")
    proc.thread(301, 4.0, comm="pjrt-tpu-tasks")
    proc.process_s = 5.0
    proc.account.sample()
    proc.gone(300)
    proc.gone(301)
    proc.account.sample()
    assert role_s(mn.ROLE_HARVEST) == pytest.approx(1.0)
    assert role_s(mn.ROLE_RUNTIME) == pytest.approx(4.0)
    assert proc.account.stats()["threads"] == []
    # The kernel hands the tid to a new thread, which starts from 0:
    # whatever it has burnt is an increase, in the new thread's role.
    proc.thread(301, 0.5, "metricsmodule")
    proc.account.sample()
    assert role_s(mn.ROLE_PUBLISH) == pytest.approx(0.5)
    assert role_s(mn.ROLE_RUNTIME) == pytest.approx(4.0)
    # Reused between two samples: the reading fell, so it is a new thread.
    proc.thread(301, 0.25, "watchdog")
    proc.account.sample()
    assert role_s(mn.ROLE_CONTROL) == pytest.approx(0.25)
    assert role_s(mn.ROLE_PUBLISH) == pytest.approx(0.5)
    # Renamed, it is the same thread: only its increase, in the new role.
    proc.thread(301, 0.75, "autocapture")
    proc.account.sample()
    assert role_s(mn.ROLE_CONTROL) == pytest.approx(0.75)
    # A thread that goes while it is read is left out, not an error.
    (proc.root / "302").mkdir()
    proc.account.sample()
    assert proc.account.samples == 6


def test_a_thread_that_hardly_ever_ran_is_read_every_eighth_sample(proc):
    """The runtime's pools are mostly threads that never run, and a
    read costs: one that has burnt next to nothing and stood still for
    four samples is read every eighth sample, and what it burns
    meanwhile is booked then. One with a past is read every sample."""
    proc.thread(400, 1.0, "engine-dispatch")
    proc.thread(401, 0.02, comm="tfrt-idle")
    proc.thread(402, 9.0, comm="llvm-worker-0")  # a compile's, gone quiet
    proc.process_s = 10.02
    for n in range(1 + cpuaccount.IDLE_AFTER):
        proc.thread(400, 1.0 + 0.25 * n, "engine-dispatch")  # never idle
        proc.account.sample()
    assert role_s(mn.ROLE_RUNTIME) == pytest.approx(9.02)
    proc.thread(402, 9.5, comm="llvm-worker-0")  # its burst is seen at once
    proc.account.sample()
    assert role_s(mn.ROLE_RUNTIME) == pytest.approx(9.52)
    woke, seen_at = 0.52, None
    proc.thread(401, woke, comm="tfrt-idle")  # it wakes, unseen at first
    for n in range(cpuaccount.IDLE_STRIDE):
        proc.thread(400, 2.25 + 0.25 * n, "engine-dispatch")
        if seen_at is not None:  # seen to run, it is read every sample
            woke += 0.25
            proc.thread(401, woke, comm="tfrt-idle")
        proc.account.sample()
        if seen_at is None and role_s(mn.ROLE_RUNTIME) > 9.6:
            seen_at = n
        assert role_s(mn.ROLE_RUNTIME) == pytest.approx(
            9.5 + (0.02 if seen_at is None else woke))
    assert seen_at is not None
    assert role_s(mn.ROLE_DISPATCH) == pytest.approx(4.0)
    rows = {r["tid"]: r for r in proc.account.stats()["threads"]}
    assert rows[401]["name"] == "tfrt-idle" and rows[401]["cpu_s"] == woke


# -- the account of this very process --------------------------------------
def _burn(cpu_s):
    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_s:
        sum(range(2000))


def test_the_roles_sum_to_the_process_on_a_run_of_busy_threads():
    account = CpuAccount()
    account.sample()
    before = {r: role_s(r) for r in mn.THREAD_ROLES}
    process_before = process_s()
    release = threading.Event()
    names = ("feed-worker-0", "engine-dispatch", "metricsmodule", "pacer")
    workers = [threading.Thread(
        target=lambda: (_burn(0.15), release.wait(30.0)), name=n)
        for n in names]
    for w in workers:
        w.start()
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:  # until each has burnt its share
        time.sleep(0.05)
        account.sample()
        if all(role_s(mn.thread_role(n)) - before[mn.thread_role(n)] >= 0.14
               for n in names):
            break
    release.set()
    for w in workers:
        w.join(30.0)
        assert not w.is_alive()
    account.sample()
    added = {r: role_s(r) - before[r] for r in mn.THREAD_ROLES}
    for n in names[:3]:
        assert 0.14 <= added[mn.thread_role(n)] < 0.3, (n, added)
    assert added[mn.ROLE_FOREIGN] >= 0.14  # the pacer, and this thread
    process = process_s() - process_before
    assert process >= 0.6
    assert sum(added.values()) == pytest.approx(process, rel=0.05), added
    assert account.source in (cpuaccount.SCHEDSTAT, cpuaccount.STAT)
    mine = [r for r in account.stats()["threads"]
            if r["tid"] == threading.get_native_id()]
    assert [r["role"] for r in mine] == [mn.thread_role(
        threading.current_thread().name)]
    account.close()


def test_a_handlers_own_accounting_adds_to_serve_exactly_once():
    import urllib.request

    from retina_tpu.server import Server

    entered, release = threading.Event(), threading.Event()

    def slow(query):
        _burn(0.05)
        entered.set()
        release.wait(30.0)
        return 200, b"ok", "text/plain"

    srv = Server("127.0.0.1:0", metrics_cache_ttl_s=0)
    srv.register_route("/slow", slow)
    srv.start()
    account = CpuAccount()
    try:
        account.sample()
        serve_before = role_s(mn.ROLE_SERVE)
        got = []
        client = threading.Thread(target=lambda: got.append(
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/slow", timeout=30).status))
        client.start()
        assert entered.wait(30.0)
        account.sample()  # the handler is alive, and has burnt 50 ms
        assert any(t.name == "http-handler" for t in threading.enumerate())
        assert all(r["name"] != "http-handler"
                   for r in account.stats()["threads"])
        assert role_s(mn.ROLE_SERVE) - serve_before < 0.04
        release.set()
        client.join(30.0)
        assert got == [200]
        deadline = time.monotonic() + 30.0
        while any(t.name == "http-handler" for t in threading.enumerate()):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        account.sample()
        # Its 50 ms once (with what the accepting thread burnt), not twice.
        assert 0.05 <= role_s(mn.ROLE_SERVE) - serve_before < 0.095
    finally:
        release.set()
        srv.stop()
        account.close()


# -- cpu_s on the spans ---------------------------------------------------------
def test_a_cpu_stage_span_reads_the_threads_clock_and_counts_it():
    from retina_tpu.obs.recorder import FlightRecorder

    def stage_cpu_s(stage):
        return get_metrics().stage_cpu_seconds.labels(
            stage=stage)._value.get()

    rec = FlightRecorder()
    with rec.span(mn.STAGE_COMBINE, trace_id=7):
        _burn(0.03)
        time.sleep(0.05)  # a wait costs seconds, not CPU
    with rec.span(mn.STAGE_FEED_FILL):  # wraps others: no clock of its own
        pass
    sp = rec.span(mn.STAGE_DEVICE_STEP)  # another thread closes it
    sp.end(n_steps=1)
    by_stage = {s["stage"]: s for s in rec.spans()}
    combine = by_stage[mn.STAGE_COMBINE]
    assert 0.03 <= combine["args"]["cpu_s"] < 0.06
    assert combine["t1"] - combine["t0"] >= 0.08
    assert stage_cpu_s(mn.STAGE_COMBINE) == pytest.approx(
        combine["args"]["cpu_s"])
    assert "cpu_s" not in by_stage[mn.STAGE_FEED_FILL]["args"]
    assert by_stage[mn.STAGE_DEVICE_STEP]["args"] == {"n_steps": 1}
    exposed = {s.labels["stage"]
               for mf in get_metrics().stage_cpu_seconds.collect()
               for s in mf.samples}
    assert exposed == {mn.STAGE_COMBINE}
    assert mn.CPU_STAGES < set(mn.STAGES)
    assert mn.STAGE_DEVICE_STEP not in mn.CPU_STAGES
