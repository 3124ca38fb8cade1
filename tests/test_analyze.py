"""Fixture tests for the tools/analyze static-analysis framework.

Each rule family gets fire / no-fire / noqa-suppressed cases on small
synthetic snippets; the driver-level tests cover baseline suppression
and exit codes.  The real repo staying finding-free is asserted
separately by tests/test_lint_clean.py (tier 1).
"""

from __future__ import annotations

import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.analyze import (  # noqa: E402
    driver,
    generic,
    rt10x,
    rt200,
    rt210,
    rt220,
    rt226,
    rt230,
    rt300,
    rt400,
)
from tools.analyze.core import (  # noqa: E402
    FileCtx,
    Reporter,
    noqa_codes,
    save_baseline,
)


def run_rule(rule, src: str, rel: str = "retina_tpu/fake_mod.py"):
    ctx = FileCtx(Path(rel), rel, textwrap.dedent(src))
    assert ctx.syntax_error is None, ctx.syntax_error
    rep = Reporter()
    rule(ctx, rep)
    return rep.findings


def codes(findings) -> list[str]:
    return [f.code for f in findings]


# ---------------------------------------------------------------- core

def test_noqa_parsing_is_code_aware():
    assert noqa_codes("x = 1") is None
    assert noqa_codes("x = 1  # noqa") == set()
    assert noqa_codes("x = 1  # noqa: RT101") == {"RT101"}
    assert noqa_codes("x  # noqa: BLE001, RT200 — reason") == \
        {"BLE001", "RT200"}
    # a noqa for a DIFFERENT code must not suppress this one
    ctx = FileCtx(Path("retina_tpu/x.py"), "retina_tpu/x.py",
                  "y = 1  # noqa: BLE001\n")
    assert not ctx.suppressed(1, "RT101")
    assert ctx.suppressed(1, "BLE001")


# ------------------------------------------------------------- generic

def test_e711_fire_nofire_noqa():
    fire = run_rule(generic.check, "def f(x):\n    return x == None\n")
    assert "E711" in codes(fire)
    ok = run_rule(generic.check, "def f(x):\n    return x is None\n")
    assert "E711" not in codes(ok)
    sup = run_rule(
        generic.check,
        "def f(x):\n    return x == None  # noqa: E711\n")
    assert "E711" not in codes(sup)


def test_b006_mutable_default():
    fire = run_rule(generic.check, "def f(x=[]):\n    return x\n")
    assert "B006" in codes(fire)


# --------------------------------------------------------------- RT100

def test_rt100_engine_thread_spawn():
    src = """
        import threading

        class SketchEngineLike:
            def start(self):
                threading.Thread(target=self._loop).start()

            def sneaky(self):
                threading.Thread(target=self._loop).start()
    """
    fire = run_rule(rt10x.check, src, rel="retina_tpu/engine.py")
    assert codes(fire).count("RT100") == 1
    assert "sneaky" in fire[0].message
    # same snippet outside engine.py: out of scope
    ok = run_rule(rt10x.check, src, rel="retina_tpu/other.py")
    assert "RT100" not in codes(ok)


# --------------------------------------------------------------- RT101

def test_rt101_fire_and_logged_nofire():
    fire = run_rule(rt10x.check, """
        try:
            f()
        except Exception:
            pass
    """)
    assert "RT101" in codes(fire)
    ok = run_rule(rt10x.check, """
        try:
            f()
        except Exception:
            log.warning("boom")
    """)
    assert "RT101" not in codes(ok)


def test_rt101_string_constant_body_is_silent():
    # satellite: a bare string "explanation" is still a swallow
    fire = run_rule(rt10x.check, '''
        try:
            f()
        except Exception:
            "best effort"
    ''')
    assert "RT101" in codes(fire)


def test_rt101_noqa_on_except_or_last_body_line():
    sup = run_rule(rt10x.check, """
        try:
            f()
        except Exception:  # noqa: RT101 — reason
            pass
    """)
    assert "RT101" not in codes(sup)
    # satellite: noqa honored on the handler's LAST body line too
    sup2 = run_rule(rt10x.check, """
        try:
            f()
        except Exception:
            pass  # noqa: RT101 — reason
    """)
    assert "RT101" not in codes(sup2)


# --------------------------------------------------------------- RT102

def test_rt102_unbounded_queue():
    fire = run_rule(rt10x.check, "import queue\nq = queue.Queue()\n")
    assert "RT102" in codes(fire)
    ok = run_rule(rt10x.check, "import queue\nq = queue.Queue(8)\n")
    assert "RT102" not in codes(ok)
    simple = run_rule(
        rt10x.check, "import queue\nq = queue.SimpleQueue()\n")
    assert "RT102" in codes(simple)


# --------------------------------------------------------------- RT200

RACY = """
    import threading

    class Supervisor:
        def __init__(self):
            self._lock = threading.Lock()
            self.counter = 0{decl_comment}

        def start(self):
            threading.Thread(
                target=self._loop, name="loop-thread"
            ).start()

        def _loop(self):
            {loop_write}

        def poke(self):
            {poke_write}
"""


def _racy(loop_write="self.counter = 1", poke_write="self.counter = 2",
          decl_comment=""):
    return RACY.format(loop_write=loop_write, poke_write=poke_write,
                       decl_comment=decl_comment)


def test_rt200_two_threads_no_lock_fires():
    fire = run_rule(rt200.check, _racy())
    assert "RT200" in codes(fire)
    assert "Supervisor.counter" in fire[0].message


def test_rt200_common_lock_no_fire():
    ok = run_rule(rt200.check, _racy(
        loop_write="with self._lock:\n                self.counter = 1",
        poke_write="with self._lock:\n                self.counter = 2",
    ))
    assert "RT200" not in codes(ok)


def test_rt200_single_thread_no_fire():
    # both writes on the same (external) thread: no race
    src = """
        class Supervisor:
            def __init__(self):
                self.counter = 0

            def poke(self):
                self.counter = 1

            def reset(self):
                self.counter = 0
    """
    assert "RT200" not in codes(run_rule(rt200.check, src))


def test_rt200_noqa_on_declaration_line():
    sup = run_rule(rt200.check, _racy(
        decl_comment="  # noqa: RT200 — benign test race"))
    assert "RT200" not in codes(sup)


def test_rt201_guarded_by_violation():
    fire = run_rule(rt200.check, _racy(
        decl_comment="  # guarded-by: self._lock",
        loop_write="with self._lock:\n                self.counter = 1",
        poke_write="self.counter = 2",
    ))
    assert codes(fire) == ["RT201"]
    assert "poke" in fire[0].message
    ok = run_rule(rt200.check, _racy(
        decl_comment="  # guarded-by: self._lock",
        loop_write="with self._lock:\n                self.counter = 1",
        poke_write="with self._lock:\n                self.counter = 2",
    ))
    assert "RT201" not in codes(ok)


def test_rt202_escaping_callback_needs_runs_on():
    src = """
        class Supervisor:
            def start(self, pool):
                pool.register(self._cb)

            def _cb(self):{runs_on}
                self.x = 1
    """
    fire = run_rule(rt200.check,
                    textwrap.dedent(src).format(runs_on=""))
    assert "RT202" in codes(fire)
    ok = run_rule(
        rt200.check,
        textwrap.dedent(src).format(runs_on="  # runs-on: pool-worker"))
    assert "RT202" not in codes(ok)


def test_rt202_runs_on_threads_feed_rt200():
    # the declared thread plus a plain method call = two writers
    src = """
        class Supervisor:
            def __init__(self):
                self.x = 0

            def start(self, pool):
                pool.register(self._cb)

            def _cb(self):  # runs-on: pool-worker*
                self.x = 1

            def poke(self):
                self.x = 2
    """
    fire = run_rule(rt200.check, src)
    assert "RT200" in codes(fire)
    assert "pool-worker*" in fire[0].message


def test_rt203_unknown_guard_lock():
    src = """
        class Supervisor:
            def __init__(self):
                self.x = 0  # guarded-by: self._nonexistent
    """
    assert "RT203" in codes(run_rule(rt200.check, src))


def test_rt204_malformed_runs_on():
    src = """
        class Supervisor:
            def _cb(self):  # runs-on: bad thread name!
                pass
    """
    assert "RT204" in codes(run_rule(rt200.check, src))


def test_rt200_ignores_non_target_classes():
    src = """
        import threading

        class SomethingElse:
            def __init__(self):
                self.x = 0

            def start(self):
                threading.Thread(target=self._loop, name="t").start()

            def _loop(self):
                self.x = 1

            def poke(self):
                self.x = 2
    """
    assert run_rule(rt200.check, src) == []


# --------------------------------------------------------------- RT210

def test_rt210_side_effect_in_traced_fn():
    src = """
        import time
        import jax

        @jax.jit
        def step(x):
            time.sleep(0.1)
            return x
    """
    fire = run_rule(rt210.check, src)
    assert "RT210" in codes(fire)


def test_rt210_no_fire_outside_traced_fn():
    src = """
        import time

        def host_loop(x):
            time.sleep(0.1)
            return x
    """
    assert run_rule(rt210.check, src) == []


def test_rt211_concretization():
    src = """
        import jax

        @jax.jit
        def step(x):
            return float(x) + 1
    """
    assert "RT211" in codes(run_rule(rt210.check, src))


def test_rt212_branch_on_tracer_fire_and_static_ok():
    fire = run_rule(rt210.check, """
        import jax

        @jax.jit
        def step(x):
            if x > 0:
                return x
            return -x
    """)
    assert "RT212" in codes(fire)
    ok = run_rule(rt210.check, """
        import jax

        @jax.jit
        def step(x):
            if x is None:
                return 0
            if len(x) > 2:
                return x
            for i in range(x.shape[0]):
                pass
            return x
    """)
    assert "RT212" not in codes(ok)


def test_rt212_static_argnames_excluded():
    src = """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("mode",))
        def step(x, mode):
            if mode:
                return x
            return -x
    """
    assert "RT212" not in codes(run_rule(rt210.check, src))


def test_rt213_attribute_mutation_in_traced_fn():
    src = """
        import jax

        class M:
            def build(self):
                return jax.jit(self._step)

            def _step(self, x):
                self.calls = 1
                return x
    """
    assert "RT213" in codes(run_rule(rt210.check, src))


def test_rt210_noqa_suppression():
    src = """
        import time
        import jax

        @jax.jit
        def step(x):
            time.sleep(0.1)  # noqa: RT210 — trace-time warm delay
            return x
    """
    assert run_rule(rt210.check, src) == []


# --------------------------------------------------- RT220 / RT230

def _mini_repo(tmp_path, doc_metrics: str, doc_config: str,
               metrics_src: str, config_src: str, usage_src: str):
    files = {
        "retina_tpu/utils/metric_names.py": metrics_src,
        "retina_tpu/config.py": config_src,
        "retina_tpu/app.py": usage_src,
        "docs/metrics.md": doc_metrics,
        "docs/configuration.md": doc_config,
    }
    ctxs = []
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        if rel.endswith(".py"):
            ctxs.append(FileCtx(p, rel, p.read_text()))
    return ctxs


METRIC_DECLS = """
    PREFIX = "networkobservability_"
    FOO = PREFIX + "foo"
    BAR = PREFIX + "bar"
"""

CONFIG_SRC = """
    class Config:
        window_seconds: int = 15
        dead_knob: bool = False
"""

USAGE_SRC = """
    from retina_tpu.utils import metric_names as mn

    def setup(ex, cfg):
        ex.new_gauge(mn.FOO, "doc")
        ex.new_counter("networkobservability_rogue", "doc")
        _ = cfg.window_seconds
        _ = cfg.typo_knob
"""


def test_rt220_family(tmp_path):
    ctxs = _mini_repo(
        tmp_path,
        doc_metrics="`networkobservability_foo` and "
                    "`networkobservability_ghost`\n",
        doc_config="window_seconds dead_knob\n",
        metrics_src=METRIC_DECLS,
        config_src=CONFIG_SRC,
        usage_src=USAGE_SRC,
    )
    rep = Reporter()
    rt220.check_program(ctxs, rep, tmp_path)
    got = codes(rep.findings)
    assert "RT220" in got   # rogue literal not declared
    assert "RT222" in got   # BAR declared, not in docs
    assert "RT223" in got   # docs mention ghost
    assert "RT224" in got   # BAR never referenced
    messages = " ".join(f.message for f in rep.findings)
    assert "rogue" in messages and "ghost" in messages


def test_rt221_literal_for_declared_series(tmp_path):
    ctxs = _mini_repo(
        tmp_path,
        doc_metrics="`networkobservability_foo` "
                    "`networkobservability_bar`\n",
        doc_config="window_seconds dead_knob\n",
        metrics_src=METRIC_DECLS,
        config_src=CONFIG_SRC,
        usage_src="""
            from retina_tpu.utils import metric_names as mn

            def setup(ex):
                ex.new_gauge(mn.FOO, "d")
                ex.new_gauge(mn.BAR, "d")
                ex.new_counter("networkobservability_bar", "d")
        """,
    )
    rep = Reporter()
    rt220.check_program(ctxs, rep, tmp_path)
    assert codes(rep.findings) == ["RT221"]


@pytest.mark.parametrize("call, code", [
    ('ex.new_adv_table("networkobservability_rogue", ["pod"])', "RT220"),
    ('ex.new_adv_table("networkobservability_bar", ["pod"])', "RT221"),
    ('ex.new_adv_table("networkobservability_" + kind, [])', "RT221"),
])
def test_a_row_table_is_held_to_a_metric_names_constant(tmp_path, call, code):
    """The pod-level families are made by ``Exporter.new_adv_table``
    (PR 32): a name that is not declared, a literal for a declared
    name and a computed name are findings there as for a gauge."""
    ctxs = _mini_repo(
        tmp_path,
        doc_metrics="`networkobservability_foo` "
                    "`networkobservability_bar`\n",
        doc_config="window_seconds dead_knob\n",
        metrics_src=METRIC_DECLS,
        config_src=CONFIG_SRC,
        usage_src=f"""
            from retina_tpu.utils import metric_names as mn

            def setup(ex, kind):
                ex.new_adv_table(mn.FOO, ["pod"])
                ex.new_adv_table(mn.BAR, [])
                {call}
        """,
    )
    rep = Reporter()
    rt220.check_program(ctxs, rep, tmp_path)
    assert codes(rep.findings) == [code]


# --------------------------------------------------------------- RT226

STAGE_DECLS = """
    STAGE_ALPHA = "alpha"
    STAGE_BETA = "beta"

    STAGES = (
        STAGE_ALPHA,
        STAGE_BETA,
    )
"""

STAGE_TABLE_OK = """\
<!-- stage-table-begin -->
| Stage | What |
|---|---|
| `alpha` | first |
| `beta` | second |
<!-- stage-table-end -->
"""


def _rt226_repo(tmp_path, metrics_src: str, usage_src: str,
                doc_obs: str):
    files = {
        "retina_tpu/utils/metric_names.py": metrics_src,
        "retina_tpu/app.py": usage_src,
        "docs/observability.md": doc_obs,
    }
    ctxs = []
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        if rel.endswith(".py"):
            ctxs.append(FileCtx(p, rel, p.read_text()))
    return ctxs


def test_rt226_clean(tmp_path):
    ctxs = _rt226_repo(
        tmp_path,
        metrics_src=STAGE_DECLS,
        usage_src="""
            from retina_tpu.utils import metric_names as mn

            def work(rec, m):
                with rec.span(mn.STAGE_ALPHA, trace_id=3):
                    sp = rec.span(mn.STAGE_BETA)
                sp.end()
                m.span(1)  # unrelated .span method: out of scope
        """,
        doc_obs=STAGE_TABLE_OK,
    )
    rep = Reporter()
    rt226.check_program(ctxs, rep, tmp_path)
    assert rep.findings == []


KIND_DECLS = STAGE_DECLS + """
    KIND_STEP = "step"
    KIND_POLL = "poll"

    PROXY_KINDS = (
        KIND_STEP,
        KIND_POLL,
    )
"""

KIND_TABLE_OK = STAGE_TABLE_OK + """
<!-- kind-table-begin -->
| Kind | What |
|---|---|
| `step` | a dispatch |
| `poll` | a readiness poll |
<!-- kind-table-end -->
"""

KIND_USAGE = """
    from retina_tpu.utils import metric_names as mn
    from retina_tpu.utils.device_proxy import run_on_device

    def work(rec, proxy):
        with rec.span(mn.STAGE_ALPHA):
            rec._step_span(mn.STAGE_BETA, 3, True).end()
        run_on_device(len, (), kind=mn.KIND_STEP)
        proxy.submit_on_device(len, (), kind=mn.KIND_POLL, parent=4)
        run_on_device(len, ())  # no kind: the default
"""


def test_rt226_kinds_clean(tmp_path):
    ctxs = _rt226_repo(tmp_path, metrics_src=KIND_DECLS,
                       usage_src=KIND_USAGE, doc_obs=KIND_TABLE_OK)
    rep = Reporter()
    rt226.check_program(ctxs, rep, tmp_path)
    assert rep.findings == []


def test_rt226_kind_drift_every_direction(tmp_path):
    ctxs = _rt226_repo(
        tmp_path,
        metrics_src=STAGE_DECLS + """
    KIND_STEP = "step"
    KIND_ORPHAN = "orphan"

    PROXY_KINDS = (
        KIND_STEP,
    )
""",
        usage_src="""
            from retina_tpu.utils import metric_names as mn
            from retina_tpu.utils.device_proxy import run_on_device

            def work(rec):
                rec.span(mn.STAGE_ALPHA).end()
                rec.span(mn.STAGE_BETA).end()
                run_on_device(len, (), kind=mn.KIND_STEP)
                run_on_device(len, (), kind="fetch")        # literal
                run_on_device(len, (), kind=mn.KIND_GHOST)  # undeclared
        """,
        doc_obs=STAGE_TABLE_OK + """
            <!-- kind-table-begin -->
            | `step` | a dispatch |
            | `phantom` | not a kind |
            <!-- kind-table-end -->
        """,
    )
    rep = Reporter()
    rt226.check_program(ctxs, rep, tmp_path)
    keys = {f.key for f in rep.findings}
    assert keys == {
        "RT226:kind-tuple:KIND_ORPHAN",
        "RT226:kind-unused:KIND_ORPHAN",
        "RT226:retina_tpu/app.py:kind:fetch",
        "RT226:retina_tpu/app.py:KIND_GHOST",
        "RT226:kind-doc-missing:orphan",
        "RT226:kind-doc-unknown:phantom",
    }


def test_rt226_missing_kind_table(tmp_path):
    ctxs = _rt226_repo(tmp_path, metrics_src=KIND_DECLS,
                       usage_src=KIND_USAGE, doc_obs=STAGE_TABLE_OK)
    rep = Reporter()
    rt226.check_program(ctxs, rep, tmp_path)
    assert [f.key for f in rep.findings] == ["RT226:kind-doc:no-table"]


def test_rt226_drift_every_direction(tmp_path):
    ctxs = _rt226_repo(
        tmp_path,
        metrics_src="""
            STAGE_ALPHA = "alpha"
            STAGE_BETA = "beta"
            STAGE_ORPHAN = "orphan"

            STAGES = (
                STAGE_ALPHA,
                STAGE_BETA,
            )
        """,
        usage_src="""
            from retina_tpu.utils import metric_names as mn

            def work(rec):
                rec.span(mn.STAGE_ALPHA).end()
                rec.span("beta").end()          # literal
                rec.span(mn.STAGE_GHOST).end()  # undeclared
        """,
        doc_obs="""\
            <!-- stage-table-begin -->
            | Stage | What |
            |---|---|
            | `alpha` | first |
            | `phantom` | not a stage |
            <!-- stage-table-end -->
        """,
    )
    rep = Reporter()
    rt226.check_program(ctxs, rep, tmp_path)
    assert all(f.code == "RT226" for f in rep.findings)
    keys = {f.key for f in rep.findings}
    assert "RT226:tuple:STAGE_ORPHAN" in keys       # not in STAGES
    assert "RT226:retina_tpu/app.py:beta" in keys   # literal span
    assert "RT226:retina_tpu/app.py:STAGE_GHOST" in keys
    assert "RT226:unused:STAGE_BETA" in keys        # never emitted
    assert "RT226:unused:STAGE_ORPHAN" in keys
    assert "RT226:doc-missing:beta" in keys
    assert "RT226:doc-missing:orphan" in keys
    assert "RT226:doc-unknown:phantom" in keys


def test_rt226_missing_stage_table(tmp_path):
    ctxs = _rt226_repo(
        tmp_path,
        metrics_src=STAGE_DECLS,
        usage_src="""
            from retina_tpu.utils import metric_names as mn

            def work(rec):
                rec.span(mn.STAGE_ALPHA).end()
                rec.span(mn.STAGE_BETA).end()
        """,
        doc_obs="no markers here\n",
    )
    rep = Reporter()
    rt226.check_program(ctxs, rep, tmp_path)
    assert [f.key for f in rep.findings] == ["RT226:doc:no-table"]


ROLE_USAGE = """
    from retina_tpu.utils import metric_names as mn

    def work(rec, book):
        rec.span(mn.STAGE_ALPHA).end()
        rec.span(mn.STAGE_BETA).end()
        book(mn.ROLE_RUNTIME)
"""

ROLE_TABLE_OK = STAGE_TABLE_OK + """
<!-- role-table-begin -->
| Role | Threads |
|---|---|
| `feed` | `engine` |
| `runtime` | the rest |
<!-- role-table-end -->
"""


@pytest.mark.parametrize("decls, doc, want", [
    # Clean: one role booked by the prefix table, one by the program.
    ("""
    ROLE_FEED = "feed"
    ROLE_RUNTIME = "runtime"

    THREAD_ROLES = (
        ROLE_FEED,
        ROLE_RUNTIME,
    )
    THREAD_ROLE_PREFIXES = (("engine", ROLE_FEED),)
""", ROLE_TABLE_OK, set()),
    # Drift in every direction: a role outside the tuple that nothing
    # books and the table lacks; a row for a role that is none.
    ("""
    ROLE_FEED = "feed"
    ROLE_RUNTIME = "runtime"
    ROLE_ORPHAN = "orphan"

    THREAD_ROLES = (
        ROLE_FEED,
        ROLE_RUNTIME,
    )
    THREAD_ROLE_PREFIXES = (("engine", ROLE_FEED),)
""", ROLE_TABLE_OK.replace("`runtime` | the rest",
                           "`runtime` | the rest |\n| `phantom` | none"),
     {"RT226:role-tuple:ROLE_ORPHAN", "RT226:role-unused:ROLE_ORPHAN",
      "RT226:role-doc-missing:orphan", "RT226:role-doc-unknown:phantom"}),
    # Listed in the tuple alone is not booked; no table at all.
    ("""
    ROLE_FEED = "feed"
    ROLE_RUNTIME = "runtime"
    ROLE_IDLE = "idle"

    THREAD_ROLES = (
        ROLE_FEED,
        ROLE_RUNTIME,
        ROLE_IDLE,
    )
    THREAD_ROLE_PREFIXES = (("engine", ROLE_FEED),)
""", STAGE_TABLE_OK,
     {"RT226:role-unused:ROLE_IDLE", "RT226:role-doc:no-table"}),
])
def test_rt226_thread_roles(tmp_path, decls, doc, want):
    ctxs = _rt226_repo(tmp_path, metrics_src=STAGE_DECLS + decls,
                       usage_src=ROLE_USAGE, doc_obs=doc)
    rep = Reporter()
    rt226.check_program(ctxs, rep, tmp_path)
    assert {f.key for f in rep.findings} == want


def test_rt230_family(tmp_path):
    ctxs = _mini_repo(
        tmp_path,
        doc_metrics="`networkobservability_foo` "
                    "`networkobservability_bar`\n",
        doc_config="window_seconds\n",  # dead_knob undocumented
        metrics_src=METRIC_DECLS,
        config_src=CONFIG_SRC,
        usage_src=USAGE_SRC,
    )
    rep = Reporter()
    rt230.check_program(ctxs, rep, tmp_path)
    got = codes(rep.findings)
    assert "RT230" in got   # cfg.typo_knob
    assert "RT231" in got   # dead_knob never read
    assert "RT232" in got   # dead_knob undocumented
    assert not any(
        "window_seconds" in f.message for f in rep.findings)


def test_rt230_foreign_cfg_annotation_opts_out(tmp_path):
    ctxs = _mini_repo(
        tmp_path,
        doc_metrics="`networkobservability_foo` "
                    "`networkobservability_bar`\n",
        doc_config="window_seconds dead_knob\n",
        metrics_src=METRIC_DECLS,
        config_src=CONFIG_SRC,
        usage_src="""
            def run(cfg: ShellConfig):
                return cfg.not_an_agent_knob

            def agent(cfg):
                return (cfg.window_seconds, cfg.dead_knob)
        """,
    )
    rep = Reporter()
    rt230.check_program(ctxs, rep, tmp_path)
    assert rep.findings == []


# ----------------------------------------------------------- driver

def _driver_repo(tmp_path) -> Path:
    """Minimal tree the driver can analyze end to end: one RT101."""
    pkg = tmp_path / "retina_tpu"
    pkg.mkdir()
    (pkg / "x.py").write_text(
        "try:\n    f()\nexcept Exception:\n    pass\n")
    return tmp_path


def test_driver_exits_nonzero_on_live_finding(tmp_path, monkeypatch):
    root = _driver_repo(tmp_path)
    monkeypatch.setattr(
        driver, "BASELINE_PATH", tmp_path / "baseline.json")
    out: list[str] = []
    rc = driver.run([], root=root, out=out.append)
    assert rc == 1
    assert any("RT101" in line for line in out)
    assert any("1 finding(s), 0 baselined" in line for line in out)


def test_driver_baseline_suppression(tmp_path, monkeypatch):
    root = _driver_repo(tmp_path)
    findings = driver.analyze(root)
    assert len(findings) == 1
    bpath = tmp_path / "baseline.json"
    save_baseline(bpath, {findings[0].key: "reviewed: test fixture"})
    monkeypatch.setattr(driver, "BASELINE_PATH", bpath)
    out: list[str] = []
    rc = driver.run([], root=root, out=out.append)
    assert rc == 0
    assert any("0 finding(s), 1 baselined" in line for line in out)


def test_driver_stale_baseline_warns(tmp_path, monkeypatch):
    root = _driver_repo(tmp_path)
    (root / "retina_tpu" / "x.py").write_text("x = 1\n")  # finding gone
    bpath = tmp_path / "baseline.json"
    save_baseline(bpath, {"RT101:retina_tpu/x.py:3": "obsolete"})
    monkeypatch.setattr(driver, "BASELINE_PATH", bpath)
    out: list[str] = []
    rc = driver.run([], root=root, out=out.append)
    assert rc == 0
    assert any("stale baseline" in line for line in out)


def test_driver_path_restriction_reports_subset(tmp_path, monkeypatch):
    root = _driver_repo(tmp_path)
    (root / "retina_tpu" / "y.py").write_text(
        "try:\n    f()\nexcept Exception:\n    pass\n")
    monkeypatch.setattr(
        driver, "BASELINE_PATH", tmp_path / "baseline.json")
    out: list[str] = []
    rc = driver.run(["retina_tpu/y.py"], root=root, out=out.append)
    assert rc == 1
    assert any("y.py" in line and "RT101" in line for line in out)
    assert not any("x.py:" in line for line in out)


def test_shipped_baseline_is_empty():
    from tools.analyze.core import load_baseline
    assert load_baseline(driver.BASELINE_PATH) == {}


# ------------------------------------------------------- RT205 lock order

LOCK_ORDER = """
    import threading

    class Supervisor:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
            self.x = 0

        def worker(self):
            with self._a:
                with self._b:
                    self.x = 1

        def other(self):
            with self._b:{noqa}
                with self._a:
                    self.x = 2
"""


def test_rt205_opposite_order_fires():
    fs = run_rule(rt200.check, LOCK_ORDER.format(noqa=""))
    assert "RT205" in codes(fs), fs
    f = [x for x in fs if x.code == "RT205"][0]
    assert "_a" in f.message and "_b" in f.message
    assert "Supervisor" in f.key


def test_rt205_same_order_no_fire():
    src = """
    import threading

    class Supervisor:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
            self.x = 0

        def worker(self):
            with self._a:
                with self._b:
                    self.x = 1

        def other(self):
            with self._a:
                with self._b:
                    self.x = 2
    """
    assert "RT205" not in codes(run_rule(rt200.check, src))


def test_rt205_noqa_on_reported_line():
    # The finding anchors at the earliest witness site: the inner
    # acquisition in `worker` (acquires _b while holding _a).
    src = LOCK_ORDER.format(noqa="").replace(
        "with self._b:\n",
        "with self._b:  # noqa: RT205\n", 1)
    assert "RT205" not in codes(run_rule(rt200.check, src))


def test_rt205_cross_method_cycle_via_calls():
    # Neither method nests two `with` blocks directly; the cycle only
    # exists through the call graph (union-held-set propagation).
    src = """
    import threading

    class Supervisor:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def _grab_b(self):
            with self._b:
                pass

        def _grab_a(self):
            with self._a:
                pass

        def fwd(self):
            with self._a:
                self._grab_b()

        def rev(self):
            with self._b:
                self._grab_a()
    """
    assert "RT205" in codes(run_rule(rt200.check, src))


def test_rt205_single_direction_no_fire():
    src = """
    import threading

    class Supervisor:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def fwd(self):
            with self._a:
                with self._b:
                    pass

        def also_fwd(self):
            with self._a:
                with self._b:
                    pass
    """
    assert "RT205" not in codes(run_rule(rt200.check, src))


# --------------------------------------------- RT305 registry coverage

def test_rt305_unregistered_jit_fires():
    src = """
    import jax

    def build():
        return jax.jit(lambda x: x + 1)
    """
    fs = run_rule(rt300.check, src)
    assert codes(fs) == ["RT305"], fs
    assert "build" in fs[0].message


def test_rt305_device_entry_covers_site():
    src = """
    import jax
    from retina_tpu.devprog import device_entry

    @device_entry("fake.build", kind="jit")
    def build():
        return jax.jit(lambda x: x + 1)
    """
    assert run_rule(rt300.check, src) == []


def test_rt305_partial_jit_decorator():
    # functools.partial(jax.jit, ...) creates the program too.
    src = """
    import jax
    from functools import partial

    def build():
        step = partial(jax.jit, donate_argnums=(0,))(lambda s: s)
        return step
    """
    assert "RT305" in codes(run_rule(rt300.check, src))


def test_rt305_shard_map_fires_and_noqa():
    src = """
    from jax.experimental.shard_map import shard_map

    def build(mesh):
        return shard_map(lambda x: x, mesh=mesh)  # noqa: RT305
    """
    assert run_rule(rt300.check, src) == []
    assert "RT305" in codes(
        run_rule(rt300.check, src.replace("  # noqa: RT305", "")))


def test_rt305_only_under_retina_tpu():
    src = """
    import jax

    def helper():
        return jax.jit(lambda x: x)
    """
    assert run_rule(rt300.check, src, rel="tools/whatever.py") == []
    assert run_rule(rt300.check, src, rel="tests/t.py") == []


# -------------------------------------------- interval engine (RT301)

def _jaxpr(fn, *args):
    import jax

    return jax.make_jaxpr(fn)(*args)


def test_interval_u32_add_wraps():
    import jax.numpy as jnp

    from tools.analyze.interval import analyze_jaxpr

    j = _jaxpr(lambda a, b: a + b, jnp.uint32(0), jnp.uint32(0))
    big = float(2 ** 31)
    res = analyze_jaxpr(j, [(0.0, big), (0.0, big)])
    assert res.wrapped and not res.unknown, res
    assert not res.ok


def test_interval_u32_add_in_range_ok():
    import jax.numpy as jnp

    from tools.analyze.interval import analyze_jaxpr

    j = _jaxpr(lambda a, b: a + b, jnp.uint32(0), jnp.uint32(0))
    res = analyze_jaxpr(j, [(0.0, 10.0), (0.0, 10.0)])
    assert res.ok, res
    assert res.out[0].hi == 20.0


def test_interval_definite_branch_prunes():
    # x <= 20 is definitely true for x in [0, 5]: the select must take
    # the then-arm and the poison arm's huge range must NOT leak out.
    import jax.numpy as jnp

    from tools.analyze.interval import analyze_jaxpr

    def f(x, y, z):
        return jnp.where(x <= 20, y, z)

    j = _jaxpr(f, jnp.uint32(0), jnp.uint32(0), jnp.uint32(0))
    res = analyze_jaxpr(j, [(0.0, 5.0), (3.0, 4.0), (100.0, 200.0)])
    assert res.ok, res
    assert res.out[0].hi == 4.0, res.out


def test_interval_scatter_add_wrap_and_ok():
    import jax.numpy as jnp

    from tools.analyze.interval import analyze_jaxpr

    def f(t, u, idx):
        return t.at[idx].add(u, mode="promise_in_bounds")

    t = jnp.zeros(4, jnp.uint32)
    u = jnp.zeros(2, jnp.uint32)
    idx = jnp.zeros(2, jnp.int32)
    j = _jaxpr(f, t, u, idx)
    big = float(2 ** 31)
    assert not analyze_jaxpr(
        j, [(0.0, big), (0.0, big), (0.0, 1.0)]).ok
    assert analyze_jaxpr(
        j, [(0.0, 100.0), (0.0, 100.0), (0.0, 1.0)]).ok


def test_interval_unknown_primitive_is_loud():
    import jax.numpy as jnp

    from tools.analyze.interval import analyze_jaxpr

    j = _jaxpr(lambda x: jnp.sin(x), jnp.float32(0))
    res = analyze_jaxpr(j, [(0.0, 1.0)])
    assert "sin" in res.unknown
    assert not res.ok


def test_rt301_envelope_catches_inflated_traffic():
    # The shipped envelope (tools/analyze/devlower.py) proves the
    # hash-table rescale counters cannot wrap; feed the SAME real
    # jaxpr an envelope 2^7 times larger and the wrap must be caught.
    from tools.analyze import devlower
    from tools.analyze.interval import analyze_jaxpr

    jaxpr, intervals = devlower.ht_rescale_target()
    res = analyze_jaxpr(jaxpr, [(float(a), float(b))
                                for a, b in intervals])
    assert res.ok, (res.wrapped, res.unknown)
    inflated = [
        (float(a), float(b) * 128.0) for a, b in intervals
    ]
    assert analyze_jaxpr(jaxpr, inflated).wrapped


# ------------------------------------------ device pass finding paths

def test_device_pass_findings_are_baselinable(tmp_path, monkeypatch):
    # A device finding keyed on the entry name must suppress via
    # baseline.json exactly like AST findings do.
    from tools.analyze.core import Finding

    monkeypatch.setattr(
        driver, "BASELINE_PATH", tmp_path / "baseline.json")
    fake = Finding(
        path="retina_tpu/models/pipeline.py", line=1, code="RT302",
        message="synthetic", key="RT302:pipeline.step:arg3")
    monkeypatch.setattr(
        driver, "analyze", lambda root=None, device=False: [fake])
    out: list[str] = []
    assert driver.run([], root=REPO, out=out.append) == 1
    save_baseline(tmp_path / "baseline.json",
                  {"RT302:pipeline.step:arg3": "reviewed: synthetic"})
    out.clear()
    assert driver.run([], root=REPO, out=out.append) == 0
    assert any("1 baselined" in line for line in out)


# ------------------------------------------------- RT400 hot-path

def run_rt400(tmp_path, files: dict[str, str]):
    """Program-rule runner: write the fixture tree, run rt400 over it."""
    ctxs = []
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        ctxs.append(FileCtx(p, rel, p.read_text()))
    rep = Reporter()
    rt400.check_program(ctxs, rep, tmp_path)
    return rep.findings


HOT_CALLER = """
    from retina_tpu.helper import stage

    class Pump:
        def drain(self):  # hot-path: event
            stage()
"""


def test_rt400_cross_module_transitive_sleep(tmp_path):
    # The blocking fact lives two modules away from the declared root;
    # the finding lands AT the fact with the root chain in the message.
    found = run_rt400(tmp_path, {
        "retina_tpu/hot.py": HOT_CALLER,
        "retina_tpu/helper.py": """
            import time

            def stage():
                deeper()

            def deeper():
                time.sleep(0.5)
        """,
    })
    assert codes(found) == ["RT400"]
    f = found[0]
    assert f.path == "retina_tpu/helper.py"
    assert "Pump.drain" in f.message and "lane=event" in f.message
    # stable key: survives line drift, usable from baseline.json
    assert f.key == "RT400:retina_tpu/helper.py:deeper:sleep"


def test_rt400_bounded_waits_do_not_fire(tmp_path):
    # Bounded waits and _nowait are the sanctioned backpressure idiom;
    # put on a provably unbounded queue never blocks (RT102's beat).
    found = run_rt400(tmp_path, {
        "retina_tpu/hot.py": """
            import queue

            class Pump:
                def __init__(self):
                    self.uq = queue.Queue()
                    self.bq = queue.Queue(maxsize=4)

                def drain(self, inq):  # hot-path: close
                    self._space.wait(0.02)
                    inq.get(timeout=1.0)
                    self.uq.put(1)
                    self.bq.put_nowait(2)
        """,
    })
    assert found == []


def test_rt400_bounded_queue_put_fires(tmp_path):
    found = run_rt400(tmp_path, {
        "retina_tpu/hot.py": """
            import queue

            class Pump:
                def __init__(self):
                    self.bq = queue.Queue(maxsize=4)

                def drain(self):  # hot-path: close
                    self.bq.put(1)
        """,
    })
    assert codes(found) == ["RT400"]
    assert "Queue.put" in found[0].message


def test_rt400_may_block_hatch_stops_descent(tmp_path):
    found = run_rt400(tmp_path, {
        "retina_tpu/hot.py": HOT_CALLER,
        "retina_tpu/helper.py": """
            import time

            def stage():  # may-block: reviewed — startup spill path, bounded by disk speed
                time.sleep(0.5)
        """,
    })
    assert found == []


def test_rt400_empty_may_block_reason_is_malformed(tmp_path):
    found = run_rt400(tmp_path, {
        "retina_tpu/hot.py": """
            import time

            def stage():  # may-block:
                time.sleep(0.5)
        """,
    })
    assert codes(found) == ["RT400"]
    assert "may-block" in found[0].message


def test_rt400_noqa_at_site(tmp_path):
    found = run_rt400(tmp_path, {
        "retina_tpu/hot.py": HOT_CALLER,
        "retina_tpu/helper.py": """
            import time

            def stage():
                time.sleep(0.5)  # noqa: RT400 — harness-only simulated latency
        """,
    })
    assert found == []


def test_rt400_unknown_lane_is_malformed(tmp_path):
    found = run_rt400(tmp_path, {
        "retina_tpu/hot.py": """
            def f():  # hot-path: turbo
                pass
        """,
    })
    assert codes(found) == ["RT400"]
    assert "turbo" in found[0].message


def test_rt401_cold_device_entry_call_fires(tmp_path):
    src = """
        import jax

        def device_entry(name, kind=None):
            def wrap(f):
                return f
            return wrap

        class Eng:
            @device_entry("eng.tbl", kind="jit")
            def _tbl_fn(self):
                return jax.jit(lambda a: a)

            def hot(self):  # hot-path: event
                self._tbl_fn()(1)
    """
    found = run_rt400(tmp_path, {"retina_tpu/eng.py": src})
    assert codes(found) == ["RT401"]
    assert "Eng._tbl_fn" in found[0].message
    # jax.jit INSIDE the @device_entry builder is not double-reported:
    # the call-site rule governs.
    assert found[0].key == "RT401:retina_tpu/eng.py:Eng.hot:Eng._tbl_fn"


def test_rt401_warm_marker_in_caller_satisfies(tmp_path):
    # Disk-cache routing at the call site (fold.py idiom): the caller
    # mentions _disk_compiled, so the builder call is warm-routed.
    found = run_rt400(tmp_path, {
        "retina_tpu/eng.py": """
            import jax

            def device_entry(name, kind=None):
                def wrap(f):
                    return f
                return wrap

            class Eng:
                @device_entry("eng.tbl", kind="jit")
                def _tbl_fn(self):
                    return jax.jit(lambda a: a)

                def hot(self):  # hot-path: event
                    fn = self._tbl_fn()
                    ex = _disk_compiled("tbl", fn, ())
                    ex(1)
        """,
    })
    assert found == []


def test_rt401_bare_jit_dispatch_fires(tmp_path):
    found = run_rt400(tmp_path, {
        "retina_tpu/eng.py": """
            import jax

            def hot(x):  # hot-path: query
                return jax.jit(lambda a: a + 1)(x)
        """,
    })
    assert codes(found) == ["RT401"]
    assert "bare jax.jit" in found[0].message


def test_rt402_untrimmed_append_and_per_record_alloc(tmp_path):
    found = run_rt400(tmp_path, {
        "retina_tpu/bank.py": """
            class Bank:
                def __init__(self):
                    self.rows = []

                def tap(self, records):  # hot-path: event
                    for r in records:
                        self.rows.append({"k": r})
        """,
    })
    got = codes(found)
    assert got.count("RT402") == 2, found  # append + dict-in-loop
    msgs = " ".join(f.message for f in found)
    assert "rows" in msgs and "per-record loop" in msgs


def test_rt402_trimmed_or_reset_containers_do_not_fire(tmp_path):
    # A per-window reset (plain or annotated assign outside __init__)
    # or an explicit trim bounds the container.
    found = run_rt400(tmp_path, {
        "retina_tpu/bank.py": """
            class Bank:
                def __init__(self):
                    self.rows = []
                    self.hist = []

                def begin_window(self):
                    self.rows: list = []

                def tap(self, rec):  # hot-path: event
                    self.rows.append(rec)
                    self.hist.append(rec)
                    del self.hist[:-16]
        """,
    })
    assert found == []


def test_rt402_only_on_event_lane(tmp_path):
    # Window-rate (close lane) growth is not per-event growth.
    found = run_rt400(tmp_path, {
        "retina_tpu/bank.py": """
            class Bank:
                def __init__(self):
                    self.rollups = []

                def close(self, win):  # hot-path: close
                    self.rollups.append(win)
        """,
    })
    assert found == []


def test_rt403_lock_convoy(tmp_path):
    src = """
        import time

        class Svc:
            def hot(self):  # hot-path: event
                with self._lock:
                    self.n = 1

            def checkpoint(self):
                with self._lock:
                    time.sleep(5)
    """
    found = run_rt400(tmp_path, {"retina_tpu/svc.py": src})
    got = [f for f in found if f.code == "RT403"]
    assert len(got) == 1, found
    assert "Svc.checkpoint" in got[0].message
    assert "lock convoy" in got[0].message
    # Witness fixed (blocking moved outside the lock): convoy gone.
    fixed = run_rt400(tmp_path, {
        "retina_tpu/svc.py": """
            import time

            class Svc:
                def hot(self):  # hot-path: event
                    with self._lock:
                        self.n = 1

                def checkpoint(self):
                    with self._lock:
                        snap = self.n
                    time.sleep(5)
        """,
    })
    assert [f for f in fixed if f.code == "RT403"] == []


def test_rt400_with_open_is_file_io(tmp_path):
    # ``with open(path) as f:`` — the context expression IS the fact.
    found = run_rt400(tmp_path, {
        "retina_tpu/hot.py": """
            def spill(path):  # hot-path: transport
                with open(path, "wb") as f:
                    f.flush()
        """,
    })
    assert codes(found) == ["RT400"]
    assert "file IO" in found[0].message


def test_rt400_structural_roots_resolve_on_real_tree():
    """Every STRUCTURAL_ROOTS entry must still name a real function —
    a rename would otherwise silently drop a whole lane's coverage."""
    ctxs = driver.parse_all(driver.REPO_ROOT)
    good = [c for c in ctxs if c.syntax_error is None]
    prog = rt400.Program(good)
    for rel_sfx, cls, meth, lane in rt400.STRUCTURAL_ROOTS:
        qual = f"{cls}.{meth}" if cls else meth
        assert lane in rt400.LANES, (rel_sfx, lane)
        assert any(
            rel.endswith(rel_sfx) and q == qual
            for (rel, q) in prog.funcs
        ), f"structural root no longer resolves: {rel_sfx}:{qual}"
