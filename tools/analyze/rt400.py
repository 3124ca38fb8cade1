"""RT400-RT403 — hot-path reachability: no blocking, no cold
compiles, no unbounded allocation on the event path.

Every recent PR re-fixed the same invariant by hand ("window closes
never serialize the feed", "offer() never blocks the close lane", "no
hot-path locks or allocation" in the recorder, "transport handlers
never pay a compile").  This pass machine-checks it: a whole-program
transitive-reachability walk over declared hot-path ROOTS flags,
anywhere reachable from a root:

  RT400 blocking primitives — time.sleep, Thread.join (no timeout),
        blocking socket send/recv/accept, subprocess, file IO,
        Queue.put/get without _nowait / timeout= / block=False
        (put on a provably UNBOUNDED queue never blocks and is not
        flagged — that is RT102's department), Event/Condition .wait()
        without a timeout.  Bounded waits (``ev.wait(0.02)``,
        ``q.get(timeout=...)``, ``t.join(timeout=...)``) are the
        sanctioned backpressure idiom and never fire.
  RT401 potential cold compiles — a bare ``jax.jit`` / ``shard_map``
        dispatch, or a call into a ``@device_entry`` builder that is
        not AOT-warmed / disk-cache-routed (neither the builder nor
        the calling function references ``_compile_cached`` /
        ``_disk_compiled`` / ``aot_disk`` / ``aot_cache``) — the
        static face of the ``fleet_merge_async`` bug class.
  RT402 unbounded per-event allocation (EVENT lane only) —
        ``self.<attr>.append/extend`` (or ``+=``) where the class
        never trims/resets the container, and object building inside
        a loop that iterates a per-record parameter.  Per-call locals
        die with the call and are fine; per-WINDOW containers that a
        non-__init__ method resets or slices are bounded and fine.
  RT403 lock convoy — a hot path acquires a lock that some OTHER
        function holds across a blocking call: the hot thread can
        convoy behind the blocker even though the hot code itself
        never blocks.  Joins the RT400 blocking facts with rt200-style
        ``with self._lock:`` lock facts.

Lane model (docs/static-analysis.md)
------------------------------------
Roots carry a LANE describing the cadence of the path:

  event      per-record rate: engine dispatch, feed-worker fill
             loops, recorder begin/record, record_hook taps.
             All four rules apply.
  close      per-window close on the device proxy: close-lane impl,
             ring/shipper offer.  RT400/401/403 (window-rate
             allocation is fine).
  transport  RPC / pubsub handler threads: Fleet Ship handlers,
             aggregator ingest.  RT400/401/403.
  query      query handlers + the node-answer path.  RT400/401/403.

Roots are declared with ``# hot-path: <lane>`` on a def line, or
derived structurally from STRUCTURAL_ROOTS (the canonical engine /
feed / recorder / shipper / ring / aggregator / hubble / detect /
fleetquery entries — tests/test_analyze.py pins that every structural
entry still resolves against the real tree, so the table cannot rot).

Escape hatches (house style)
----------------------------
  * ``# may-block: <reason>`` on a callee's def line: the walk does
    not descend into it and its facts are excused — the written
    reason is the review.  (For RT403 the callee still counts as
    blocking when some function holds a lock across it: the
    annotation says "this blocks and that is OK *here*", not "this
    does not block".)
  * ``# noqa: RT40x — reason`` on the reported line.
  * the stable-key baseline (tools/analyze/baseline.json).

Resolution is deliberately precision-biased: ``self.m()``, module
functions, ``from``-imports, ``self.<attr>``/local receivers typed by
construction or annotation, return-annotated factories
(``get_recorder().begin``), ``list[T]``-element iteration, and
virtual dispatch from an abstract base to its subclasses.  Unresolved
calls contribute no edges and no facts — a missed finding beats a
wall of false positives (same stance as rt200).
``run_on_device(fn)`` / ``submit_on_device(fn)`` are call edges into
``fn`` (the proxy hop is the sanctioned mechanism, its wait IS the
device work), never blocking primitives themselves.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path

from tools.analyze.core import FileCtx, Reporter

LANES = ("event", "close", "transport", "query")

HOT_PATH_RE = re.compile(r"#\s*hot-path:\s*([A-Za-z_-]+)")
MAY_BLOCK_RE = re.compile(r"#\s*may-block:(?P<reason>[^#]*)")

# Canonical structural roots: (path suffix, class or None, method,
# lane).  tests/test_analyze.py::test_rt400_structural_roots_resolve
# asserts every entry resolves on the real tree.
STRUCTURAL_ROOTS = (
    ("retina_tpu/engine.py", "SketchEngine", "step_records", "event"),
    ("retina_tpu/engine.py", "SketchEngine", "_dispatch", "event"),
    ("retina_tpu/engine.py", "SketchEngine", "_build_quantum", "event"),
    ("retina_tpu/engine.py", "SketchEngine", "_close_window_impl",
     "close"),
    ("retina_tpu/engine.py", "SketchEngine", "_submit_close_window",
     "close"),
    ("retina_tpu/parallel/feed.py", "FeedWorker", "_loop", "event"),
    ("retina_tpu/parallel/feed.py", "FeedWorker", "push", "event"),
    ("retina_tpu/obs/recorder.py", "FlightRecorder", "span", "event"),
    ("retina_tpu/obs/recorder.py", "Span", "end", "event"),
    ("retina_tpu/fleet/shipper.py", "SnapshotShipper", "offer", "close"),
    ("retina_tpu/timetravel/ring.py", "SnapshotRing", "offer", "close"),
    ("retina_tpu/fleet/aggregator.py", "FleetAggregator", "ingest",
     "transport"),
    ("retina_tpu/hubble/server.py", "HubbleServer", "_fleet_ship",
     "transport"),
    ("retina_tpu/detect/base.py", "DetectorBank", "observe", "event"),
    ("retina_tpu/fleetquery/service.py", "FleetQueryService", "handle",
     "query"),
    ("retina_tpu/fleetquery/service.py", "LocalNodeClient", "query",
     "query"),
    ("retina_tpu/timetravel/query.py", "QueryService", "handle",
     "query"),
)

DEVICE_PROXY_FUNCS = {"run_on_device", "submit_on_device"}

# Source markers that say "this function routes compiles through the
# AOT disk cache" (engine._compile_cached, timetravel.fold's
# _disk_compiled wrapper).  Either the builder or its caller carrying
# one satisfies RT401.
WARM_MARKERS = ("_compile_cached", "_disk_compiled", "aot_disk",
                "aot_cache")

# Parameter names that mean "one block of per-event records" — loops
# iterating one of these row-by-row are per-EVENT loops (RT402).
RECORD_PARAMS = {"records", "recs", "rows", "events", "rec"}

_THREADISH_RE = re.compile(r"thread|proc|worker", re.I)
_SOCKISH_RE = re.compile(r"sock|conn", re.I)
_QUEUEISH_RE = re.compile(r"(^|_)q$|queue", re.I)

# Pseudo-types for receivers we can classify without a class in the
# universe.
Q_UNBOUNDED = "<queue-unbounded>"
Q_BOUNDED = "<queue-bounded>"
T_STR = "<str>"
T_THREAD = "<thread>"


@dataclasses.dataclass
class Fact:
    """One direct blocking/compile/alloc observation in a function."""

    kind: str  # "sleep" | "join" | "socket" | "subprocess" | ...
    lineno: int
    detail: str


@dataclasses.dataclass
class CallSite:
    spec: tuple  # resolution spec, see _classify_call
    lineno: int
    with_depth: int  # how many enclosing with-acquisitions


@dataclasses.dataclass
class Acquire:
    lock: str  # qualified lock id
    lineno: int
    facts_inside: bool
    calls_inside: list[tuple]  # resolution specs made under the lock


class FuncInfo:
    def __init__(self, ctx: FileCtx, node, qualname: str, cls=None):
        self.ctx = ctx
        self.rel = ctx.rel
        self.node = node
        self.qualname = qualname  # "Class.m" | "f" | "f.closure"
        self.cls = cls  # ClassInfo | None
        self.lineno = node.lineno
        self.facts: list[Fact] = []
        self.jit_sites: list[int] = []
        self.entry_calls: list[tuple[str, int]] = []  # (target qual, ln)
        self.calls: list[CallSite] = []
        self.acquires: list[Acquire] = []
        self.appends: list[tuple[str, int, str]] = []  # (attr, ln, op)
        self.loop_allocs: list[tuple[int, str]] = []
        self.local_types: dict[str, object] = {}
        line = ctx.line_at(node.lineno)
        m = HOT_PATH_RE.search(line)
        self.lane_annot = m.group(1) if m else None
        self.lane_annot_line = node.lineno if m else 0
        mb = MAY_BLOCK_RE.search(line)
        self.may_block = mb.group("reason").strip() if mb else None
        self.may_block_present = mb is not None
        self.is_device_entry = any(
            (isinstance(d, ast.Call)
             and ((isinstance(d.func, ast.Name)
                   and d.func.id == "device_entry")
                  or (isinstance(d.func, ast.Attribute)
                      and d.func.attr == "device_entry")))
            for d in node.decorator_list
        )
        end = getattr(node, "end_lineno", node.lineno) or node.lineno
        seg = "\n".join(ctx.lines[node.lineno - 1:end])
        self.warm_routed = any(m in seg for m in WARM_MARKERS)
        body = node.body
        self.abstract = (
            len(body) <= 2
            and isinstance(body[-1], ast.Raise)
            and "NotImplementedError" in ast.dump(body[-1])
        )


class ClassInfo:
    def __init__(self, ctx: FileCtx, node: ast.ClassDef):
        self.ctx = ctx
        self.rel = ctx.rel
        self.node = node
        self.name = node.name
        self.methods: dict[str, FuncInfo] = {}
        self.bases = [
            b.id if isinstance(b, ast.Name)
            else b.attr if isinstance(b, ast.Attribute) else None
            for b in node.bases
        ]
        self.attr_types: dict[str, object] = {}
        self.attr_elem_types: dict[str, str] = {}
        # attrs assigned (plain =) in some non-__init__ method, or
        # trimmed with del-slice/pop/clear: growth is bounded per
        # window/call, not per process lifetime.
        self.trimmed_attrs: set[str] = set()


def _ann_name(ann) -> str | None:
    """Type annotation expr -> plain class name, unwrapping Optional/
    quotes; returns None for anything fancier."""
    if ann is None:
        return None
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        try:
            ann = ast.parse(ann.value, mode="eval").body
        except SyntaxError:
            return None
    if isinstance(ann, ast.Name):
        return ann.id
    if isinstance(ann, ast.Attribute):
        return ann.attr
    if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
        left = _ann_name(ann.left)
        if left is not None and left != "None":
            return left
        return _ann_name(ann.right)
    return None


def _ann_elem_name(ann) -> str | None:
    """``list[T]`` / ``tuple[T, ...]`` annotation -> T's name."""
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        try:
            ann = ast.parse(ann.value, mode="eval").body
        except SyntaxError:
            return None
    if (isinstance(ann, ast.Subscript)
            and isinstance(ann.value, ast.Name)
            and ann.value.id in ("list", "tuple", "List", "Sequence")):
        sl = ann.slice
        if isinstance(sl, ast.Tuple) and sl.elts:
            sl = sl.elts[0]
        return _ann_name(sl)
    return None


def _call_type(call: ast.Call) -> object | None:
    """Constructor-call expr -> pseudo/class-name type."""
    f = call.func
    name = (f.id if isinstance(f, ast.Name)
            else f.attr if isinstance(f, ast.Attribute) else None)
    if name == "Queue":
        maxsize = None
        if call.args:
            maxsize = call.args[0]
        for kw in call.keywords:
            if kw.arg == "maxsize":
                maxsize = kw.value
        if maxsize is None or (
                isinstance(maxsize, ast.Constant) and maxsize.value == 0):
            return Q_UNBOUNDED
        return Q_BOUNDED
    if name == "Thread":
        return T_THREAD
    return name


class Program:
    """Whole-program index: every function/method in the retina_tpu
    tree, with resolved call edges, blocking facts and lock facts."""

    def __init__(self, ctxs: list[FileCtx]):
        self.funcs: dict[tuple[str, str], FuncInfo] = {}
        self.classes: dict[tuple[str, str], ClassInfo] = {}
        self.class_by_name: dict[str, list[ClassInfo]] = {}
        self.func_by_name: dict[str, list[FuncInfo]] = {}
        self.imports: dict[str, dict[str, tuple[str | None, str]]] = {}
        self.subclasses: dict[str, list[ClassInfo]] = {}
        self.ctxs = [c for c in ctxs
                     if c.rel.startswith("retina_tpu/")
                     and c.tree is not None]
        for ctx in self.ctxs:
            self._index_file(ctx)
        for cls_list in self.class_by_name.values():
            for ci in cls_list:
                for b in ci.bases:
                    if b:
                        self.subclasses.setdefault(b, []).append(ci)
        for fi in list(self.funcs.values()):
            _FuncWalker(self, fi).walk()

    # -- indexing ------------------------------------------------------
    def _index_file(self, ctx: FileCtx) -> None:
        imps: dict[str, tuple[str | None, str]] = {}
        self.imports[ctx.rel] = imps
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                rel = node.module.replace(".", "/") + ".py"
                for a in node.names:
                    imps[a.asname or a.name] = (rel, a.name)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    rel = a.name.replace(".", "/") + ".py"
                    imps[a.asname or a.name.split(".")[0]] = (rel, "")
        for node in ctx.tree.body:
            if isinstance(node, ast.ClassDef):
                ci = ClassInfo(ctx, node)
                self.classes[(ctx.rel, ci.name)] = ci
                self.class_by_name.setdefault(ci.name, []).append(ci)
                for stmt in node.body:
                    if isinstance(stmt, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        fi = FuncInfo(ctx, stmt,
                                      f"{ci.name}.{stmt.name}", cls=ci)
                        ci.methods[stmt.name] = fi
                        self._register(fi)
                self._collect_class_types(ci)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fi = FuncInfo(ctx, node, node.name)
                self._register(fi)

    def _register(self, fi: FuncInfo) -> None:
        self.funcs[(fi.rel, fi.qualname)] = fi
        self.func_by_name.setdefault(
            fi.qualname.split(".")[-1], []).append(fi)

    def _collect_class_types(self, ci: ClassInfo) -> None:
        init = ci.methods.get("__init__")
        param_anns: dict[str, ast.expr] = {}
        if init is not None:
            for a in init.node.args.args + init.node.args.kwonlyargs:
                if a.annotation is not None:
                    param_anns[a.arg] = a.annotation
            for node in ast.walk(init.node):
                if isinstance(node, (ast.Assign, ast.AnnAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    val = node.value
                    for t in targets:
                        if not (isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"):
                            continue
                        if (isinstance(node, ast.AnnAssign)
                                and node.annotation is not None):
                            el = _ann_elem_name(node.annotation)
                            if el:
                                ci.attr_elem_types[t.attr] = el
                            nm = _ann_name(node.annotation)
                            if nm:
                                ci.attr_types.setdefault(t.attr, nm)
                        ty = self._value_type(val, param_anns, ci)
                        if ty is not None:
                            ci.attr_types.setdefault(t.attr, ty)
                        el = self._value_elem_type(val, param_anns)
                        if el is not None:
                            ci.attr_elem_types.setdefault(t.attr, el)
        # trim / per-window-reset detection (source scan of the class)
        grown: set[str] = set()
        for m in ci.methods.values():
            for node in ast.walk(m.node):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("append", "extend")
                        and isinstance(node.func.value, ast.Attribute)
                        and isinstance(node.func.value.value, ast.Name)
                        and node.func.value.value.id == "self"):
                    grown.add(node.func.value.attr)
                elif (isinstance(node, ast.AugAssign)
                        and isinstance(node.target, ast.Attribute)
                        and isinstance(node.target.value, ast.Name)
                        and node.target.value.id == "self"):
                    grown.add(node.target.attr)
        start = ci.node.lineno - 1
        end = getattr(ci.node, "end_lineno", None) or len(ci.ctx.lines)
        seg = "\n".join(ci.ctx.lines[start:end])
        for attr in grown:
            pats = (f"del self.{attr}[", f"self.{attr}.popleft(",
                    f"self.{attr}.pop(0", f"self.{attr}.clear(",
                    f"self.{attr} = self.{attr}[")
            if any(p in seg for p in pats):
                ci.trimmed_attrs.add(attr)
                continue
            if ci.attr_types.get(attr) == "deque":
                # deque(maxlen=...) bounds itself; a bare deque() is
                # checked via the constructor args below.
                init_line = ""
                for mn, mi in ci.methods.items():
                    if mn != "__init__":
                        continue
                    for node in ast.walk(mi.node):
                        if (isinstance(node, ast.Assign)
                                and isinstance(node.value, ast.Call)):
                            for t in node.targets:
                                if (isinstance(t, ast.Attribute)
                                        and t.attr == attr):
                                    init_line = ast.dump(node.value)
                if "maxlen" in init_line:
                    ci.trimmed_attrs.add(attr)
                    continue
            for mn, mi in ci.methods.items():
                if mn in ("__init__", "__post_init__"):
                    continue
                reset = any(
                    (isinstance(node, ast.Assign)
                     and any(isinstance(t, ast.Attribute)
                             and isinstance(t.value, ast.Name)
                             and t.value.id == "self"
                             and t.attr == attr
                             for t in node.targets))
                    or (isinstance(node, ast.AnnAssign)
                        and node.value is not None
                        and isinstance(node.target, ast.Attribute)
                        and isinstance(node.target.value, ast.Name)
                        and node.target.value.id == "self"
                        and node.target.attr == attr)
                    for node in ast.walk(mi.node)
                )
                if reset:
                    ci.trimmed_attrs.add(attr)
                    break

    def _value_type(self, val, param_anns, ci=None) -> object | None:
        if isinstance(val, ast.BoolOp) and val.values:
            return self._value_type(val.values[-1], param_anns, ci)
        if isinstance(val, ast.Call):
            f = val.func
            fname = (f.id if isinstance(f, ast.Name)
                     else f.attr if isinstance(f, ast.Attribute)
                     else None)
            if fname and fname.startswith("get_"):
                for cand in self.func_by_name.get(fname, ()):
                    ret = _ann_name(cand.node.returns)
                    if ret:
                        return ret
            return _call_type(val)
        if isinstance(val, ast.Name) and val.id in param_anns:
            return _ann_name(param_anns[val.id])
        if isinstance(val, ast.Constant) and isinstance(val.value, str):
            return T_STR
        return None

    def _value_elem_type(self, val, param_anns) -> str | None:
        """``self.x = list(param)`` with ``param: list[T]`` -> T."""
        if (isinstance(val, ast.Call) and isinstance(val.func, ast.Name)
                and val.func.id == "list" and val.args
                and isinstance(val.args[0], ast.Name)
                and val.args[0].id in param_anns):
            return _ann_elem_name(param_anns[val.args[0].id])
        return None

    # -- resolution ----------------------------------------------------
    def resolve_class(self, rel: str, name: str) -> ClassInfo | None:
        ci = self.classes.get((rel, name))
        if ci is not None:
            return ci
        imp = self.imports.get(rel, {}).get(name)
        if imp is not None and imp[1]:
            return self.classes.get((imp[0], imp[1]))
        cands = self.class_by_name.get(name, ())
        return cands[0] if len(cands) == 1 else None

    def resolve_func(self, rel: str, name: str) -> FuncInfo | None:
        fi = self.funcs.get((rel, name))
        if fi is not None:
            return fi
        imp = self.imports.get(rel, {}).get(name)
        if imp is not None and imp[1]:
            return self.funcs.get((imp[0], imp[1]))
        return None

    def resolve_method(
        self, ci: ClassInfo, name: str
    ) -> list[FuncInfo]:
        """C.name with abstract-base virtual dispatch."""
        seen: set[str] = set()
        cur: ClassInfo | None = ci
        fi = None
        while cur is not None and cur.name not in seen:
            seen.add(cur.name)
            fi = cur.methods.get(name)
            if fi is not None:
                break
            nxt = None
            for b in cur.bases:
                if b:
                    nxt = self.resolve_class(cur.rel, b)
                    if nxt is not None:
                        break
            cur = nxt
        if fi is None:
            return []
        if not fi.abstract:
            return [fi]
        out = [fi]
        stack = [ci.name]
        visited = set()
        while stack:
            base = stack.pop()
            if base in visited:
                continue
            visited.add(base)
            for sub in self.subclasses.get(base, ()):
                m = sub.methods.get(name)
                if m is not None:
                    out.append(m)
                stack.append(sub.name)
        return out


class _FuncWalker:
    """Single AST walk of one function: collects typed locals, call
    sites, blocking facts, jit facts, alloc facts and lock facts."""

    def __init__(self, prog: Program, fi: FuncInfo):
        self.prog = prog
        self.fi = fi
        self.types: dict[str, object] = {}
        args = fi.node.args
        for a in (args.args + args.kwonlyargs
                  + ([args.vararg] if args.vararg else [])
                  + ([args.kwarg] if args.kwarg else [])):
            if a is not None and a.annotation is not None:
                nm = _ann_name(a.annotation)
                if nm:
                    self.types[a.arg] = nm
        self.record_params = {
            a.arg for a in args.args + args.kwonlyargs
            if a.arg in RECORD_PARAMS
        }
        self.local_defs: dict[str, str] = {}

    # receiver expr -> type (class name / pseudo-type) or None
    def _recv_type(self, node) -> object | None:
        fi = self.fi
        if isinstance(node, ast.Name):
            return self.types.get(node.id)
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self" and fi.cls is not None):
            return fi.cls.attr_types.get(node.attr)
        if isinstance(node, ast.Call):
            return self.prog._value_type(node, {}, fi.cls)
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return T_STR
        return None

    def _recv_name(self, node) -> str:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr
        return ""

    def walk(self) -> None:
        fi = self.fi
        for stmt in fi.node.body:
            self._visit(stmt, with_stack=[], loop_record=False)

    def _visit(self, n, with_stack: list[Acquire],
               loop_record: bool) -> None:
        fi = self.fi
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            pseudo = f"{fi.qualname}.{n.name}"
            sub = FuncInfo(fi.ctx, n, pseudo, cls=fi.cls)
            self.prog.funcs[(fi.rel, pseudo)] = sub
            self.local_defs[n.name] = pseudo
            _FuncWalker(self.prog, sub).walk()
            return
        if isinstance(n, ast.With):
            inner = list(with_stack)
            for item in n.items:
                lid = self._lock_id(item.context_expr)
                if lid is not None:
                    acq = Acquire(lid, n.lineno, False, [])
                    fi.acquires.append(acq)
                    inner.append(acq)
            # the context expressions themselves can be facts
            # (``with open(path) as f:`` is hot-path file IO)
            for item in n.items:
                self._visit(item.context_expr, inner, loop_record)
            for stmt in n.body:
                self._visit(stmt, inner, loop_record)
            return
        if isinstance(n, ast.For):
            rec_loop = loop_record or (
                isinstance(n.iter, ast.Name)
                and n.iter.id in self.record_params
            )
            for child in ast.iter_child_nodes(n):
                self._visit(child, with_stack, rec_loop)
            return
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
            ty = self.prog._value_type(n.value, {}, fi.cls)
            if ty is not None:
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        self.types[t.id] = ty
        if isinstance(n, ast.AugAssign):
            # ``self.x += [item]`` / ``+= f"..."`` is container/str
            # growth; ``self.n += len(block)`` is a scalar counter and
            # is fine — gate on an unambiguously sequence-building RHS.
            t = n.target
            if (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                    and isinstance(n.value, (ast.List, ast.ListComp,
                                             ast.JoinedStr))):
                fi.appends.append((t.attr, n.lineno, "+="))
        if loop_record and isinstance(
                n, (ast.ListComp, ast.DictComp, ast.SetComp, ast.Dict,
                    ast.List, ast.JoinedStr)):
            fi.loop_allocs.append(
                (n.lineno, type(n).__name__))
        if isinstance(n, ast.Call):
            self._classify_call(n, with_stack, loop_record)
        for child in ast.iter_child_nodes(n):
            self._visit(child, with_stack, loop_record)

    def _lock_id(self, node) -> str | None:
        fi = self.fi
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"):
            owner = fi.cls.name if fi.cls is not None else fi.qualname
            if ("lock" in node.attr.lower()
                    or "mutex" in node.attr.lower()):
                return f"{fi.rel}:{owner}.{node.attr}"
            ty = (fi.cls.attr_types.get(node.attr)
                  if fi.cls is not None else None)
            if ty in ("Lock", "RLock", "Condition"):
                return f"{fi.rel}:{owner}.{node.attr}"
            return None
        if isinstance(node, ast.Name) and "lock" in node.id.lower():
            return f"{fi.rel}:{node.id}"
        return None

    def _fact(self, kind: str, lineno: int, detail: str,
              with_stack: list[Acquire]) -> None:
        self.fi.facts.append(Fact(kind, lineno, detail))
        for acq in with_stack:
            acq.facts_inside = True

    def _classify_call(self, call: ast.Call,
                       with_stack: list[Acquire],
                       loop_record: bool) -> None:
        fi, prog = self.fi, self.prog
        func = call.func
        kwargs = {kw.arg for kw in call.keywords if kw.arg}
        has_timeout = "timeout" in kwargs or "timeout_s" in kwargs
        nonblocking = any(
            kw.arg == "block"
            and isinstance(kw.value, ast.Constant)
            and kw.value.value is False
            for kw in call.keywords
        ) or any(
            kw.arg == "blocking"
            and isinstance(kw.value, ast.Constant)
            and kw.value.value is False
            for kw in call.keywords
        )

        def add_call(spec: tuple) -> None:
            site = CallSite(spec, call.lineno, len(with_stack))
            fi.calls.append(site)
            for acq in with_stack:
                acq.calls_inside.append(spec)

        # jax.jit / pjit / shard_map dispatch sites
        fname = (func.id if isinstance(func, ast.Name)
                 else func.attr if isinstance(func, ast.Attribute)
                 else None)
        if fname in ("jit", "pjit", "shard_map") and not (
                isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id not in ("jax", "pjit")):
            fi.jit_sites.append(call.lineno)

        # run_on_device(fn) / submit_on_device(fn): edge into fn
        if fname in DEVICE_PROXY_FUNCS and call.args:
            tgt = call.args[0]
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                add_call(("self", tgt.attr))
            elif isinstance(tgt, ast.Name):
                if tgt.id in self.local_defs:
                    add_call(("local", self.local_defs[tgt.id]))
                else:
                    add_call(("name", tgt.id))
            return

        if isinstance(func, ast.Name):
            name = func.id
            if name in self.local_defs:
                add_call(("local", self.local_defs[name]))
                return
            if name == "open":
                self._fact("file-io", call.lineno, "open()", with_stack)
                return
            if name == "sleep":
                imp = prog.imports.get(fi.rel, {}).get("sleep")
                if imp and imp[0] == "time.py":
                    self._fact("sleep", call.lineno, "time.sleep",
                               with_stack)
                    return
            add_call(("name", name))
            return

        if not isinstance(func, ast.Attribute):
            return
        meth = func.attr
        recv = func.value

        # module-qualified primitives
        if isinstance(recv, ast.Name):
            base = recv.id
            if base == "time" and meth == "sleep":
                self._fact("sleep", call.lineno, "time.sleep",
                           with_stack)
                return
            if base == "subprocess" and meth in (
                    "run", "Popen", "call", "check_call",
                    "check_output"):
                self._fact("subprocess", call.lineno,
                           f"subprocess.{meth}", with_stack)
                return
            if base == "os" and meth in ("system", "popen"):
                self._fact("subprocess", call.lineno, f"os.{meth}",
                           with_stack)
                return
            # module function call: mod.f()
            imp = prog.imports.get(fi.rel, {}).get(base)
            if imp is not None and not imp[1]:
                tgt = prog.funcs.get((imp[0], meth))
                if tgt is not None:
                    add_call(("func", imp[0], meth))
                    return

        # self.m() / typed-receiver method calls
        rtype = self._recv_type(recv)
        if (isinstance(recv, ast.Name) and recv.id == "self"
                and fi.cls is not None):
            add_call(("method", fi.cls.rel, fi.cls.name, meth))
            return
        if isinstance(rtype, str) and not rtype.startswith("<"):
            ci = prog.resolve_class(fi.rel, rtype)
            if ci is not None:
                add_call(("method", ci.rel, ci.name, meth))
                return
        # iteration element of a list[T] self attribute:
        # ``for d in self.detectors: d.judge(...)`` — handled via
        # local type seeding in _visit's For handling? cheap variant:
        if (isinstance(recv, ast.Name) and fi.cls is not None
                and recv.id not in self.types):
            elem = None
            for attr, el in fi.cls.attr_elem_types.items():
                # single-letter loop vars over self.<attr> iterables
                if recv.id in (el.lower()[:1], attr.rstrip("s"), "d"):
                    elem = el
                    break
            if elem is not None:
                ci = prog.resolve_class(fi.rel, elem)
                if ci is not None:
                    add_call(("method", ci.rel, ci.name, meth))
                    return

        # primitive heuristics on unresolved receivers
        rname = self._recv_name(recv)
        if meth == "join":
            if rtype == T_STR or isinstance(recv, ast.Constant):
                return
            if (rtype == T_THREAD or _THREADISH_RE.search(rname)) \
                    and not has_timeout and not call.args:
                self._fact("thread-join", call.lineno,
                           f"{rname or '?'}.join() without timeout",
                           with_stack)
            return
        if meth in ("recv", "recvfrom", "accept", "sendall"):
            if _SOCKISH_RE.search(rname):
                self._fact("socket", call.lineno,
                           f"{rname}.{meth}()", with_stack)
            return
        if meth in ("read_text", "read_bytes", "write_text",
                    "write_bytes"):
            self._fact("file-io", call.lineno, f"{rname}.{meth}()",
                       with_stack)
            return
        if meth in ("put", "get"):
            queueish = rtype in (Q_BOUNDED, Q_UNBOUNDED) or (
                rtype is None and _QUEUEISH_RE.search(rname))
            if not queueish or has_timeout or nonblocking:
                return
            if meth == "put" and rtype == Q_UNBOUNDED:
                return  # unbounded put never blocks (RT102's beat)
            self._fact("queue-" + meth, call.lineno,
                       f"{rname or 'queue'}.{meth}() without "
                       "timeout/_nowait", with_stack)
            return
        if meth == "wait":
            if not call.args and not has_timeout:
                self._fact("event-wait", call.lineno,
                           f"{rname or '?'}.wait() without timeout",
                           with_stack)
            return
        if meth.endswith("_nowait"):
            return

        # append/extend growth on self attributes (RT402a)
        if (meth in ("append", "extend", "appendleft")
                and isinstance(recv, ast.Attribute)
                and isinstance(recv.value, ast.Name)
                and recv.value.id == "self"):
            fi.appends.append((recv.attr, call.lineno, meth))
            return


# ----------------------------------------------------------------------
# reachability + reporting

def _roots(prog: Program, rep: Reporter) -> list[tuple[FuncInfo, str]]:
    roots: list[tuple[FuncInfo, str]] = []
    seen: set[tuple[str, str]] = set()
    for fi in prog.funcs.values():
        if fi.lane_annot is None:
            continue
        if fi.lane_annot not in LANES:
            rep.add(fi.ctx, fi.lineno, "RT400",
                    f"unknown hot-path lane {fi.lane_annot!r} "
                    f"(expected one of {', '.join(LANES)})",
                    key=f"RT400:{fi.rel}:{fi.qualname}:bad-lane")
            continue
        roots.append((fi, fi.lane_annot))
        seen.add((fi.rel, fi.qualname))
    for rel_sfx, cls, meth, lane in STRUCTURAL_ROOTS:
        qual = f"{cls}.{meth}" if cls else meth
        for (rel, q), fi in prog.funcs.items():
            if rel.endswith(rel_sfx) and q == qual \
                    and (rel, q) not in seen:
                roots.append((fi, lane))
                seen.add((rel, q))
    for fi in prog.funcs.values():
        if fi.may_block_present and not fi.may_block:
            rep.add(fi.ctx, fi.lineno, "RT400",
                    "empty may-block reason — the written reason IS "
                    "the review",
                    key=f"RT400:{fi.rel}:{fi.qualname}:bad-may-block")
    return roots


def _edges(prog: Program, fi: FuncInfo) -> list[FuncInfo]:
    out: list[FuncInfo] = []
    for site in fi.calls:
        out.extend(_resolve_spec(prog, fi, site.spec))
    return out


def _resolve_spec(prog: Program, fi: FuncInfo,
                  spec: tuple) -> list[FuncInfo]:
    """Memoized: the can_block fixpoint re-resolves the same specs
    every iteration."""
    cache = prog.__dict__.setdefault("_spec_cache", {})
    key = (fi.rel, fi.qualname, spec)
    hit = cache.get(key)
    if hit is None:
        hit = cache[key] = _resolve_spec_uncached(prog, fi, spec)
    return hit


def _resolve_spec_uncached(prog: Program, fi: FuncInfo,
                           spec: tuple) -> list[FuncInfo]:
    if spec[0] == "self" and fi.cls is not None:
        return prog.resolve_method(fi.cls, spec[1])
    if spec[0] == "local":
        sub = prog.funcs.get((fi.rel, spec[1]))
        return [sub] if sub is not None else []
    if spec[0] == "name":
        tgt = prog.resolve_func(fi.rel, spec[1])
        return [tgt] if tgt is not None else []
    if spec[0] == "func":
        tgt = prog.funcs.get((spec[1], spec[2]))
        return [tgt] if tgt is not None else []
    if spec[0] == "method":
        ci = prog.classes.get((spec[1], spec[2]))
        if ci is None:
            return []
        return prog.resolve_method(ci, spec[3])
    return []


_FACT_LABEL = {
    "sleep": "time.sleep", "thread-join": "Thread.join",
    "socket": "blocking socket call", "subprocess": "subprocess",
    "file-io": "file IO", "queue-put": "blocking Queue.put",
    "queue-get": "blocking Queue.get",
    "event-wait": "Event.wait without timeout",
}


def check_program(ctxs: list[FileCtx], rep: Reporter,
                  root: Path) -> None:
    prog = Program(ctxs)
    roots = _roots(prog, rep)
    if not roots:
        return

    # BFS per lane; remember one witness path per reached function.
    reached: dict[tuple[str, str], tuple[str, FuncInfo, tuple]] = {}
    for rfi, lane in roots:
        stack: list[tuple[FuncInfo, tuple]] = [(rfi, (rfi.qualname,))]
        while stack:
            fi, path = stack.pop()
            k = (fi.rel, fi.qualname)
            if k in reached:
                continue
            reached[k] = (lane, rfi, path)
            if fi.may_block is not None:
                continue  # reviewed escape hatch: do not descend
            for nxt in _edges(prog, fi):
                nk = (nxt.rel, nxt.qualname)
                if nk not in reached:
                    stack.append((nxt, path + (nxt.qualname,)))

    def via(path: tuple, lane: str) -> str:
        chain = " <- ".join(reversed(path[-4:]))
        return f"[lane={lane}] reached via {chain}"

    reported: set[str] = set()

    def add(fi: FuncInfo, lineno: int, code: str, msg: str,
            key: str) -> None:
        if key in reported:
            return
        reported.add(key)
        rep.add(fi.ctx, lineno, code, msg, key=key)

    for (rel, qual), (lane, rfi, path) in sorted(reached.items()):
        fi = prog.funcs[(rel, qual)]
        if fi.may_block is not None and fi is not rfi:
            continue
        # RT400: blocking primitives
        for f in fi.facts:
            add(fi, f.lineno, "RT400",
                f"{_FACT_LABEL.get(f.kind, f.kind)} on the hot path: "
                f"{f.detail} — {via(path, lane)}. Fix, or "
                "`# may-block: <reason>` on the callee / "
                "`# noqa: RT400 — reason` here",
                key=f"RT400:{rel}:{qual}:{f.kind}")
        # RT401: cold compiles
        if not fi.warm_routed and not fi.is_device_entry:
            for ln in fi.jit_sites:
                add(fi, ln, "RT401",
                    "bare jax.jit/shard_map dispatch on the hot path "
                    f"— first call pays the compile — {via(path, lane)}",
                    key=f"RT401:{rel}:{qual}:jit")
        for site in fi.calls:
            for tgt in _resolve_spec(prog, fi, site.spec):
                if not tgt.is_device_entry:
                    continue
                if tgt.warm_routed or fi.warm_routed:
                    continue
                add(fi, site.lineno, "RT401",
                    f"call into @device_entry builder {tgt.qualname} "
                    "with no AOT warm / disk-cache routing — first "
                    "call on this lane pays the compile "
                    f"(fleet_merge_async bug class) — {via(path, lane)}",
                    key=f"RT401:{rel}:{qual}:{tgt.qualname}")
        # RT402: unbounded per-event allocation (event lane only)
        if lane == "event":
            for attr, ln, op in fi.appends:
                ci = fi.cls
                if ci is not None and attr in ci.trimmed_attrs:
                    continue
                add(fi, ln, "RT402",
                    f"self.{attr}.{op} grows an untrimmed container "
                    f"on the event path — {via(path, lane)}. Bound it "
                    "(trim/reset/deque(maxlen)) or noqa with a reason",
                    key=f"RT402:{rel}:{qual}:{attr}")
            for ln, kind in fi.loop_allocs:
                add(fi, ln, "RT402",
                    f"{kind} allocation inside a per-record loop — "
                    f"{via(path, lane)}. Vectorize the block instead "
                    "of building objects per event",
                    key=f"RT402:{rel}:{qual}:loop:{ln}")

    # RT403: lock convoys — join hot acquisitions with locks held
    # across blocking calls anywhere in the program.
    can_block: dict[tuple[str, str], bool] = {}
    for k, fi in prog.funcs.items():
        can_block[k] = bool(fi.facts) or fi.may_block is not None
    changed = True
    guard = 0
    while changed and guard <= len(prog.funcs) + 2:
        changed = False
        guard += 1
        for k, fi in prog.funcs.items():
            if can_block[k]:
                continue
            for site in fi.calls:
                for tgt in _resolve_spec(prog, fi, site.spec):
                    if can_block.get((tgt.rel, tgt.qualname)):
                        can_block[k] = True
                        changed = True
                        break
                if can_block[k]:
                    break

    held_across_block: dict[str, tuple[FuncInfo, int]] = {}
    for fi in prog.funcs.values():
        for acq in fi.acquires:
            blocking = acq.facts_inside or any(
                can_block.get((t.rel, t.qualname))
                for spec in acq.calls_inside
                for t in _resolve_spec(prog, fi, spec)
            )
            if blocking and acq.lock not in held_across_block:
                held_across_block[acq.lock] = (fi, acq.lineno)

    for (rel, qual), (lane, rfi, path) in sorted(reached.items()):
        fi = prog.funcs[(rel, qual)]
        if fi.may_block is not None and fi is not rfi:
            continue
        for acq in fi.acquires:
            witness = held_across_block.get(acq.lock)
            if witness is None or witness[0] is fi:
                continue
            wfi, wln = witness
            add(fi, acq.lineno, "RT403",
                f"hot path acquires {acq.lock.split(':')[-1]} which "
                f"{wfi.qualname} ({wfi.rel}:{wln}) holds across a "
                f"blocking call — lock convoy — {via(path, lane)}",
                key=f"RT403:{rel}:{qual}:{acq.lock.split(':')[-1]}")
