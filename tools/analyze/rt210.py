"""RT210-RT214 — Python purity of JAX-traced functions.

A function handed to ``jax.jit`` / ``shard_map`` runs ONCE at trace
time; its Python-level side effects do not re-execute per step, and
host interaction with tracer values either fails outright or silently
constant-folds.  Every such bug class in this repo's history looked
correct in review — so the analyzer encodes them:

  RT210 host side-effect call inside a traced function: ``time.*``,
        ``logging.*`` / ``self.log.*`` / ``print``, Python ``random.*``
        — executes once at trace time, not per step
  RT211 host materialization of a traced value: ``float()/int()/
        bool()/complex()`` on a tracer, ``.item()/.tolist()``,
        ``np.asarray/np.array`` — raises ConcretizationTypeError (or
        silently constant-folds a weak type) at trace time
  RT212 Python control flow on a traced value (``if``/``while``/
        ``assert``/ternary/``for`` over a tracer) — branches are
        resolved once at trace time; use lax.cond/select/fori_loop
  RT213 mutation of non-traced state from inside a traced function
        (``global`` writes, ``self.<attr> = ...``) — happens once at
        trace time, invisible to subsequent steps
  RT214 nested def inside a traced function re-jitted per call
        (``jax.jit`` applied INSIDE a traced body) — retrace storm

Traced-function discovery
-------------------------
Decorator forms (``@jit``, ``@jax.jit``, ``@partial(jax.jit, ...)``,
``@_partial(jax.jit, ...)``), call forms (``jax.jit(fn)``,
``_shard_map(local_step, ...)`` where ``fn`` is a same-scope def), and
same-file transitive callees of traced functions (checked for RT210/
RT213/RT214 only — their parameter taint is unknown, and guessing
would flood RT211/RT212 with false positives).

Taint model
-----------
Parameters of a traced function are tracer-valued (minus ``self``/
``cls`` and ``static_argnames``); taint propagates through simple
assignments and arithmetic.  Static projections UNTAINT: ``.shape``,
``.ndim``, ``.dtype``, ``.size``, ``.sharding``, ``len()``,
``isinstance()``, ``is None`` / ``is not None`` comparisons — all are
Python values at trace time and are legitimate branch conditions.
"""

from __future__ import annotations

import ast

from tools.analyze.core import FileCtx, Reporter

JIT_NAMES = {"jit"}
SHARD_NAMES = {"shard_map"}
PARTIAL_NAMES = {"partial", "_partial"}

STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                "weak_type"}
UNTAINT_CALLS = {"len", "isinstance", "type", "hasattr", "range",
                 "enumerate", "zip"}
# Calls returning a Python sequence OF tracers: iterating the
# sequence is ordinary Python (static length), even though each
# element is traced.
PY_SEQUENCE_CALLS = {"tree_leaves", "tree_flatten", "tree_map",
                     "items", "keys", "values", "split"}
CONCRETIZE_CALLS = {"float", "int", "bool", "complex"}
CONCRETIZE_METHODS = {"item", "tolist"}
SIDE_EFFECT_MODULES = {"time", "logging", "random", "os", "sys"}
LOG_METHODS = {"debug", "info", "warning", "error", "exception",
               "critical", "log"}


def _callable_name(func: ast.expr) -> str | None:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _is_jit_expr(node: ast.expr) -> bool:
    """`jit` / `jax.jit` as a bare expression."""
    return _callable_name(node) in JIT_NAMES and (
        isinstance(node, ast.Name)
        or (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id in ("jax", "jnp"))
    )


def _static_argnames(call: ast.Call | None) -> set[str]:
    names: set[str] = set()
    if call is None:
        return names
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if (isinstance(n, ast.Constant)
                        and isinstance(n.value, str)):
                    names.add(n.value)
    return names


def _traced_defs(
    tree: ast.Module,
) -> tuple[dict[int, tuple[ast.AST, set[str]]], dict[str, ast.AST]]:
    """-> ({id(fn-node): (fn-node, static-argnames)}, {name: fn-node}).

    The name index covers every def in the file (module, class, and
    nested scope) — good enough for same-file call resolution.
    """
    defs_by_name: dict[str, ast.AST] = {}
    traced: dict[int, tuple[ast.AST, set[str]]] = {}

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, node)
            for dec in node.decorator_list:
                if _is_jit_expr(dec):
                    traced[id(node)] = (node, set())
                elif isinstance(dec, ast.Call):
                    fname = _callable_name(dec.func)
                    if fname in PARTIAL_NAMES and dec.args \
                            and (_is_jit_expr(dec.args[0])
                                 or _callable_name(dec.args[0])
                                 in SHARD_NAMES):
                        traced[id(node)] = (node, _static_argnames(dec))
                    elif _is_jit_expr(dec.func) \
                            or fname in SHARD_NAMES:
                        traced[id(node)] = (node, _static_argnames(dec))

    # call forms: jax.jit(fn), _shard_map(local_step, mesh, ...)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fname = _callable_name(node.func)
        is_jit = _is_jit_expr(node.func)
        is_shard = fname in SHARD_NAMES
        if not (is_jit or is_shard):
            continue
        arg0 = node.args[0]
        target = None
        if isinstance(arg0, ast.Name):
            target = defs_by_name.get(arg0.id)
        elif (isinstance(arg0, ast.Attribute)
              and isinstance(arg0.value, ast.Name)
              and arg0.value.id == "self"):
            target = defs_by_name.get(arg0.attr)
        if target is not None and id(target) not in traced:
            traced[id(target)] = (target, _static_argnames(node))
    return traced, defs_by_name


class _PurityCheck:
    def __init__(self, ctx: FileCtx, rep: Reporter, fn, statics: set[str],
                 taint_params: bool):
        self.ctx = ctx
        self.rep = rep
        self.fn = fn
        self.tainted: set[str] = set()
        if taint_params:
            args = fn.args
            for a in (args.posonlyargs + args.args + args.kwonlyargs):
                if a.arg not in ("self", "cls") and a.arg not in statics:
                    self.tainted.add(a.arg)
        self.taint_params = taint_params

    # -- taint ---------------------------------------------------------
    def _tainted(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.tainted
        if isinstance(e, ast.Attribute):
            if e.attr in STATIC_ATTRS:
                return False
            return self._tainted(e.value)
        if isinstance(e, ast.Call):
            fname = _callable_name(e.func)
            if fname in UNTAINT_CALLS or fname in CONCRETIZE_CALLS:
                return False  # python-scalar result (RT211 flags misuse)
            if (isinstance(e.func, ast.Attribute)
                    and self._tainted(e.func.value)):
                return True  # tracer method call: x.sum()
            return any(self._tainted(a) for a in e.args) or any(
                self._tainted(kw.value) for kw in e.keywords)
        if isinstance(e, ast.Compare):
            # `x is None` / `x is not None`: identity vs a Python
            # singleton — resolved at trace time, legitimate
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in e.ops):
                return False
            return (self._tainted(e.left)
                    or any(self._tainted(c) for c in e.comparators))
        if isinstance(e, (ast.BinOp,)):
            return self._tainted(e.left) or self._tainted(e.right)
        if isinstance(e, ast.UnaryOp):
            return self._tainted(e.operand)
        if isinstance(e, ast.BoolOp):
            return any(self._tainted(v) for v in e.values)
        if isinstance(e, ast.Subscript):
            return self._tainted(e.value)
        if isinstance(e, ast.IfExp):
            return self._tainted(e.body) or self._tainted(e.orelse)
        if isinstance(e, (ast.Tuple, ast.List)):
            return any(self._tainted(el) for el in e.elts)
        if isinstance(e, ast.Starred):
            return self._tainted(e.value)
        return False

    # -- checks --------------------------------------------------------
    def _check_call(self, n: ast.Call) -> None:
        func = n.func
        fname = _callable_name(func)
        # RT210: host side effects
        if fname == "print":
            self.rep.add(self.ctx, n.lineno, "RT210",
                         f"print() inside traced `{self.fn.name}` runs "
                         "once at trace time (use jax.debug.print)")
        elif (isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id in SIDE_EFFECT_MODULES):
            self.rep.add(
                self.ctx, n.lineno, "RT210",
                f"{func.value.id}.{func.attr}() inside traced "
                f"`{self.fn.name}` executes once at trace time, not "
                "per step")
        elif (isinstance(func, ast.Attribute)
                and func.attr in LOG_METHODS
                and isinstance(func.value, (ast.Name, ast.Attribute))
                and (_callable_name(func.value) or "").lstrip("_")
                in ("log", "logger")):
            self.rep.add(
                self.ctx, n.lineno, "RT210",
                f"logging call inside traced `{self.fn.name}` fires "
                "once at trace time (use jax.debug.print / callback)")
        # RT214: re-jit inside a traced body
        if _is_jit_expr(func):
            self.rep.add(
                self.ctx, n.lineno, "RT214",
                f"jax.jit applied inside traced `{self.fn.name}` — "
                "the inner function is re-traced on every outer trace")
        if not self.taint_params:
            return
        # RT211: concretization of tracers
        if fname in CONCRETIZE_CALLS and n.args \
                and self._tainted(n.args[0]):
            self.rep.add(
                self.ctx, n.lineno, "RT211",
                f"{fname}() on a traced value in `{self.fn.name}` "
                "raises ConcretizationTypeError at trace time")
        elif (isinstance(func, ast.Attribute)
                and func.attr in CONCRETIZE_METHODS
                and self._tainted(func.value)):
            self.rep.add(
                self.ctx, n.lineno, "RT211",
                f".{func.attr}() on a traced value in "
                f"`{self.fn.name}` forces a host sync at trace time")
        elif (isinstance(func, ast.Attribute)
                and func.attr in ("asarray", "array")
                and isinstance(func.value, ast.Name)
                and func.value.id in ("np", "numpy")
                and n.args and self._tainted(n.args[0])):
            self.rep.add(
                self.ctx, n.lineno, "RT211",
                f"np.{func.attr}() on a traced value in "
                f"`{self.fn.name}` materializes the tracer on host")

    def run(self) -> list[str]:
        """Walk the body; returns same-file callee names for the
        transitive pass."""
        callees: list[str] = []

        def visit(n: ast.AST) -> None:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n is not self.fn:
                return  # nested defs trace lazily; checked if invoked
            if isinstance(n, ast.Global):
                self.rep.add(
                    self.ctx, n.lineno, "RT213",
                    f"global write inside traced `{self.fn.name}` "
                    "happens once at trace time")
            if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (n.targets if isinstance(n, ast.Assign)
                           else [n.target])
                for t in targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        self.rep.add(
                            self.ctx, n.lineno, "RT213",
                            f"self.{t.attr} mutated inside traced "
                            f"`{self.fn.name}` — trace-time only, "
                            "invisible to later steps")
                # taint propagation through simple assignments
                if self.taint_params and isinstance(n, ast.Assign) \
                        and n.value is not None:
                    is_tainted = self._tainted(n.value)
                    for t in targets:
                        names = [t] if isinstance(t, ast.Name) else [
                            el for el in getattr(t, "elts", [])
                            if isinstance(el, ast.Name)]
                        for nm in names:
                            if is_tainted:
                                self.tainted.add(nm.id)
                            else:
                                self.tainted.discard(nm.id)
            if isinstance(n, ast.Call):
                self._check_call(n)
                if isinstance(n.func, ast.Name):
                    callees.append(n.func.id)
            if self.taint_params:
                if isinstance(n, (ast.If, ast.While)) \
                        and self._tainted(n.test):
                    self.rep.add(
                        self.ctx, n.lineno, "RT212",
                        f"Python branch on a traced value in "
                        f"`{self.fn.name}` resolves once at trace "
                        "time (use lax.cond / jnp.where)")
                if isinstance(n, ast.Assert) and self._tainted(n.test):
                    self.rep.add(
                        self.ctx, n.lineno, "RT212",
                        f"assert on a traced value in "
                        f"`{self.fn.name}` (use checkify or drop it)")
                if isinstance(n, ast.IfExp) and self._tainted(n.test):
                    self.rep.add(
                        self.ctx, n.lineno, "RT212",
                        f"ternary on a traced value in "
                        f"`{self.fn.name}` (use jnp.where)")
                if isinstance(n, ast.For) and self._tainted(n.iter) \
                        and not (
                            isinstance(n.iter, ast.Call)
                            and _callable_name(n.iter.func)
                            in PY_SEQUENCE_CALLS):
                    self.rep.add(
                        self.ctx, n.lineno, "RT212",
                        f"Python loop over a traced value in "
                        f"`{self.fn.name}` unrolls at trace time "
                        "(use lax.fori_loop / scan)")
            for child in ast.iter_child_nodes(n):
                visit(child)

        for stmt in self.fn.body:
            visit(stmt)
        return callees


def check(ctx: FileCtx, rep: Reporter) -> None:
    if "retina_tpu" not in ctx.path.parts:
        return
    traced, defs_by_name = _traced_defs(ctx.tree)
    seen = set(traced)
    queue = list(traced.values())
    first_pass = len(queue)
    i = 0
    while i < len(queue):
        fn, statics = queue[i]
        # transitive callees get RT210/RT213/RT214 only (unknown taint)
        taint_params = i < first_pass
        callees = _PurityCheck(ctx, rep, fn, statics, taint_params).run()
        for name in callees:
            callee = defs_by_name.get(name)
            if callee is not None and id(callee) not in seen:
                seen.add(id(callee))
                queue.append((callee, set()))
        i += 1
