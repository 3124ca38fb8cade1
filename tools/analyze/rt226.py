"""RT226 — recorder span-name, proxy-kind and thread-role drift
(whole-program).

The contract (the RT220 analog for the flight recorder): the
``STAGE_*`` constants in ``utils/metric_names.py`` are the single
registry of pipeline stage names; every span opened through
``FlightRecorder.span`` resolves to a registry constant; the stage
table in ``docs/observability.md`` (between the ``stage-table-begin``/
``stage-table-end`` markers) lists every stage and mentions no stage
that does not exist. The device proxy's call kinds are held the same
way: ``KIND_*`` constants, the ``PROXY_KINDS`` tuple, the ``kind=`` of
every ``run_on_device``/``submit_on_device`` call, and the kind table
(``kind-table-begin``/``kind-table-end``). So are the roles of the
host-CPU account: ``ROLE_*`` constants, the ``THREAD_ROLES`` tuple, the
roles the program books (a prefix table's entry, a ``ROLE_*`` named
under ``retina_tpu/``) and the role table (``role-table-begin``/
``role-table-end``). Drift in any direction is a finding:

  RT226 span opened under a stage not declared in the registry
        (string literal, unknown STAGE_* reference, or a registry
        constant missing from the STAGES tuple);
        a registry stage never emitted through any recorder;
        a proxied call whose kind is a literal or an undeclared
        KIND_* reference, a KIND_* constant missing from
        PROXY_KINDS, or a registry kind no proxied call names (a
        kind is added with its first call site);
        a ROLE_* constant missing from THREAD_ROLES, or one nothing
        books; or
        a docs/observability.md table out of sync with its registry
        (either direction).

Scope: calls under ``retina_tpu/`` whose first argument is a
``STAGE_``-prefixed name (``rec.span(...)`` and helpers that forward
to it), ``.span("literal", ...)`` calls, and the ``kind=`` keyword of
the two proxy entry points.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

from tools.analyze.core import FileCtx, Reporter

METRIC_NAMES_REL = "retina_tpu/utils/metric_names.py"
DOC_REL = "docs/observability.md"
TABLE_BEGIN = "<!-- stage-table-begin -->"
TABLE_END = "<!-- stage-table-end -->"
KIND_TABLE_BEGIN = "<!-- kind-table-begin -->"
KIND_TABLE_END = "<!-- kind-table-end -->"
ROLE_TABLE_BEGIN = "<!-- role-table-begin -->"
ROLE_TABLE_END = "<!-- role-table-end -->"
DOC_STAGE_RE = re.compile(r"`([a-z0-9_]+)`")
PROXY_FUNCS = {"run_on_device", "submit_on_device"}


def _registry(ctx: FileCtx, prefix: str) -> dict[str, tuple[str, int]]:
    """``prefix``* const name -> (string value, decl lineno)."""
    out: dict[str, tuple[str, int]] = {}
    for stmt in ctx.tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id.startswith(prefix)
                and isinstance(stmt.value, ast.Constant)
                and isinstance(stmt.value.value, str)):
            out[stmt.targets[0].id] = (stmt.value.value, stmt.lineno)
    return out


def _names_tuple(ctx: FileCtx, name: str) -> set[str]:
    """Constant names listed in the ordered tuple ``name``."""
    for stmt in ctx.tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id == name
                and isinstance(stmt.value, ast.Tuple)):
            return {
                e.id for e in stmt.value.elts if isinstance(e, ast.Name)
            }
    return set()


def _const_name(node: ast.AST) -> str | None:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def check_program(ctxs: list[FileCtx], rep: Reporter, root: Path) -> None:
    by_rel = {c.rel: c for c in ctxs}
    mn_ctx = by_rel.get(METRIC_NAMES_REL)
    if mn_ctx is None:
        return
    registry = _registry(mn_ctx, "STAGE_")  # const -> (value, lineno)
    values = {v for v, _ in registry.values()}
    in_tuple = _names_tuple(mn_ctx, "STAGES")
    kinds = _registry(mn_ctx, "KIND_")
    kinds_in_tuple = _names_tuple(mn_ctx, "PROXY_KINDS")
    for name, (value, lineno) in sorted(kinds.items()):
        if name not in kinds_in_tuple:
            rep.add(mn_ctx, lineno, "RT226",
                    f"proxy kind constant {name} (\"{value}\") is "
                    "missing from the PROXY_KINDS tuple",
                    key=f"RT226:kind-tuple:{name}")

    roles = _registry(mn_ctx, "ROLE_")
    roles_in_tuple = _names_tuple(mn_ctx, "THREAD_ROLES")
    roles_booked = _role_references(ctxs)
    for name, (value, lineno) in sorted(roles.items()):
        if name not in roles_in_tuple:
            rep.add(mn_ctx, lineno, "RT226",
                    f"thread role constant {name} (\"{value}\") is "
                    "missing from the THREAD_ROLES tuple",
                    key=f"RT226:role-tuple:{name}")
        if name not in roles_booked:
            rep.add(mn_ctx, lineno, "RT226",
                    f"thread role constant {name} (\"{value}\") is "
                    "booked by nothing: no prefix table names it and no "
                    "code under retina_tpu/ does",
                    key=f"RT226:role-unused:{name}")

    # A declared constant absent from the ordered STAGES tuple never
    # gets its histogram child pre-ordered in stage_report — drift.
    for name, (value, lineno) in sorted(registry.items()):
        if name not in in_tuple:
            rep.add(mn_ctx, lineno, "RT226",
                    f"stage constant {name} (\"{value}\") is missing "
                    "from the STAGES tuple",
                    key=f"RT226:tuple:{name}")

    # --- emission sites under retina_tpu/: span(<stage>, ...) and the
    # kind= of proxied calls -------------------------------------------
    emitted: set[str] = set()
    kinds_used: set[str] = set()
    prod = [
        c for c in ctxs
        if c.rel.startswith("retina_tpu/") and c.rel != METRIC_NAMES_REL
    ]
    for ctx in prod:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if _const_name(func) in PROXY_FUNCS:
                _check_kind(ctx, node, kinds, kinds_used, rep)
            if not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if isinstance(func, ast.Attribute) and func.attr == "span":
                    rep.add(ctx, node.lineno, "RT226",
                            f'span "{arg.value}" opened from a literal — '
                            "use the utils.metric_names STAGE_ constant",
                            key=f"RT226:{ctx.rel}:{arg.value}")
                continue
            const = _const_name(arg)
            if const is None or not const.startswith("STAGE_"):
                continue  # not a span site — out of scope
            if const not in registry:
                rep.add(ctx, node.lineno, "RT226",
                        f"span constant {const} is not declared in "
                        "utils/metric_names.py",
                        key=f"RT226:{ctx.rel}:{const}")
            else:
                emitted.add(const)

    # --- declared but never emitted ----------------------------------
    for name, (value, lineno) in sorted(registry.items()):
        if name not in emitted:
            rep.add(mn_ctx, lineno, "RT226",
                    f"stage constant {name} (\"{value}\") is never "
                    "emitted through a recorder span",
                    key=f"RT226:unused:{name}")
    for name, (value, lineno) in sorted(kinds.items()):
        if name not in kinds_used:
            rep.add(mn_ctx, lineno, "RT226",
                    f"proxy kind constant {name} (\"{value}\") is "
                    "named by no proxied call — add a kind with its "
                    "first call site",
                    key=f"RT226:kind-unused:{name}")

    # --- docs/observability.md stage and kind tables, two-way --------
    doc_path = root / DOC_REL
    doc_lines = (
        doc_path.read_text().splitlines() if doc_path.exists() else []
    )
    doc_ctx = FileCtx.__new__(FileCtx)  # lightweight shell for .md
    doc_ctx.path = doc_path
    doc_ctx.rel = DOC_REL
    doc_ctx.src = "\n".join(doc_lines)
    doc_ctx.lines = doc_lines
    doc_ctx.tree = None
    doc_ctx.syntax_error = None

    _check_table(doc_ctx, rep, "stage", values, TABLE_BEGIN, TABLE_END,
                 "doc")
    if kinds:
        _check_table(doc_ctx, rep, "proxy kind",
                     {v for v, _ in kinds.values()}, KIND_TABLE_BEGIN,
                     KIND_TABLE_END, "kind-doc")
    if roles:
        _check_table(doc_ctx, rep, "thread role",
                     {v for v, _ in roles.values()}, ROLE_TABLE_BEGIN,
                     ROLE_TABLE_END, "role-doc")


def _role_references(ctxs: list[FileCtx]) -> set[str]:
    """``ROLE_*`` names read anywhere under ``retina_tpu/`` but in the
    ``THREAD_ROLES`` tuple itself (which lists them all)."""
    out: set[str] = set()
    for ctx in ctxs:
        if not ctx.rel.startswith("retina_tpu/"):
            continue
        for stmt in ast.walk(ctx.tree):
            if (isinstance(stmt, ast.Assign)
                    and isinstance(stmt.targets[0], ast.Name)
                    and stmt.targets[0].id == "THREAD_ROLES"):
                skip = {id(n) for n in ast.walk(stmt)}
                break
        else:
            skip = set()
        for node in ast.walk(ctx.tree):
            if id(node) in skip:
                continue
            name = _const_name(node)
            if (name and name.startswith("ROLE_")
                    and isinstance(getattr(node, "ctx", None), ast.Load)):
                out.add(name)
    return out


def _check_kind(ctx: FileCtx, node: ast.Call, kinds: dict,
                used: set[str], rep: Reporter) -> None:
    """The ``kind=`` of one run_on_device/submit_on_device call; the
    registry kinds it names go into ``used``."""
    for kw in node.keywords:
        if kw.arg != "kind":
            continue
        if (isinstance(kw.value, ast.Constant)
                and isinstance(kw.value.value, str)):
            rep.add(ctx, node.lineno, "RT226",
                    f'proxied call of kind "{kw.value.value}" from a '
                    "literal — use the utils.metric_names KIND_ constant",
                    key=f"RT226:{ctx.rel}:kind:{kw.value.value}")
            continue
        const = _const_name(kw.value)
        if const is None or not const.startswith("KIND_"):
            continue
        if const in kinds:
            used.add(const)
        else:
            rep.add(ctx, node.lineno, "RT226",
                    f"proxy kind constant {const} is not declared in "
                    "utils/metric_names.py",
                    key=f"RT226:{ctx.rel}:{const}")


def _check_table(doc_ctx: FileCtx, rep: Reporter, what: str,
                 values: set[str], begin: str, end: str,
                 key: str) -> None:
    """One registry against its table in the doc, both directions."""
    table: dict[str, int] = {}  # token -> doc lineno
    inside = False
    for i, line in enumerate(doc_ctx.lines, start=1):
        if begin in line:
            inside = True
            continue
        if end in line:
            inside = False
            continue
        if inside:
            m = DOC_STAGE_RE.search(line)
            if m:
                table.setdefault(m.group(1), i)

    if not table:
        rep.add(doc_ctx, 1, "RT226",
                f"{DOC_REL} has no {what} table between the "
                f"{begin} / {end} markers",
                key=f"RT226:{key}:no-table")
        return
    for value in sorted(values):
        if value not in table:
            rep.add(doc_ctx, 1, "RT226",
                    f'{what} "{value}" has no row in the {DOC_REL} '
                    f"{what} table",
                    key=f"RT226:{key}-missing:{value}")
    for tok, lineno in sorted(table.items()):
        if tok not in values:
            rep.add(doc_ctx, lineno, "RT226",
                    f'{DOC_REL} {what} table mentions "{tok}" which is '
                    "not declared in utils/metric_names.py",
                    key=f"RT226:{key}-unknown:{tok}")
