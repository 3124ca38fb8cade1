"""RT220-RT224 — metric-name drift (whole-program).

The contract: ``utils/metric_names.py`` is the single registry of
exported series names; every registration in ``metrics.py`` and the
modules resolves to a declared constant; ``docs/metrics.md`` lists
every series and mentions no series that does not exist.  Drift in
any direction (code ahead of docs, docs ahead of code, dead
declarations) is a finding:

  RT220 metric registered under a name not declared in
        utils/metric_names.py
  RT221 metric registered from a string literal / unresolvable
        expression instead of a metric_names constant
  RT222 declared series missing from docs/metrics.md
  RT223 docs/metrics.md mentions a series that is not declared
  RT224 declared series never registered or referenced anywhere

The hubble flow-observability registry (``new_hubble_*``) is a
separate compatibility surface with its own naming (hubble_*) and is
out of scope.  Label-key constants (L_*) are not series names.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

from tools.analyze.core import FileCtx, Reporter

METRIC_NAMES_REL = "retina_tpu/utils/metric_names.py"
DOC_REL = "docs/metrics.md"
PREFIX = "networkobservability_"

REG_FUNCS = {
    "new_gauge", "new_counter", "new_histogram",
    "new_adv_gauge", "new_adv_counter", "new_adv_histogram",
    "new_adv_table",
}

DOC_SERIES_RE = re.compile(r"networkobservability_[a-z0-9_]+")


def _fold_constants(tree: ast.Module) -> dict[str, str]:
    """Constant-fold the module-level string assignments of
    metric_names.py (NAME = PREFIX + "suffix" chains)."""
    consts: dict[str, str] = {}

    def fold(node: ast.expr) -> str | None:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.Name):
            return consts.get(node.id)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            left, right = fold(node.left), fold(node.right)
            if left is not None and right is not None:
                return left + right
        return None

    for stmt in tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)):
            val = fold(stmt.value)
            if val is not None:
                consts[stmt.targets[0].id] = val
    return consts


def _declared_series(ctx: FileCtx) -> dict[str, tuple[str, int]]:
    """name -> (value, decl lineno) for every exported series."""
    consts = _fold_constants(ctx.tree)
    linenos: dict[str, int] = {}
    for stmt in ctx.tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)):
            linenos[stmt.targets[0].id] = stmt.lineno
    out: dict[str, tuple[str, int]] = {}
    for name, value in consts.items():
        if not value.startswith(PREFIX):
            continue
        if name.endswith("PREFIX"):  # building blocks, not series
            continue
        out[name] = (value, linenos.get(name, 1))
    return out


def _registration_aliases(fn: ast.AST) -> set[str]:
    """Local names bound to registration functions, e.g.
    ``g, c = ex.new_gauge, ex.new_counter``."""
    aliases: set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        for target, value in _assign_pairs(node):
            if (isinstance(target, ast.Name)
                    and isinstance(value, ast.Attribute)
                    and value.attr in REG_FUNCS):
                aliases.add(target.id)
    return aliases


def _assign_pairs(node: ast.Assign):
    for target in node.targets:
        if (isinstance(target, ast.Tuple)
                and isinstance(node.value, ast.Tuple)
                and len(target.elts) == len(node.value.elts)):
            yield from zip(target.elts, node.value.elts)
        else:
            yield target, node.value


def check_program(ctxs: list[FileCtx], rep: Reporter, root: Path) -> None:
    by_rel = {c.rel: c for c in ctxs}
    mn_ctx = by_rel.get(METRIC_NAMES_REL)
    if mn_ctx is None:
        return
    series = _declared_series(mn_ctx)  # const name -> (value, lineno)
    values = {v for v, _ in series.values()}

    prod = [
        c for c in ctxs
        if c.rel.startswith("retina_tpu/") and c.rel != METRIC_NAMES_REL
    ]

    # --- registrations: resolve first args, flag drift -------------
    used_consts: set[str] = set()
    for ctx in prod:
        aliases = _registration_aliases(ctx.tree)
        for node in ast.walk(ctx.tree):
            # any mn.CONST / imported CONST reference marks the
            # constant as used (values plumbed through variables
            # still originate at one of these references)
            if isinstance(node, ast.Attribute) and node.attr in series:
                used_consts.add(node.attr)
            elif isinstance(node, ast.Name) and node.id in series:
                used_consts.add(node.id)
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            is_reg = (
                (isinstance(func, ast.Attribute) and func.attr in REG_FUNCS)
                or (isinstance(func, ast.Name) and func.id in aliases)
            )
            if not is_reg or not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Attribute) or isinstance(arg, ast.Name):
                continue  # constant reference — handled above
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if arg.value in values:
                    rep.add(ctx, node.lineno, "RT221",
                            f'metric "{arg.value}" registered from a '
                            "literal — use the utils.metric_names "
                            "constant",
                            key=f"RT221:{ctx.rel}:{arg.value}")
                else:
                    rep.add(ctx, node.lineno, "RT220",
                            f'metric "{arg.value}" registered but not '
                            "declared in utils/metric_names.py",
                            key=f"RT220:{ctx.rel}:{arg.value}")
            else:
                rep.add(ctx, node.lineno, "RT221",
                        "metric registered from a non-constant "
                        "expression — declare it in "
                        "utils/metric_names.py",
                        key=f"RT221:{ctx.rel}:{node.lineno}")

    # --- docs/metrics.md two-way check -----------------------------
    doc_path = root / DOC_REL
    doc_lines = (
        doc_path.read_text().splitlines() if doc_path.exists() else []
    )
    doc_text = "\n".join(doc_lines)
    for name, (value, lineno) in sorted(series.items()):
        if value not in doc_text and value + "_total" not in doc_text:
            rep.add(mn_ctx, lineno, "RT222",
                    f'series "{value}" ({name}) has no entry in '
                    f"{DOC_REL}",
                    key=f"RT222:{name}")

    # Doc tokens must resolve to declared series.  Prometheus counter
    # exposition appends `_total`; docs may use either spelling.
    doc_ok = values | {v + "_total" for v in values}
    doc_ctx = FileCtx.__new__(FileCtx)  # lightweight shell for .md
    doc_ctx.path = doc_path
    doc_ctx.rel = DOC_REL
    doc_ctx.src = doc_text
    doc_ctx.lines = doc_lines
    doc_ctx.tree = None
    doc_ctx.syntax_error = None
    for i, line in enumerate(doc_lines, start=1):
        for tok in DOC_SERIES_RE.findall(line):
            tok = tok.rstrip("_")
            if tok == PREFIX.rstrip("_"):
                continue  # prose mention of the prefix itself
            if tok in ("networkobservability_adv",
                       "networkobservability_sketch",
                       "networkobservability_fleet",
                       "networkobservability_tpu_timetravel",
                       "networkobservability_tpu_autocapture",
                       "networkobservability_tpu_soak",
                       "networkobservability_tpu_detector",
                       "networkobservability_fleet_query"):
                continue  # prose mention of a family prefix
            if tok not in doc_ok:
                rep.add(doc_ctx, i, "RT223",
                        f'doc mentions "{tok}" which is not declared '
                        "in utils/metric_names.py",
                        key=f"RT223:{tok}")

    # --- declared but never used -----------------------------------
    for name, (value, lineno) in sorted(series.items()):
        if name not in used_consts:
            rep.add(mn_ctx, lineno, "RT224",
                    f"series constant {name} ({value}) is never "
                    "registered or referenced outside metric_names",
                    key=f"RT224:{name}")
