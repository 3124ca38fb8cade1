"""RT300 family: device-program analysis + the RT305 registry rule.

Two faces:

- ``check(ctx, rep)`` — RT305, a pure-AST per-file rule that runs in
  the default (fast) lint: every ``jax.jit`` / ``shard_map`` call
  site under ``retina_tpu/`` must live inside a function carrying a
  ``@device_entry(...)`` decorator (retina_tpu/devprog.py), so the
  device-program registry provably covers every program the repo can
  put on an accelerator.

- ``check_device(ctxs, rep, root)`` — the heavy pass behind
  ``python tools/lint.py --device``: lazily imports
  tools/analyze/devlower.py (the ONLY module that imports jax —
  pinned to the CPU backend with 4 synthetic devices), AOT-lowers
  every registered entry point, and walks the jaxprs / compiled HLO:

  RT300  merge algebra — every ``*_merge`` combines state through
         associative/commutative primitives only (add / max / the
         compare-select join), proven at the primitive level.
  RT301  counter overflow — (a) every declared pure-sum u32 counter's
         carry chain is scatter-add/add/structural only, (b) the
         config-derived per-window bound k * envelope * window fits
         u32, (c) interval analysis of the HT-rescale under the
         documented envelope shows no in-window wrap, and (d) every
         u32 state leaf is classified pure-sum or exempt.
  RT302  donation coverage — lowered args_info must show the expected
         donations (hot-path consumed state) and non-donations
         (resident tables the host rereads).
  RT303  sharding audit — compiled HLO may contain only each entry's
         expected collectives; anything else is an implicit gather /
         forced replication.
  RT304  host/device predicate parity — numpy mirrors executed
         against their device twins over the packed-field domain.

Findings anchor at the registered entry's definition line where one
exists (via the DeviceEntry record), else at devlower.py itself.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

from tools.analyze.core import FileCtx, Reporter

# ---------------------------------------------------------------------
# RT305 — registry exhaustiveness (pure AST, default lint)

_SHARD_MAP_NAMES = {"shard_map"}


def _is_jit_expr(node: ast.expr) -> bool:
    """jax.jit / jit referenced as a value (e.g. partial(jax.jit, ...))."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return True
    return isinstance(node, ast.Name) and node.id == "jit"


def _is_program_site(call: ast.Call) -> str | None:
    """Return 'jit' / 'shard_map' if this Call creates a device
    program, else None."""
    f = call.func
    if _is_jit_expr(f):
        return "jit"
    if isinstance(f, ast.Attribute) and f.attr in _SHARD_MAP_NAMES:
        return "shard_map"
    if isinstance(f, ast.Name) and f.id in _SHARD_MAP_NAMES:
        return "shard_map"
    # functools.partial(jax.jit, ...) — the jit reference rides as an
    # argument.
    if isinstance(f, ast.Name) and f.id in {"partial", "_partial"} or (
        isinstance(f, ast.Attribute) and f.attr == "partial"
    ):
        if any(_is_jit_expr(a) for a in call.args):
            return "jit"
    return None


def _has_device_entry(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
    for dec in fn.decorator_list:
        d = dec.func if isinstance(dec, ast.Call) else dec
        if isinstance(d, ast.Name) and d.id == "device_entry":
            return True
        if isinstance(d, ast.Attribute) and d.attr == "device_entry":
            return True
    return False


def check(ctx: FileCtx, rep: Reporter) -> None:
    """RT305: unregistered jax.jit / shard_map site under retina_tpu/."""
    if not ctx.rel.startswith("retina_tpu/"):
        return
    if ctx.rel.endswith("devprog.py"):
        return  # the registry itself
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(ctx.tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        kind = _is_program_site(node)
        if kind is None:
            continue
        covered = False
        cur = node
        fn_name = "<module>"
        while cur in parents:
            cur = parents[cur]
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if fn_name == "<module>":
                    fn_name = cur.name
                if _has_device_entry(cur):
                    covered = True
                    break
        if not covered:
            rep.add(
                ctx, node.lineno, "RT305",
                f"{kind} site in `{fn_name}` is not covered by a "
                f"@device_entry registration — the device-program "
                f"analysis (lint.py --device) cannot see it",
                key=f"RT305:{ctx.rel}:{fn_name}",
            )


# ---------------------------------------------------------------------
# Device pass helpers (no jax at module scope — devlower is imported
# inside check_device only)

def _prod_map(jaxpr) -> dict:
    m = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            m[ov] = eqn
    return m


def _sub_jaxpr(eqn):
    sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    if sub is None:
        return None
    return sub.jaxpr if hasattr(sub, "jaxpr") else sub


def _collect_prims(jaxpr, call_prims, out: list) -> None:
    """(primitive_name, eqn) for every eqn, recursing through call
    primitives (which are transparent and not themselves counted)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in call_prims:
            sub = _sub_jaxpr(eqn)
            if sub is not None:
                _collect_prims(sub, call_prims, out)
                continue
        out.append((eqn.primitive.name, eqn))


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _algebra_violations(closed, allowed, call_prims) -> list[str]:
    """Primitive names in the jaxpr outside `allowed`. An `add` with a
    literal operand is index arithmetic from gather/take lowering
    (negative-index normalization adds the axis size constant), not a
    state combination — treated as structural."""
    pairs: list = []
    _collect_prims(closed.jaxpr, call_prims, pairs)
    bad = []
    for name, eqn in pairs:
        if name in allowed:
            continue
        if name == "add" and any(_is_literal(v) for v in eqn.invars):
            continue
        bad.append(name)
    return sorted(set(bad))


def _pure_sources(closed, out_idx: int, carry_prims, structural,
                  call_prims) -> frozenset[int]:
    """Flat input positions reachable from output `out_idx` through
    pure carry chains only (scatter-add carries operand 0; add carries
    either operand; structural ops carry all operands; any other
    primitive ends the path). Success-on-any-path: an impure branch is
    simply not a source."""
    jaxpr = closed.jaxpr
    memo: dict = {}

    def rec(jx, var, pm, invar_pos):
        if _is_literal(var):
            return frozenset()
        key = (id(jx), var)
        if key in memo:
            return memo[key]
        memo[key] = frozenset()  # DAG; placeholder for re-reads
        if var in invar_pos:
            res = frozenset({invar_pos[var]})
            memo[key] = res
            return res
        eqn = pm.get(var)
        if eqn is None:  # constvar
            return frozenset()
        nm = eqn.primitive.name
        out: set[int] = set()
        if nm in call_prims:
            sub = _sub_jaxpr(eqn)
            if sub is not None:
                k = eqn.outvars.index(var)
                sub_pm = _prod_map(sub)
                sub_pos = {v: i for i, v in enumerate(sub.invars)}
                for j in rec(sub, sub.outvars[k], sub_pm, sub_pos):
                    out |= rec(jx, eqn.invars[j], pm, invar_pos)
        elif nm in carry_prims and nm.startswith("scatter"):
            out |= rec(jx, eqn.invars[0], pm, invar_pos)
        elif nm in carry_prims or nm in structural:
            for v in eqn.invars:
                out |= rec(jx, v, pm, invar_pos)
        res = frozenset(out)
        memo[key] = res
        return res

    pm = _prod_map(jaxpr)
    invar_pos = {v: i for i, v in enumerate(jaxpr.invars)}
    return rec(jaxpr, jaxpr.outvars[out_idx], pm, invar_pos)


_CARRY_PRIMS = frozenset({"add", "scatter-add"})


# ---------------------------------------------------------------------
# The device pass

def check_device(ctxs: list[FileCtx], rep: Reporter, root: Path) -> None:
    old_limit = sys.getrecursionlimit()
    sys.setrecursionlimit(max(old_limit, 100_000))
    try:
        _check_device(ctxs, rep, root)
    finally:
        sys.setrecursionlimit(old_limit)


def _check_device(ctxs: list[FileCtx], rep: Reporter, root: Path) -> None:
    from tools.analyze import devlower as dl  # imports jax (CPU, 4 dev)
    from tools.analyze.interval import analyze_jaxpr

    by_rel = {c.rel: c for c in ctxs}
    reg = dl.registry()

    fallback = by_rel.get("tools/analyze/devlower.py")
    if fallback is None:  # restricted file set: synthesize the anchor
        p = Path(__file__).resolve().parent / "devlower.py"
        fallback = FileCtx(p, "tools/analyze/devlower.py", p.read_text())
        by_rel[fallback.rel] = fallback

    def report(entry_name: str, code: str, msg: str, subkey: str) -> None:
        e = reg.get(entry_name)
        ctx, line = fallback, 1
        if e is not None:
            c = by_rel.get(e.module.replace(".", "/") + ".py")
            if c is not None:
                ctx, line = c, e.lineno
        rep.add(
            ctx, line, code, msg,
            key=f"{code}:{entry_name}:{subkey}",
        )

    # -- registry <-> recipe inventory parity --------------------------
    cov = dl.RECIPE_COVERAGE
    for name in sorted(set(reg) - set(cov)):
        report(
            name, "RT300",
            f"registered device entry `{name}` has no analysis recipe "
            f"in tools/analyze/devlower.py — the device pass cannot "
            f"see it",
            "uncovered",
        )
    for name in sorted(set(cov) - set(reg)):
        report(
            name, "RT300",
            f"analysis recipe `{name}` has no registered device entry "
            f"— stale RECIPE_COVERAGE row",
            "stale",
        )

    # -- RT300: merge algebra ------------------------------------------
    for recipe in dl.merge_recipes():
        bad = _algebra_violations(
            recipe.jaxpr, recipe.allowed, dl.CALL_PRIMS
        )
        if bad:
            report(
                recipe.entry, "RT300",
                f"merge `{recipe.entry}` ({recipe.algebra} algebra) "
                f"uses non-associative/commutative primitives "
                f"{bad} — cross-node merge order would change results",
                "algebra",
            )

    # trace smokes: building them IS the check (they must still trace
    # under the tiny shapes)
    dl.update_trace_smokes()

    # -- RT301a: pure-sum carry chains ---------------------------------
    targets = dl.step_purity_targets() + dl.op_purity_targets()
    for t in targets:
        srcs = _pure_sources(
            t.jaxpr, t.out_idx, _CARRY_PRIMS, dl.STRUCTURAL,
            dl.CALL_PRIMS,
        )
        if t.in_idx not in srcs:
            report(
                t.entry, "RT301",
                f"counter `{t.counter}` in `{t.entry}` is not carried "
                f"by a pure scatter-add/add chain from its state input "
                f"— the per-window overflow bound does not apply to it "
                f"(classify it in COUNTER_EXEMPT or fix the update "
                f"path)",
                f"purity:{t.counter}",
            )

    # -- RT301d: every u32 state leaf classified -----------------------
    for leaf in dl.classify_state_counters():
        report(
            "pipeline.step", "RT301",
            f"u32 PipelineState leaf `{leaf}` is neither declared a "
            f"pure-sum counter nor exempted with a reason "
            f"(devlower.PURE_SUM_COUNTERS / COUNTER_EXEMPT)",
            f"unclassified:{leaf}",
        )

    # -- RT301b: config-derived per-window wrap bound ------------------
    wrap = dl.window_wrap_report()
    if not wrap["ok"]:
        report(
            "pipeline.step", "RT301",
            f"per-window counter bound k*envelope*window = "
            f"{wrap['k']}*{wrap['envelope']}*{wrap['window_seconds']} "
            f"= {wrap['bound']} exceeds u32 — a pure-sum counter can "
            f"wrap inside one window at the configured maxima",
            "window-bound",
        )

    # -- RT301c: HT-rescale interval analysis --------------------------
    jaxpr, intervals = dl.ht_rescale_target()
    res = analyze_jaxpr(jaxpr, intervals)
    for w in res.wrapped:
        report(
            "pipeline.step", "RT301",
            f"ht_rescale can wrap u32 under the documented envelope "
            f"(packets<=2^28, k<=config): {w}",
            f"ht-rescale:{w.split(':')[0]}",
        )
    for u in sorted(set(res.unknown)):
        report(
            "pipeline.step", "RT301",
            f"interval engine has no transfer function for primitive "
            f"`{u}` in ht_rescale — add it to tools/analyze/"
            f"interval.py TRANSFER (analysis is blind to it)",
            f"ht-rescale-unknown:{u}",
        )

    # -- RT302/RT303: lowered entry audits -----------------------------
    for a in dl.entry_audits():
        for i in a.donate_expect:
            leaves = a.arg_donated[i]
            if not leaves or not all(leaves):
                report(
                    a.entry, "RT302",
                    f"`{a.entry}` arg {i} is hot-path consumed state "
                    f"but not (fully) donated — the old buffer stays "
                    f"live across the call",
                    f"donate:{i}",
                )
        for i in a.keep_expect:
            if any(a.arg_donated[i]):
                report(
                    a.entry, "RT302",
                    f"`{a.entry}` arg {i} is a RESIDENT operand (host "
                    f"rereads it) but is donated — the engine would "
                    f"reread a deleted buffer",
                    f"keep:{i}",
                )
        seen = {c for c in dl.COLLECTIVE_OPS if c in a.hlo_text}
        for c in sorted(seen - a.allowed_collectives):
            report(
                a.entry, "RT303",
                f"`{a.entry}` compiles to an unexpected `{c}` — an "
                f"implicit cross-device gather or forced replication "
                f"not in the entry's expected-collective set",
                f"collective:{c}",
            )

    # -- RT304: host/device predicate parity ---------------------------
    for p in dl.parity_report():
        report(
            "pipeline.step", "RT304",
            f"host/device predicate divergence: {p}",
            f"parity:{p.split(':')[0]}",
        )
