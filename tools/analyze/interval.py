"""Interval analysis over jaxprs (RT301c, docs/static-analysis.md).

Propagates integer value intervals through a jaxpr's equations and
reports every operation whose result can leave its dtype's range —
i.e. every place a u32 counter or product can silently wrap on device.
DUNE (arxiv 2212.04816) is the motivating failure: sketch accuracy
collapses when counters saturate, and nothing in the output says so.

Design points:

- **Sound, not complete.** Every transfer function over-approximates:
  the true set of reachable values is inside [lo, hi]. "no wrap
  reported" is therefore a proof under the stated input envelope;
  a reported wrap may be a false alarm (intervals are non-relational).
- **Definite branches prune.** A comparison whose operand intervals
  do not overlap yields [0,0] or [1,1], and ``select_n`` with a
  definite predicate takes exactly one arm — this is what lets the
  Horvitz-Thompson rescale (models/pipeline.py ``ht_rescale``) prove
  its multiply cannot wrap under the documented per-row envelope: the
  saturation guard ``packets > lim`` is definitely false there, so
  the poisoned cap arm never joins the result.
- **Unknown primitives are loud.** An unmodeled primitive gets the
  full dtype range (sound) AND is recorded in ``unknown`` — the
  caller (rt300) turns that into a finding, so new primitives in an
  analyzed program can't silently weaken the proof.

The module is deliberately jax-free: it walks jaxpr objects
duck-typed (``eqn.primitive.name``, ``var.aval``), so the fast AST
lint can import rule modules without ever touching jax.
"""

from __future__ import annotations

import dataclasses
from typing import Any

# dtype name -> (min, max). Missing name (floats) => unbounded, no
# wrap tracking (IEEE saturates to inf, it does not wrap).
_RANGES = {
    "bool": (0, 1),
    "uint8": (0, 2**8 - 1),
    "uint16": (0, 2**16 - 1),
    "uint32": (0, 2**32 - 1),
    "uint64": (0, 2**64 - 1),
    "int8": (-(2**7), 2**7 - 1),
    "int16": (-(2**15), 2**15 - 1),
    "int32": (-(2**31), 2**31 - 1),
    "int64": (-(2**63), 2**63 - 1),
}

_UNBOUNDED = (float("-inf"), float("inf"))


def dtype_range(dtype: Any) -> tuple[float, float]:
    return _RANGES.get(str(dtype), _UNBOUNDED)


@dataclasses.dataclass(frozen=True)
class Interval:
    lo: float
    hi: float

    def __post_init__(self) -> None:
        assert self.lo <= self.hi, (self.lo, self.hi)


@dataclasses.dataclass
class IntervalResult:
    out: list[Interval]
    wrapped: list[str]  # ops whose result can leave its dtype range
    unknown: list[str]  # primitive names with no transfer function

    @property
    def ok(self) -> bool:
        return not self.wrapped and not self.unknown


def _hull(a: Interval, b: Interval) -> Interval:
    return Interval(min(a.lo, b.lo), max(a.hi, b.hi))


# ---------------------------------------------------------------------
# Per-primitive transfer functions. Each takes (eqn, ins) and returns
# the raw (lo, hi) BEFORE dtype clamping; the driver clamps and flags.

def _t_add(eqn, ins):
    return ins[0].lo + ins[1].lo, ins[0].hi + ins[1].hi


def _t_sub(eqn, ins):
    return ins[0].lo - ins[1].hi, ins[0].hi - ins[1].lo


def _t_mul(eqn, ins):
    prods = [
        a * b
        for a in (ins[0].lo, ins[0].hi)
        for b in (ins[1].lo, ins[1].hi)
    ]
    return min(prods), max(prods)


def _t_div(eqn, ins):
    # Integer division with a non-negative numerator (the only form the
    # analyzed programs use). Divisor interval including 0 falls back
    # to the numerator's own range (x // 1 bound).
    a, b = ins
    lo_div = b.hi if b.hi >= 1 else 1
    hi_div = b.lo if b.lo >= 1 else 1
    return a.lo // lo_div, a.hi // hi_div


def _t_max(eqn, ins):
    return max(ins[0].lo, ins[1].lo), max(ins[0].hi, ins[1].hi)


def _t_min(eqn, ins):
    return min(ins[0].lo, ins[1].lo), min(ins[0].hi, ins[1].hi)


def _t_and(eqn, ins):
    # Bitwise AND of non-negative ints: result <= min of either bound.
    return 0, min(ins[0].hi, ins[1].hi)


def _t_or(eqn, ins):
    # a | b <= a + b for non-negative ints.
    return max(ins[0].lo, ins[1].lo), ins[0].hi + ins[1].hi


def _t_xor(eqn, ins):
    return 0, ins[0].hi + ins[1].hi


def _t_not(eqn, ins):
    # Boolean not (the only `not` the analyzed programs produce).
    return 1 - ins[0].hi, 1 - ins[0].lo


def _cmp(kind):
    def t(eqn, ins):
        a, b = ins
        definite = {
            "lt": (a.hi < b.lo, a.lo >= b.hi),
            "le": (a.hi <= b.lo, a.lo > b.hi),
            "gt": (a.lo > b.hi, a.hi <= b.lo),
            "ge": (a.lo >= b.hi, a.hi < b.lo),
            "eq": (a.lo == a.hi == b.lo == b.hi, a.hi < b.lo or a.lo > b.hi),
            "ne": (a.hi < b.lo or a.lo > b.hi, a.lo == a.hi == b.lo == b.hi),
        }[kind]
        if definite[0]:
            return 1, 1
        if definite[1]:
            return 0, 0
        return 0, 1

    return t


def _t_select(eqn, ins):
    pred, cases = ins[0], ins[1:]
    if pred.lo == pred.hi and 0 <= int(pred.lo) < len(cases):
        c = cases[int(pred.lo)]
        return c.lo, c.hi
    lo = min(c.lo for c in cases)
    hi = max(c.hi for c in cases)
    return lo, hi


def _t_identity(eqn, ins):
    return ins[0].lo, ins[0].hi


def _t_convert(eqn, ins):
    return ins[0].lo, ins[0].hi  # clamp (with flag) handled by driver


def _t_reduce_sum(eqn, ins):
    n = _reduce_count(eqn)
    lo = ins[0].lo * n if ins[0].lo < 0 else ins[0].lo
    return lo, ins[0].hi * n


def _reduce_count(eqn) -> int:
    in_sz = _aval_size(eqn.invars[0].aval)
    out_sz = max(1, _aval_size(eqn.outvars[0].aval))
    return max(1, in_sz // out_sz)


def _aval_size(aval) -> int:
    sz = 1
    for d in getattr(aval, "shape", ()):
        sz *= int(d)
    return sz


def _t_shift_left(eqn, ins):
    return ins[0].lo << int(ins[1].lo), ins[0].hi << int(ins[1].hi)


def _t_shift_right(eqn, ins):
    return ins[0].lo >> int(ins[1].hi), ins[0].hi >> int(ins[1].lo)


def _t_iota(eqn, ins):
    return 0, max(0, _aval_size(eqn.outvars[0].aval) - 1)


def _t_pow(eqn, ins):
    y = int(eqn.params.get("y", 1))
    vals = [ins[0].lo ** y, ins[0].hi ** y]
    return min(vals), max(vals)


TRANSFER = {
    "add": _t_add,
    "sub": _t_sub,
    "mul": _t_mul,
    "div": _t_div,
    "max": _t_max,
    "min": _t_min,
    "and": _t_and,
    "or": _t_or,
    "xor": _t_xor,
    "not": _t_not,
    "lt": _cmp("lt"),
    "le": _cmp("le"),
    "gt": _cmp("gt"),
    "ge": _cmp("ge"),
    "eq": _cmp("eq"),
    "ne": _cmp("ne"),
    "select_n": _t_select,
    "convert_element_type": _t_convert,
    "broadcast_in_dim": _t_identity,
    "reshape": _t_identity,
    "squeeze": _t_identity,
    "transpose": _t_identity,
    "slice": _t_identity,
    "rev": _t_identity,
    "copy": _t_identity,
    "stop_gradient": _t_identity,
    "reduce_max": _t_identity,
    "reduce_min": _t_identity,
    "reduce_or": _t_identity,
    "reduce_and": _t_identity,
    "reduce_sum": _t_reduce_sum,
    "shift_left": _t_shift_left,
    "shift_right_logical": _t_shift_right,
    "shift_right_arithmetic": _t_shift_right,
    "iota": _t_iota,
    "integer_pow": _t_pow,
    "concatenate": None,  # handled inline (n-ary hull)
}

_CALL_PRIMS = {"jit", "closed_call", "custom_jvp_call", "custom_vjp_call"}


def _literal_interval(val) -> Interval:
    try:
        import numpy as _np

        return Interval(float(_np.min(val)), float(_np.max(val)))
    except Exception:
        return Interval(float(val), float(val))


def analyze_jaxpr(
    closed_or_open: Any,
    in_intervals: list[tuple[float, float]],
) -> IntervalResult:
    """Propagate intervals through a jaxpr.

    ``in_intervals`` gives (lo, hi) per flattened input; returns the
    output intervals plus every potentially-wrapping op and every
    unmodeled primitive encountered (including inside nested jit calls).
    """
    jaxpr = getattr(closed_or_open, "jaxpr", closed_or_open)
    consts = list(getattr(closed_or_open, "consts", ()))
    res = IntervalResult(out=[], wrapped=[], unknown=[])
    env: dict[Any, Interval] = {}

    for var, cval in zip(jaxpr.constvars, consts):
        env[var] = _literal_interval(cval)
    if len(in_intervals) != len(jaxpr.invars):
        raise ValueError(
            f"expected {len(jaxpr.invars)} input intervals, "
            f"got {len(in_intervals)}"
        )
    for var, (lo, hi) in zip(jaxpr.invars, in_intervals):
        env[var] = Interval(lo, hi)

    def read(v) -> Interval:
        if hasattr(v, "val"):  # Literal
            return _literal_interval(v.val)
        return env[v]

    def run(jx, local_env):
        for i, eqn in enumerate(jx.eqns):
            name = eqn.primitive.name

            def rd(v):
                if hasattr(v, "val"):
                    return _literal_interval(v.val)
                return local_env[v]

            if name in _CALL_PRIMS:
                inner = eqn.params.get("jaxpr") or eqn.params.get(
                    "call_jaxpr"
                )
                inner_jx = getattr(inner, "jaxpr", inner)
                inner_consts = list(getattr(inner, "consts", ()))
                inner_env: dict[Any, Interval] = {}
                for cv, cval in zip(inner_jx.constvars, inner_consts):
                    inner_env[cv] = _literal_interval(cval)
                for iv, ov in zip(inner_jx.invars, eqn.invars):
                    inner_env[iv] = rd(ov)
                run(inner_jx, inner_env)
                for outv, innerv in zip(eqn.outvars, inner_jx.outvars):
                    local_env[outv] = (
                        _literal_interval(innerv.val)
                        if hasattr(innerv, "val")
                        else inner_env[innerv]
                    )
                continue

            ins = [rd(v) for v in eqn.invars]
            out_aval = eqn.outvars[0].aval
            dmin, dmax = dtype_range(getattr(out_aval, "dtype", "?"))

            if name == "concatenate":
                lo = min(x.lo for x in ins)
                hi = max(x.hi for x in ins)
            elif name in ("scatter-add", "scatter_add"):
                # counter.at[idx].add(w): bound = carry.hi + sum of all
                # update weights (every update could land in one cell).
                n_upd = _aval_size(eqn.invars[2].aval)
                lo = ins[0].lo
                hi = ins[0].hi + ins[2].hi * n_upd
            elif name in ("scatter-max", "scatter_max"):
                lo = ins[0].lo
                hi = max(ins[0].hi, ins[2].hi)
            elif name in TRANSFER and TRANSFER[name] is not None:
                lo, hi = TRANSFER[name](eqn, ins)
            else:
                res.unknown.append(name)
                lo, hi = dmin, dmax

            if lo < dmin or hi > dmax:
                if dmax != float("inf"):
                    res.wrapped.append(
                        f"{name} (eqn {i}): range [{lo}, {hi}] exceeds "
                        f"{getattr(out_aval, 'dtype', '?')}"
                    )
                lo, hi = max(lo, dmin), min(hi, dmax)
                if lo > hi:  # entire range out of dtype: clamp fully
                    lo, hi = dmin, dmax
            out_iv = Interval(lo, hi)
            for ov in eqn.outvars:
                local_env[ov] = out_iv

    run(jaxpr, env)
    for v in jaxpr.outvars:
        res.out.append(read(v))
    return res
