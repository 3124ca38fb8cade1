"""Device-program lowering harness for the RT300 family.

Builds every registered ``@device_entry`` program (retina_tpu/
devprog.py) under a tiny synthetic 4-device CPU mesh and hands the
jaxprs / lowered executables to tools/analyze/rt300.py:

- merge jaxprs + their algebra whitelists          (RT300)
- pure-sum counter chains and the overflow envelope (RT301)
- lowered args_info donation audits                (RT302)
- compiled HLO collective scans                    (RT303)
- host/device predicate parity sweeps              (RT304)

This module is the ONLY analysis module that imports jax, and the
import happens at module scope AFTER forcing the CPU backend with 4
synthetic devices — so it must only ever be imported lazily, from
``rt300.check_device`` (the default AST lint never loads it). If jax
was already imported by the host process (in-process test runners),
the env vars are no-ops and the harness degrades to however many
devices exist; `python tools/lint.py --device` always runs in a fresh
process and therefore always gets the full 4-device mesh.

Every shape here is deliberately tiny (width 8 sketches, batch 8):
the checks are properties of the PROGRAM (which primitives, which
donations, which collectives), not of the data, and tiny shapes keep
the full sweep well under the 60s tier-1 budget.
"""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()

import dataclasses
import itertools
import threading
from typing import Any

import warnings

import jax
import jax.numpy as jnp
import numpy as np

# Deliberate policy (RT302): consumed wire/stacked operands are
# donated even where output shapes preclude aliasing — donation makes
# jax delete the caller's reference, so an accidental host reread of a
# consumed buffer errors loudly instead of silently double-using it.
# The advisory "not usable" warning is therefore expected here.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from retina_tpu.devprog import DeviceEntry, load_registry

# ---------------------------------------------------------------------
# Documented analysis envelope (RT301). These are the load-bearing
# assumptions of the no-overflow proof; docs/static-analysis.md RT301
# spells them out and the finding messages reference them.

# Per-node events per 1s window the engine is sized for: 2^28 (~268M
# ev/s) is >100x the measured single-node ceiling (bench.py); every
# u32 pure-sum counter cell can absorb at most the whole window's
# packet weight.
MAX_PACKETS_PER_WINDOW = 1 << 28

# Per combined ROW packet weight entering the HT rescale: a row
# aggregates one flow's quantum within one flush, bounded by the same
# per-window envelope.
MAX_PACKETS_PER_ROW = 1 << 28

U32_MAX = 2**32 - 1


# ---------------------------------------------------------------------
# Algebra whitelists (RT300). STRUCTURAL ops move values without
# combining them; SUM/MAX are the two associative-commutative reduction
# algebras; JOIN is the compare/select join-semilattice of
# TopKTable.merge (lexicographic (count, first-differing-key) max —
# associative, commutative, idempotent).

STRUCTURAL = frozenset({
    "reshape", "broadcast_in_dim", "convert_element_type", "transpose",
    "squeeze", "slice", "concatenate", "pad", "copy", "rev", "iota",
})
SUM = frozenset({"add"})
MAX = frozenset({"max"})
JOIN = frozenset({
    "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "xor", "not",
    "select_n", "argmax", "argmin", "reduce_or", "reduce_and",
    "reduce_max", "reduce_min", "gather",
})
# Batched (stacked-axis) reductions the fleet merge applies.
STACK_REDUCE = frozenset({"reduce_sum", "reduce_max"})

# Call primitives: transparent wrappers the jaxpr walkers recurse into.
CALL_PRIMS = frozenset({
    "jit", "closed_call", "custom_jvp_call", "custom_vjp_call",
})


@dataclasses.dataclass
class MergeRecipe:
    entry: str
    algebra: str  # human label: "sum" | "max" | "join" | composite
    jaxpr: Any  # ClosedJaxpr
    allowed: frozenset[str]


@dataclasses.dataclass
class PurityTarget:
    entry: str  # registry entry the chain lives in
    counter: str  # human path, e.g. "state.flow_hh.cms.table"
    jaxpr: Any  # ClosedJaxpr
    out_idx: int  # flattened output position of the counter
    in_idx: int  # flattened input position of its carry source


@dataclasses.dataclass
class EntryAudit:
    entry: str
    n_args: int
    arg_donated: list[list[bool]]  # per top-level arg, per leaf
    donate_expect: tuple[int, ...]  # args that MUST be donated
    keep_expect: tuple[int, ...]  # args that MUST NOT be donated
    hlo_text: str
    allowed_collectives: frozenset[str]
    aliased: bool  # compiled program aliases at least one input/output


COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "reduce-scatter",
)


# ---------------------------------------------------------------------
# Tiny fixtures

def _mesh() -> Mesh:
    devs = jax.devices()
    return Mesh(np.array(devs[: min(4, len(devs))]), ("d",))


def _tiny_pipeline():
    from retina_tpu.models.pipeline import PipelineConfig, TelemetryPipeline

    cfg = PipelineConfig(
        n_pods=16,
        n_drop_reasons=4,
        n_dns_qtypes=4,
        cms_depth=2,
        cms_width=64,
        topk_slots=8,
        hll_precision=4,
        hll_pod_precision=4,
        entropy_buckets=8,
        conntrack_slots=16,
        latency_slots=8,
        latency_buckets=8,
        enable_invertible=True,
        inv_depth=2,
        inv_width=8,
        inv_hi_width=8,
    )
    return TelemetryPipeline(cfg), cfg


def _pipeline_args(pipe):
    """Concrete tiny args for TelemetryPipeline.step (positional)."""
    from retina_tpu.models.identity import IdentityMap

    b = 8
    state = pipe.init_state()
    records = jnp.zeros((b, 16), jnp.uint32)
    n_valid = jnp.uint32(0)
    now_s = jnp.uint32(1)
    ident = IdentityMap.zeros(1 << 4, seed=1)
    apiserver_ip = jnp.uint32(0)
    filt = IdentityMap.zeros(1 << 4, seed=99)
    sample_k = jnp.uint32(1)
    return (
        state, records, n_valid, now_s, ident, apiserver_ip, filt,
        sample_k,
    )


def _protos():
    from retina_tpu.ops.countmin import CountMinSketch
    from retina_tpu.ops.entropy import EntropyWindow
    from retina_tpu.ops.hyperloglog import HyperLogLog
    from retina_tpu.ops.invertible import InvertibleSketch
    from retina_tpu.ops.topk import HeavyHitterSketch, TopKTable

    return {
        "cms": CountMinSketch.zeros(2, 8, seed=1),
        "topk": TopKTable.zeros(2, 8, seed=1),
        "hh": HeavyHitterSketch.zeros(2, depth=2, width=8, n_slots=8, seed=1),
        "hll": HyperLogLog.zeros(2, 4, seed=1),
        "entropy": EntropyWindow.zeros(2, 8, seed=1),
        "inv": InvertibleSketch.zeros(2, 8, 4, seed=1),
    }


# ---------------------------------------------------------------------
# RT300: merge jaxprs

def merge_recipes() -> list[MergeRecipe]:
    p = _protos()
    mk = jax.make_jaxpr

    def jp(a):
        return mk(lambda x, y: x.merge(y))(a, a)

    recipes = [
        MergeRecipe("cms.merge", "sum", jp(p["cms"]), SUM | STRUCTURAL),
        MergeRecipe("hll.merge", "max", jp(p["hll"]), MAX | STRUCTURAL),
        MergeRecipe(
            "entropy.merge", "sum", jp(p["entropy"]), SUM | STRUCTURAL
        ),
        MergeRecipe("inv.merge", "sum", jp(p["inv"]), SUM | STRUCTURAL),
        MergeRecipe(
            "topk.merge", "join", jp(p["topk"]), JOIN | STRUCTURAL
        ),
        MergeRecipe(
            "hh.merge", "sum+join", jp(p["hh"]), SUM | JOIN | STRUCTURAL
        ),
    ]
    recipes.append(_fleet_merge_recipe())
    recipes.append(_timetravel_fold_recipe())
    return recipes


def _fleet_stub():
    from retina_tpu.fleet.aggregator import FleetAggregator

    agg = FleetAggregator.__new__(FleetAggregator)
    agg._merge_cache = {}
    return agg


def _fleet_merge_arrays(n: int = 3) -> tuple[dict, tuple, dict]:
    """A representative stacked-arrays dict: one sum family, one max
    family, one candidate-table pair, so every branch of the fleet
    merge closure is traced."""
    stacked = {
        "flow_cms": jnp.zeros((n, 2, 8), jnp.uint32),
        "flow_keys": jnp.zeros((n, 8, 4), jnp.uint32),
        "flow_counts": jnp.zeros((n, 8), jnp.uint32),
        "hll_flows": jnp.zeros((n, 2, 4), jnp.uint32),
        "entropy": jnp.zeros((n, 2, 8), jnp.float32),
        "totals": jnp.zeros((n, 16), jnp.uint32),
    }
    names = tuple(sorted(stacked))
    seeds = {"flow": 1}
    return stacked, names, seeds


def _fleet_merge_recipe() -> MergeRecipe:
    agg = _fleet_stub()
    stacked, names, seeds = _fleet_merge_arrays()
    fn = agg._merge_fn(3, seeds, names)
    jaxpr = jax.make_jaxpr(fn)(stacked)
    # Union whitelist: the fleet merge folds every family in one
    # program (sums + HLL max + candidate-table join); the per-family
    # strictness comes from the per-op recipes above.
    return MergeRecipe(
        "fleet.merge", "sum+max+join", jaxpr,
        SUM | MAX | JOIN | STRUCTURAL | STACK_REDUCE,
    )


def _timetravel_stub():
    from retina_tpu.timetravel.fold import RangeFold

    return RangeFold()


def _timetravel_fold_recipe() -> MergeRecipe:
    """The time-axis fold (timetravel/fold.py) runs the same batched
    reduction as the fleet merge over stacked RING slots instead of
    stacked nodes — same algebra obligation, same whitelist."""
    fold = _timetravel_stub()
    stacked, names, seeds = _fleet_merge_arrays()
    fn = fold._fold_fn(3, seeds, names)
    jaxpr = jax.make_jaxpr(fn)(stacked)
    return MergeRecipe(
        "timetravel.range_fold", "sum+max+join", jaxpr,
        SUM | MAX | JOIN | STRUCTURAL | STACK_REDUCE,
    )


# ---------------------------------------------------------------------
# Trace-only smokes: update kernels that carry no algebra obligation
# (max/select updates) still get traced so the inventory covers them.

def update_trace_smokes() -> list[tuple[str, Any]]:
    p = _protos()
    mk = jax.make_jaxpr
    k = jnp.zeros((8,), jnp.uint32)
    w = jnp.zeros((8,), jnp.uint32)
    g = jnp.zeros((8,), jnp.uint32)
    m = jnp.zeros((8,), bool)
    return [
        ("topk.update", mk(lambda s: s.update([k, k], w))(p["topk"])),
        ("hh.update", mk(lambda s: s.update([k, k], w))(p["hh"])),
        ("hll.update", mk(lambda s: s.update([k, k], g, m))(p["hll"])),
    ]


# ---------------------------------------------------------------------
# RT301a: pure-sum counter carrier chains

# PipelineState leaves (dotted attribute paths) that are u32 pure-sum
# counters: their whole in-window update path must be scatter-add /
# add so the per-window overflow bound (RT301b) actually applies.
PURE_SUM_COUNTERS = (
    "flow_hh.cms.table",
    "svc_hh.cms.table",
    "dns_hh.cms.table",
    "inv_flow.planes",
    "inv_flow.weights",
    "inv_hi.planes",
    "inv_hi.weights",
    "pod_forward",
    "pod_drop",
    "pod_tcpflags",
    "pod_dns",
    "pod_retrans",
    "lat_hist",
)

# State leaves (path prefixes) deliberately NOT pure-sum, with the
# reviewed reason — kept here so a new counter field must be
# classified one way or the other (rt300 flags unclassified u32
# leaves via classify_state_counters).
COUNTER_EXEMPT = {
    "totals": "documented wrap: u32 lane counters, host keeps exact f64",
    "ct_totals": "two-limb u32 pair with explicit carry (_sum64)",
    "node_counters": "derived per-window tallies (masked selects), "
                     "reset every snapshot cycle",
    "flow_hh.table": "candidate table: join-semilattice, not sums",
    "svc_hh.table": "candidate table: join-semilattice, not sums",
    "dns_hh.table": "candidate table: join-semilattice, not sums",
    "hll_flows": "HLL registers: max algebra",
    "hll_src_per_reason": "HLL registers: max algebra",
    "hll_src_per_pod": "HLL registers: max algebra",
    "entropy": "float32 histogram (IEEE saturates, no wrap)",
    "anomaly": "float EWMA state",
    "conntrack": "slotted connection table: set/overwrite semantics",
    "lat_key": "latency probe keys: overwrite semantics",
    "lat_ts": "latency probe timestamps: overwrite semantics",
}


class _Tag:
    """Unique leaf marker used to recover dotted attribute paths from
    keyless custom pytrees (PipelineState registers without keypaths,
    so tree_flatten_with_path only yields flat indices)."""

    __slots__ = ("i",)

    def __init__(self, i: int):
        self.i = i


def _leaf_names(tree) -> dict[int, str]:
    """flat-leaf-index -> dotted attribute path, by mapping every leaf
    to a _Tag and walking the reconstructed pytree's dataclass
    attributes."""
    cnt = itertools.count()
    tagged = jax.tree_util.tree_map(lambda _: _Tag(next(cnt)), tree)
    names: dict[int, str] = {}

    def walk(obj, prefix):
        if isinstance(obj, _Tag):
            names[obj.i] = prefix
        elif dataclasses.is_dataclass(obj):
            for f in dataclasses.fields(obj):
                sub = getattr(obj, f.name)
                walk(sub, f"{prefix}.{f.name}" if prefix else f.name)
        elif isinstance(obj, (list, tuple)):
            for i, sub in enumerate(obj):
                walk(sub, f"{prefix}[{i}]")
        elif isinstance(obj, dict):
            for kk, sub in obj.items():
                walk(sub, f"{prefix}[{kk}]")
        # anything else (aux scalars like seeds) is not a leaf

    walk(tagged, "")
    n = len(jax.tree_util.tree_leaves(tree))
    if len(names) != n:
        raise AssertionError(
            f"leaf-name walk found {len(names)} of {n} leaves — "
            "an unregistered container hides leaves from getattr"
        )
    return names


def step_purity_targets() -> list[PurityTarget]:
    """The pipeline.step jaxpr plus (out_idx, in_idx) pairs for every
    pure-sum counter leaf of PipelineState.

    state is positional arg 0 so its leaves open the jaxpr invars; the
    returned new_state shares the state treedef and flattens first in
    the (new_state, summary) output, so out_idx == in_idx."""
    pipe, _cfg = _tiny_pipeline()
    args = _pipeline_args(pipe)
    closed = jax.make_jaxpr(pipe.step)(*args)
    by_name = {v: k for k, v in _leaf_names(args[0]).items()}
    targets = []
    for c in PURE_SUM_COUNTERS:
        if c not in by_name:
            raise AssertionError(
                f"PURE_SUM_COUNTERS entry is not a PipelineState "
                f"leaf: {c}"
            )
        idx = by_name[c]
        targets.append(
            PurityTarget(
                entry="pipeline.step", counter=c, jaxpr=closed,
                out_idx=idx, in_idx=idx,
            )
        )
    return targets


def op_purity_targets() -> list[PurityTarget]:
    """Per-op pure-sum chains: sketch.update must carry its counter
    through scatter-add/add only."""
    p = _protos()
    k = jnp.zeros((8,), jnp.uint32)
    w = jnp.zeros((8,), jnp.uint32)
    g = jnp.zeros((8,), jnp.uint32)
    out = []

    cms_j = jax.make_jaxpr(lambda s: s.update([k, k], w))(p["cms"])
    out.append(PurityTarget("cms.update", "cms.table", cms_j, 0, 0))

    ent_j = jax.make_jaxpr(lambda s: s.update([k, k], g, w))(p["entropy"])
    out.append(
        PurityTarget("entropy.update", "entropy.counts", ent_j, 0, 0)
    )

    inv_j = jax.make_jaxpr(lambda s: s.update([k, k, k, k], w))(p["inv"])
    out.append(PurityTarget("inv.update", "inv.planes", inv_j, 0, 0))
    out.append(PurityTarget("inv.update", "inv.weights", inv_j, 1, 1))
    return out


def classify_state_counters() -> list[str]:
    """Every u32 PipelineState leaf must be either in
    PURE_SUM_COUNTERS or COUNTER_EXEMPT — returns the unclassified
    (a new counter field fails RT301 until it is classified)."""
    pipe, _cfg = _tiny_pipeline()
    shape = jax.eval_shape(pipe.init_state)
    names = _leaf_names(shape)
    leaves = jax.tree_util.tree_leaves(shape)
    pure = set(PURE_SUM_COUNTERS)
    unclassified = []
    for i, leaf in enumerate(leaves):
        if str(leaf.dtype) != "uint32":
            continue
        name = names[i]
        if name in pure:
            continue
        if any(
            name == e or name.startswith(e + ".") or
            name.startswith(e + "[")
            for e in COUNTER_EXEMPT
        ):
            continue
        unclassified.append(name)
    return unclassified


# ---------------------------------------------------------------------
# RT301b: per-window wrap bound from config maxima

def window_wrap_report() -> dict[str, Any]:
    from retina_tpu.config import Config

    cfg = Config()
    k = max(1, int(cfg.overload_sample_k))
    window = max(1, int(np.ceil(cfg.window_seconds)))
    bound = k * MAX_PACKETS_PER_WINDOW * window
    return {
        "k": k,
        "window_seconds": window,
        "envelope": MAX_PACKETS_PER_WINDOW,
        "bound": bound,
        "ok": bound <= U32_MAX,
    }


# ---------------------------------------------------------------------
# RT301c: HT-rescale interval target

def ht_rescale_target() -> tuple[Any, list[tuple[int, int]]]:
    """(closed_jaxpr, input intervals) for models.pipeline.ht_rescale
    under the documented per-row envelope."""
    from retina_tpu.models.pipeline import ht_rescale

    b = 8
    jaxpr = jax.make_jaxpr(ht_rescale)(
        jnp.zeros((b,), jnp.uint32),
        jnp.zeros((b,), jnp.uint32),
        jnp.zeros((b,), bool),
        jnp.uint32(1),
    )
    from retina_tpu.config import Config

    k = max(1, int(Config().overload_sample_k))
    intervals = [
        (0, MAX_PACKETS_PER_ROW),  # packets
        (0, MAX_PACKETS_PER_ROW),  # bytes (same per-row envelope)
        (0, 1),  # exempt
        (1, k),  # sample_k
    ]
    return jaxpr, intervals


# ---------------------------------------------------------------------
# RT302/RT303: lowered entry audits

def _arg_donated(obj, n_args: int) -> list[list[bool]]:
    """Per top-level positional arg, the donated flag of each leaf."""
    info = obj.args_info
    if (
        isinstance(info, tuple)
        and len(info) == 2
        and isinstance(info[1], dict)
    ):
        info = info[0]
    return [
        [a.donated for a in jax.tree_util.tree_leaves(info[i])]
        for i in range(n_args)
    ]


def _audit(
    entry: str,
    lowered,
    n_args: int,
    donate: tuple[int, ...] = (),
    keep: tuple[int, ...] = (),
    allowed: frozenset[str] = frozenset(),
) -> EntryAudit:
    compiled = lowered.compile() if hasattr(lowered, "compile") else lowered
    hlo = compiled.as_text()
    return EntryAudit(
        entry=entry,
        n_args=n_args,
        arg_donated=_arg_donated(lowered, n_args),
        donate_expect=donate,
        keep_expect=keep,
        hlo_text=hlo,
        allowed_collectives=allowed,
        aliased="input_output_alias" in hlo,
    )


def _engine_stub(mesh: Mesh):
    from retina_tpu.config import Config
    from retina_tpu.engine import SketchEngine

    eng = SketchEngine.__new__(SketchEngine)
    eng.cfg = dataclasses.replace(
        Config(), batch_capacity=16, flow_dict_slots=32
    )
    eng.n_devices = mesh.size
    eng._rec_sharding = NamedSharding(mesh, P("d"))
    eng._replicated = NamedSharding(mesh, P())
    eng._pad_cache = {}
    eng._fd_lock = threading.Lock()
    eng._desc_table = None
    eng._fd_id_bits = max(
        1, (eng.cfg.flow_dict_slots - 1).bit_length()
    )
    # The stub never touches a disk cache, so the AOT signature is
    # inert here.
    eng._aot_sig = ""
    return eng


def entry_audits() -> list[EntryAudit]:
    mesh = _mesh()
    audits: list[EntryAudit] = []

    # -- single-chip pipeline ------------------------------------------
    pipe, cfg = _tiny_pipeline()
    args = _pipeline_args(pipe)
    step_low = pipe.jitted_step().lower(*args)
    audits.append(
        _audit(
            "pipeline.step", step_low, len(args),
            donate=(0,),
            keep=(4, 6),  # ident / filter_map are resident tables
        )
    )
    ew_low = pipe.jitted_end_window().lower(args[0], 4.0)
    audits.append(
        _audit("pipeline.end_window", ew_low, 2, donate=(0,))
    )

    from retina_tpu.ops.countmin import CountMinSketch, cms_update_jit

    proto = CountMinSketch.zeros(2, 8, seed=1)
    kcols = [jnp.zeros((8,), jnp.uint32)] * 2
    cms_low = cms_update_jit.lower(
        proto, kcols, jnp.zeros((8,), jnp.uint32)
    )
    audits.append(_audit("cms.update_jit", cms_low, 3, donate=(0,)))

    # -- sharded telemetry programs ------------------------------------
    from retina_tpu.models.identity import IdentityMap
    from retina_tpu.parallel.telemetry import ShardedTelemetry

    st = ShardedTelemetry(cfg, mesh)
    d, b = mesh.size, 8
    state = st.init_state()
    records = jnp.zeros((d, b, 16), jnp.uint32)
    n_valid = jnp.zeros((d,), jnp.uint32)
    ident = IdentityMap.zeros(1 << 4, seed=1)
    filt = IdentityMap.zeros(1 << 4, seed=99)
    u = jnp.uint32(0)

    audits.append(
        _audit(
            "sharded.init_state", st._build_init_state().lower(), 0,
        )
    )
    step_prog = st._build_step()
    audits.append(
        _audit(
            "sharded.step",
            step_prog._jitted.lower(
                state, records, n_valid, u, ident, u, filt, u,
                jnp.uint32(1),
            ),
            9,
            donate=(0,),
            keep=(4, 6),
            allowed=frozenset({"all-reduce"}),
        )
    )
    audits.append(
        _audit(
            "sharded.end_window",
            st._build_end_window()._jitted.lower(
                state, jnp.float32(4.0)
            ),
            2,
            donate=(0,),
            allowed=frozenset({"all-reduce"}),
        )
    )
    audits.append(
        _audit(
            "sharded.snapshot",
            st._build_snapshot()._jitted.lower(state, u),
            2,
            keep=(0,),  # snapshot must NOT consume resident state
            allowed=frozenset({"all-reduce", "all-gather"}),
        )
    )
    audits.append(
        _audit(
            "sharded.fleet_export",
            st._build_fleet_export()._jitted.lower(state),
            1,
            keep=(0,),
            allowed=frozenset({"all-reduce", "all-gather"}),
        )
    )
    audits.append(
        _audit(
            "sharded.inv_decode",
            st._build_inv_decode()._jitted.lower(state, u),
            2,
            keep=(0,),
            allowed=frozenset({"all-reduce"}),
        )
    )
    flat_fn, _leaves, _treedef = st._build_snapshot_flat(state)
    audits.append(
        _audit(
            "sharded.snapshot_flat",
            flat_fn._jitted.lower(state, u),
            2,
            keep=(0,),
            allowed=frozenset({"all-reduce", "all-gather"}),
        )
    )

    # -- engine ingest programs ----------------------------------------
    # Ingest crosses the host->device placement boundary: the wire
    # array arrives sharded but meta is replicated and the derived
    # per-device validity counts must land sharded, so XLA emits
    # placement collectives over the SMALL wire/meta arrays. Those are
    # inherent to ingestion; RT303's teeth are on the state-resident
    # entries above (step/end_window: all-reduce only; merges: none).
    eng = _engine_stub(mesh)
    audits.append(
        _audit(
            "engine.ingest", eng._ingest_fn(8), 2,
            donate=(0,),
            allowed=frozenset({"collective-permute"}),
        )
    )
    audits.append(
        _audit(
            "engine.ingest_new", eng._ingest_new_fn(8), 3,
            donate=(0, 2),
            allowed=frozenset({"all-gather", "collective-permute"}),
        )
    )
    audits.append(
        _audit(
            "engine.ingest_known", eng._ingest_known_fn(8), 3,
            donate=(0,),
            keep=(2,),  # resident descriptor table, reread every flush
            allowed=frozenset(
                {"all-reduce", "all-gather", "collective-permute"}
            ),
        )
    )
    audits.append(
        _audit("engine.desc_table", eng._desc_table_fn().lower(), 0)
    )
    # Folding a flush's two one-window sides into one step window is
    # per device: each moves its own rows, nothing crosses the mesh.
    audits.append(
        _audit("engine.fold_sides", eng._fold_sides_fn(), 4, donate=(0,))
    )

    # -- fleet merge ---------------------------------------------------
    agg = _fleet_stub()
    stacked, names, seeds = _fleet_merge_arrays()
    fm_low = agg._merge_fn(3, seeds, names).lower(stacked)
    audits.append(_audit("fleet.merge", fm_low, 1, donate=(0,)))

    # -- timetravel range fold ----------------------------------------
    fold = _timetravel_stub()
    stacked, names, seeds = _fleet_merge_arrays()
    tt_low = fold._fold_fn(3, seeds, names).lower(stacked)
    audits.append(_audit("timetravel.range_fold", tt_low, 1, donate=(0,)))

    # -- timetravel range decode --------------------------------------
    # Tiny invertible region: width 8, depth 2, 4 key cols -> 160 bit
    # planes; CMS table at matching width. No donation: the operands
    # are live ring snapshot state.
    from retina_tpu.timetravel.fold import _decode_program

    planes = jnp.zeros((2, 8, 160), jnp.uint32)
    weights = jnp.zeros((2, 8), jnp.uint32)
    table = jnp.zeros((2, 8), jnp.uint32)
    td_low = _decode_program(planes.shape, 9, 1).lower(
        planes, weights, table
    )
    audits.append(_audit("timetravel.range_decode", td_low, 3))

    # -- timetravel range extract -------------------------------------
    # Derived answers over one folded snapshot (shape = stacked[0]).
    from retina_tpu.timetravel.fold import _extract_program

    stacked, _names, seeds = _fleet_merge_arrays()
    sub = {
        k: stacked[k][0]
        for k in ("flow_cms", "flow_keys", "hll_flows", "entropy")
    }
    ex_names = tuple(sorted(sub))
    ex_shapes = tuple(sub[n].shape for n in ex_names)
    ex_low = _extract_program(ex_names, ex_shapes, seeds).lower(sub)
    audits.append(_audit("timetravel.range_extract", ex_low, 1))

    # -- detector scoring programs ------------------------------------
    # Tiny host-built feature inputs (detect/features.py); no donation:
    # the arrays are window accumulators the host reuses.
    from retina_tpu.detect.programs import (
        dnstunnel_program, portscan_program, synflood_program,
    )

    ps_keys = jnp.zeros((16, 4), jnp.uint32)
    ps_w = jnp.zeros((16,), jnp.float32)
    ps_low = portscan_program(16, 8, 4, 0x5CA7).lower(ps_keys, ps_w)
    audits.append(_audit("detect.portscan", ps_low, 2))

    dt_low = dnstunnel_program(64, 0xD25).lower(
        jnp.zeros((1, 64), jnp.float32)
    )
    audits.append(_audit("detect.dnstunnel", dt_low, 1))

    sf_low = synflood_program().lower(jnp.zeros((9,), jnp.float32))
    audits.append(_audit("detect.synflood", sf_low, 1))

    return audits


# ---------------------------------------------------------------------
# RT304: host/device predicate parity

def _ip_domain(rng) -> np.ndarray:
    vals = [0, 1, 0xFF, 0xFFFFFFFF, 0x0A000001, 0xC0A80101]
    vals += [1 << i for i in range(32)]
    vals += list(rng.randint(0, 2**32, size=64, dtype=np.uint64))
    return np.asarray(vals, np.uint32)


def parity_report() -> list[str]:
    """Execute host predicates against their device twins over the
    packed-field bit domain; returns mismatch descriptions."""
    from retina_tpu.models import pipeline as dev
    from retina_tpu.runtime import overload as host

    rng = np.random.RandomState(0)
    problems: list[str] = []

    # priority_class vs priority_class_np -----------------------------
    ips = _ip_domain(rng)
    src = np.tile(ips, len(ips))
    dst = np.repeat(ips, len(ips))
    mask_cases = [
        (0, 0),
        (0xFFFFFF00, 0x0A000000),
        (0xFFFF0000, 0xC0A80000),
        (0x80000000, 0x80000000),
        (1, 1),
        (1, 0),
        (0xFFFFFFFF, 0x0A000001),
    ]
    for mask, match in mask_cases:
        got_dev = np.asarray(
            dev.priority_class(
                jnp.asarray(src), jnp.asarray(dst), mask, match
            )
        )
        got_host = host.priority_class_np(src, dst, mask, match)
        if not np.array_equal(got_dev, got_host):
            n = int(np.sum(got_dev != got_host))
            problems.append(
                f"priority_class: device and host disagree on {n} of "
                f"{len(src)} inputs (mask=0x{mask:08x}, "
                f"match=0x{match:08x})"
            )

    # sample_exempt vs row_tiers > TIER_BACKGROUND --------------------
    from retina_tpu.events.schema import F

    packets_dom = np.asarray(
        [0, 1, 62, 63, 64, 65, 127, 128, 2**16, 2**31, U32_MAX]
        + [1 << i for i in range(32)],
        np.uint32,
    )
    ts_dom = np.asarray([0, 1, 0x80000000, U32_MAX], np.uint32)
    pri_ips = np.asarray([0, 0x0A000001, 0x0A0000FF, 0x0B000001], np.uint32)

    pk = np.tile(
        np.repeat(packets_dom, len(ts_dom) * len(ts_dom)), len(pri_ips)
    )
    tsv = np.tile(
        np.tile(np.repeat(ts_dom, len(ts_dom)), len(packets_dom)),
        len(pri_ips),
    )
    tse = np.tile(
        np.tile(ts_dom, len(ts_dom) * len(packets_dom)), len(pri_ips)
    )
    sip = np.repeat(pri_ips, len(packets_dom) * len(ts_dom) * len(ts_dom))
    n = len(pk)

    class _Cfg:
        overload_exempt_packets = 64
        overload_priority_ip_mask = 0xFFFFFF00
        overload_priority_ip_match = 0x0A000000

    rec = np.zeros((n, 16), np.uint32)
    rec[:, F.PACKETS] = pk
    rec[:, F.TSVAL] = tsv
    rec[:, F.TSECR] = tse
    rec[:, F.SRC_IP] = sip
    host_exempt = host.row_tiers(rec, _Cfg) > host.TIER_BACKGROUND

    is_pri = np.asarray(
        dev.priority_class(
            jnp.asarray(sip), jnp.zeros((n,), jnp.uint32),
            _Cfg.overload_priority_ip_mask,
            _Cfg.overload_priority_ip_match,
        )
    )
    dev_exempt = np.asarray(
        dev.sample_exempt(
            jnp.asarray(pk), jnp.asarray(tsv), jnp.asarray(tse),
            jnp.asarray(is_pri), _Cfg.overload_exempt_packets,
        )
    )
    if not np.array_equal(dev_exempt, host_exempt):
        bad = int(np.sum(dev_exempt != host_exempt))
        problems.append(
            f"sample_exempt: device predicate and host row_tiers "
            f"exemption disagree on {bad} of {n} packed-field inputs"
        )
    return problems


# ---------------------------------------------------------------------
# Inventory parity: which registry entries the recipes above cover.

RECIPE_COVERAGE = {
    # RT300 merge algebra
    "cms.merge": "merge",
    "hll.merge": "merge",
    "entropy.merge": "merge",
    "inv.merge": "merge",
    "topk.merge": "merge",
    "hh.merge": "merge",
    # RT301 purity
    "cms.update": "purity",
    "entropy.update": "purity",
    "inv.update": "purity",
    # trace smokes (max/join updates carry no sum obligation)
    "topk.update": "trace",
    "hh.update": "trace",
    "hll.update": "trace",
    # RT302/RT303 lowered audits
    "pipeline.step": "audit",
    "pipeline.end_window": "audit",
    "cms.update_jit": "audit",
    "sharded.init_state": "audit",
    "sharded.step": "audit",
    "sharded.end_window": "audit",
    "sharded.snapshot": "audit",
    "sharded.fleet_export": "audit",
    "sharded.inv_decode": "audit",
    "sharded.snapshot_flat": "audit",
    "engine.ingest": "audit",
    "engine.ingest_new": "audit",
    "engine.ingest_known": "audit",
    "engine.desc_table": "audit",
    "engine.fold_sides": "audit",
    "fleet.merge": "merge+audit",
    "timetravel.range_fold": "merge+audit",
    "timetravel.range_decode": "audit",
    "timetravel.range_extract": "audit",
    "detect.portscan": "audit",
    "detect.dnstunnel": "audit",
    "detect.synflood": "audit",
}


def registry() -> dict[str, DeviceEntry]:
    return load_registry()
