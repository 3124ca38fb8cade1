"""Sharded telemetry: the multi-chip version of models/pipeline.py.

Reference analog (SURVEY.md §2.6): the reference's cross-node story is N
independent agents + Prometheus scrape-side merges + the Hubble relay; the
TPU-native replacement runs the SAME fused pipeline step on every mesh
device over a connection-partitioned event shard, and merges at scrape
time with XLA collectives:

    dense counter rectangles, CMS tables, entropy histograms  -> psum
    HLL register banks                                        -> pmax
    heavy-hitter candidate tables                             -> all_gather
    conntrack tables                                          -> no merge
        (connection-consistent partitioning makes them disjoint; only the
        active-connection gauge is psum'd)

On a multi-host mesh (jax.distributed), the same psum reduces over ICI
within a slice and DCN across hosts — no NCCL/MPI analog is written by
hand, XLA inserts the collectives from the shardings.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import pickle
import re
import threading
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from retina_tpu.devprog import device_entry
from retina_tpu.models.identity import IdentityMap
from retina_tpu.models.pipeline import (
    SCOPE_END_WINDOW, STEP_SCOPES, PipelineConfig, PipelineState,
    TelemetryPipeline,
)
from retina_tpu.ops.invertible import decode_verified
from retina_tpu.ops.topk import TopKTable

# Operator scopes (``jax.named_scope``) of every device program of the
# agent. The profiler's device events carry an operation's HLO line but
# not its ``op_name`` metadata (read on the attached v5e: the stats of
# an ``XLA Ops`` event are its offset, its duration and a time scale),
# so each program's map from instruction name to scope is taken from
# the executable's own text where the executable is obtained (compiled
# or loaded from the AOT cache: one way for both), kept here, read
# in-process through ``op_scope_map()`` and written beside the trace of
# a ``/debug/profile`` session (``op_scopes.json``). A fusion's
# metadata is its root's, so a fusion belongs to the scope of its root.
SCOPE_SNAPSHOT_MERGE = "snapshot_merge"
SCOPE_SNAPSHOT_CONCAT = "snapshot_concat"
SCOPE_INGEST_UNPACK = "ingest_unpack"
OP_SCOPES = frozenset(STEP_SCOPES + (
    SCOPE_END_WINDOW, SCOPE_SNAPSHOT_MERGE, SCOPE_SNAPSHOT_CONCAT,
    SCOPE_INGEST_UNPACK,
))
_OP_SCOPE_LOCK = threading.Lock()
_OP_SCOPE_MAP: dict[str, dict[str, str]] = {}  # module -> {instr: scope}
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_HLO_INSTR = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', re.M
)


def scopes_of_text(hlo_text: str) -> tuple[str, dict[str, str]]:
    """(module name, {instruction name: scope}) of one compiled
    program's text: for every instruction whose ``op_name`` passes
    through a registered scope, the outermost such scope."""
    m = _HLO_MODULE.search(hlo_text)
    out: dict[str, str] = {}
    for instr, op_name in _HLO_INSTR.findall(hlo_text):
        for part in op_name.split("/"):
            if part in OP_SCOPES:
                out[instr] = part
                break
    return (m.group(1) if m else ""), out


def note_op_scopes(ex) -> None:
    """Keep the scope map of one executable, compiled or loaded.
    Best-effort: a runtime that cannot print an executable leaves that
    program's operations unscoped."""
    try:
        module, table = scopes_of_text(ex.as_text())
    except Exception:  # noqa: RT101 — diagnostics only
        return
    if module and table:
        with _OP_SCOPE_LOCK:
            _OP_SCOPE_MAP.setdefault(module, {}).update(table)


def op_scope_map() -> dict[str, dict[str, str]]:
    """``{module: {instruction: scope}}`` of every program this process
    has obtained so far (a copy)."""
    with _OP_SCOPE_LOCK:
        return {m: dict(t) for m, t in _OP_SCOPE_MAP.items()}


def write_op_scopes(path: str) -> None:
    """:func:`op_scope_map` as JSON at ``path``, a place the caller
    chose (atomic; best-effort)."""
    doc = op_scope_map()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    except OSError:
        _aot_log().warning("op scope map not written to %s", path,
                           exc_info=True)


# On-disk AOT executable cache accounting (ROADMAP item 5: compile cost
# swings 2.1s->96.1s and bucket-grid warm is 214s PER PROCESS — a disk
# cache keyed on (jax version, topology, config signature) makes warm
# cost survive restarts). Module-level so bench diag can report hit/miss
# across every AotProgram instance in the process.
_AOT_DISK_LOCK = threading.Lock()
_AOT_DISK_STATS = {"hits": 0, "misses": 0, "errors": 0}
# Per-program-tag breakdown of the same counters: the BENCH_r06
# regression (hits=1 misses=26) was invisible in the totals — the
# per-tag view names exactly which programs keep re-compiling.
_AOT_TAG_STATS: dict[str, dict[str, int]] = {}


def aot_disk_cache_stats() -> dict[str, Any]:
    """Process-wide disk-cache counters: ``hits`` (deserialized from
    disk, compile skipped), ``misses`` (compiled + persisted),
    ``errors`` (load/save attempts that failed; always fell back to a
    fresh compile, never fatal). ``by_tag`` breaks the same counters
    down per program tag (step, snapshot, ingest buckets, ...)."""
    with _AOT_DISK_LOCK:
        out: dict[str, Any] = dict(_AOT_DISK_STATS)
        out["by_tag"] = {t: dict(s) for t, s in _AOT_TAG_STATS.items()}
        return out


def _aot_disk_bump(field: str, tag: str = "") -> None:
    with _AOT_DISK_LOCK:
        _AOT_DISK_STATS[field] += 1
        if tag:
            _AOT_TAG_STATS.setdefault(
                tag, {"hits": 0, "misses": 0, "errors": 0}
            )[field] += 1


# -- free-function disk layer -----------------------------------------
# Shared by AotProgram (the telemetry step/end-window programs) and the
# engine's per-bucket ingest jits (engine._compile_cached): the bucket
# grid is the bulk of the 214s r05 warm, so it must ride the same disk
# cache as the step programs for a warm boot to land under 10s.

def _exec_devices(mesh: Mesh | None) -> list:
    """The devices one cached executable runs on: the mesh's, or the
    single default device for the mesh-less query programs
    (timetravel/fold.py), which compile from concrete uncommitted
    arguments."""
    if mesh is not None:
        return list(mesh.devices.ravel())
    return [jax.local_devices()[0]]


@functools.lru_cache(maxsize=1)
def _source_fingerprint() -> str:
    """Hash of the package's own source. The disk cache is consulted
    BEFORE a program is traced, so its key cannot see the program: a
    cache directory that outlives a code change (a node's hostPath, a
    CI machine's kept cache) would otherwise serve the old executable
    for new code whenever the config happens to be unchanged."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()[:16]


def aot_disk_path(
    cache_dir: str, mesh: Mesh | None, tag: str, config_sig: str, key
) -> str:
    """Cache-file path for one (program tag, input-signature) pair,
    keyed by jax version + package source + the executable's own device
    set + config signature so a stale entry can never load into a
    mismatched process."""
    devs = _exec_devices(mesh)
    topo = "{}:{}:{}".format(
        jax.default_backend(), ",".join(str(d.id) for d in devs),
        devs[0].device_kind,
    )
    raw = "|".join((
        jax.__version__, _source_fingerprint(), topo, tag, config_sig,
        repr(key),
    ))
    h = hashlib.sha256(raw.encode()).hexdigest()[:32]
    return os.path.join(cache_dir, f"{tag}-{h}.aotx")


def aot_disk_load(path: str, mesh: Mesh | None, tag: str = ""):
    """Deserialize a cached executable onto ``mesh``'s devices, or None
    (best-effort: corrupt/truncated file or incompatible executable
    fall back to a fresh compile, counted under ``errors``). ``tag``
    feeds the per-program counters and the hit/miss log line.

    ``execution_devices`` is always passed: left out,
    ``deserialize_and_load`` assumes every device of the backend, and an
    executable compiled for fewer loads without complaint and then
    fails at its first call."""
    if not os.path.exists(path):
        return None
    try:
        from jax.experimental import serialize_executable as se

        with open(path, "rb") as f:
            payload = pickle.load(f)
        ex = se.deserialize_and_load(
            payload["exe"], payload["in_tree"], payload["out_tree"],
            execution_devices=_exec_devices(mesh),
        )
        _aot_disk_bump("hits", tag)
        if tag:
            _aot_log().debug("aot disk HIT tag=%s path=%s", tag, path)
        note_op_scopes(ex)
        return ex
    except Exception:
        _aot_disk_bump("errors", tag)
        _aot_log().warning(
            "aot disk load failed tag=%s path=%s", tag, path,
            exc_info=True,
        )
        return None


def aot_disk_save(path: str, ex, tag: str = "") -> None:
    """Persist a compiled executable (best-effort; never fails the
    caller — persisting is an optimization only)."""
    try:
        from jax.experimental import serialize_executable as se

        payload_exe, in_tree, out_tree = se.serialize(ex)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(
                {"exe": payload_exe, "in_tree": in_tree,
                 "out_tree": out_tree},
                f,
            )
        os.replace(tmp, path)
        _aot_disk_bump("misses", tag)
        if tag:
            _aot_log().info(
                "aot disk MISS tag=%s (compiled + persisted)", tag
            )
    except Exception:
        _aot_disk_bump("errors", tag)
        _aot_log().warning(
            "aot disk save failed tag=%s path=%s", tag, path,
            exc_info=True,
        )


def _aot_log():
    from retina_tpu.log import logger

    return logger("aot.cache")


class AotProgram:
    """Aval-keyed AOT executable cache around a jitted program.

    The plain ``jax.jit`` cache keys on input *shardings* as well as
    avals, and the state pytree's sharding spelling flips between
    ``init_state``'s ``out_shardings`` (``P(('data',))``) and the
    jit-normalized step output — so the very first warm-up step used to
    compile TWICE (the 2.1s->96.1s cold-start swings, ROADMAP item 5).
    This wrapper keys ONLY on (tree structure, per-leaf shape/dtype) and
    lowers each signature once with canonical shardings; the compiled
    executable then accepts committed arrays with any equivalent
    sharding spelling as well as raw host (numpy) arrays, so ragged
    feeds and recovery rebuilds reuse the one resident executable.

    ``donate_argnums`` declared on the wrapped jit carry through
    ``lower().compile()`` untouched. ``_cache_size()`` mirrors the
    private jit introspection hook the stability tests assert on.

    When ``cache_dir`` is set, each compiled executable is additionally
    persisted to disk via ``jax.experimental.serialize_executable``,
    keyed by (jax version, the mesh's devices, ``config_sig``, program
    tag, input signature) — a later process with the same key skips XLA
    compilation entirely. Every disk interaction is best-effort: any
    failure (unpicklable trees, corrupt file, read-only dir) falls back
    to a fresh in-process compile and is counted under ``errors``.
    """

    def __init__(self, jitted, mesh: Mesh, sharded_spec,
                 sharded_argnums: tuple[int, ...],
                 cache_dir: str = "", tag: str = "prog",
                 config_sig: str = ""):
        self._jitted = jitted
        self._mesh = mesh
        self._spec = sharded_spec
        self._sharded_argnums = frozenset(sharded_argnums)
        self._execs: dict[Any, Any] = {}
        self._cache_dir = cache_dir
        self._tag = tag
        self._config_sig = config_sig

    def _signature(self, args) -> Any:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return treedef, tuple(
            (np.shape(leaf), np.dtype(
                getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
            ).name)
            for leaf in leaves
        )

    # -- disk layer (delegates to the module-level free functions so the
    # engine's bucket-grid compiles share one format and one stats pool) -
    def _disk_path(self, key) -> str:
        return aot_disk_path(
            self._cache_dir, self._mesh, self._tag, self._config_sig, key
        )

    def _disk_load(self, path: str):
        return aot_disk_load(path, self._mesh, tag=self._tag)

    def _lower(self, args, key=None):
        if self._cache_dir and key is not None:
            path = self._disk_path(key)
            ex = self._disk_load(path)
            if ex is not None:
                return ex

        def struct(i, leaf):
            sh = NamedSharding(
                self._mesh,
                self._spec if i in self._sharded_argnums else P(),
            )
            return jax.ShapeDtypeStruct(
                np.shape(leaf), np.asarray(leaf).dtype
                if not hasattr(leaf, "dtype") else leaf.dtype,
                sharding=sh,
            )

        specs = tuple(
            jax.tree.map(lambda leaf, i=i: struct(i, leaf), arg)
            for i, arg in enumerate(args)
        )
        ex = self._jitted.lower(*specs).compile()
        note_op_scopes(ex)
        if self._cache_dir and key is not None:
            aot_disk_save(self._disk_path(key), ex, tag=self._tag)
        return ex

    def __call__(self, *args):
        key = self._signature(args)
        ex = self._execs.get(key)
        if ex is None:
            ex = self._lower(args, key=key)
            self._execs[key] = ex
        return ex(*args)

    def _cache_size(self) -> int:
        return len(self._execs)


class ShardedTelemetry:
    """TelemetryPipeline spread over a jax.sharding.Mesh.

    Per-device state carries a leading device axis of size D; events arrive
    as (D, B, F) connection-partitioned batches (parallel/partition.py).
    """

    def __init__(self, config: PipelineConfig, mesh: Mesh,
                 aot_cache_dir: str = ""):
        self.pipeline = TelemetryPipeline(config)
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.n_devices = mesh.size
        self._sharded_spec = P(self.axes)  # dim0 split over every mesh axis
        self._aot_cache_dir = aot_cache_dir
        # Config identity for the disk cache key: the dataclass repr
        # covers every field that changes compiled code (widths, depths,
        # feature toggles) deterministically.
        self._config_sig = repr(config)
        self._step = None
        self._end_window = None
        self._snapshot = None
        self._snapshot_flat = None
        self._fleet_export = None
        self._inv_decode = None

    # ------------------------------------------------------------------
    @device_entry("sharded.init_state", kind="jit")
    def _build_init_state(self):
        """Builder split from init_state so the device-program analysis
        (tools/analyze/rt300.py) can lower and audit the jit without
        executing it."""
        single = jax.eval_shape(self.pipeline.init_state)
        d = self.n_devices

        @partial(
            jax.jit,
            out_shardings=NamedSharding(self.mesh, self._sharded_spec),
        )
        def mk():
            return jax.tree.map(
                lambda s: jnp.zeros((d,) + s.shape, s.dtype), single
            )

        return mk

    def init_state(self) -> PipelineState:
        return self._build_init_state()()

    # ------------------------------------------------------------------
    @device_entry("sharded.step", kind="shard_map")
    def _build_step(self):
        def local_step(
            state, records, n_valid, now_s, ident, apiserver_ip, filt, lost,
            sample_k,
        ):
            s = jax.tree.map(lambda x: x[0], state)
            new, summary = self.pipeline.step(
                s, records[0], n_valid[0], now_s, ident, apiserver_ip,
                filter_map=filt, sample_k=sample_k,
            )
            # Host-side partition overflow losses land in totals[7] ("lost")
            # on one device only, so the snapshot psum counts them once —
            # the reference's LostEventsCounter accounting rule
            # (packetparser_linux.go:692-697: drop, count, never block).
            first = jax.lax.axis_index(self.axes) == 0
            new = dataclasses.replace(
                new,
                totals=new.totals.at[7].add(jnp.where(first, lost, 0)),
            )
            new = jax.tree.map(lambda x: x[None], new)
            out = {
                "events": jax.lax.psum(summary["events"], self.axes),
                "ct_reports": jax.lax.psum(summary["ct_reports"], self.axes),
                "report_mask": summary["report_mask"][None],
                "report_packets": summary["report_packets"][None],
                "report_bytes": summary["report_bytes"][None],
            }
            return new, out

        sh = self._sharded_spec
        fn = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(sh, sh, sh, P(), P(), P(), P(), P(), P()),
            out_specs=(
                sh,
                {
                    "events": P(),
                    "ct_reports": P(),
                    "report_mask": sh,
                    "report_packets": sh,
                    "report_bytes": sh,
                },
            ),
        )
        # AOT-wrapped (AotProgram): argnums 0-2 (state, records, n_valid)
        # carry the mesh sharding, the scalar/replicated tail does not.
        return AotProgram(
            jax.jit(fn, donate_argnums=(0,)), self.mesh,
            self._sharded_spec, (0, 1, 2),
            cache_dir=self._aot_cache_dir, tag="step",
            config_sig=self._config_sig,
        )

    def _put_sharded(self, x):
        """Place a dim0-sharded step input. Host (numpy/list) batches get
        an explicit ``device_put`` onto the mesh sharding so each device
        receives ONLY its shard — ``jnp.asarray`` used to commit the full
        batch to the default device first and let the executable reshard
        it, which made the 8-device feed SLOWER than 1 device (the
        MULTICHIP_r05 replication overhead). Device-resident arrays pass
        through with a dtype check only — no extra transfer."""
        if isinstance(x, jax.Array):
            return x if x.dtype == jnp.uint32 else x.astype(jnp.uint32)
        host = np.asarray(x, dtype=np.uint32)
        if self.n_devices == 1:
            return jnp.asarray(host)
        return jax.device_put(
            host, NamedSharding(self.mesh, self._sharded_spec)
        )

    def step(
        self,
        state: PipelineState,
        records,  # (D, B, F) uint32
        n_valid,  # (D,) uint32
        now_s,  # scalar uint32
        ident: IdentityMap,
        apiserver_ip=0,
        filter_map: IdentityMap | None = None,  # explicit IPs of interest
        lost=0,  # host-side partition overflow count (ShardedBatch.lost)
        sample_k=1,  # overload 1-in-k factor (ShardedBatch.sample_k)
    ) -> tuple[PipelineState, dict[str, jnp.ndarray]]:
        if self._step is None:
            self._step = self._build_step()
        if filter_map is None:
            filter_map = IdentityMap.zeros(1 << 4, seed=99)
        return self._step(
            state,
            self._put_sharded(records),
            self._put_sharded(n_valid),
            jnp.asarray(now_s, jnp.uint32),
            ident,
            jnp.asarray(apiserver_ip, jnp.uint32),
            filter_map,
            # Packet-weighted loss counts can exceed 2^32 in one batch;
            # the device totals are u32 and wrap (like every reference
            # kernel counter) — the host-side Prometheus lost_events
            # counter (float64) stays exact. Device-resident scalars
            # (the engine's coalesced-ingest outputs) pass through
            # untouched — coercing them via int() would force a
            # device->host readback per step.
            jnp.asarray(
                int(lost) & 0xFFFFFFFF
                if isinstance(lost, (int, np.integer)) else lost,
                jnp.uint32,
            ),
            # Same pass-through rule as ``lost``: the engine hands a
            # device-resident scalar from its per-k cache on the hot
            # path; host ints only show up in tests/direct callers.
            jnp.asarray(
                int(sample_k) & 0xFFFFFFFF
                if isinstance(sample_k, (int, np.integer)) else sample_k,
                jnp.uint32,
            ),
        )

    # ------------------------------------------------------------------
    @device_entry("sharded.end_window", kind="shard_map")
    def _build_end_window(self):
        def local_end(state, z_thresh):
            with jax.named_scope(SCOPE_END_WINDOW):
                s = jax.tree.map(lambda x: x[0], state)
                # Merge window histograms first so every device computes
                # the entropy of the UNION stream, then updates its
                # (replicated) anomaly EWMA identically.
                merged_ent = dataclasses.replace(
                    s.entropy,
                    counts=jax.lax.psum(s.entropy.counts, self.axes),
                )
                h = merged_ent.entropy_bits()
                # Idle windows (including the engine's compile() warm-up)
                # must not seed/poison the EWMA baseline — same contract
                # as the single-chip end_window (models/pipeline.py).
                active = merged_ent.counts.sum(axis=-1) > 0
                anomaly, flags, z = s.anomaly.observe(
                    h, z_thresh=z_thresh, active=active
                )
                new = dataclasses.replace(
                    s, entropy=s.entropy.reset(), anomaly=anomaly
                )
                new = jax.tree.map(lambda x: x[None], new)
            return new, {"entropy_bits": h, "anomaly": flags, "zscore": z}

        sh = self._sharded_spec
        fn = jax.shard_map(
            local_end,
            mesh=self.mesh,
            in_specs=(sh, P()),
            out_specs=(sh, {"entropy_bits": P(), "anomaly": P(), "zscore": P()}),
            # anomaly/zscore derive from the per-device EWMA state, which is
            # replicated by construction (only ever updated with the psum'd
            # window entropy) — the checker cannot prove that invariant.
            check_vma=False,
        )
        return AotProgram(
            jax.jit(fn, donate_argnums=(0,)), self.mesh,
            self._sharded_spec, (0,),
            cache_dir=self._aot_cache_dir, tag="endwin",
            config_sig=self._config_sig,
        )

    def end_window(
        self, state: PipelineState, z_thresh: float = 4.0
    ) -> tuple[PipelineState, dict[str, jnp.ndarray]]:
        if self._end_window is None:
            self._end_window = self._build_end_window()
        return self._end_window(state, jnp.asarray(z_thresh, jnp.float32))

    # ------------------------------------------------------------------
    @device_entry("sharded.snapshot", kind="shard_map")
    def _build_snapshot(self):
        ax = self.axes

        def local_snap(state, now_s):
            s = jax.tree.map(lambda x: x[0], state)
            psum = lambda x: jax.lax.psum(x, ax)
            pmax = lambda x: jax.lax.pmax(x, ax)
            gather = lambda x: jax.lax.all_gather(x, ax, axis=0)

            def hll_est(hll):
                merged = dataclasses.replace(hll, registers=pmax(hll.registers))
                return merged.estimate()

            def hh_gather(hh):
                return {
                    # (D, S, C) and (D, S): union of per-device candidates.
                    "keys": gather(hh.table.key_rows),
                    "counts": gather(hh.table.counts),
                }

            return {
                "pod_forward": psum(s.pod_forward),
                "pod_drop": psum(s.pod_drop),
                "pod_tcpflags": psum(s.pod_tcpflags),
                "pod_dns": psum(s.pod_dns),
                "pod_retrans": psum(s.pod_retrans),
                "node_counters": psum(s.node_counters),
                "totals": psum(s.totals),
                # Two-limb u32 counters cannot psum (a summed lo limb may
                # wrap and lose the carry) — gather per-device limbs and
                # reassemble 64-bit values on host (conntrack_gc()).
                "ct_totals": gather(s.ct_totals),
                "lat_hist": psum(s.lat_hist),
                "hll_flows": hll_est(s.hll_flows),
                "hll_src_per_reason": hll_est(s.hll_src_per_reason),
                "hll_src_per_pod": hll_est(s.hll_src_per_pod),
                "flow_hh": hh_gather(s.flow_hh),
                "svc_hh": hh_gather(s.svc_hh),
                "dns_hh": hh_gather(s.dns_hh),
                "active_conns": psum(s.conntrack.active_connections(now_s)),
            }

        fn = jax.shard_map(
            local_snap,
            mesh=self.mesh,
            in_specs=(self._sharded_spec, P()),
            out_specs=P(),  # every output is collective-merged => replicated
            # The vma checker cannot see through estimate()/gather chains,
            # but psum/pmax/all_gather outputs are replicated by definition.
            check_vma=False,
        )
        # AOT-wrapped like _build_step: the scrape/export programs were
        # the bulk of the BENCH_r06 hits=1/misses=26 warm regression —
        # every restart re-lowered them while only the step program hit
        # disk.
        return AotProgram(
            jax.jit(fn), self.mesh, self._sharded_spec, (0,),
            cache_dir=self._aot_cache_dir, tag="snapshot",
            config_sig=self._config_sig,
        )

    def snapshot(self, state: PipelineState, now_s) -> dict[str, Any]:
        """Merged scrape-time readout (device dict; np.asarray leaves to read)."""
        if self._snapshot is None:
            self._snapshot = self._build_snapshot()
        return self._snapshot(state, jnp.asarray(now_s, jnp.uint32))

    # ------------------------------------------------------------------
    @device_entry("sharded.fleet_export", kind="shard_map")
    def _build_fleet_export(self):
        ax = self.axes
        d = self.n_devices

        def local_fx(state):
            s = jax.tree.map(lambda x: x[0], state)
            psum = lambda x: jax.lax.psum(x, ax)
            pmax = lambda x: jax.lax.pmax(x, ax)
            gather = lambda x: jax.lax.all_gather(x, ax, axis=0)

            def fold_table(table):
                # Gather every device's candidate table, then fold with
                # the join-semilattice merge (ops/topk.py) so the wire
                # snapshot carries ONE (S, C) table per family.
                keys = gather(table.key_rows)  # (D, S, C)
                counts = gather(table.counts)  # (D, S)
                t = TopKTable(keys[0], counts[0], seed=table.seed)
                for i in range(1, d):
                    t = t.merge(
                        TopKTable(keys[i], counts[i], seed=table.seed)
                    )
                return t

            out = {}
            for fam, hh in (  # noqa: RT212 — static 3-family tuple; intended unroll
                ("flow", s.flow_hh), ("svc", s.svc_hh), ("dns", s.dns_hh)
            ):
                t = fold_table(hh.table)
                out[f"{fam}_cms"] = psum(hh.cms.table)
                out[f"{fam}_keys"] = t.key_rows
                out[f"{fam}_counts"] = t.counts
            out["hll_flows"] = pmax(s.hll_flows.registers)
            out["hll_src_per_pod"] = pmax(s.hll_src_per_pod.registers)
            out["entropy"] = psum(s.entropy.counts)
            out["totals"] = psum(s.totals)
            if self.pipeline.config.enable_invertible:
                # Pure sums: the aggregator's default sum-merge branch
                # recovers cluster-wide keys from these without any node
                # shipping raw keys (fleet/aggregator.py).
                out["inv_flow_planes"] = psum(s.inv_flow.planes)
                out["inv_flow_weights"] = psum(s.inv_flow.weights)
                out["inv_hi_planes"] = psum(s.inv_hi.planes)
                out["inv_hi_weights"] = psum(s.inv_hi.weights)
            return out

        fn = jax.shard_map(
            local_fx,
            mesh=self.mesh,
            in_specs=(self._sharded_spec,),
            out_specs=P(),  # every output collective-merged => replicated
            check_vma=False,
        )
        return AotProgram(
            jax.jit(fn), self.mesh, self._sharded_spec, (0,),
            cache_dir=self._aot_cache_dir, tag="fleet_export",
            config_sig=self._config_sig,
        )

    def fleet_export(self, state: PipelineState) -> dict[str, Any]:
        """Device-merged wire snapshot for the fleet rollup tier
        (fleet/codec.py array catalog). Async dispatch: the shipper does
        the readback off the proxy (fleet/shipper.py)."""
        if self._fleet_export is None:
            self._fleet_export = self._build_fleet_export()
        return self._fleet_export(state)

    @staticmethod
    def fleet_seeds(state: PipelineState) -> dict[str, int]:
        """Per-family sketch hash seeds (pytree aux — host-side attribute
        reads, no device sync). Shipped in every frame so the aggregator
        can refuse cross-seed merges."""
        return {
            "flow": int(state.flow_hh.cms.seed),
            "svc": int(state.svc_hh.cms.seed),
            "dns": int(state.dns_hh.cms.seed),
            "hll_flows": int(state.hll_flows.seed),
            "hll_src_per_pod": int(state.hll_src_per_pod.seed),
            "entropy": int(state.entropy.seed),
            "inv_flow": int(state.inv_flow.seed),
            "inv_hi": int(state.inv_hi.seed),
        }

    # ------------------------------------------------------------------
    @device_entry("sharded.inv_decode", kind="shard_map")
    def _build_inv_decode(self):
        ax = self.axes

        def local_dec(state, min_weight):
            s = jax.tree.map(lambda x: x[0], state)
            psum = lambda x: jax.lax.psum(x, ax)
            # Decode the UNION sketch (devices hold connection-disjoint
            # shards, the arrays are pure sums) against the union CMS —
            # same merge contract the fleet aggregator applies node-wide.
            merged_cms = dataclasses.replace(
                s.flow_hh.cms, table=psum(s.flow_hh.cms.table)
            )

            def region(inv, tier):
                merged = dataclasses.replace(
                    inv,
                    planes=psum(inv.planes),
                    weights=psum(inv.weights),
                )
                cols, est, ok = decode_verified(
                    merged, merged_cms, min_weight=0
                )
                ok = ok & (est >= min_weight)
                tiers = jnp.full(est.shape, tier, jnp.uint32)
                return cols, jnp.where(ok, est, 0), ok, tiers

            f_cols, f_est, f_ok, f_tier = region(s.inv_flow, 0)
            h_cols, h_est, h_ok, h_tier = region(s.inv_hi, 1)
            keys = jnp.stack(
                [jnp.concatenate([a, b]) for a, b in zip(f_cols, h_cols)],
                axis=1,
            )  # (M, C) u32
            return {
                "keys": keys,
                "est": jnp.concatenate([f_est, h_est]),
                "ok": jnp.concatenate([f_ok, h_ok]),
                "tier": jnp.concatenate([f_tier, h_tier]),
            }

        fn = jax.shard_map(
            local_dec,
            mesh=self.mesh,
            in_specs=(self._sharded_spec, P()),
            out_specs=P(),  # psum-merged inputs => replicated decode
            check_vma=False,
        )
        return AotProgram(
            jax.jit(fn), self.mesh, self._sharded_spec, (0,),
            cache_dir=self._aot_cache_dir, tag="inv_decode",
            config_sig=self._config_sig,
        )

    def inv_decode(self, state: PipelineState, min_weight=0) -> dict[str, Any]:
        """Window-close invertible decode (fixed shape, async dispatch
        like fleet_export — caller reads back off the proxy). Returns
        device arrays: ``keys (M, C) u32``, ``est (M,)``, ``ok (M,)``,
        ``tier (M,)`` (0 = main region, 1 = priority region); rows with
        ``ok == False`` are noise. M = D*W_flow + D*W_hi; the same key
        can decode from up to D buckets — hosts dedupe (np.unique)."""
        if self._inv_decode is None:
            self._inv_decode = self._build_inv_decode()
        return self._inv_decode(state, jnp.asarray(min_weight, jnp.uint32))

    # ------------------------------------------------------------------
    @device_entry("sharded.snapshot_flat", kind="jit")
    def _build_snapshot_flat(self, state: PipelineState):
        # Trace through the UNDERLYING jit (an AotProgram cannot run
        # under eval_shape/jit tracing — its executables take concrete
        # arrays); the flat program gets its own AOT disk entry below.
        base = self._build_snapshot()._jitted
        shapes = jax.eval_shape(base, state, np.uint32(0))
        leaves, treedef = jax.tree_util.tree_flatten(shapes)

        def flat_fn(st, now_s):
            with jax.named_scope(SCOPE_SNAPSHOT_MERGE):
                d = base(st, now_s)
            with jax.named_scope(SCOPE_SNAPSHOT_CONCAT):
                out = []
                for leaf in jax.tree_util.tree_leaves(d):
                    if leaf.dtype != jnp.uint32:
                        leaf = jax.lax.bitcast_convert_type(
                            leaf.astype(
                                jnp.float32
                                if jnp.issubdtype(leaf.dtype, jnp.floating)
                                else jnp.uint32
                            ),
                            jnp.uint32,
                        )
                    out.append(leaf.reshape(-1))
                return jnp.concatenate(out)

        prog = AotProgram(
            jax.jit(flat_fn), self.mesh, self._sharded_spec, (0,),
            cache_dir=self._aot_cache_dir, tag="snapshot_flat",
            config_sig=self._config_sig,
        )
        return prog, leaves, treedef

    def snapshot_host(self, state: PipelineState, now_s) -> dict[str, Any]:
        """Merged snapshot delivered to HOST memory in ONE device->host
        transfer: every leaf is bitcast to u32, raveled, and concatenated
        on device, so the readback is a single contiguous buffer instead
        of ~25 per-leaf round trips (each round trip costs full link
        latency; measured 2.7-21s per scrape on a congested link vs the
        <1s budget)."""
        return self.snapshot_flat_finish(
            self.snapshot_flat_dispatch(state, now_s)
        )

    def snapshot_flat_dispatch(self, state: PipelineState, now_s):
        """Enqueue the flat-snapshot computation and return the DEVICE
        array immediately (async dispatch) — no blocking transfer.

        Split from :meth:`snapshot_flat_finish` so the engine can run
        the dispatch on the device-proxy thread (ordered against steps;
        the state reference is captured before any later donating step
        executes) while the multi-second device->host readback blocks
        only the snapshot *caller's* thread. Before the split the proxy
        spent ~30% of its steady-state wall clock inside snapshot
        readbacks on a congested link, stalling the whole dispatch
        pipeline behind scrape/GC traffic."""
        if self._snapshot_flat is None:
            self._snapshot_flat = self._build_snapshot_flat(state)
        fn, _, _ = self._snapshot_flat
        return fn(state, jnp.asarray(now_s, jnp.uint32))

    def snapshot_flat_finish(self, flat_dev) -> dict[str, Any]:
        """Unflatten a flat snapshot buffer back into the snapshot
        dict. Pass a HOST (numpy) buffer when calling off the device
        proxy (engine.snapshot uses fetch_on_device for the readback);
        a device array is also accepted, but then the np.asarray below
        is a blocking device call and must run on the proxy thread."""
        fn, leaf_shapes, treedef = self._snapshot_flat
        flat = np.asarray(flat_dev)
        out = []
        off = 0
        for spec in leaf_shapes:
            n = int(np.prod(spec.shape)) if spec.shape else 1
            chunk = flat[off : off + n]
            off += n
            if np.issubdtype(spec.dtype, np.floating):
                chunk = chunk.view(np.float32).astype(spec.dtype)
            elif chunk.dtype != spec.dtype:
                chunk = chunk.view(np.uint32).astype(spec.dtype)
            out.append(
                chunk.reshape(spec.shape) if spec.shape else chunk[0]
            )
        return jax.tree_util.tree_unflatten(treedef, out)


def topk_from_snapshot(
    snap: dict[str, Any], name: str, k: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side top-k over a snapshot's gathered candidate tables.

    Returns (keys (k', C), counts (k',)) sorted descending, k' <= k.
    Per-device counts for the SAME key are summed before ranking: sketches
    keyed above the connection level (svc_hh pod pairs, dns_hh query
    hashes) split one key's traffic across devices, so each device's table
    holds a partial count of its shard — the sum of per-device CMS
    estimates of disjoint sub-streams estimates the total. For
    connection-level keys (flow_hh) devices are key-disjoint and the
    group-sum is a no-op.
    """
    hh = snap[name]
    keys = np.asarray(hh["keys"])  # (D, S, C)
    counts = np.asarray(hh["counts"])  # (D, S)
    d, sl, c = keys.shape
    flat_keys = keys.reshape(d * sl, c)
    flat_counts = counts.reshape(d * sl).astype(np.uint64)
    nonzero = flat_counts > 0
    flat_keys, flat_counts = flat_keys[nonzero], flat_counts[nonzero]
    if not len(flat_keys):
        return flat_keys, flat_counts
    uniq, inv = np.unique(flat_keys, axis=0, return_inverse=True)
    summed = np.zeros(len(uniq), np.uint64)
    np.add.at(summed, inv, flat_counts)
    order = np.argsort(summed)[::-1][:k]
    return uniq[order], summed[order]
