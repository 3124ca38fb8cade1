"""Jitted semilattice fold over ring slots + host-side range queries.

One range query ``[e0, e1)`` = stack the selected ring slots and run
the SAME batched reduction the fleet aggregator runs across nodes
(fleet/aggregator.py ``fleet.merge``): sum for CM tables / entropy
histograms / totals / invertible planes, max for HLL register banks,
join-semilattice fold for the heavy-hitter candidate tables. Because
every per-array op is associative and commutative (RT300 proves it for
the registered program), a 7-window query is exactly the sketch the
engine WOULD have built had the window been 7× longer — time is just
another merge axis.

The fold is cached per ``(n_slots, array signature, seeds)`` like the
fleet merge cache: queries over the same span length hit a compiled
executable, and ``donate_argnums=(0,)`` recycles the stacked staging
buffer (RT302).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from retina_tpu.devprog import device_entry
from retina_tpu.ops.countmin import CountMinSketch
from retina_tpu.ops.entropy import EntropyWindow
from retina_tpu.ops.hyperloglog import HyperLogLog
from retina_tpu.ops.invertible import InvertibleSketch, decode_verified
from retina_tpu.ops.topk import TopKTable

# Same families / dims as the fleet tier — ring slots follow the fleet
# array catalog (fleet/codec.py), so the fold speaks the same schema.
HH_FAMILIES = ("flow", "svc", "dns")
ENTROPY_DIMS = ("src_ip", "dst_ip", "dst_port")

# AOT executable disk cache for the query programs (same format and
# counters as parallel/telemetry.py — the BENCH_r06 hits=1/misses=26
# regression was these plus the scrape/export programs re-lowering on
# every restart). The builders keep returning plain lowerable jits
# (devlower RT302 lowers them); the disk consult happens in the host
# wrappers below, which hold both the concrete args and the cache key.
_AOT_CACHE_DIR = ""
_AOT_EXEC_CACHE: dict[Any, Any] = {}


def set_aot_cache_dir(path: str) -> None:
    """Point the query-program disk cache at ``cfg.aot_cache_dir``
    (daemon/bench boot). Empty disables the disk layer — the in-process
    jit caches still apply."""
    global _AOT_CACHE_DIR
    _AOT_CACHE_DIR = path or ""


def _args_sig(args: tuple) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return str(treedef), tuple(
        (np.shape(leaf), np.dtype(
            getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
        ).name)
        for leaf in leaves
    )


def _disk_compiled(tag: str, jitted, args: tuple):  # may-block: AOT disk-cache consult, once per (program, signature) — _AOT_EXEC_CACHE serves every later call in-memory; a one-time ms-scale load on the query lane beats a seconds-scale recompile
    """Executable for one (program, concrete-args signature):
    in-memory first, then the shared AOT disk cache, else
    lower+compile+persist. Without a cache dir, the plain jitted fn
    (jax's own jit cache) is returned unchanged."""
    if not _AOT_CACHE_DIR:
        return jitted
    from retina_tpu.parallel.telemetry import (
        aot_disk_load, aot_disk_path, aot_disk_save,
    )

    key = _args_sig(args)
    ck = (tag, key)
    ex = _AOT_EXEC_CACHE.get(ck)
    if ex is None:
        path = aot_disk_path(_AOT_CACHE_DIR, None, tag, "", key)
        ex = aot_disk_load(path, None, tag=tag)
        if ex is None:
            ex = jitted.lower(*args).compile()
            aot_disk_save(path, ex, tag=tag)
        _AOT_EXEC_CACHE[ck] = ex
    return ex


class RangeFold:
    """Stateless-per-query fold engine with a compiled-executable cache.

    Thread-safe for concurrent ``fold`` calls: the cache dict is only
    ever populated (benign last-writer-wins race), and each call builds
    its own stacked input.
    """

    def __init__(self) -> None:
        self._cache: dict[Any, Any] = {}

    @device_entry("timetravel.range_fold", kind="jit")
    def _fold_fn(self, n: int, seeds: dict[str, int], names: tuple):
        key = (n, names, tuple(sorted(seeds.items())))
        fn = self._cache.get(key)
        if fn is not None:
            return fn

        def fold(stacked):
            out = {}
            for name in names:
                arr = stacked[name]
                if name.startswith("hll_"):
                    out[name] = jnp.max(arr, axis=0)
                elif name.endswith("_keys") or name.endswith("_counts"):
                    continue
                else:
                    out[name] = jnp.sum(arr, axis=0)
            for fam in HH_FAMILIES:
                kname, cname = f"{fam}_keys", f"{fam}_counts"
                if kname not in stacked:  # noqa: RT212 — dict-key test, static per jit cache key
                    continue
                seed = int(seeds.get(fam, 0))
                t = TopKTable(stacked[kname][0], stacked[cname][0],
                              seed=seed)
                for i in range(1, n):
                    t = t.merge(
                        TopKTable(stacked[kname][i], stacked[cname][i],
                                  seed=seed)
                    )
                out[kname], out[cname] = t.key_rows, t.counts
            return out

        fn = jax.jit(fold, donate_argnums=(0,))
        self._cache[key] = fn
        return fn

    def fold(
        self, slots: list[dict[str, Any]], seeds: dict[str, int]
    ) -> dict[str, np.ndarray]:
        """Fold N ring slots (dicts of host arrays sharing the fleet
        array catalog) into one merged host-side snapshot."""
        if not slots:  # noqa: RT212 — host-side slot list, not a tracer
            raise ValueError("range fold over an empty slot selection")
        names = sorted(set.intersection(*(set(s) for s in slots)))
        stacked = {
            name: jnp.asarray(np.stack([s[name] for s in slots]))
            for name in names
        }
        fn = self._fold_fn(len(slots), seeds, tuple(names))
        merged = _disk_compiled("range_fold", fn, (stacked,))(stacked)
        return {k: np.asarray(v) for k, v in merged.items()}


# Compiled extraction programs keyed by (names, shapes, seeds): the
# scalar answers (cardinality, entropy bits, candidate re-counts) come
# out of ONE compiled program per snapshot signature — eager per-sketch
# queries are hundreds of small dispatches, too slow for the query
# path's latency contract.
_EXTRACT_CACHE: dict[Any, Any] = {}


@device_entry("timetravel.range_extract", kind="jit")
def _extract_program(names: tuple, shapes: tuple, seeds: dict[str, int]):
    """Jitted derived-answer extraction over a folded snapshot:
    HLL cardinality, entropy bits, and the span-CMS re-count of every
    heavy-hitter candidate table row."""
    key = (names, shapes, tuple(sorted(seeds.items())))
    fn = _EXTRACT_CACHE.get(key)
    if fn is not None:
        return fn

    def run(merged):
        out = {}
        if "hll_flows" in merged:  # noqa: RT212 — dict-key test, static per jit cache key
            out["cardinality"] = HyperLogLog(
                registers=merged["hll_flows"],
                seed=int(seeds.get("hll_flows", 0)),
            ).estimate()
        if "entropy" in merged:  # noqa: RT212 — dict-key test, static per jit cache key
            out["entropy_bits"] = EntropyWindow(
                counts=merged["entropy"],
                seed=int(seeds.get("entropy", 0)),
            ).entropy_bits()
        for fam in HH_FAMILIES:
            kname = f"{fam}_keys"
            if kname not in merged or f"{fam}_cms" not in merged:  # noqa: RT212 — dict-key test, static per jit cache key
                continue
            cms = CountMinSketch(
                table=merged[f"{fam}_cms"], seed=int(seeds.get(fam, 0))
            )
            kr = merged[kname]
            cols = [kr[:, c] for c in range(kr.shape[1])]
            out[f"{fam}_est"] = cms.query(cols)
        return out

    fn = jax.jit(run)
    _EXTRACT_CACHE[key] = fn
    return fn


def range_extract(
    merged: dict[str, np.ndarray], seeds: dict[str, int]
) -> dict[str, Any]:
    """Host wrapper: run the compiled extraction program and unpack to
    plain python/numpy. Returns ``cardinality`` (float),
    ``entropy_bits`` (dim -> bits), and ``<fam>_est`` aligned with
    ``merged[<fam>_keys]``."""
    wanted = {"hll_flows", "entropy"}
    for fam in HH_FAMILIES:
        if f"{fam}_keys" in merged and f"{fam}_cms" in merged:
            wanted |= {f"{fam}_keys", f"{fam}_cms"}
    sub = {n: jnp.asarray(merged[n]) for n in sorted(wanted & set(merged))}
    if not sub:
        return {}
    names = tuple(sorted(sub))
    shapes = tuple(sub[n].shape for n in names)
    fn = _extract_program(names, shapes, seeds)
    raw = _disk_compiled("range_extract", fn, (sub,))(sub)
    out: dict[str, Any] = {
        k: np.asarray(v) for k, v in raw.items()
    }
    if "cardinality" in out:
        out["cardinality"] = float(out["cardinality"][0])
    if "entropy_bits" in out:
        bits = out["entropy_bits"]
        out["entropy_bits"] = {
            dim: float(bits[i])
            for i, dim in enumerate(ENTROPY_DIMS)
            if i < len(bits)
        }
    return out


# Compiled decode programs keyed by (planes shape, inv seed, cms seed):
# eager decode_verified is hundreds of small dispatches (~0.5s on CPU),
# far too slow for the query path's latency contract.
_DECODE_CACHE: dict[Any, Any] = {}


@device_entry("timetravel.range_decode", kind="jit")
def _decode_program(shape: tuple, inv_seed: int, cms_seed: int):
    """Jitted invertible decode + CMS verification for one region of
    the span-summed snapshot: (planes, weights, cms_table) ->
    (keys (D*W, C), est (D*W,), ok (D*W,))."""
    key = (tuple(shape), inv_seed, cms_seed)
    fn = _DECODE_CACHE.get(key)
    if fn is not None:
        return fn

    def run(planes, weights, table):
        inv = InvertibleSketch(
            planes=planes, weights=weights, seed=inv_seed
        )
        cms = CountMinSketch(table=table, seed=cms_seed)
        cols, est, ok = decode_verified(inv, cms)
        return jnp.stack(cols, axis=1), est, ok

    fn = jax.jit(run)
    _DECODE_CACHE[key] = fn
    return fn


# -- host-side range queries over a folded snapshot -------------------

def range_topk(
    merged: dict[str, np.ndarray],
    seeds: dict[str, int],
    fam: str = "flow",
    k: int = 32,
    candidates: np.ndarray | None = None,
    est: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Top-k over the span: candidate keys (the folded join table, or
    decoded invertible keys) re-counted by the SUMMED CMS — exact
    span-wide totals up to CMS overestimate, mirroring the fleet
    cluster top-k. Pass ``est`` (range_extract's ``<fam>_est``, aligned
    with the folded candidate table) to skip the eager CMS re-count —
    the query service's latency-bounded path."""
    kname, cname = f"{fam}_keys", f"{fam}_counts"
    if candidates is None and est is not None and kname in merged:
        cand, cest = merged[kname], est.astype(np.uint64)
        occupied = merged[cname] > 0
        cand, cest = cand[occupied], cest[occupied]
        order = np.argsort(cest)[::-1][:k]
        sel = cest[order] > 0
        return cand[order][sel], cest[order][sel]
    if candidates is not None and len(candidates):
        cand = candidates.astype(np.uint32).reshape(len(candidates), -1)
    elif kname in merged:
        cand = merged[kname][merged[cname] > 0]
    else:
        return np.zeros((0, 0), np.uint32), np.zeros((0,), np.uint64)
    if not len(cand):
        return np.zeros((0, 0), np.uint32), np.zeros((0,), np.uint64)
    cand = np.unique(cand, axis=0)
    cms = CountMinSketch(
        table=merged[f"{fam}_cms"], seed=int(seeds.get(fam, 0))
    )
    key_cols = [jnp.asarray(cand[:, c]) for c in range(cand.shape[1])]
    est = np.asarray(cms.query(key_cols)).astype(np.uint64)
    order = np.argsort(est)[::-1][:k]
    sel = est[order] > 0
    return cand[order][sel], est[order][sel]


def range_cardinality(
    merged: dict[str, np.ndarray], seeds: dict[str, int]
) -> float:
    """Distinct flows over the span (HLL registers max-merged across
    windows count each flow once however many windows it spans)."""
    if "hll_flows" not in merged:
        return 0.0
    hll = HyperLogLog(
        registers=merged["hll_flows"],
        seed=int(seeds.get("hll_flows", 0)),
    )
    return float(np.asarray(hll.estimate())[0])


def range_entropy(
    merged: dict[str, np.ndarray], seeds: dict[str, int]
) -> dict[str, float]:
    """Plug-in Shannon entropy of the span-summed histograms — exactly
    the single-window estimate of the concatenated stream."""
    if "entropy" not in merged:
        return {}
    ent = EntropyWindow(
        counts=merged["entropy"], seed=int(seeds.get("entropy", 0))
    )
    bits = np.asarray(ent.entropy_bits())
    return {
        dim: float(bits[i])
        for i, dim in enumerate(ENTROPY_DIMS)
        if i < len(bits)
    }


def range_decode(
    merged: dict[str, np.ndarray], seeds: dict[str, int]
) -> dict[str, Any] | None:
    """Heavy-key recovery from the span-summed invertible planes,
    verified against the span-summed flow CMS. A key too light to
    decode in any single window surfaces once its span-wide weight
    dominates a bucket. Returns keys/est/tier sorted descending plus
    per-source packet attribution ``sources = (src_ips, packets)``;
    None when the slots carried no invertible state."""
    if "inv_flow_planes" not in merged or "flow_cms" not in merged:
        return None
    all_keys, all_est, all_tier = [], [], []
    for region, tier in (("inv_flow", 0), ("inv_hi", 1)):
        if f"{region}_planes" not in merged:
            continue
        planes = merged[f"{region}_planes"]
        fn = _decode_program(
            planes.shape,
            int(seeds.get(region, 0)),
            int(seeds.get("flow", 0)),
        )
        args = (
            jnp.asarray(planes),
            jnp.asarray(merged[f"{region}_weights"]),
            jnp.asarray(merged["flow_cms"]),
        )
        cols, est, ok = _disk_compiled("range_decode", fn, args)(*args)
        okh = np.asarray(ok, bool)
        keys = np.asarray(cols)[okh]
        all_keys.append(keys.astype(np.uint32))
        all_est.append(np.asarray(est)[okh].astype(np.uint64))
        all_tier.append(np.full(len(keys), tier, np.uint32))
    if not all_keys:
        return None
    keys = np.concatenate(all_keys)
    est = np.concatenate(all_est)
    tier = np.concatenate(all_tier)
    if len(keys):
        uniq, idx = np.unique(keys, axis=0, return_index=True)
        keys, est, tier = uniq, est[idx], tier[idx]
        order = np.argsort(est)[::-1]
        keys, est, tier = keys[order], est[order], tier[order]
        srcs, sinv = np.unique(keys[:, 0], return_inverse=True)
        spk = np.zeros(len(srcs), np.uint64)
        np.add.at(spk, sinv, est)
        sorder = np.argsort(spk)[::-1]
        sources = (srcs[sorder], spk[sorder])
    else:
        sources = (np.zeros((0,), np.uint32), np.zeros((0,), np.uint64))
    return {"keys": keys, "est": est, "tier": tier, "sources": sources}
