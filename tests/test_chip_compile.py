"""Compile the main path's device programs for a described TPU v5e.

No chip is attached here: the TPU compiler that ships with the
installation compiles for a chip that is described, and refuses what the
real chip's compiler would refuse (unaligned slices, too much fast
memory, a program that does not fit 16 GB of HBM). Shapes are the
documented deployment's (deploy/manifests/configmap.yaml): batch 131072,
Count-Min 4 x 65536, 2048 top-k slots, 262144 conntrack slots.

Everything that touches the topology lives in the module-scoped ``chip``
fixture below: only the xdist worker that is handed this file loads the
TPU library, and every worker collects the same tests. Compiles run in
the test's own process, with JAX's persistent compilation cache off (an
entry written for a described chip cannot be read back without one).
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from retina_tpu.config import load_config
from retina_tpu.engine import pipeline_config_from
from retina_tpu.events.schema import NUM_FIELDS
from retina_tpu.models.identity import IdentityMap
from retina_tpu.models.pipeline import TelemetryPipeline
from retina_tpu.parallel.telemetry import ShardedTelemetry
from retina_tpu.parallel.wire import PACKED_FIELDS, unpack_records_device

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 1024**3  # one v5e chip


@dataclasses.dataclass
class Chip:
    topo: object
    cfg: object  # the deployment's Config
    pcfg: object  # its PipelineConfig

    @property
    def one(self) -> SingleDeviceSharding:
        return SingleDeviceSharding(self.topo.devices[0])

    def sharded(self, n_dev: int):
        """ShardedTelemetry on the first n_dev described devices, with
        the shape specs of its state."""
        mesh = Mesh(np.array(self.topo.devices[:n_dev]), ("data",))
        st = ShardedTelemetry(self.pcfg, mesh)
        sh = NamedSharding(mesh, P(("data",)))
        single = jax.eval_shape(st.pipeline.init_state)
        state = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (n_dev,) + s.shape, s.dtype, sharding=sh
            ),
            single,
        )
        return st, state, sh, NamedSharding(mesh, P())


@pytest.fixture(scope="module")
def chip(tmp_path_factory):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with open(os.path.join(ROOT, "deploy", "manifests",
                           "configmap.yaml")) as f:
        text = yaml.safe_load(f)["data"]["config.yaml"]
    path = tmp_path_factory.mktemp("deploy") / "config.yaml"
    path.write_text(text)
    cfg = load_config(str(path), env={})
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield Chip(topo, cfg, pipeline_config_from(cfg))
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def on(tree, sharding):
    """Shape specs of ``tree`` placed with ``sharding``."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def compiled(fn, *specs, donate=()):
    ex = jax.jit(fn, donate_argnums=donate).lower(*specs).compile()
    ma = ex.memory_analysis()
    assert (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
    ) < HBM_BYTES
    return ex, ma


def _cols(chip: Chip, n: int, dtype=jnp.uint32):
    b = chip.cfg.batch_capacity
    return [jax.ShapeDtypeStruct((b,), dtype, sharding=chip.one)] * n


SKETCHES = {
    # The sketch updates the fused step is made of, with the operands
    # models/pipeline.py feeds them.
    "countmin_topk": (
        "flow_hh", lambda s, a, b, c, d, w: s.update([a, b, c, d], w),
        (4, jnp.uint32), (1, jnp.uint32),
    ),
    "hyperloglog": (
        "hll_src_per_reason", lambda s, k, g, m: s.update([k], g, m),
        (2, jnp.uint32), (1, jnp.bool_),
    ),
    "entropy": (
        "entropy", lambda s, k, g, w: s.update([k], g, w),
        (2, jnp.uint32), (1, jnp.float32),
    ),
}


@pytest.mark.parametrize("name", sorted(SKETCHES))
def test_sketch_update_compiles(chip, name):
    field, fn, (n_a, dt_a), (n_b, dt_b) = SKETCHES[name]
    state = on(
        getattr(jax.eval_shape(TelemetryPipeline(chip.pcfg).init_state),
                field),
        chip.one,
    )
    _, ma = compiled(
        fn, state, *_cols(chip, n_a, dt_a), *_cols(chip, n_b, dt_b),
        donate=(0,),
    )
    # The sketch is updated in place: donation survived compilation.
    assert ma.alias_size_in_bytes > 0


def test_window_close_compiles_and_donates(chip):
    st, state, _, rep = chip.sharded(1)
    ex = st._build_end_window()._jitted.lower(
        state, jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    ).compile()
    assert ex.memory_analysis().alias_size_in_bytes > 0


def test_snapshot_compiles(chip):
    st, state, _, rep = chip.sharded(1)
    ex = st._build_snapshot()._jitted.lower(
        state, jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep)
    ).compile()
    ma = ex.memory_analysis()
    # The scrape readout is small beside the state it reads.
    assert 0 < ma.output_size_in_bytes < ma.argument_size_in_bytes


def test_wire_unpack_compiles(chip):
    """The unpack half of the full-capacity ingest program: 12 packed
    lanes -> the 16-lane records the step consumes."""
    b = chip.cfg.batch_capacity
    wire = jax.ShapeDtypeStruct((1, b, PACKED_FIELDS), jnp.uint32,
                                sharding=chip.one)
    scalar = jax.ShapeDtypeStruct((), jnp.uint32, sharding=chip.one)
    _, ma = compiled(unpack_records_device, wire, scalar, scalar)
    assert ma.output_size_in_bytes == b * NUM_FIELDS * 4


def test_fold_of_a_flush_two_sides_compiles(chip):
    """The program that folds a flush's new-descriptor window and its
    known window into one step window (engine.fold_side_windows), at
    the deployment's batch."""
    from retina_tpu.engine import fold_side_windows

    b = chip.cfg.batch_capacity
    win = jax.ShapeDtypeStruct((1, b, NUM_FIELDS), jnp.uint32,
                               sharding=chip.one)
    nv = jax.ShapeDtypeStruct((1,), jnp.uint32, sharding=chip.one)
    _, ma = compiled(fold_side_windows, win, nv, win, nv, donate=(0,))
    assert ma.output_size_in_bytes >= b * NUM_FIELDS * 4


def test_fold_of_a_flush_two_sides_compiles_on_four_chips(chip):
    """The same fold over the v5e-4 host's mesh: each chip folds its own
    two windows, so the program holds no collective."""
    from retina_tpu.engine import fold_side_windows

    _, _, sh, _ = chip.sharded(4)
    b = chip.cfg.batch_capacity
    win = jax.ShapeDtypeStruct((4, b, NUM_FIELDS), jnp.uint32, sharding=sh)
    nv = jax.ShapeDtypeStruct((4,), jnp.uint32, sharding=sh)
    ex = jax.jit(fold_side_windows, donate_argnums=(0,),
                 out_shardings=(sh, sh)).lower(win, nv, win, nv).compile()
    ma = ex.memory_analysis()
    assert ma.output_size_in_bytes >= b * NUM_FIELDS * 4  # a chip's share
    text = ex.as_text()
    assert "all-reduce" not in text and "all-gather" not in text


def test_snapshot_merge_on_four_chips_has_collectives(chip):
    """The scrape-time merge across a 2x2 host: psum / pmax lower to
    all-reduce, the candidate tables to all-gather."""
    st, state, _, rep = chip.sharded(4)
    ex = st._build_snapshot()._jitted.lower(
        state, jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep)
    ).compile()
    text = ex.as_text()
    assert "all-reduce" in text
    assert "all-gather" in text


@pytest.mark.slow  # ~140 s: the seven-operand bitonic sort of ops/conntrack.py
def test_conntrack_update_compiles(chip):
    state = on(
        jax.eval_shape(TelemetryPipeline(chip.pcfg).init_state).conntrack,
        chip.one,
    )
    u32 = _cols(chip, 7)
    mask = _cols(chip, 1, jnp.bool_)[0]
    now = jax.ShapeDtypeStruct((), jnp.uint32, sharding=chip.one)

    def update(ct, src, dst, ports, proto, flags, bytes_, pkts, m, now_s):
        return ct.process(src, dst, ports, proto, flags, now_s, bytes_, m,
                          packets_=pkts)

    _, ma = compiled(update, state, *u32, mask, now, donate=(0,))
    assert ma.alias_size_in_bytes > 0


@pytest.mark.slow  # ~200 s: the whole fused step, conntrack + latency on
def test_fused_step_compiles_and_donates(chip):
    st, state, sh, rep = chip.sharded(1)
    b = chip.cfg.batch_capacity
    scalar = jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep)
    table = jax.ShapeDtypeStruct(
        (chip.cfg.identity_slots, 2), jnp.uint32, sharding=rep
    )
    ex = st._build_step()._jitted.lower(
        state,
        jax.ShapeDtypeStruct((1, b, NUM_FIELDS), jnp.uint32, sharding=sh),
        jax.ShapeDtypeStruct((1,), jnp.uint32, sharding=sh),
        scalar, IdentityMap(table=table, seed=0), scalar,
        IdentityMap(table=table, seed=99), scalar, scalar,
    ).compile()
    ma = ex.memory_analysis()
    assert ma.alias_size_in_bytes > 0
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < HBM_BYTES
