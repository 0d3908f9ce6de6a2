"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX, so the Pallas kernels of the
main path — the join probe (Q5-Q8), the segment aggregate (Q9-Q12) and
the top-k selection (Q11) — compile here exactly as they would on the
chip: alone, and under the 4-partition vmap that sim mode wraps around
them, at the one-chip cell's widths, and alone at the widths of a chip
of the four-chip cell, which holds one partition. Mosaic refuses layouts
the interpreter accepts (unaligned blocks, bool loop carries, 1-D
refs), and these tests catch that without chip time. One whole grouped
plan (Q9 through ``Executor.compile``) must lower to a program that
calls the kernels (``tpu_custom_call``), in sim mode on one chip and in
spmd mode over four, there with a collective; and one scan plan (Q1)
to a mask compaction with no ``while`` loop.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers
must all collect the same tests.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import Executor, compile_query
from repro.core.queries import ALL
from repro.kernels import hash_join, seg_aggregate, seg_topk

P = 4            # partitions: 4 a chip under sim mode's vmap, or 1 a chip
#                  of four under spmd mode's shard_map
# Per-chip widths by how a cell lays out its partitions:
#   alone, vmap4  ghcnd-1m (1M readings, 500 stations) on one chip:
#                 per partition 250k /sensors rows, 50k /sensors_min|max
#                 rows; a 512-row gathered /stations build; 512 groups
#   spmd4         ghcnd-4m-x4 (4M readings, 2,000 stations), one
#                 partition a chip: 1M /sensors rows, 200k
#                 /sensors_min|max rows; a 2,048-row build; 2,048 groups
WIDTHS = {
    "alone": dict(sensor_rows=250_000, station_rows=512, pair_rows=50_000,
                  groups=512),
    "spmd4": dict(sensor_rows=1_000_000, station_rows=2_048,
                  pair_rows=200_000, groups=2_048),
}
WIDTHS["vmap4"] = WIDTHS["alone"]
MODES = ["alone", "vmap4", "spmd4"]
COLLECTIVE = re.compile(r" (all-gather|all-reduce|all-to-all|"
                        r"collective-permute|reduce-scatter)(-start)?\(")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype, batch):
    shape = (P,) + shape if batch else shape
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, batch, *args):
    fn = jax.vmap(fn) if batch else fn
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("side", ["stations", "pairs"])
def test_join_probe_compiles(one_chip, side, mode):
    # Q5-Q7: stations x sensors on 1 key; Q8: min x max on 2 keys,
    # the build side gathered from every partition
    w, batch = WIDTHS[mode], mode == "vmap4"
    nb, np_, nkeys = ((w["station_rows"], w["sensor_rows"], 1)
                      if side == "stations" else
                      (P * w["pair_rows"], w["pair_rows"], 2))

    def key(n):
        return _sds(one_chip, (n,), jnp.int32, batch)

    def mask(n):
        return _sds(one_chip, (n,), jnp.bool_, batch)

    c = _compile(lambda bk, bv, pk, pv: hash_join.block_join_probe(
        bk, bv, pk, pv), batch, tuple(key(nb) for _ in range(nkeys)),
        mask(nb), tuple(key(np_) for _ in range(nkeys)), mask(np_))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("nc", [1, 3])       # Q9's avg; Q12's sum/min/max
def test_segmented_aggregate_compiles(one_chip, nc, mode):
    w, batch = WIDTHS[mode], mode == "vmap4"
    n = w["sensor_rows"]
    c = _compile(lambda v, ok, seg, valid: seg_aggregate.segmented_aggregate(
        v, ok, seg, valid, w["groups"]), batch,
        _sds(one_chip, (n, nc), jnp.float32, batch),
        _sds(one_chip, (n, nc), jnp.bool_, batch),
        _sds(one_chip, (n,), jnp.int32, batch),
        _sds(one_chip, (n,), jnp.bool_, batch))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("mode", MODES)
def test_segment_topk_compiles(one_chip, mode):
    # Q11: the invalid-sink flag, the negated f32 sum, the key's rank
    g, batch = WIDTHS[mode]["groups"], mode == "vmap4"
    keys = (_sds(one_chip, (g,), jnp.int32, batch),
            _sds(one_chip, (g,), jnp.float32, batch),
            _sds(one_chip, (g,), jnp.int32, batch))
    c = _compile(lambda *k: seg_topk.segment_topk(k, 16), batch, *keys)
    assert "tpu_custom_call" in c.as_text()


def _steer_to_tpu(monkeypatch):
    """The executor asks ``jax.default_backend()`` for its kernel
    routing; here the CPU answers, so a test steers that answer to the
    chip's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for var in ("REPRO_FORCE_JNP", "REPRO_KERNEL_INTERPRET"):
        monkeypatch.delenv(var, raising=False)


def test_grouped_plan_calls_kernels(one_chip, weather_db, monkeypatch):
    """Q9's whole sim-mode program, traced as on a TPU backend, lowers
    for the chip with the Pallas kernels in it."""
    _steer_to_tpu(monkeypatch)
    ex = Executor(weather_db)
    cp = ex.compile(compile_query(ALL["Q9"]))
    assert cp.config.use_pallas_join and cp.config.use_pallas_segments
    tables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), ex.tables)
    assert "tpu_custom_call" in cp.fn.lower(tables).compile().as_text()


def test_grouped_plan_spmd_over_four_chips(topo, weather_db, monkeypatch):
    """Q9's whole spmd-mode program, one partition on each of the four
    described chips of a v5e:2x2 host, lowers with the Pallas kernels
    and a collective across the chips in it. The described chips hold
    no arrays, so the tables are given as shapes with the shardings
    ``Executor.place_on_mesh`` would put them on, and the placement
    itself is left out."""
    _steer_to_tpu(monkeypatch)
    monkeypatch.setattr(Executor, "place_on_mesh",
                        lambda self, mesh, axis="data": None)
    mesh = Mesh(np.array(topo.devices[:P]), ("data",))
    ex = Executor(weather_db)
    assert ex.num_partitions == P
    cp = ex.compile(compile_query(ALL["Q9"]), mode="spmd", mesh=mesh)
    assert cp.config.use_pallas_join and cp.config.use_pallas_segments

    def shape(name, a):
        spec = PartitionSpec() if name == "__derived__" else \
            PartitionSpec("data")
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))
    tables = {k: jax.tree.map(lambda a, k=k: shape(k, a), v)
              for k, v in ex.tables.items()}
    text = cp.fn.lower(tables).compile().as_text()
    assert "tpu_custom_call" in text
    assert COLLECTIVE.search(text)


def test_scan_plan_compacts_without_loop(one_chip, weather_db):
    """Q1's DataScan lowered for the chip takes the blocked compaction:
    ops run under ``rows_from_mask``, none of them a ``while`` loop.
    Nothing steers the backend: ``rows_from_mask`` picks its lowering by
    the platform the program is lowered for."""
    ex = Executor(weather_db)
    cp = ex.compile(compile_query(ALL["Q1"]))
    tables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), ex.tables)
    lines = cp.fn.lower(tables).compile().as_text().splitlines()
    scoped = [ln for ln in lines if "rows_from_mask" in ln]
    assert scoped
    assert not [ln for ln in scoped if " while(" in ln]
