"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX, so the Pallas kernels of the
main path — the join probe (Q5-Q8), the segment aggregate (Q9-Q12) and
the top-k selection (Q11) — compile here exactly as they would on the
chip: alone, and under the 4-partition vmap that sim mode wraps around
them, at the widths ``chip_smoke.py`` serves. Mosaic refuses layouts
the interpreter accepts (unaligned blocks, bool loop carries, 1-D
refs), and these tests catch that without chip time. One whole grouped
plan (Q9 through ``Executor.compile``) must lower to a program that
calls the kernels (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers
must all collect the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import Executor, compile_query
from repro.core.queries import ALL
from repro.kernels import hash_join, seg_aggregate, seg_topk

P = 4            # partitions per chip in sim mode
SENSOR_ROWS = 250_000   # per-partition /sensors scan width, 1M readings
STATION_ROWS = 512      # gathered /stations build side
PAIR_ROWS = 50_000      # per-partition /sensors_min|max scan width
GROUPS = 512            # presized segment space of 500 stations


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype, batch):
    shape = (P,) + shape if batch else shape
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, batch, *args):
    fn = jax.vmap(fn) if batch else fn
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("batch", [False, True], ids=["alone", "vmap4"])
@pytest.mark.parametrize("nb,np_,nkeys", [
    (STATION_ROWS, SENSOR_ROWS, 1),          # Q5-Q7: stations x sensors
    (P * PAIR_ROWS, PAIR_ROWS, 2),           # Q8: min x max on 2 keys
], ids=["stations", "pairs"])
def test_join_probe_compiles(one_chip, nb, np_, nkeys, batch):
    def key(n):
        return _sds(one_chip, (n,), jnp.int32, batch)

    def mask(n):
        return _sds(one_chip, (n,), jnp.bool_, batch)

    c = _compile(lambda bk, bv, pk, pv: hash_join.block_join_probe(
        bk, bv, pk, pv), batch, tuple(key(nb) for _ in range(nkeys)),
        mask(nb), tuple(key(np_) for _ in range(nkeys)), mask(np_))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("batch", [False, True], ids=["alone", "vmap4"])
@pytest.mark.parametrize("nc", [1, 3])       # Q9's avg; Q12's sum/min/max
def test_segmented_aggregate_compiles(one_chip, nc, batch):
    n = SENSOR_ROWS
    c = _compile(lambda v, ok, seg, valid: seg_aggregate.segmented_aggregate(
        v, ok, seg, valid, GROUPS), batch,
        _sds(one_chip, (n, nc), jnp.float32, batch),
        _sds(one_chip, (n, nc), jnp.bool_, batch),
        _sds(one_chip, (n,), jnp.int32, batch),
        _sds(one_chip, (n,), jnp.bool_, batch))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("batch", [False, True], ids=["alone", "vmap4"])
def test_segment_topk_compiles(one_chip, batch):
    # Q11: the invalid-sink flag, the negated f32 sum, the key's rank
    keys = (_sds(one_chip, (GROUPS,), jnp.int32, batch),
            _sds(one_chip, (GROUPS,), jnp.float32, batch),
            _sds(one_chip, (GROUPS,), jnp.int32, batch))
    c = _compile(lambda *k: seg_topk.segment_topk(k, 16), batch, *keys)
    assert "tpu_custom_call" in c.as_text()


def test_grouped_plan_calls_kernels(one_chip, weather_db, monkeypatch):
    """Q9's whole sim-mode program, traced as on a TPU backend, lowers
    for the chip with the Pallas kernels in it. The executor asks
    ``jax.default_backend()`` for its kernel routing; here the CPU
    answers, so the test steers that answer to the chip's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for var in ("REPRO_FORCE_JNP", "REPRO_KERNEL_INTERPRET"):
        monkeypatch.delenv(var, raising=False)
    ex = Executor(weather_db)
    cp = ex.compile(compile_query(ALL["Q9"]))
    assert cp.config.use_pallas_join and cp.config.use_pallas_segments
    tables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), ex.tables)
    assert "tpu_custom_call" in cp.fn.lower(tables).compile().as_text()
