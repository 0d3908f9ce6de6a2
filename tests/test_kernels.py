"""Per-kernel allclose vs the ref.py oracles, swept over shapes/dtypes
(interpret=True executes the exact TPU kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.hash_join import block_join_probe
from repro.kernels.seg_aggregate import segmented_aggregate

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("nb,np_,nkeys", [(128, 256, 1), (256, 128, 2),
                                          (512, 512, 2)])
def test_block_join_sweep(nb, np_, nkeys):
    bk = [jnp.asarray(RNG.choice(5000, nb, replace=False), jnp.int32)]
    pk = [jnp.asarray(RNG.integers(0, 6000, np_), jnp.int32)]
    if nkeys == 2:
        bk.append(jnp.asarray(RNG.integers(0, 40, nb), jnp.int32))
        pk.append(jnp.asarray(RNG.integers(0, 40, np_), jnp.int32))
    bv = jnp.asarray(RNG.random(nb) > 0.15)
    pv = jnp.asarray(RNG.random(np_) > 0.15)
    pos, matched = block_join_probe(tuple(bk), bv, tuple(pk), pv,
                                    block_p=128, block_b=128,
                                    interpret=True)
    wpos, wm = ref.block_join_probe(tuple(bk), bv, tuple(pk), pv)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(wpos))
    np.testing.assert_array_equal(np.asarray(matched), np.asarray(wm))


def test_join_kernel_agrees_with_executor_probe():
    """Pallas probe vs the executor's sorted-hash probe (independent
    algorithms must agree on unique build keys)."""
    from repro.core.executor import hash_join_probe
    nb, np_ = 256, 512
    bk = (jnp.asarray(RNG.choice(10_000, nb, replace=False), jnp.int32),)
    pk = (jnp.asarray(RNG.integers(0, 12_000, np_), jnp.int32),)
    bv = jnp.ones(nb, bool)
    pv = jnp.ones(np_, bool)
    pos1, m1, _ = hash_join_probe(bk, bv, pk, pv, bucket=4)
    pos2, m2 = block_join_probe(bk, bv, pk, pv, interpret=True)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(pos1), np.asarray(pos2))


@pytest.mark.parametrize("n,s,bn", [(512, 32, 128), (2048, 128, 512),
                                    (1024, 7, 256)])
def test_segmented_sum_count(n, s, bn):
    """The kernel's one-column sum/count equals the executor's legacy
    twin, ``ref.segmented_sum_count``."""
    vals = jnp.asarray(RNG.normal(size=n), jnp.float32)
    segs = jnp.asarray(RNG.integers(-1, s + 2, n), jnp.int32)
    valid = jnp.asarray(RNG.random(n) > 0.25)
    got_c, got_s, _, _ = segmented_aggregate(
        vals[:, None], jnp.ones((n, 1), bool), segs, valid, s,
        block_n=bn, interpret=True)
    want_s, want_c = ref.segmented_sum_count(vals, segs, valid, s)
    np.testing.assert_allclose(np.asarray(got_s[:, 0]),
                               np.asarray(want_s), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got_c),
                                  np.asarray(want_c))
