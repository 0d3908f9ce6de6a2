"""rows_from_mask's two lowerings against numpy.

Both compactions are called directly, so the TPU one (blocked) runs
here on the CPU too; each must give ``np.flatnonzero(mask)[:cap]``
padded with NEG, ``valid`` and ``overflow`` bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.physical import (NEG, _compact_blocked, _compact_search,
                                 rows_from_mask)

PATHS = {"search": _compact_search, "blocked": _compact_blocked}


def expected(mask: np.ndarray, cap: int):
    cap = min(cap, mask.shape[0])
    nz = np.flatnonzero(mask)
    idx = np.full(cap, NEG, np.int32)
    k = min(cap, nz.size)
    idx[:k] = nz[:k]
    return idx, np.arange(cap) < nz.size, np.bool_(nz.size > cap)


def _mask(n, p, seed=0):
    return np.random.default_rng(seed).random(n) < p


def _exact(n, k, seed=0):
    """n lanes, exactly k of them set at random positions."""
    m = np.zeros(n, bool)
    m[np.random.default_rng(seed).choice(n, k, replace=False)] = True
    return m


CASES = {
    "empty": (np.zeros(1000, bool), 100),
    "full": (np.ones(1000, bool), 1000),
    "full_over": (np.ones(1000, bool), 300),
    "total_eq_cap": (_exact(1000, 64), 64),
    "total_gt_cap": (_exact(1000, 65), 64),
    "cap_gt_n": (_mask(300, 0.3), 400),
    "random": (_mask(5000, 0.14, seed=1), 1024),
    "one_lane": (np.ones(1, bool), 1),
    "ragged_tail": (_mask(129, 0.5, seed=2), 129),
    "last_lane_only": (np.arange(4096) == 4095, 16),
}


def _check(got, want):
    idx, valid, ovf = (np.asarray(a) for a in got)
    assert idx.dtype == np.int32
    np.testing.assert_array_equal(idx, want[0])
    np.testing.assert_array_equal(valid, want[1])
    assert ovf == want[2]


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("path", list(PATHS))
def test_compaction_matches_flatnonzero(path, case):
    mask, cap = CASES[case]
    fn = jax.jit(PATHS[path], static_argnums=1)
    _check(fn(jnp.asarray(mask), cap), expected(mask, cap))


@pytest.mark.parametrize("path", list(PATHS))
def test_compaction_vmap_partitions(path):
    """4 partitions under vmap, as sim mode runs them, each with its
    own total: none, under the cap, at it, past it."""
    n, cap = 2000, 300
    masks = np.stack([np.zeros(n, bool), _exact(n, 120, seed=3),
                      _exact(n, cap, seed=4), _mask(n, 0.6, seed=5)])
    got = jax.jit(jax.vmap(lambda m: PATHS[path](m, cap)))(
        jnp.asarray(masks))
    for k in range(masks.shape[0]):
        _check([a[k] for a in got], expected(masks[k], cap))


def test_cpu_lowering_keeps_the_binary_search():
    """On the CPU the dispatcher lowers to the search alone: its one
    ``while`` loop, and no scatter."""
    text = jax.jit(lambda m: rows_from_mask(m, 100)).lower(
        jnp.zeros(1000, bool)).compile().as_text()
    assert text.count(" while(") == 1
    assert " scatter(" not in text
