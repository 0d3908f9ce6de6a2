"""Per-kernel allclose vs the ref.py oracles, swept over shapes/dtypes
(interpret=True executes the exact TPU kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_bhgd
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.hash_join import block_join_probe
from repro.kernels.seg_aggregate import segmented_aggregate

RNG = np.random.default_rng(7)


def _qkv(bh, bhkv, sq, sk, d, dtype):
    q = jnp.asarray(RNG.normal(size=(bh, sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(bhkv, sk, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(bhkv, sk, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("sq,sk,d,g", [(128, 128, 64, 2),
                                       (64, 256, 32, 1),
                                       (256, 128, 128, 4)])
def test_flash_attention_shapes(sq, sk, d, g, dtype, tol):
    bh, bhkv = 2 * g, 2
    q, k, v = _qkv(bh, bhkv, sq, sk, d, dtype)
    out = flash_attention_bhsd(q, k, v, g=g, causal=True,
                               block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v, g=g, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("causal,window,softcap",
                         [(True, None, None), (True, 64, None),
                          (True, None, 30.0), (False, None, None),
                          (True, 64, 30.0)])
def test_flash_attention_variants(causal, window, softcap):
    q, k, v = _qkv(4, 2, 128, 128, 64, jnp.float32)
    out = flash_attention_bhsd(q, k, v, g=2, causal=causal,
                               window=window, softcap=softcap,
                               block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v, g=2, causal=causal,
                               window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("g,sk,d", [(4, 256, 64), (8, 512, 128),
                                    (1, 128, 32)])
def test_decode_attention_shapes(g, sk, d):
    bh = 4
    q = jnp.asarray(RNG.normal(size=(bh, g, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(bh, sk, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(bh, sk, d)), jnp.float32)
    kv_len = jnp.asarray(RNG.integers(1, sk + 1, bh), jnp.int32)
    out = decode_attention_bhgd(q, k, v, kv_len, block_k=64,
                                interpret=True)
    want = ref.decode_attention(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_window_softcap():
    bh, g, sk, d = 2, 4, 256, 64
    q = jnp.asarray(RNG.normal(size=(bh, g, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(bh, sk, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(bh, sk, d)), jnp.float32)
    kv_len = jnp.asarray([100, 250], jnp.int32)
    out = decode_attention_bhgd(q, k, v, kv_len, window=32,
                                softcap=25.0, block_k=64,
                                interpret=True)
    want = ref.decode_attention(q, k, v, kv_len, window=32,
                                softcap=25.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_matches_model_dense_path():
    """Kernel vs the model's dense decode attention (different code)."""
    from repro.models.attention import decode_attention as model_dec
    B, G, Hkv, Sk, D = 2, 4, 2, 128, 64
    Hq = G * Hkv
    q = jnp.asarray(RNG.normal(size=(B, 1, Hq, D)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(B, Sk, Hkv, D)), jnp.float32)
    vc = jnp.asarray(RNG.normal(size=(B, Sk, Hkv, D)), jnp.float32)
    kvl = jnp.asarray([60, 128], jnp.int32)
    out = ops.decode_attention(q, kc, vc, kvl, block_k=64)
    want = model_dec(q, kc, vc, kv_len=kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


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


def test_model_attention_pallas_impl_path():
    """models.attention(impl='pallas') routes through the kernel and
    matches the dense path."""
    from repro.models.attention import attention
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 64
    q = jnp.asarray(RNG.normal(size=(B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
    out_p = attention(q, k, v, causal=True, impl="pallas")
    out_d = attention(q, k, v, causal=True, impl="dense")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                               atol=2e-5, rtol=2e-5)
