"""Sharding-rule unit tests on the production meshes (AbstractMesh —
no devices needed for spec computation)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch import mesh as mesh_lib
from repro.models import model as model_lib


def abstract_pod(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("multi", [False, True])
def test_param_specs_are_valid(arch, multi):
    """Every spec axis divides its dim; no axis used twice per leaf."""
    cfg = get_config(arch)
    mesh = abstract_pod(multi)
    tree = model_lib.abstract_params(cfg)
    specs = mesh_lib.param_specs(cfg, mesh, tree)

    def check(leaf, spec):
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        used = []
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                assert a not in used, f"axis {a} twice in {spec}"
                used.append(a)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (leaf.shape, spec, dim, size)

    jax.tree.map(check, tree, specs,
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", ["llama3-8b", "llama4-scout-17b-a16e",
                                  "mamba2-370m"])
def test_big_weights_are_sharded(arch):
    """The large matrices must not be replicated on the pod mesh."""
    cfg = get_config(arch)
    mesh = abstract_pod()
    tree = model_lib.abstract_params(cfg)
    specs = mesh_lib.param_specs(cfg, mesh, tree)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    sflat = jax.tree.leaves(specs,
                            is_leaf=lambda x: isinstance(x, P))
    import numpy as np
    for (path, leaf), spec in zip(flat, sflat):
        n = int(np.prod(leaf.shape))
        if n >= 1 << 22:            # >= 4M params
            assert any(s is not None for s in spec), (path, spec)


def test_embed_sharded_vocab_and_dmodel():
    cfg = get_config("llama3-8b")
    specs = mesh_lib.param_specs(cfg, abstract_pod())
    assert tuple(specs["embed"]) == ("model", "data")
    assert tuple(specs["lm_head"]) == ("data", "model")


def test_moe_experts_over_model_axis():
    cfg = get_config("llama4-scout-17b-a16e")
    specs = mesh_lib.param_specs(cfg, abstract_pod())
    blk = specs["blocks"][0]["moe"]
    assert tuple(blk["wi_gate"])[:2] == (None, "model")  # (K, E, d, ff)
    assert tuple(blk["wo"])[:2] == (None, "model")


def test_kv_cache_seq_sharding_long_context():
    """long_500k (b=1): sequence takes both axes."""
    cfg = get_config("mamba2-370m")
    mesh = abstract_pod()
    from repro.models.model import init_cache
    caches = init_cache(get_config("gemma3-12b"), 1, 524288,
                        abstract=True)
    specs = mesh_lib.cache_specs(get_config("gemma3-12b"), mesh, caches)
    kv = specs[0]["k"]     # first period slot is local attn for gemma3
    assert kv[1] is None                    # batch=1 unshardable
    assert kv[2] == ("data", "model")       # seq over both axes


def test_kv_cache_batch_sharding_decode32k():
    cfg = get_config("llama3-8b")
    mesh = abstract_pod()
    from repro.models.model import init_cache
    caches = init_cache(cfg, 128, 32768, abstract=True)
    specs = mesh_lib.cache_specs(cfg, mesh, caches)
    kv = specs[0]["k"]
    assert kv[1] == "data"                  # batch over data
    assert kv[2] == "model"                 # seq split-K over model


def test_batch_specs_pod_axis():
    cfg = get_config("llama3-8b")
    mesh = abstract_pod(multi=True)
    spec = mesh_lib.batch_specs(
        cfg, mesh, {"tokens": jax.ShapeDtypeStruct((256, 4096),
                                                   jnp.int32)})
    assert spec["tokens"][0] == ("pod", "data")
