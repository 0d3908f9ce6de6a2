"""Production mesh + sharding rules (DESIGN.md §6).

Mesh axes: ``data`` (FSDP/batch) × ``model`` (TP/EP), with an outer
``pod`` axis for multi-pod runs. Nothing below indexes the pod axis
except collectives, so the design extends to arbitrary pod counts.

Sharding rules are name-based over the parameter tree:
  embed (V,d)               -> (model, data)
  attention wq/wk/wv (d,H)  -> (data, model);  wo (H,d) -> (model, data)
  mlp wi/gate (d,ff)        -> (data, model);  wo (ff,d) -> (model, data)
  moe experts (E,d,ff)      -> E over model (expert parallelism),
                               d/ff over data
  mamba in-proj (d,din)     -> (data, model);  out (din,d) -> (model, data)
  norms / small vectors     -> replicated
Dims that do not divide the axis size stay unsharded (uneven shards are
rejected rather than silently misplaced).

Batch dims shard over (pod, data). Decode KV caches shard sequence over
``model`` (split-K flash-decode) and batch over (pod, data); when batch
is too small (long_500k: batch=1) the sequence takes both axes.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.model import ModelConfig


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(num_devices: Optional[int] = None) -> Mesh:
    """Small mesh over the actual local devices (tests/examples)."""
    n = num_devices or len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         (AxisType.Auto, AxisType.Auto))


def _axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    if name is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def _sh(mesh: Mesh, dim: int, name):
    """Axis name if it exists in the mesh and divides dim, else None."""
    if name is None:
        return None
    if isinstance(name, tuple):
        names = tuple(n for n in name if n in mesh.axis_names)
        if not names:
            return None
        if dim % _axis_size(mesh, names) == 0:
            return names if len(names) > 1 else names[0]
        # try prefixes (e.g. batch too small for pod*data -> data only)
        for k in range(len(names) - 1, 0, -1):
            if dim % _axis_size(mesh, names[:k]) == 0:
                return names[:k] if k > 1 else names[0]
        return None
    if name not in mesh.axis_names:
        return None
    return name if dim % _axis_size(mesh, name) == 0 else None


BATCH = ("pod", "data")
FSDP = "data"
TP = "model"


def _param_spec(path: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Name-based sharding rule for one parameter leaf."""
    s = partial(_sh, mesh)
    name = path.split("/")[-1]
    in_blocks = "blocks" in path
    k = 1 if in_blocks else 0          # leading stacked-layer dim

    def spec(*names):
        full = [None] * k + list(names)
        full = full[:len(shape)] + [None] * (len(shape) - len(full))
        return P(*[s(shape[i], full[i]) for i in range(len(shape))])

    if name == "embed":
        return spec(TP, FSDP)
    if name == "lm_head":
        return spec(FSDP, TP)
    if name == "frontend_proj":
        return spec(None, FSDP)
    if name in ("wq", "wk", "wv", "wz", "wx", "wi_gate", "wi_up", "wi",
                "w_gate", "wdt"):
        if "moe" in path and name in ("wi_gate", "wi_up"):
            return spec(TP, FSDP, None)     # (K, E, d, ff): EP over model
        return spec(FSDP, TP)
    if name == "wo":
        if "moe" in path:
            return spec(TP, None, FSDP)     # (K, E, ff, d)
        return spec(TP, FSDP)
    if name in ("wB", "wC"):
        return spec(FSDP, None)
    if name == "router":
        return spec(FSDP, None)
    if name == "conv_w":
        return spec(None, TP)
    if name in ("dt_bias", "a_log", "D"):
        return spec(TP)
    # norms, biases, small vectors: replicated
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
        else:
            parts.append(str(e))
    return "/".join(parts)


def param_specs(cfg: ModelConfig, mesh: Mesh, abstract_params=None):
    """PartitionSpec tree matching the parameter pytree."""
    from repro.models.model import abstract_params as abs_p
    tree = abstract_params if abstract_params is not None else abs_p(cfg)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _param_spec(_path_str(p), l.shape, mesh), tree)


def opt_specs(cfg: ModelConfig, mesh: Mesh, abstract_opt) -> Any:
    """Optimizer state: m/v shadow the param tree; step replicated."""
    ps = param_specs(cfg, mesh)
    return {"step": P(), "m": ps, "v": ps}


def batch_specs(cfg: ModelConfig, mesh: Mesh, batch_tree) -> Any:
    def one(path, leaf):
        name = _path_str(path).split("/")[-1]
        shape = leaf.shape
        if name == "positions":          # (3, B, S)
            return P(None, _sh(mesh, shape[1], BATCH), None)
        return P(_sh(mesh, shape[0], BATCH),
                 *([None] * (len(shape) - 1)))

    return jax.tree_util.tree_map_with_path(one, batch_tree)


def cache_specs(cfg: ModelConfig, mesh: Mesh, cache_tree) -> Any:
    """Decode caches. Attention k/v: (K, B, Smax, Hkv, hd) — batch over
    (pod, data), sequence over model (split-K decode). If batch can't
    use the data axis (long_500k b=1), sequence takes (data, model)."""
    def one(path, leaf):
        name = _path_str(path).split("/")[-1]
        shape = leaf.shape
        if name in ("k", "v"):
            b_ax = _sh(mesh, shape[1], BATCH)
            used = set()
            if b_ax is not None:
                used = set(b_ax) if isinstance(b_ax, tuple) else {b_ax}
            seq_axes = tuple(a for a in ("data", "model")
                             if a in mesh.axis_names and a not in used)
            s_ax = _sh(mesh, shape[2], seq_axes if len(seq_axes) > 1
                       else (seq_axes[0] if seq_axes else None))
            return P(None, b_ax, s_ax, None, None)
        if name == "conv":               # (K, B, W, C)
            return P(None, _sh(mesh, shape[1], BATCH), None,
                     _sh(mesh, shape[3], TP))
        if name == "ssm":                # (K, B, H, N, Pd)
            return P(None, _sh(mesh, shape[1], BATCH),
                     _sh(mesh, shape[2], TP), None, None)
        raise ValueError(name)

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
