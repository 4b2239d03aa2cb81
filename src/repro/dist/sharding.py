"""Logical-axis sharding rules and tree-wide sharding resolution.

A rule set maps LOGICAL axis names (the tuples carried in param/cache
spec trees, see layers/common.py) to mesh axes.  One rule set serves
every arch; per-tensor robustness (dedup, divisibility) lives in
``layers.common.logical_to_pspec``.

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model")
multi-pod (see launch/mesh.py).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec


def _dp(multi_pod: bool):
    """The data-parallel submesh (batch axis)."""
    return ("pod", "data") if multi_pod else "data"


def _base_rules(multi_pod: bool) -> dict[str, Any]:
    return {
        # activations
        "batch": _dp(multi_pod),
        "heads_dim": "model",
        "kv_heads_dim": "model",
        "head_dim": "model",     # fallback when head count won't divide
        # params
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "experts": "model",
        "vocab": "model",
        "kv_lora": "model",
        "q_lora": "model",
        "ssm_heads": "model",
        "ssm_inner": "model",
        # scan-stacked layer axis is never sharded
        "layers": None,
    }


def rules_train(multi_pod: bool, fsdp: bool = False) -> dict[str, Any]:
    """Training rules: TP over 'model'; FSDP additionally shards the
    embed dim of params over the data axis (gathered per-layer)."""
    r = _base_rules(multi_pod)
    r["embed"] = _dp(multi_pod) if fsdp else None
    return r


def rules_decode(multi_pod: bool) -> dict[str, Any]:
    """Decode rules: replicated embed (latency path re-gathers nothing),
    batch over data, TP over model."""
    r = _base_rules(multi_pod)
    r["embed"] = None
    return r


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def param_shardings(mesh, shapes, specs, rules: dict[str, Any]):
    """NamedSharding tree matching ``shapes``'s structure.

    ``specs`` mirrors ``shapes`` with tuple-of-logical-axis leaves.
    """
    from repro.layers.common import logical_to_pspec

    def one(axes, shape_struct):
        spec = logical_to_pspec(tuple(axes), rules, shape_struct.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, specs, shapes, is_leaf=_is_axes)


def batch_shardings(mesh, bspec, rules: dict[str, Any]):
    """Shard every batch leaf along its leading (batch) dim."""
    from repro.layers.common import logical_to_pspec

    def one(s):
        axes = ("batch",) + (None,) * (len(s.shape) - 1)
        return NamedSharding(mesh, logical_to_pspec(axes, rules, s.shape, mesh))

    return jax.tree.map(one, bspec)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def shard_meshes(n_shards: int, *, devices=None):
    """One 1x1 ("data", "model") decode mesh per shard.

    Shards take distinct devices of ``devices`` (``jax.devices()`` by
    default) whenever there are at least ``n_shards`` of them.  With
    fewer CPU devices — the single-process test case — the list is
    tiled round-robin: shard isolation (pools, jit caches, indexes)
    comes from each shard's own Engine instance, not from the mesh, so
    sharing a host device keeps the same semantics.  Accelerators are
    never tiled: two shards on one chip would only contend for it.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    from jax.sharding import Mesh
    import numpy as np

    devices = list(jax.devices() if devices is None else devices)
    if len(devices) >= n_shards:
        per = len(devices) // n_shards
        leads = [devices[i * per] for i in range(n_shards)]
    elif devices[0].platform == "cpu":
        leads = [devices[i % len(devices)] for i in range(n_shards)]
    else:
        raise ValueError(f"{n_shards} shards need {n_shards} "
                         f"{devices[0].platform} devices, "
                         f"found {len(devices)}")
    # one PRIMARY device per shard: the engine datapath is single-device
    # within a shard (params pinned, pools donated in place), so each
    # shard's mesh is 1x1 over its lead — model-parallel-within-shard
    # would widen the model axis here
    return [Mesh(np.asarray([d], dtype=object).reshape(1, 1),
                 axis_names=("data", "model")) for d in leads]
