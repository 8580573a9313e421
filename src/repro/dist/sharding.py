"""Parameter/cache sharding: logical axis names -> mesh axes.

Model code never names mesh axes.  Weights declare *logical* axes in
their ``PDef``s (``embed``, ``heads``, ``ff``, ``vocab``, ``expert``,
...) and a :class:`~repro.configs.base.ShardingStrategy` picks the rule
table that maps each logical axis onto zero or more mesh axes.  The
resolver then enforces the physical constraints the rule tables cannot
know about:

* a mesh axis that does not exist on this mesh is dropped (the same
  model runs on ``(data, model)``, ``(pod, data, model)`` and ``(1, 1)``
  smoke meshes);
* a mesh axis whose size does not divide the dimension is dropped
  (kv_heads=2 on model=4 stays replicated rather than crashing);
* a mesh axis is used at most once per spec (PartitionSpec rule).

``submesh_for`` is the bridge from the operator's resource layer: a
Fluxion ``ResourceSet`` (n hosts x chips/host) becomes a
``(data=hosts, model=chips)`` JAX sub-mesh over exactly the chips the
allocation names, degrading to whatever this process actually has.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ShardingStrategy

# A rule maps a logical axis name to a mesh axis, a tuple of mesh axes,
# or None (replicated).
Rule = Union[str, Tuple[str, ...], None]

# mesh axes that carry the data-parallel dimension, outermost first
DATA_AXES = ("pod", "data")


# --------------------------------------------------------------------------
# Mesh helpers
# --------------------------------------------------------------------------


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto`` (sharding propagates through
    ops, as the rule tables assume)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    """Product of the named mesh axes' sizes (1 for the empty tuple)."""
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
        if axes else 1


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in DATA_AXES if a in mesh.shape)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


# --------------------------------------------------------------------------
# Rule tables
# --------------------------------------------------------------------------


def param_rules(strategy: ShardingStrategy) -> Dict[str, Rule]:
    """Weight sharding rules (see models/layers.py for the axis names)."""
    tp = "model" if strategy.tensor_parallel else None
    if strategy.fsdp_params:
        # ZeRO-3; without TP the model axis joins the FSDP domain
        embed: Rule = "data" if strategy.tensor_parallel \
            else ("data", "model")
    else:
        embed = None
    if not strategy.expert_parallel:
        expert: Rule = None
    elif strategy.hierarchical_moe:
        # experts span the pod tier too (pod-major), so each pod holds
        # only n_experts/P expert weights and MoE dispatch has a
        # cross-pod hop to schedule (models/moe.py routes it
        # hierarchically); on a pod-less mesh this resolves back to
        # plain model-axis expert parallelism
        expert = ("pod", "model")
    else:
        expert = "model"
    return {
        "embed": embed,
        "heads": tp,
        "kv_heads": tp,
        "ff": tp,
        "vocab": tp,
        "expert": expert,
        "mamba_in": tp,
        "xl_in": tp,
        "xl_heads": tp,
    }


def opt_rules(strategy: ShardingStrategy) -> Dict[str, Rule]:
    """Optimizer-state rules: ZeRO-1 — states shard over the data axis
    even when the parameters themselves are replicated."""
    rules = dict(param_rules(strategy))
    if rules.get("embed") is None:
        rules["embed"] = "data"
    return rules


def cache_rules(strategy: ShardingStrategy) -> Dict[str, Rule]:
    """Decode-state rules (see transformer.cache_defs for the names).

    ``pages`` is the paged KV pool's page dim: ``paged_cache_defs`` only
    names it when the engine built a multi-shard allocator, so a pool
    shards over the data tier exactly when the host-side free lists are
    partitioned to match (slot-sharded pages; see serve/paging)."""
    tp = "model" if strategy.tensor_parallel else None
    return {
        "batch": DATA_AXES,
        "pages": DATA_AXES,
        "kv_seq": tp if strategy.kv_seq_axis == "model" else None,
        "kv_heads": tp,
        "mamba_in": tp,
        "xl_in": tp,
        "xl_heads": tp,
    }


# --------------------------------------------------------------------------
# Resolution
# --------------------------------------------------------------------------


def resolve_spec(shape: Sequence[int], axes: Sequence[Optional[str]],
                 rules: Dict[str, Rule], mesh: Mesh) -> PartitionSpec:
    """Logical axes -> PartitionSpec under this mesh's constraints."""
    used: set = set()
    spec = []
    for dim, name in zip(shape, axes):
        rule = rules.get(name) if name is not None else None
        cand: Tuple[str, ...] = () if rule is None else (
            rule if isinstance(rule, tuple) else (rule,))
        cand = tuple(a for a in cand if a in mesh.shape and a not in used)
        while cand and dim % axis_size(mesh, cand) != 0:
            cand = cand[:-1]
        if not cand:
            spec.append(None)
            continue
        used.update(cand)
        spec.append(cand[0] if len(cand) == 1 else cand)
    return PartitionSpec(*spec)


def tree_shardings(defs, mesh: Mesh, rules: Dict[str, Rule]):
    """PDef tree -> NamedSharding tree."""
    from repro.models import params as P   # deferred: models import us
    return P.tree_map(
        lambda d: NamedSharding(
            mesh, resolve_spec(d.shape, d.axes, rules, mesh)), defs)


def cache_shardings(cdefs, mesh: Mesh, strategy: ShardingStrategy):
    return tree_shardings(cdefs, mesh, cache_rules(strategy))


def batch_sharding(mesh: Mesh, ndim: int, global_batch: int,
                   strategy: ShardingStrategy,
                   seq_dim: Optional[int] = None) -> NamedSharding:
    """Model-input sharding: batch over the data axes, optionally the
    sequence dim over the model axis (sequence-parallel residuals)."""
    spec: list = [None] * ndim
    d = data_axes(mesh)
    if not strategy.tensor_parallel and "model" in mesh.shape:
        d = d + ("model",)
    while d and global_batch % axis_size(mesh, d) != 0:
        d = d[:-1]
    if d:
        spec[0] = d[0] if len(d) == 1 else d
    if (seq_dim is not None and strategy.tensor_parallel
            and "model" in mesh.shape and "model" not in d):
        spec[seq_dim] = "model"
    return NamedSharding(mesh, PartitionSpec(*spec))


# --------------------------------------------------------------------------
# ResourceSet -> sub-mesh (the operator/JAX bridge)
# --------------------------------------------------------------------------


def _pod_tier(rset) -> Optional[Tuple[int, int]]:
    """(n_pods, hosts_per_pod) when the allocation spans pods evenly.

    The pod tier only rises when it is well-formed: ≥ 2 distinct pods,
    the same host count in each, hosts grouped pod-contiguously (the
    graph numbers hosts pod-major, and matchers return sorted ids).
    Anything else — legacy ResourceSets without pod info, ragged spans
    — flattens to the classic (data, model) mesh.
    """
    pods = tuple(getattr(rset, "pods", ()) or ())
    if len(pods) != rset.n_hosts or len(set(pods)) < 2:
        return None
    if list(pods) != sorted(pods):
        return None
    counts = {p: pods.count(p) for p in set(pods)}
    if len(set(counts.values())) != 1:
        return None
    return len(counts), next(iter(counts.values()))


def submesh_for(rset, devices=None) -> Mesh:
    """Map a Flux ``ResourceSet`` allocation onto a JAX device sub-mesh.

    The allocation's chip ids index the process's device list directly
    — the resource graph drives physical placement.  Hosts become the
    ``data`` axis, chips-per-host the ``model`` axis; an allocation
    whose hosts span pods (the ``Host.pod`` field the graph carries)
    yields a ``(pod, data, model)`` mesh instead of flattening pod
    locality away, so the comm layer can schedule around the slow
    cross-pod links.  When the allocation names more chips than this
    process has (orchestration benches simulate fleets far larger than
    the dev box), the mesh degrades to the largest (hosts, chips) grid
    that fits, down to a single device.
    """
    devices = list(jax.devices() if devices is None else devices)
    nd = len(devices)
    cids = rset.chip_ids()
    if cids and len(cids) <= nd and max(cids) < nd:
        devs = [devices[c] for c in cids]
        tier = _pod_tier(rset)
        if tier is not None:
            n_pods, per_pod = tier
            shape: Tuple[int, ...] = (n_pods, per_pod,
                                      rset.chips_per_host)
            axes: Tuple[str, ...] = ("pod", "data", "model")
        else:
            shape = (rset.n_hosts, rset.chips_per_host)
            axes = ("data", "model")
    else:
        hosts = max(1, min(rset.n_hosts, nd))
        chips = max(1, min(rset.chips_per_host, nd // hosts))
        devs = devices[:hosts * chips]
        shape = (hosts, chips)
        axes = ("data", "model")
    arr = np.asarray(devs, dtype=object).reshape(shape)
    return Mesh(arr, axes)
