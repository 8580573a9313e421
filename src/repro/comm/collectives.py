"""Topology-aware gradient collectives: the two-phase hierarchical sync.

``sync_grads`` is the comm layer's core: a ``shard_map``-based gradient
reduction that follows the tier structure ``CommTopology`` derives from
the mesh instead of whatever a flat ``psum`` lowers to:

1. **reduce-scatter inside each pod** over the fast ``data`` axis —
   every host ends up owning one shard of its pod's summed gradient;
2. **all-reduce the shards across pods** over the slow ``pod`` axis —
   the only phase that touches the contended DCN links, and the only
   phase ``compress.compress_payload`` quantizes to int8;
3. **all-gather back** over ``data`` so every device holds the full
   synced gradient.

The composition is numerically interchangeable with a flat ``psum``
over ``(pod, data)`` (pinned per-strategy by tests/test_comm.py).

Inputs are STACKED per-chunk gradients (leading dim ``n_chunks``,
sharded ``(pod, data)``, chunks pod-major), produced by the train
step's microbatch loop — that stacking is what exposes a pre-sync
gradient to intercept at all: under plain global-view autodiff the SPMD
partitioner emits the data-parallel all-reduce itself and there is no
seam to schedule.  Before the scatter each pod's chunk sum is scaled to
the POD-MEAN gradient, a quantity invariant under resizes of the data
tier, so elastic remesh cannot perturb what the compressor sees.

``resolve_policy`` is the single fallback gate: a strategy asking for
hierarchical/compressed sync on a mesh that cannot honor it degrades
to flat sync with one structured ``CommFallbackWarning`` — or raises
``CommTopologyError`` when the strategy pins ``comm_strict``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from jax import shard_map as _shard_map

from repro.comm import compress as efc
from repro.comm.topology import CommTopology
from repro.configs.base import ShardingStrategy
from repro.dist import sharding as shd
from repro.models import params as P

# logical name of the stacked-gradient chunk dim in the rule table
DP_CHUNK_AXIS = "dp_chunks"


class CommFallbackWarning(UserWarning):
    """The requested comm schedule degraded to flat sync (one per build)."""


class CommTopologyError(ValueError):
    """``comm_strict``: the mesh cannot honor the requested schedule."""


@dataclasses.dataclass(frozen=True)
class CommPolicy:
    """Resolved (strategy x mesh) communication decision."""

    hierarchical: bool = False
    compress: bool = False
    block: int = 256
    pods: int = 0                  # compression schema rows (strategy)
    buckets: int = 1               # sync buckets (1 = monolithic)


def degrade(strategy: ShardingStrategy, why: str, mesh=None) -> None:
    """Flat-sync fallback: warn once per step build, or raise under
    ``comm_strict`` — the silent-no-op failure mode is pinned out.

    The warning MESSAGE carries the mesh axis-shape: the warnings
    registry dedups on message text, so an elastic remesh onto a
    *different* degraded mesh re-warns instead of being swallowed by
    the first mesh's warning (two distinct degradations are two
    warnings; rebuilding on the SAME mesh stays deduped).
    """
    msg = (f"comm: strategy {strategy.name!r} requested hierarchical/"
           f"compressed gradient sync but {why}; falling back to flat sync")
    if mesh is not None:
        msg += f" [mesh={dict(mesh.shape)}]"
    if strategy.comm_strict:
        raise CommTopologyError(msg)
    warnings.warn(msg, CommFallbackWarning, stacklevel=3)


def resolve_policy(strategy: ShardingStrategy, mesh) -> CommPolicy:
    """Decide what the comm layer actually does on this mesh."""
    if not (strategy.hierarchical_collectives or strategy.compress_cross_pod):
        return CommPolicy()
    topo = CommTopology.from_mesh(mesh)
    if not topo.has_pod_tier:
        degrade(strategy, "the mesh has no pod tier (axis 'pod' missing "
                f"or size 1)", mesh=mesh)
        return CommPolicy()
    compress = bool(strategy.compress_cross_pod)
    if compress and topo.pod_size != strategy.compress_pods:
        degrade(strategy, f"the mesh pod tier ({topo.pod_size}) does not "
                f"match strategy.compress_pods ({strategy.compress_pods}) "
                "— the error-feedback schema is strategy-sized", mesh=mesh)
        compress = False
    return CommPolicy(hierarchical=True, compress=compress,
                      block=strategy.compress_block,
                      pods=strategy.compress_pods,
                      buckets=max(int(strategy.comm_buckets), 1))


# --------------------------------------------------------------------------
# Sharding rules for stacked gradients / the EF residual
# --------------------------------------------------------------------------


def _no_pod(rule):
    if rule is None:
        return None
    t = rule if isinstance(rule, tuple) else (rule,)
    t = tuple(a for a in t if a != "pod")
    return t[0] if len(t) == 1 else (t or None)


def grad_rules(strategy: ShardingStrategy):
    """Rule table for the comm layer's trees.  The stacked chunk dim
    owns the data-parallel axes and the residual's leading dim owns
    ``pod``; trailing dims keep only tensor/expert axes (a ZeRO-3
    ``embed -> data`` rule would collide with the chunk dim).  ``pod``
    is stripped from every param rule for the same reason: a
    ``hierarchical_moe`` expert rule of ``("pod", "model")`` would be
    silently truncated on the chunk-stacked INPUT (the chunk dim
    already holds pod) but kept on the chunk-free OUTPUT spec, and the
    mismatched local shapes make shard_map mis-concatenate the expert
    dim.  Phase 2 psums over ``pod`` anyway, so synced gradients are
    pod-replicated by construction."""
    rules = {k: _no_pod(v) for k, v in shd.param_rules(strategy).items()}
    rules["embed"] = None
    rules[DP_CHUNK_AXIS] = shd.DATA_AXES
    rules[efc.EF_POD_AXIS] = "pod"
    return rules


def stacked_specs(defs, mesh, strategy: ShardingStrategy, n_chunks: int):
    rules = grad_rules(strategy)
    return P.tree_map(
        lambda d: shd.resolve_spec((n_chunks,) + d.shape,
                                   (DP_CHUNK_AXIS,) + d.axes, rules, mesh),
        defs)


def grad_out_specs(defs, mesh, strategy: ShardingStrategy):
    rules = grad_rules(strategy)
    return P.tree_map(
        lambda d: shd.resolve_spec(d.shape, d.axes, rules, mesh), defs)


def ef_specs(model_defs, mesh, strategy: ShardingStrategy):
    rules = grad_rules(strategy)
    return P.tree_map(
        lambda d: shd.resolve_spec(d.shape, d.axes, rules, mesh),
        efc.ef_defs(model_defs, strategy))


def ef_shardings(model_defs, mesh, strategy: ShardingStrategy):
    """NamedSharding tree for the residual (train_state_shardings hook)."""
    return shd.tree_shardings(efc.ef_defs(model_defs, strategy), mesh,
                              grad_rules(strategy))


# --------------------------------------------------------------------------
# The two-phase sync
# --------------------------------------------------------------------------


def sync_grads(stacked, defs, mesh, policy: CommPolicy,
               strategy: ShardingStrategy, residual=None):
    """Hierarchically reduce stacked per-chunk gradients to their mean.

    ``stacked``: pytree matching ``defs``; each leaf is
    ``(n_chunks, *param_shape)`` of per-chunk MEAN gradients, chunk
    ``i`` covering rows ``[i*B/n, (i+1)*B/n)`` of the global batch.
    Chunks shard pod-major over ``(pod, data)``, so pod ``p`` always
    owns the same row range whatever the data-tier size.

    Returns ``(mean_grads, new_residual)``; the residual passes through
    untouched unless ``policy.compress`` and a residual tree is given.
    """
    leaves = jax.tree_util.tree_leaves(stacked)
    n_chunks = leaves[0].shape[0]
    pod = int(dict(mesh.shape).get("pod", 1))
    data = int(dict(mesh.shape).get("data", 1))
    has_data = data > 1
    block = int(policy.block)
    compress = bool(policy.compress) and residual is not None

    def _sync_leaf(g, e):
        shape = g.shape[1:]
        # local chunk partial sum, scaled to the pod-mean gradient:
        # sum over a pod's n_chunks/pod chunks of per-chunk means,
        # divided by that count — invariant under data-tier resizes
        g = g.sum(axis=0).astype(jnp.float32) * (pod / float(n_chunks))
        flat = g.reshape(-1)
        n = flat.shape[0]
        unit = data * block
        padded = -(-n // unit) * unit
        flat = jnp.pad(flat, (0, padded - n))
        # phase 1: reduce-scatter inside the pod over the fast axis
        s = (jax.lax.psum_scatter(flat, "data", scatter_dimension=0,
                                  tiled=True) if has_data else flat)
        if compress:
            # phase 2 (compressed): each pod quantizes payload + carry,
            # only int8 codes + block scales cross the DCN boundary
            e_flat = jnp.pad(e[0].astype(jnp.float32).reshape(-1),
                             (0, padded - n))
            k = padded // data
            d_idx = jax.lax.axis_index("data") if has_data else 0
            e_slice = jax.lax.dynamic_slice(e_flat, (d_idx * k,), (k,))
            x = s + e_slice
            deq, err = efc.compress_payload(x, block)
            s = jax.lax.psum(deq, "pod")
            e_new = (jax.lax.all_gather(err, "data", tiled=True)
                     if has_data else err)
            e_new = e_new[:n].reshape(shape)[None].astype(e.dtype)
        else:
            # phase 2: all-reduce the shards across pods
            s = jax.lax.psum(s, "pod")
            e_new = e
        # phase 3: all-gather the synced shards back inside the pod
        out = (jax.lax.all_gather(s, "data", tiled=True)
               if has_data else s)
        return (out[:n] / pod).reshape(shape), e_new

    in_g = stacked_specs(defs, mesh, strategy, n_chunks)
    out_g = grad_out_specs(defs, mesh, strategy)

    if not compress:
        def body(gs):
            return jax.tree_util.tree_map(
                lambda g: _sync_leaf(g, None)[0], gs)
        synced = _shard_map(body, mesh=mesh, in_specs=(in_g,),
                            out_specs=out_g, check_vma=False)(stacked)
        return synced, residual

    in_e = ef_specs(defs, mesh, strategy)

    def body(gs, es):
        gl, tdef = jax.tree_util.tree_flatten(gs)
        el = tdef.flatten_up_to(es)
        outs = [_sync_leaf(g, e) for g, e in zip(gl, el)]
        return (jax.tree_util.tree_unflatten(tdef, [o[0] for o in outs]),
                jax.tree_util.tree_unflatten(tdef, [o[1] for o in outs]))

    synced, new_ef = _shard_map(
        body, mesh=mesh, in_specs=(in_g, in_e), out_specs=(out_g, in_e),
        check_vma=False)(stacked, residual)
    return synced, new_ef


# --------------------------------------------------------------------------
# Bucketed sync: one two-phase schedule per bucket, reverse-layer order
# --------------------------------------------------------------------------


def sync_grads_bucketed(stacked, defs, mesh, policy: CommPolicy,
                        strategy: ShardingStrategy, residual=None):
    """:func:`sync_grads`, issued as ``policy.buckets`` independent
    collectives in reverse-layer order.

    Backward finalizes deep layers' gradients first, so emitting the
    deep buckets' cross-pod phase as its OWN collective — instead of
    one monolithic sync over the whole tree — lets the runtime overlap
    DCN transfers with the still-running shallow backward (async
    dispatch on real hardware; ``comm.overlap.schedule_overlap`` prices
    the hidden fraction for the simulator/bench).  The reduction per
    leaf is untouched, so the result is numerically interchangeable
    with the monolithic sync for every bucket count, and per-bucket EF
    residuals are just path-slices of the one strategy-schema'd
    residual tree — checkpoints and elastic remesh see no difference.
    """
    from repro.comm import bucketing

    if policy.buckets <= 1:
        return sync_grads(stacked, defs, mesh, policy, strategy,
                          residual=residual)
    buckets = bucketing.partition_buckets(defs, policy.buckets)
    d_sub = bucketing.bucket_subtrees(defs, defs, buckets)
    g_sub = bucketing.bucket_subtrees(stacked, defs, buckets)
    e_sub = (bucketing.bucket_subtrees(residual, defs, buckets)
             if residual is not None else [None] * len(buckets))
    g_out, e_out = [], []
    for db, gb, eb in zip(d_sub, g_sub, e_sub):
        g, e = sync_grads(gb, db, mesh, policy, strategy, residual=eb)
        g_out.append(g)
        e_out.append(e)
    synced = bucketing.unbucket_leaves(g_out, defs, buckets)
    if residual is None:
        return synced, residual
    return synced, bucketing.unbucket_leaves(e_out, defs, buckets)
