"""Hierarchical collectives: the JoSS reduce-placement insight applied to
gradient reduction and MoE dispatch.

The paper's policy A/B place the reduce phase so shuffle bytes stay inside
one datacenter. The gradient-all-reduce analogue on a (pod, data, model)
mesh: reduce-scatter over the in-pod 'data' axis FIRST (ICI, cheap), then
all-reduce only the 1/|data| shard over 'pod' (DCN, scarce), then
all-gather in-pod. DCN bytes drop from 2·(P-1)/P·|g| to 2·(P-1)/P·|g|/D —
a |data|x reduction of the scarce-link traffic (16x on the production
mesh). Same trick for MoE: a two-hop all_to_all exchanges within the pod
first so only pod-aggregated expert traffic crosses the DCN.

These run inside shard_map; the pjit-level baseline lets XLA emit a flat
all-reduce instead, and the dry-run roofline quantifies the difference
(EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def hierarchical_psum(x: jax.Array, *, data_axis: str = "data",
                      pod_axis: Optional[str] = "pod") -> jax.Array:
    """In-pod reduce-scatter -> cross-pod all-reduce -> in-pod all-gather.

    Call inside shard_map. Result == lax.psum over (data, pod) axes.
    Requires x.shape[0] divisible by the data-axis size.
    """
    x = jax.lax.psum_scatter(x, data_axis, scatter_dimension=0, tiled=True)
    if pod_axis is not None:
        x = jax.lax.psum(x, pod_axis)
    return jax.lax.all_gather(x, data_axis, axis=0, tiled=True)


def flat_psum(x: jax.Array, *, data_axis: str = "data",
              pod_axis: Optional[str] = "pod") -> jax.Array:
    """Baseline: one flat all-reduce over both axes."""
    axes = (data_axis,) if pod_axis is None else (pod_axis, data_axis)
    return jax.lax.psum(x, axes)


def make_grad_allreduce(mesh: Mesh, *, hierarchical: bool = True):
    """shard_map'd gradient all-reduce over the batch axes for a pytree of
    replicated gradient leaves (leading dim divisible by |data|)."""
    pod_axis = "pod" if "pod" in mesh.axis_names else None
    fn = hierarchical_psum if hierarchical else flat_psum

    def reduce_tree(grads):
        def one(g):
            red = partial(fn, data_axis="data", pod_axis=pod_axis)
            spec = P()  # replicated in, replicated out
            # check_vma=False: the scatter->psum->gather chain's output IS
            # replicated over 'data' but the static checker can't see it
            return jax.shard_map(red, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False)(g)
        return jax.tree_util.tree_map(one, grads)

    return reduce_tree


def two_hop_all_to_all(x: jax.Array, *, pod_axis: str = "pod",
                       inner_axis: str = "model") -> jax.Array:
    """MoE dispatch across pods in two hops: exchange within the pod
    first, then one aggregated exchange across pods. Inside shard_map;
    x: (n_total_ranks, ...) where n_total_ranks = |pod| * |inner|,
    laid out pod-major (destination rank = pod * |inner| + inner_rank).

    Wire effect: per-token DCN crossings drop from one small message per
    (src, dst) rank pair to one aggregated message per pod pair.
    """
    # psum of a literal 1 folds to the axis size (jax.lax.axis_size does
    # not exist; this is the supported idiom and stays a static int)
    n_pod = jax.lax.psum(1, pod_axis)
    n_inner = jax.lax.psum(1, inner_axis)
    rest = x.shape[1:]
    # hop 1 (ICI): exchange so each inner rank holds its column for all pods
    x = x.reshape((n_pod, n_inner) + rest)
    x = jax.lax.all_to_all(x, inner_axis, split_axis=1, concat_axis=1,
                           tiled=False)
    # now (n_pod, n_inner, ...) with inner dim = source inner ranks
    # hop 2 (DCN): one aggregated exchange across pods
    x = jax.lax.all_to_all(x, pod_axis, split_axis=0, concat_axis=0,
                           tiled=False)
    return x.reshape((n_pod * n_inner,) + rest)
