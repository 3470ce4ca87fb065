"""Expert-parallel MoE dispatch with explicit all_to_all (shard_map).

The baseline sort-based dispatch (moe.py) is correct but lowers terribly
under SPMD: the global scatter/gather over a (E*C, d) buffer becomes
zero-fill + all-reduce of the WHOLE expert buffer per layer (measured:
8.8 TB/device/step of all-reduce for dbrx train_4k — EXPERIMENTS.md §Perf).

Here the token->expert shuffle is what it physically is — an all_to_all
over the 'model' (expert-parallel) axis, computed per device inside
shard_map:

  1. route the ~T/n_dev local tokens (local top-k, local capacity),
  2. pack a (n_ranks, experts_per_rank, C_local, d) send buffer,
  3. all_to_all over 'model'  (tokens travel to their expert's shard),
  4. run the local experts over their received tokens,
  5. reverse all_to_all, weighted-combine locally.

Wire bytes per device per layer: 2 * E * C_local * d * dtype — for dbrx
train_4k that is ~200x less than the baseline's buffer all-reduces.

This mirrors JoSS policy B: tokens are "map tasks" placed where their
expert ("input block") lives; the combine is the reduce phase, returned to
the token's home rank. The per-(pod,data) replica groups of the all_to_all
keep the shuffle inside the ICI domain — no DCN crossing (policy A's
scoping), because experts are replicated across pods.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.sharding.partition import current_rules, mesh_axis_size


def _local_pack(cfg: ArchConfig, router: jax.Array, xt: jax.Array,
                C: int) -> Tuple[jax.Array, jax.Array, jax.Array,
                                 jax.Array, jax.Array]:
    """Route local tokens into a (E, C, d) send buffer.

    Returns (buffer, dest flat slot per (token,choice), token ids, gates,
    aux loss)."""
    T, d = xt.shape
    E, k = cfg.n_experts, cfg.moe_topk
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    gates = (topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
             ).astype(xt.dtype)
    density = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(
        1.0) / topi.size
    aux = E * jnp.sum(density * probs.mean(axis=0))

    e_flat = topi.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    g_flat = gates.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    es, ts, gs = e_flat[order], t_flat[order], g_flat[order]
    starts = jnp.searchsorted(es, jnp.arange(E, dtype=es.dtype))
    rank = jnp.arange(T * k, dtype=jnp.int32) - starts[es].astype(jnp.int32)
    keep = rank < C
    dest = jnp.where(keep, es.astype(jnp.int32) * C + rank, E * C)
    buf = jnp.zeros((E * C + 1, xt.shape[1]), xt.dtype).at[dest].set(
        xt[ts])
    return buf[:-1].reshape(E, C, -1), dest, ts, gs * keep, aux


def _expert_compute(cfg: ArchConfig, wi: jax.Array, wo: jax.Array,
                    x: jax.Array) -> jax.Array:
    """x: (E_loc, n, d) tokens for this rank's experts."""
    h = jnp.einsum("end,edf->enf", x, wi)
    if cfg.act == "swiglu":
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    return jnp.einsum("enf,efd->end", h, wo)


def moe_ffn_ep(cfg: ArchConfig, p: Dict[str, jax.Array], x: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE FFN. Requires active (mesh, rules) with the
    'experts' logical axis mapped to a mesh axis; falls back to the dense
    sort-based path otherwise (single-device tests)."""
    from repro import flags
    active = current_rules()
    if active is None or flags.moe_dense():
        from repro.models.moe import moe_ffn
        return moe_ffn(cfg, p, x)
    mesh, rules = active
    ep_axis = rules.get("experts")
    M = mesh_axis_size(mesh, ep_axis)
    if M <= 1 or cfg.n_experts % M:
        from repro.models.moe import moe_ffn
        return moe_ffn(cfg, p, x)
    if isinstance(ep_axis, tuple):
        ep_axis = ep_axis[0]

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_topk
    # token layout: batch over the batch axes, seq over the EP axis.
    # This matches the surrounding residual-stream sharding exactly (batch
    # sharded, seq sharded-or-replicated over 'model'), so entering and
    # leaving the shard_map never reshards the activations — without this
    # SPMD falls into "involuntary full rematerialization" full-batch
    # gathers (measured: +3.5 TB/dev/step for dbrx; EXPERIMENTS.md §Perf).
    batch_axes = rules.get("batch")
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    batch_axes = tuple(a for a in (batch_axes or ())
                       if a in mesh.axis_names)
    b_size = mesh_axis_size(mesh, batch_axes)
    if B % b_size or S % M:
        from repro.models.moe import moe_ffn
        return moe_ffn(cfg, p, x)
    t_loc = (B // b_size) * (S // M)
    # local per-expert capacity, 8-aligned
    C = max(8, int(-(-cfg.capacity_factor * t_loc * k / E // 8) * 8))

    all_axes = tuple(mesh.axis_names)

    def shard_fn(xb, router, wi, wo):
        # xb: (B_loc, S_loc, d); wi/wo: (E/M, d, f) local experts
        xt = xb.reshape(-1, xb.shape[-1])
        buf, dest, ts, gs, aux = _local_pack(cfg, router, xt, C)
        # shuffle: tokens -> expert shards (within the EP replica group)
        send = buf.reshape(M, E // M, C, d)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: (M, E/M, C, d) = per-source-rank tokens for local experts
        y = _expert_compute(cfg, wi, wo,
                            recv.transpose(1, 0, 2, 3).reshape(
                                E // M, M * C, d))
        y = y.reshape(E // M, M, C, d).transpose(1, 0, 2, 3)
        # reverse shuffle: results back to the tokens' home ranks
        back = jax.lax.all_to_all(y, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        yf = jnp.concatenate([back.reshape(E * C, d),
                              jnp.zeros((1, d), back.dtype)], axis=0)
        vals = yf[dest] * gs[:, None]
        out = jnp.zeros((t_loc, d), x.dtype).at[ts].add(
            vals.astype(x.dtype))
        aux = jax.lax.pmean(aux, all_axes)
        return out.reshape(xb.shape), aux

    token_spec = P(batch_axes if batch_axes else None, ep_axis)
    out, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(token_spec, P(), P(ep_axis), P(ep_axis)),
        out_specs=(token_spec, P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wo"])
    return out, aux
