"""Map / combine / shuffle / reduce in pure JAX.

Single-device path (`local_mapreduce`, `measure_fp`) for correctness and FP
profiling, and a mesh path (`mesh_mapreduce`) where the shuffle is a real
`jax.lax.all_to_all` inside `shard_map` over a chosen mesh axis set. JoSS's
placement decisions select those axes: policy A keeps the shuffle on
intra-pod axes only; policies B/C let it cross the `pod` axis and pin the
reduced output's sharding (reduce placement == out_shardings).

Both paths name their stages for the profiler with `jax.named_scope`
(op_name metadata only; the compiled programs are otherwise unchanged):
`mr.map`, `mr.sort` and `mr.segment`, and on the mesh path `mr.pack`
(`_partition_pack`) and `mr.shuffle` (the two all_to_alls). The mesh path
is one jitted program, `_mesh_mapreduce`, keyed on (spec, mesh, axes,
slack). `local_mapreduce` and `mesh_mapreduce` open the host span
`mr.dispatch` around their dispatch.

The pack is one sort by destination that carries keys and values, then one
contiguous slice per destination: no gather, no scatter, no spill slot.
Records past a destination's buffer are counted (`dropped`) and lost;
which of them an over-full destination keeps is unspecified.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compile_cache import enable_compile_cache
from repro.mapreduce.jobs import EMPTY, KVBatch, MapReduceSpec


# ------------------------------------------------------------- local plane --
def _sort_reduce(keys: jax.Array, values: jax.Array, nbytes: jax.Array,
                 *, combined_bytes: bool
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort by key and aggregate each key's values/bytes.

    Returns (unique_keys, summed_values, out_bytes, n_unique); slots beyond
    n_unique (and the EMPTY segment) carry key == EMPTY.

    combined_bytes=True models a combiner's output size: one serialized kv
    per unique key (representative key bytes), else the sum of member bytes.

    The sort carries values and bytes as its payload, so nothing gathers by a
    permutation. It need not be stable: the sums are exact and every member
    of a key has the same byte size.

    Segmenting scatters nothing: after the sort each key is one run, whose
    sum is the difference of the prefix sums at its last slot and at the
    previous run's last slot. A second sort brings the run ends, keyed by
    their key (EMPTY elsewhere), to the front in ascending order with their
    prefix sums. Integer prefix differences wrap as the sums' own adds do,
    so every sum is exact modulo 2**32.
    """
    with jax.named_scope("mr.sort"):
        k, v, b = jax.lax.sort((keys, values, nbytes), num_keys=1,
                               is_stable=False)
    with jax.named_scope("mr.segment"):
        last = jnp.concatenate([k[:-1] != k[1:], jnp.ones((1,), bool)])
        # one kv per key (the run end's bytes), else the members' sum
        ukeys, cv, cb = jax.lax.sort(
            (jnp.where(last, k, EMPTY), jnp.cumsum(v, dtype=v.dtype),
             b if combined_bytes else jnp.cumsum(b, dtype=b.dtype)),
            num_keys=1, is_stable=False)

        def run_sums(c):
            return c - jnp.concatenate([jnp.zeros((1,), c.dtype), c[:-1]])

        valid = ukeys != EMPTY
        out_bytes = jnp.where(valid, cb if combined_bytes else run_sums(cb),
                              0)
        n_unique = jnp.sum(valid.astype(jnp.int32))
        return (ukeys,
                jnp.where(valid, run_sums(cv), 0).astype(values.dtype),
                out_bytes.astype(nbytes.dtype), n_unique)


def run_map(spec: MapReduceSpec, tokens: jax.Array, lengths: jax.Array,
            doc_id) -> KVBatch:
    with jax.named_scope("mr.map"):
        kv = spec.map_fn(tokens, lengths, jnp.asarray(doc_id, jnp.int32))
    if spec.combine_in_map:
        k, v, b, _ = _sort_reduce(kv.keys, kv.values, kv.nbytes,
                                  combined_bytes=True)
        kv = KVBatch(k, v, b, kv.cap)
    return kv


@partial(jax.jit, static_argnums=0)
def _local_mapreduce(spec: MapReduceSpec, tokens: jax.Array,
                     lengths: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    kv = run_map(spec, tokens, lengths, 0)
    k, v, _, n = _sort_reduce(kv.keys, kv.values, kv.nbytes,
                              combined_bytes=False)
    return k, v, n


def local_mapreduce(spec: MapReduceSpec, tokens: jax.Array,
                    lengths: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Map+combine+reduce of one shard on one device (the test oracle path).

    Returns (unique_keys, counts, n_unique): the first n_unique slots hold
    the distinct keys in ascending order, the rest EMPTY."""
    with jax.profiler.TraceAnnotation("mr.dispatch", job=spec.name):
        enable_compile_cache()
        return _local_mapreduce(spec, tokens, lengths)


@partial(jax.jit, static_argnums=0)
def _fp_one(spec: MapReduceSpec, tokens, lengths):
    kv = run_map(spec, tokens, lengths, 0)
    emitted = jnp.sum(kv.nbytes)
    consumed = jnp.sum(jnp.where(tokens >= 0, lengths, 0))
    return emitted / jnp.maximum(consumed, 1)


def measure_fp(spec: MapReduceSpec, shards_tokens: np.ndarray,
               shards_lengths: np.ndarray) -> np.ndarray:
    """Per-shard filtering percentage (paper Figs. 1-2): map-output bytes over
    map-input bytes, for a (n_shards, S) batch of shards."""
    enable_compile_cache()
    fn = jax.vmap(lambda t, l: _fp_one(spec, t, l))
    return np.asarray(fn(jnp.asarray(shards_tokens),
                         jnp.asarray(shards_lengths)))


# -------------------------------------------------------------- mesh plane --
def _partition_pack(kv: KVBatch, n_dest: int, cap_dest: int):
    """Bucket kv records by destination = key % n_dest into fixed-size
    per-destination buffers (EMPTY-padded); returns (keys, vals) shaped
    (n_dest, cap_dest) plus the number of dropped (overflow) records.

    One unstable sort by destination carries the keys and values, so
    nothing gathers by a permutation; EMPTY records sort last. Row j is
    then one contiguous slice of cap_dest slots at destination j's start
    (the exclusive prefix sum of the per-destination counts), its slots
    past j's count set to EMPTY/0, so nothing scatters either. Records
    past cap_dest in an over-full destination are counted in `dropped`
    and lost; which of its records the row keeps is unspecified.
    """
    dest = jnp.where(kv.keys == EMPTY, jnp.uint32(n_dest), kv.keys % n_dest)
    _, k, v = jax.lax.sort((dest, kv.keys, kv.values), num_keys=1,
                           is_stable=False)
    counts = jnp.sum(dest == jnp.arange(n_dest, dtype=dest.dtype)[:, None],
                     axis=1, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts
    # cap_dest slots of padding, so that no slice is clamped
    k = jnp.concatenate([k, jnp.full((cap_dest,), EMPTY, k.dtype)])
    v = jnp.concatenate([v, jnp.zeros((cap_dest,), v.dtype)])
    slot = jnp.arange(cap_dest)

    def rows(x, fill):
        return jnp.stack([
            jnp.where(slot < counts[j],
                      jax.lax.dynamic_slice(x, (starts[j],), (cap_dest,)),
                      fill)
            for j in range(n_dest)])
    dropped = jnp.sum(jnp.maximum(counts - cap_dest, 0))
    return rows(k, EMPTY), rows(v, 0), dropped


def mesh_mapreduce(spec: MapReduceSpec, tokens, lengths, mesh: Mesh,
                   shuffle_axes: Sequence[str] = ("data",),
                   shard_axes: Optional[Sequence[str]] = None,
                   slack: int = 4):
    """Distributed MapReduce over `mesh`.

    tokens/lengths: (n_shards, S) arrays, n_shards divisible by the product
    of `shard_axes` sizes (input placement; defaults to `shuffle_axes`).
    The shuffle all_to_alls keys over `shuffle_axes` only, so reducer d
    owns keys with key % D == d within each shuffle group. Passing
    shard_axes=('pod','data') with shuffle_axes=('data',) is JoSS policy A:
    every pod reduces its own shards with ZERO cross-pod shuffle bytes.

    One jitted program, `_mesh_mapreduce`, per (spec, mesh, shuffle_axes,
    shard_axes, slack): a second call with the same shapes compiles
    nothing. It opens the host span `mr.dispatch` around its dispatch, as
    `local_mapreduce` does.

    Returns (unique_keys, counts, n_unique, dropped); leading dim = number
    of shard groups.
    """
    shuffle_axes = tuple(shuffle_axes)
    shard_axes = tuple(shard_axes) if shard_axes else shuffle_axes
    n_groups = int(np.prod([mesh.shape[a] for a in shard_axes]))
    if tokens.shape[0] % n_groups:
        raise ValueError(
            f"n_shards {tokens.shape[0]} not divisible by {n_groups}")
    with jax.profiler.TraceAnnotation("mr.dispatch", job=spec.name):
        enable_compile_cache()
        return _mesh_mapreduce(spec, mesh, shuffle_axes, shard_axes,
                               int(slack), tokens, lengths)


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _mesh_mapreduce(spec: MapReduceSpec, mesh: Mesh,
                    shuffle_axes: Tuple[str, ...],
                    shard_axes: Tuple[str, ...], slack: int,
                    tokens: jax.Array, lengths: jax.Array):
    D = int(np.prod([mesh.shape[a] for a in shuffle_axes]))
    cap = tokens.shape[1] * spec.cap_mult
    cap_dest = slack * -(-cap // D)
    pspec = P(shard_axes)

    def shard_fn(tok, lng):
        # tok: (n_shards/n_groups, S) local shards
        idx = jax.lax.axis_index(shard_axes)

        def one(t, l):
            return run_map(spec, t, l, idx)
        kv = jax.vmap(one)(tok, lng)
        flat = KVBatch(kv.keys.reshape(-1), kv.values.reshape(-1),
                       kv.nbytes.reshape(-1), kv.cap * tok.shape[0])
        with jax.named_scope("mr.pack"):
            bk, bv, dropped = _partition_pack(flat, D,
                                              cap_dest * tok.shape[0])
        # the shuffle: one all_to_all over the chosen axes
        with jax.named_scope("mr.shuffle"):
            rk = jax.lax.all_to_all(bk, shuffle_axes, split_axis=0,
                                    concat_axis=0, tiled=True)
            rv = jax.lax.all_to_all(bv, shuffle_axes, split_axis=0,
                                    concat_axis=0, tiled=True)
        rk = rk.reshape(-1)
        rv = rv.reshape(-1)
        uk, uv, _, n = _sort_reduce(rk, rv, jnp.zeros_like(rv),
                                    combined_bytes=False)
        return (uk[None], uv[None], n[None], dropped[None])

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(pspec, pspec),
                       out_specs=(pspec, pspec, pspec, pspec))
    return fn(tokens, lengths)
