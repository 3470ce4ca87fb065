"""The paper's five benchmarks (§6, PUMA [29][33]) as JAX map functions.

A corpus shard is a pair of int32 arrays (token ids, token byte lengths).
Each map function emits fixed-capacity (key, value, nbytes, valid) arrays:

  WC    - key = token id,             value = 1, bytes = len(word) + 4
  SC    - key = hash(3-gram),         value = 1, bytes = 3-gram bytes + 4
  II    - key = token id,             value = doc id, bytes = len + 4 (combined per shard)
  Grep  - key = position,             value = 1, only where token == pattern
  Permu - keys = 3 rotations/3-gram,  value = 1, bytes = 3 * (3-gram bytes)

The filtering percentage FP (paper Eq. 1-2) is emitted bytes / input bytes,
so it depends on the *input type* (web documents have long markup tokens,
paper Tables 1-4) exactly as the paper observes in Figs. 1-2.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: sentinel for unoccupied kv slots (uint32 max)
EMPTY = np.uint32(0xFFFFFFFF)


@dataclasses.dataclass(frozen=True)
class KVBatch:
    """Fixed-capacity kv batch; slots with key == EMPTY are invalid."""

    keys: jax.Array    # uint32 (cap,)
    values: jax.Array  # int32  (cap,)
    nbytes: jax.Array  # int32  (cap,) serialized size of each kv pair
    cap: int

    def tree_flatten(self):  # pragma: no cover - pytree plumbing
        return (self.keys, self.values, self.nbytes), self.cap

    @classmethod
    def tree_unflatten(cls, cap, leaves):  # pragma: no cover
        return cls(*leaves, cap)


jax.tree_util.register_pytree_node(
    KVBatch, KVBatch.tree_flatten, KVBatch.tree_unflatten)


@dataclasses.dataclass(frozen=True)
class MapReduceSpec:
    """One benchmark: map fn + capacity multiple + reduce combiner."""

    name: str
    #: map_fn(tokens, lengths, doc_id) -> KVBatch with cap = mult * len(tokens)
    map_fn: Callable[[jax.Array, jax.Array, jax.Array], KVBatch]
    cap_mult: int
    combine_in_map: bool  # run a map-side combiner (affects FP, like Hadoop)


def _emit(keys, values, nbytes, valid) -> KVBatch:
    keys = jnp.where(valid, keys.astype(jnp.uint32), EMPTY)
    values = jnp.where(valid, values, 0).astype(jnp.int32)
    nbytes = jnp.where(valid, nbytes, 0).astype(jnp.int32)
    return KVBatch(keys, values, nbytes, keys.shape[0])


def wc_map(tokens, lengths, doc_id) -> KVBatch:
    valid = tokens >= 0
    return _emit(tokens, jnp.ones_like(tokens), lengths + 4, valid)


def _gram3(tokens):
    """Hash of each 3 consecutive tokens (positions 0..n-3)."""
    a = tokens
    b = jnp.roll(tokens, -1)
    c = jnp.roll(tokens, -2)
    h = (a.astype(jnp.uint32) * jnp.uint32(2654435761)
         ^ b.astype(jnp.uint32) * jnp.uint32(40503)
         ^ c.astype(jnp.uint32) * jnp.uint32(69427))
    n = tokens.shape[0]
    ok = (jnp.arange(n) < n - 2) & (a >= 0) & (b >= 0) & (c >= 0)
    return h, ok


def sc_map(tokens, lengths, doc_id) -> KVBatch:
    h, ok = _gram3(tokens)
    size = lengths + jnp.roll(lengths, -1) + jnp.roll(lengths, -2) + 4
    return _emit(h, jnp.ones_like(tokens), size, ok)


def ii_map(tokens, lengths, doc_id) -> KVBatch:
    valid = tokens >= 0
    return _emit(tokens, jnp.full_like(tokens, doc_id), lengths + 4, valid)


def grep_map_factory(pattern_id: int):
    def grep_map(tokens, lengths, doc_id) -> KVBatch:
        valid = tokens == pattern_id
        pos = jnp.arange(tokens.shape[0])
        return _emit(pos, jnp.ones_like(tokens), lengths + 4, valid)
    return grep_map


def permu_map(tokens, lengths, doc_id) -> KVBatch:
    """3 rotations of each 3-gram; each record costs one sequence unit, so
    emitted bytes ~ 3x input -> FP ~ 3 (paper Table 5)."""
    h, ok = _gram3(tokens)
    size = lengths
    rots = []
    for r in (0, 1, 2):
        hr = h ^ jnp.uint32((r * 0x9E3779B9) & 0xFFFFFFFF)
        rots.append((hr, jnp.ones_like(tokens), size, ok))
    keys = jnp.concatenate([x[0] for x in rots])
    vals = jnp.concatenate([x[1] for x in rots])
    szs = jnp.concatenate([x[2] for x in rots])
    oks = jnp.concatenate([x[3] for x in rots])
    return _emit(keys, vals, szs, oks)


#: content token ids start here; ids below are web markup ('<page>', ...)
MARKUP_IDS = 64

#: Grep's default pattern: a fairly common content word (paper runs common
#: and uncommon patterns; see grep_map_factory for custom patterns)
GREP_PATTERN = MARKUP_IDS + 2

JOBS: Dict[str, MapReduceSpec] = {
    # PUMA's WC / II emit one record per occurrence (no combiner): FP ~ 1.0+
    "WC": MapReduceSpec("WC", wc_map, 1, combine_in_map=False),
    # SC combines duplicate 3-grams map-side: web boilerplate -> FP < 1
    "SC": MapReduceSpec("SC", sc_map, 1, combine_in_map=True),
    "II": MapReduceSpec("II", ii_map, 1, combine_in_map=False),
    "Grep": MapReduceSpec("Grep", grep_map_factory(GREP_PATTERN), 1,
                          combine_in_map=False),
    "Permu": MapReduceSpec("Permu", permu_map, 3, combine_in_map=False),
}


def word_len(token_ids: np.ndarray) -> np.ndarray:
    """Deterministic byte length per token id (a word has one spelling).

    Markup ids are long (paper Table 2: avg 22, '<format>text/x-wiki</format>'
    etc.); content ids follow a short-word distribution (Table 4: avg ~7.8).
    """
    t = token_ids.astype(np.uint64)
    h = (t * np.uint64(2654435761)) % np.uint64(1 << 32)
    markup = 12 + (h % np.uint64(22))          # 12..33, mean ~22.5
    content = 2 + (h % np.uint64(12))          # 2..13, mean ~7.5
    return np.where(token_ids < MARKUP_IDS, markup, content).astype(np.int32)


# ---------------------------------------------------------------- corpora --
def corpus(kind: str, n_tokens: int, seed: int = 0, vocab: int = 4096
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Synthetic corpora mirroring the paper's two input types (Tables 1-4).

    web:     boilerplate markup runs (8 templates over ids < MARKUP_IDS)
             interleaved with Zipf content words -> long avg word length,
             highly repetitive 3-grams (Table 1: '<contributor>' x6294).
    non-web: plain Zipf content words, short lengths (Tables 3-4).
    """
    rng = np.random.RandomState(seed)
    content_span = max(2, vocab - MARKUP_IDS)
    if kind == "web":
        templates = [rng.randint(0, MARKUP_IDS, size=rng.randint(6, 13))
                     for _ in range(8)]
        out: list = []
        while len(out) < n_tokens:
            if rng.rand() < 0.55:
                out.extend(templates[rng.randint(len(templates))])
            else:
                z = int(rng.zipf(1.3)) % content_span
                out.append(MARKUP_IDS + z)
        tokens = np.asarray(out[:n_tokens], dtype=np.int32)
    elif kind == "non-web":
        z = rng.zipf(1.3, size=n_tokens).astype(np.int64) % content_span
        tokens = (MARKUP_IDS + z).astype(np.int32)
    else:
        raise ValueError(f"unknown corpus kind {kind!r}")
    return tokens, word_len(tokens)


#: one HDFS block of job input (the paper's 128 MB blocks,
#: ``repro.sim.workloads.BLOCK_MB``)
BLOCK_BYTES = 128 << 20
#: token slots of one block: the non-web corpus averages ~6.44 B per
#: token, so 128 MiB fills ~20.8M slots and the rest are padding
BLOCK_TOKENS = 20 << 20


def block(seed: int, n_slots: int = BLOCK_TOKENS,
          n_bytes: int = BLOCK_BYTES) -> Tuple[np.ndarray, np.ndarray]:
    """One input block of the non-web corpus: the longest run of tokens
    whose byte lengths sum to at most ``n_bytes``, padded to ``n_slots``
    with token -1 and length 0, which every map function skips."""
    tokens, lengths = corpus("non-web", n_slots, seed=seed)
    ends = np.cumsum(lengths, dtype=np.int64)
    if ends[-1] < n_bytes:
        raise ValueError(f"{n_slots} token slots hold only {ends[-1]} "
                         f"bytes, short of {n_bytes}")
    n = int(np.searchsorted(ends, n_bytes, side="right"))
    tokens[n:] = -1
    lengths[n:] = 0
    return tokens, lengths
