"""MapReduce input blocks made on the device from a seed.

A block is English-like plain text for the paper's PUMA jobs (arXiv
1808.08040): content words drawn by Zipf's law over a vocabulary whose
size the configuration takes from Heaps' law, each word with the
deterministic byte length of ``repro.mapreduce.jobs.word_len``, cut to
the longest run of tokens whose bytes fit one HDFS block and padded with
token -1.

Tokens are drawn by the alias method over the exact Zipf probabilities
(one table lookup per slot), so a 20 Mi-slot block is a few milliseconds
of one jitted call.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np


def zipf_pmf(s: float, words: int) -> np.ndarray:
    """Zipf's law over ``words`` ranks: rank ``r`` (from 1) has
    probability ``r**-s / sum_k k**-s``."""
    p = np.arange(1, words + 1, dtype=np.float64) ** -float(s)
    return p / p.sum()


def alias_table(p: np.ndarray):
    """Vose's alias table of ``p``: draw ``i`` uniformly, keep it with
    probability ``keep[i]``, else take ``alias[i]``."""
    n = len(p)
    scaled = p * n
    keep = np.ones(n)
    alias = np.arange(n)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        keep[s], alias[s] = scaled[s], g
        scaled[g] -= 1.0 - scaled[s]
        (small if scaled[g] < 1.0 else large).append(g)
    return keep, alias


def block_key(seed: int, index: int) -> int:
    """32-bit generator seed of block ``index`` of a run seeded ``seed``."""
    return int(np.random.SeedSequence([int(seed), int(index)])
               .generate_state(1)[0])


@lru_cache(maxsize=4)
def _generator(slots: int, nbytes: int, markup: int, words: int,
               zipf_s: float, sharding):
    import jax
    import jax.numpy as jnp

    keep, alias = alias_table(zipf_pmf(zipf_s, words))
    keep = jnp.asarray(keep, jnp.float32)
    alias = jnp.asarray(alias, jnp.int32)

    def one(k):
        ki, ku = jax.random.split(jax.random.key(k))
        i = jax.random.randint(ki, (slots,), 0, words, jnp.int32)
        u = jax.random.uniform(ku, (slots,), jnp.float32)
        r = jnp.where(u < keep[i], i, alias[i])
        tok = (markup + r).astype(jnp.int32)
        h = tok.astype(jnp.uint32) * jnp.uint32(2654435761)
        length = (2 + h % 12).astype(jnp.int32)   # a content word's
        ends = jnp.cumsum(length)
        fits = ends <= nbytes
        return (jnp.where(fits, tok, -1), jnp.where(fits, length, 0),
                jnp.sum(fits.astype(jnp.int32)), ends[-1])

    return jax.jit(jax.vmap(one), out_shardings=(
        None if sharding is None else (sharding,) * 4))


def make_blocks(keys, block: dict, corpus: dict, sharding=None):
    """Blocks ``(tokens, lengths, n_valid)`` for each 32-bit key, stacked
    on a leading axis and made in one jitted call on the device (placed by
    ``sharding`` when given). ``block`` gives ``slots`` and
    ``block_bytes``; ``corpus`` gives ``markup_ids``, ``words`` and
    ``zipf_s``."""
    import jax.numpy as jnp
    nbytes = int(block["block_bytes"])
    fn = _generator(int(block["slots"]), nbytes,
                    int(corpus["markup_ids"]), int(corpus["words"]),
                    float(corpus["zipf_s"]), sharding)
    tok, lng, n, total = fn(jnp.asarray(np.asarray(keys, np.uint32)))
    total = np.asarray(total)
    if (total < nbytes).any():
        raise ValueError(f"{block['slots']} token slots hold only "
                         f"{total.min()} bytes, short of {nbytes}")
    return tok, lng, np.asarray(n)
