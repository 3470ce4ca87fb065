"""JAX MapReduce engine vs a Python-dict oracle + FP measurements
(reproducing the paper's Figs. 1-2 qualitative structure)."""
import collections

import jax.numpy as jnp
import numpy as np
import pytest

from repro.mapreduce import JOBS, corpus, local_mapreduce, measure_fp
from repro.mapreduce.engine import _sort_reduce
from repro.mapreduce.jobs import EMPTY, block, word_len
from repro.mapreduce.reference import emission, reduce_counts


def python_wordcount(tokens):
    c = collections.Counter(int(t) for t in tokens if t >= 0)
    return c


def test_wordcount_matches_python_oracle():
    tok, lng = corpus("non-web", 2048, seed=1)
    k, v, n = local_mapreduce(JOBS["WC"], jnp.asarray(tok),
                              jnp.asarray(lng))
    got = {int(kk): int(vv) for kk, vv in zip(np.asarray(k), np.asarray(v))
           if kk != EMPTY}
    expect = python_wordcount(tok)
    assert got == dict(expect)
    assert int(n) == len(expect)


def test_grep_counts_pattern_occurrences():
    from repro.mapreduce.jobs import grep_map_factory, MapReduceSpec
    tok, lng = corpus("web", 1024, seed=2)
    pattern = int(tok[10])
    spec = MapReduceSpec("Grep", grep_map_factory(pattern), 1, False)
    k, v, n = local_mapreduce(spec, jnp.asarray(tok), jnp.asarray(lng))
    assert int(v.sum()) == int((tok == pattern).sum())


def test_fp_depends_on_input_type():
    """Paper Figs. 1-2: FP of a benchmark differs by input type, and Grep
    FP << WC FP <= Permu FP ~= 3."""
    tok_w, lng_w = corpus("web", 8192, seed=3)
    tok_t, lng_t = corpus("non-web", 8192, seed=4)
    fps = {}
    for name in ("WC", "SC", "Grep", "Permu"):
        fw = float(measure_fp(JOBS[name], tok_w[None], lng_w[None])[0])
        ft = float(measure_fp(JOBS[name], tok_t[None], lng_t[None])[0])
        fps[name] = (fw, ft)
    assert fps["Grep"][0] < 0.2
    assert fps["Permu"][0] == pytest.approx(3.0, abs=0.2)
    assert fps["Permu"][1] == pytest.approx(3.0, abs=0.2)
    # web vs non-web FP differs markedly for WC (markup length effect)
    assert abs(fps["WC"][0] - fps["WC"][1]) > 0.1


def test_fp_stable_across_shards():
    """Paper §4.1: per-shard FP std is small relative to the mean for a
    fixed input type -> the averaged-FP reduction (Eq. 2) is sound."""
    shards_t, shards_l = [], []
    for s in range(8):
        t, l = corpus("web", 4096, seed=100 + s)
        shards_t.append(t)
        shards_l.append(l)
    fps = measure_fp(JOBS["WC"], np.stack(shards_t), np.stack(shards_l))
    assert float(np.std(fps)) < 0.15 * float(np.mean(fps))


#: measure_fp of SC and II on 3 shards of 2048 tokens (corpus seeds 40-42),
#: as the engine computed them with scatters in `_sort_reduce`
FROZEN_FP = {
    ("web", "II"): [1.1856039, 1.1868656, 1.1780405],
    ("web", "SC"): [0.5986814, 0.6095714, 0.57467616],
    ("non-web", "II"): [1.6170998, 1.6261082, 1.6320013],
    ("non-web", "SC"): [3.2737477, 3.2318864, 3.2383893],
}


@pytest.mark.parametrize("kind, name", sorted(FROZEN_FP))
def test_fp_is_frozen(kind, name):
    """FP equals the frozen values exactly. SC's map-side combiner is the
    one caller that keeps `_sort_reduce`'s combined bytes; II emits one
    record per occurrence and stands beside it."""
    shards = [corpus(kind, 2048, seed=s) for s in (40, 41, 42)]
    fps = measure_fp(JOBS[name], np.stack([t for t, _ in shards]),
                     np.stack([l for _, l in shards]))
    np.testing.assert_array_equal(
        fps, np.array(FROZEN_FP[kind, name], np.float32))


def test_word_len_deterministic_and_typed():
    ids = np.array([1, 1, 70, 70, 200], np.int32)
    l1, l2 = word_len(ids), word_len(ids)
    np.testing.assert_array_equal(l1, l2)
    assert l1[0] == l1[1]
    # markup ids are long on average (paper Table 2 vs Table 4)
    markup = word_len(np.arange(0, 64, dtype=np.int32)).mean()
    content = word_len(np.arange(64, 4096, dtype=np.int32)).mean()
    assert markup > content


@pytest.mark.parametrize("kind", ["web", "non-web"])
@pytest.mark.parametrize("name", sorted(JOBS))
def test_local_mapreduce_matches_numpy_reference(name, kind):
    """Every job's keys and counts equal the numpy oracle exactly; the
    slots past n_unique are empty."""
    tok, lng = corpus(kind, 4096, seed=5)
    k, v, n = local_mapreduce(JOBS[name], jnp.asarray(tok),
                              jnp.asarray(lng))
    k, v, n = np.asarray(k), np.asarray(v), int(n)
    keys, counts = reduce_counts(*emission(name, tok))
    assert n == len(keys) > 0
    np.testing.assert_array_equal(k[:n], keys)
    np.testing.assert_array_equal(v[:n].astype(np.int64), counts)
    assert np.all(k[n:] == EMPTY)


def _kv_slots(case, n=4096, seed=11):
    """Map-output slots as the map functions emit them: uint32 keys (EMPTY
    slots carry value 0 and 0 bytes), one byte size per key. ``wrap`` holds
    values near 2**30, so the running sum and each key's own sum overflow
    int32; the ``edges`` cases hold one valid slot, keys just below EMPTY
    against the EMPTY run, and one key in every slot."""
    rng = np.random.default_rng(seed)
    if case == "all_empty":
        keys = np.full(n, EMPTY, np.uint32)
    elif case == "distinct":
        keys = rng.permutation(np.arange(n, dtype=np.uint32) * 977 + 5)
    elif case == "edges-single":
        keys = np.full(n, EMPTY, np.uint32)
        keys[rng.integers(n)] = 977
    elif case == "edges-below-empty":
        keys = rng.choice(np.array([0, 5, EMPTY - 1, EMPTY], np.uint32), n)
    elif case == "edges-one-key":
        keys = np.full(n, 977, np.uint32)
    else:  # duplicates, EMPTY slots, and keys just below EMPTY
        pool = np.concatenate([rng.integers(0, 1 << 31, 200, np.uint32),
                               EMPTY - np.arange(1, 4, dtype=np.uint32)])
        keys = rng.choice(pool, n)
        keys[rng.random(n) < 0.3] = EMPTY
    valid = keys != EMPTY
    lo, hi = ((2**30 - 64, 2**30 + 64) if case == "wrap" else (-5, 100))
    values = np.where(valid, rng.integers(lo, hi, n), 0).astype(np.int32)
    nbytes = np.where(valid, keys % 11 + 2, 0).astype(np.int32)
    return keys, values, nbytes


@pytest.mark.parametrize("case", [
    "mixed", "distinct", "all_empty", "wrap", "edges-single",
    "edges-below-empty", "edges-one-key"])
@pytest.mark.parametrize("combined_bytes", [True, False])
def test_sort_reduce_matches_numpy(case, combined_bytes):
    """Unique keys ascending, each key's summed values and its output bytes
    (one representative kv, or the members' sum) equal numpy's exactly,
    int32 sums taken modulo 2**32; slots past n_unique hold EMPTY, 0 and
    0."""
    keys, values, nbytes = _kv_slots(case)
    k, v, b, n = (np.asarray(a) for a in _sort_reduce(
        jnp.asarray(keys), jnp.asarray(values), jnp.asarray(nbytes),
        combined_bytes=combined_bytes))
    valid = keys != EMPTY
    want_k, inverse = np.unique(keys[valid], return_inverse=True)

    def sums(x):
        total = np.zeros(len(want_k), np.int64)
        np.add.at(total, inverse, x[valid])
        return total.astype(np.int32)  # wraps modulo 2**32

    want_v = sums(values)
    want_b = want_k % 11 + 2 if combined_bytes else sums(nbytes)
    u = len(want_k)
    assert int(n) == u
    assert k.dtype == np.uint32 and v.dtype == b.dtype == np.int32
    np.testing.assert_array_equal(k[:u], want_k)
    np.testing.assert_array_equal(v[:u], want_v)
    np.testing.assert_array_equal(b[:u], want_b)
    assert np.all(k[u:] == EMPTY) and np.all(v[u:] == 0)
    assert np.all(b[u:] == 0)


def test_block_cuts_at_byte_budget_and_pads():
    tok, lng = block(3, n_slots=4096, n_bytes=20000)
    n = int((tok >= 0).sum())
    assert 0 < n < 4096
    assert np.all(tok[n:] == -1) and np.all(lng[n:] == 0)
    assert int(lng.sum()) <= 20000 < int(lng.sum()) + int(
        word_len(corpus("non-web", 4096, seed=3)[0][n:n + 1])[0])
    with pytest.raises(ValueError, match="short of"):
        block(3, n_slots=16, n_bytes=20000)
