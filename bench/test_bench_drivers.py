"""Each driver called directly on the CPU at a tiny size: a run record
with what the metric readers read, and ``correct`` true; and the corpus
its blocks are drawn from."""
import time

from bench import spec
from bench.tiny import tiny_cell
from bench.window import Window

SEED = 2 ** 33 + 12345


def run_driver(name: str, cell=None, seed: int = SEED):
    import jax
    cell = cell or tiny_cell(name)
    return cell, cell.driver(cell, seed=seed, seconds=0.01, window=Window(),
                             devices=jax.devices()[:1],
                             t_start=time.perf_counter())


def test_mr_local_record():
    cell, rec = run_driver("mr-puma5-1chip")
    c = rec["counters"]
    assert rec["correct"], rec["checks"]
    assert c["jobs"] == rec["attempted"] == 5
    assert c["programs_in_window"] == {"compiled": 0, "loaded": 0}
    assert c["least_bytes"] > 8 * c["valid_tokens"]
    assert rec["e2e"]["mr_input_records_per_s"] > 0
    assert rec["e2e"]["mr_job_p50_ms"] > 0
    tr = {"busy_s": 0.01, "busy_s_total": 0.01, "window_s": 0.02,
          "idle_pct": [50.0], "devices": [{"all_to_all_s": 0.0}]}
    got = spec.read_per_layer(cell, dict(rec, trace=tr, peaks={
        "hbm_bytes_per_s": 819e9}))
    assert set(got) == {"mr.device_ms_per_block", "mr_job_roofline",
                        "device_idle_pct.mr"}


def test_setup_is_timed_by_phase():
    _, rec = run_driver("mr-puma5-1chip")
    phases = rec["setup_phases"]
    assert list(phases) == ["blocks_s"] + [
        f"warm_{j}_s" for j in ("WC", "SC", "II", "Grep", "Permu")]
    assert all(v > 0 for v in phases.values())
    assert sum(phases.values()) < rec["setup_s"]


BLOCK = {"slots": 1 << 16, "block_bytes": 300_000}
CORPUS = {"markup_ids": 64, "words": 5000, "zipf_s": 1.0}


def test_same_seed_same_blocks():
    import numpy as np
    from bench.corpus import block_key, make_blocks
    block = {"slots": 4096, "block_bytes": 16384}
    a = make_blocks([block_key(SEED, 0), block_key(SEED, 1)], block, CORPUS)
    b = make_blocks([block_key(SEED, 0), block_key(SEED, 1)], block, CORPUS)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0][0]), np.asarray(a[0][1]))


def test_blocks_follow_the_corpus_law():
    """Tokens follow Zipf's law over the content words, each with its
    one byte length, cut to the longest run that fits the block's
    bytes."""
    import numpy as np
    from bench.corpus import block_key, make_blocks, zipf_pmf
    tok, lng, n = make_blocks([block_key(SEED, 0)], BLOCK, CORPUS)
    tok, lng, n = np.asarray(tok[0]), np.asarray(lng[0]), int(n[0])
    assert (tok[:n] >= 64).all() and (tok[n:] == -1).all()
    assert (lng[n:] == 0).all()
    h = (tok[:n].astype(np.uint64) * 2654435761) % (1 << 32)
    assert np.array_equal(lng[:n], 2 + h % 12)
    assert lng.sum() <= 300_000 < lng.sum() + 2 + 12
    freq = np.bincount(tok[:n] - 64, minlength=5000) / n
    pmf = zipf_pmf(1.0, 5000)
    assert np.abs(freq[:8] - pmf[:8]).max() < 0.01


def test_the_vocabulary_follows_heaps_law():
    """The configuration's word count is the Zipf vocabulary whose
    expected distinct words in one block equal Heaps' law at the block's
    expected token count, and a block's slots hold its bytes."""
    import numpy as np
    from bench.corpus import zipf_pmf
    cfg = spec.resolve(spec.load_benchmark(), "mr-puma5-1chip").config
    corpus, block = cfg["corpus"], cfg["block"]
    p = zipf_pmf(corpus["zipf_s"], corpus["words"])
    ids = (corpus["markup_ids"] + np.arange(len(p))).astype(np.uint64)
    word_bytes = 2 + (ids * 2654435761) % (1 << 32) % 12
    tokens = block["block_bytes"] / (p * word_bytes).sum()
    heaps = corpus["heaps_k"] * tokens ** corpus["heaps_b"]
    distinct = -np.expm1(tokens * np.log1p(-p)).sum()
    assert abs(distinct - heaps) < 2
    assert tokens < 0.9 * block["slots"]
