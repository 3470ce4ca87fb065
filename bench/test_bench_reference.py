"""The frozen reference: it imports nothing of the program, and agrees
with the program's own oracle."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import spec


def test_reference_imports_nothing_of_the_program():
    code = ("import sys, json\n"
            "import numpy as np\n"
            "from bench.reference.mapreduce import emission, reduce_counts\n"
            "t = np.arange(64, 1064, dtype=np.int32) % 100 + 64\n"
            "for job in ('WC', 'SC', 'II', 'Grep', 'Permu'):\n"
            "    reduce_counts(*emission(job, t))\n"
            "print(json.dumps(sorted(n for n in sys.modules "
            "if n == 'repro' or n.startswith('repro.'))))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


@pytest.mark.parametrize("job", ["WC", "SC", "II", "Grep", "Permu"])
def test_mapreduce_reference_equals_the_programs_oracle(job):
    from bench.reference.mapreduce import emission, reduce_counts
    from repro.mapreduce import reference as oracle
    from repro.mapreduce.jobs import block
    tokens, _ = block(3, n_slots=1 << 15, n_bytes=1 << 17)
    keys, value = emission(job, tokens)
    want_keys, want_vals = oracle.emission(job, tokens)
    assert np.array_equal(keys, want_keys)
    assert np.all(want_vals == value)
    uk, counts = reduce_counts(keys, value)
    ok, oc = oracle.reduce_counts(want_keys, want_vals)
    assert np.array_equal(uk, ok) and np.array_equal(counts, oc)


@pytest.mark.parametrize("job", ["WC", "SC", "II", "Grep", "Permu"])
def test_reference_equals_the_oracle_on_the_benchmark_corpus(job):
    """The same on a block of the benchmark's own corpus: a Zipf
    vocabulary far wider than the program's test blocks."""
    from bench.corpus import block_key, make_blocks
    from bench.reference.mapreduce import emission, reduce_counts
    from repro.mapreduce import reference as oracle
    corpus = spec.resolve(spec.load_benchmark(),
                          "mr-puma5-1chip").config["corpus"]
    tok, _, _ = make_blocks([block_key(2 ** 40 + 3, 0)],
                            {"slots": 1 << 15, "block_bytes": 1 << 17},
                            corpus)
    tokens = np.asarray(tok[0])
    keys, value = emission(job, tokens)
    want_keys, want_vals = oracle.emission(job, tokens)
    assert np.array_equal(keys, want_keys)
    assert np.all(want_vals == value)
    uk, counts = reduce_counts(keys, value)
    ok, oc = oracle.reduce_counts(want_keys, want_vals)
    assert np.array_equal(uk, ok) and np.array_equal(counts, oc)
