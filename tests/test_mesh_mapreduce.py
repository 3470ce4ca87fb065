"""The mesh MapReduce engine on fake CPU devices (in a subprocess, because
the device count locks at first jax init): every job against the local
path on 8 devices, the jitted mesh path against the benchmark's shuffle
reference on a 2x2 (pod, data) mesh, and the pack's spill count when one
reducer's share outgrows its buffer."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

JOB_NAMES = ["WC", "SC", "II", "Grep", "Permu"]
SCOPES = [("pod", "data"), ("data",)]


def run_sub(code: str, devices: int = 8) -> str:
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n" +
            textwrap.dedent(code))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(REPO, "src"), REPO]))
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.parametrize("job", JOB_NAMES)
def test_mesh_mapreduce_matches_local(job):
    """8 shards of 512 tokens, one per device, shuffled over all 8: the
    reducers together hold each shard's ``local_mapreduce`` summed over
    the shards. II's value is the doc id, which the mesh path sets to the
    shard's device and the local path to 0, so II is checked as the sum
    of device x WordCount."""
    out = run_sub(f"""
    import collections
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.mapreduce import JOBS, corpus, local_mapreduce, mesh_mapreduce
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    job = {job!r}
    toks, lens = [], []
    for s in range(8):
        t, l = corpus("non-web", 512, seed=s)
        toks.append(t); lens.append(l)
    toks = jnp.asarray(np.stack(toks)); lens = jnp.asarray(np.stack(lens))
    uk, uv, n, dropped = mesh_mapreduce(JOBS[job], toks, lens, mesh,
                                        shuffle_axes=("data",))
    assert int(dropped.sum()) == 0
    got = {{}}
    for d in range(8):
        for kk, vv in zip(np.asarray(uk[d]), np.asarray(uv[d])):
            if kk != 0xFFFFFFFF:
                assert int(kk) % 8 == d, (kk, d)
                got[int(kk)] = got.get(int(kk), 0) + int(vv)

    def local(name, s):
        k, v, m = local_mapreduce(JOBS[name], toks[s], lens[s])
        m = int(m)
        return zip(np.asarray(k[:m]).tolist(), np.asarray(v[:m]).tolist())

    expect = collections.Counter()
    for s in range(8):
        for kk, vv in local("WC" if job == "II" else job, s):
            expect[kk] += s * vv if job == "II" else vv
    assert len(expect) > 0
    assert got == dict(expect), (len(got), len(expect))
    print("MR_OK")
    """)
    assert "MR_OK" in out


@pytest.mark.parametrize("job", JOB_NAMES)
@pytest.mark.parametrize("shuffle", SCOPES)
def test_jitted_mesh_mapreduce_matches_the_shuffle_reference(job, shuffle):
    """On a 2x2 (pod, data) mesh, 2 blocks of 64 Ki slots per chip: every
    reducer holds exactly what ``bench/reference/shuffle.py`` says, none
    drops a record, and a second call compiles and loads nothing. II's
    value is the doc id, the mapping device's number, where the reference
    counts a single block (doc id 0): its counts are weighed here."""
    out = run_sub(f"""
    import jax, numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from bench.corpus import block_key, make_blocks
    from bench.device import CompileCounter
    from bench.reference import shuffle
    from repro.mapreduce import JOBS, mesh_mapreduce
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("pod", "data"),
                axis_types=(AxisType.Auto,) * 2)
    corpus = {{"markup_ids": 64, "words": 5000, "zipf_s": 1.0}}
    tok, lng, _ = make_blocks(
        [block_key(2 ** 33 + 7, b) for b in range(8)],
        {{"slots": 1 << 16, "block_bytes": 1 << 18}}, corpus,
        NamedSharding(mesh, P(("pod", "data"))))
    args = (JOBS[{job!r}], tok, lng, mesh)
    kw = dict(shuffle_axes={shuffle!r}, shard_axes=("pod", "data"))
    uk, uv, n, dropped = jax.block_until_ready(mesh_mapreduce(*args, **kw))
    counter = CompileCounter()
    mark = counter.mark()
    jax.block_until_ready(mesh_mapreduce(*args, **kw))
    again = counter.since(mark)
    assert again == {{"compiled": 0, "loaded": 0}}, again
    assert int(np.asarray(dropped).sum()) == 0
    lay = shuffle.Layout((2, 2), ("pod", "data"), {shuffle!r}, 2)
    tokn = np.asarray(tok)
    want = shuffle.reducer_outputs({job!r}, tokn, lay)
    if {job!r} == "II":
        occ = [np.bincount(np.concatenate([tokn[b][tokn[b] >= 0]
                                           for b in lay.blocks(d)]),
                           minlength=1 << 14) for d in range(4)]
        want = [(wk, sum(d * occ[d][wk] for d in lay.members(g)))
                for g, (wk, _) in enumerate(want)]
    uk, uv, n = np.asarray(uk), np.asarray(uv), np.asarray(n)
    for g, (wk, wc) in enumerate(want):
        m = int(n[g])
        assert m == len(wk) > 0, (g, m, len(wk))
        assert np.array_equal(uk[g, :m], wk)
        assert np.array_equal(uv[g, :m].astype(np.int64), wc)
        assert (uk[g, m:] == 0xFFFFFFFF).all()
    print("MESH_OK")
    """, devices=4)
    assert "MESH_OK" in out


@pytest.mark.parametrize("job", ["WC", "Permu"])
@pytest.mark.parametrize("shuffle", SCOPES)
def test_mesh_mapreduce_drops_exactly_the_records_past_cap_dest(job, shuffle):
    """slack=1 on a 2x2 (pod, data) mesh, 2 blocks of 4 Ki tokens per
    chip, three in four tokens made multiples of 4 so that reducer 0 (it
    owns ``key % D == 0``) is sent more than its buffer holds. Each
    chip's ``dropped`` is, per destination, the records beyond its
    ``cap_dest`` slots, counted here from the map's emitted keys; what
    the reducers keep is the rest, each on its owner."""
    out = run_sub(f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, Mesh
    from bench.reference.mapreduce import emission
    from repro.mapreduce import JOBS, corpus, mesh_mapreduce
    from repro.mapreduce.jobs import word_len
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("pod", "data"),
                axis_types=(AxisType.Auto,) * 2)
    job, axes, S = {job!r}, {shuffle!r}, 4096
    rng = np.random.RandomState(5)
    toks = []
    for s in range(8):
        t, _ = corpus("non-web", S, seed=s)
        toks.append(np.where(rng.rand(S) < 0.75, 4 * t, t).astype(np.int32))
    toks = np.stack(toks)
    lens = word_len(toks)
    spec = JOBS[job]
    uk, uv, n, dropped = mesh_mapreduce(
        spec, jnp.asarray(toks), jnp.asarray(lens), mesh,
        shuffle_axes=axes, shard_axes=("pod", "data"), slack=1)
    D = 4 if len(axes) == 2 else 2
    cap_dest = -(-S * spec.cap_mult // D)
    dropped = np.asarray(dropped)
    emitted = 0
    for g in range(4):
        keys = np.concatenate([emission(job, toks[b])[0]
                               for b in (2 * g, 2 * g + 1)])
        emitted += len(keys)
        per = np.bincount(keys % D, minlength=D)
        assert per[0] > 2 * cap_dest, (g, per, cap_dest)
        assert int(dropped[g]) == int(np.maximum(per - 2 * cap_dest,
                                                 0).sum()), (g, per)
    uk, uv, n = np.asarray(uk), np.asarray(uv), np.asarray(n)
    kept = 0
    for g in range(4):
        m = int(n[g])
        assert (uk[g, :m] % D == g % D).all()
        kept += int(uv[g, :m].astype(np.int64).sum())
    assert kept == emitted - int(dropped.sum()) < emitted
    print("SPILL_OK")
    """, devices=4)
    assert "SPILL_OK" in out
