"""Multi-device semantics (8 fake CPU devices in a subprocess, because
device count locks at first jax init): shard_map collectives, the
hierarchical psum equivalence, the two-hop all_to_all, the mesh
mapreduce engine (and, on 4 devices, the jitted mesh path against the
benchmark's shuffle reference), and a tiny sharded train-step
lowering."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def test_hierarchical_psum_equals_flat():
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.sharding.collectives import hierarchical_psum, flat_psum
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
    h = jax.shard_map(partial(hierarchical_psum, data_axis="data",
                          pod_axis="pod"),
                  mesh=mesh, in_specs=P("model"), out_specs=P("model"),
                  check_vma=False)(x)
    f = jax.shard_map(partial(flat_psum, data_axis="data", pod_axis="pod"),
                  mesh=mesh, in_specs=P("model"), out_specs=P("model"),
                  check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(h), np.asarray(f), rtol=1e-6)
    print("PSUM_OK")
    """)
    assert "PSUM_OK" in out


def test_two_hop_all_to_all_matches_flat():
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.sharding.collectives import two_hop_all_to_all
    mesh = jax.make_mesh((2, 4), ("pod", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    # global input: (8 ranks) x (8 dest-chunks) x payload
    x = jnp.arange(8 * 8 * 3, dtype=jnp.float32).reshape(8, 8, 3)

    def flat(xl):
        return jax.lax.all_to_all(xl[0], ("pod", "model"), split_axis=0,
                                  concat_axis=0, tiled=True)[None]

    def hier(xl):
        return two_hop_all_to_all(xl[0], pod_axis="pod",
                                  inner_axis="model")[None]

    spec = P(("pod", "model"))
    a = jax.shard_map(flat, mesh=mesh, in_specs=spec, out_specs=spec)(x)
    b = jax.shard_map(hier, mesh=mesh, in_specs=spec, out_specs=spec)(x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    print("A2A_OK")
    """)
    assert "A2A_OK" in out


def test_mesh_mapreduce_matches_local():
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.mapreduce import JOBS, corpus, local_mapreduce, mesh_mapreduce
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    spec = JOBS["WC"]
    toks, lens = [], []
    for s in range(8):
        t, l = corpus("non-web", 512, seed=s)
        toks.append(t); lens.append(l)
    toks = jnp.asarray(np.stack(toks)); lens = jnp.asarray(np.stack(lens))
    uk, uv, n, dropped = mesh_mapreduce(spec, toks, lens, mesh,
                                        shuffle_axes=("data",))
    assert int(dropped.sum()) == 0
    got = {}
    for d in range(8):
        for kk, vv in zip(np.asarray(uk[d]), np.asarray(uv[d])):
            if kk != 0xFFFFFFFF:
                got[int(kk)] = got.get(int(kk), 0) + int(vv)
    import collections
    expect = collections.Counter()
    for row in np.asarray(toks):
        expect.update(int(x) for x in row if x >= 0)
    assert got == dict(expect), (len(got), len(expect))
    print("MR_OK")
    """)
    assert "MR_OK" in out


@pytest.mark.parametrize("job", ["WC", "SC"])
@pytest.mark.parametrize("shuffle", [("pod", "data"), ("data",)])
def test_jitted_mesh_mapreduce_matches_the_shuffle_reference(job, shuffle):
    """On a 2x2 (pod, data) mesh, 2 blocks of 64 Ki slots per chip: every
    reducer holds exactly what ``bench/reference/shuffle.py`` says, none
    drops a record, and a second call compiles and loads nothing."""
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
    want = shuffle.reducer_outputs({job!r}, np.asarray(tok), lay)
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


def test_tiny_sharded_train_step_executes():
    """Not just lowering: run a real sharded train step on 8 devices."""
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.common import axes_tree, shape_tree
    from repro.sharding import DEFAULT_RULES, tree_shardings, use_rules
    from repro.train import TrainConfig, adamw_init, make_train_step
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("qwen3-4b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    specs = model.param_specs()
    psh = tree_shardings(mesh, DEFAULT_RULES, axes_tree(specs),
                         shape_tree(specs))
    params = jax.device_put(params, psh)
    opt = adamw_init(params)
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, (8, 32)), jnp.int32)}
    step = make_train_step(model, TrainConfig(n_micro=2))
    with use_rules(mesh, DEFAULT_RULES):
        fn = jax.jit(step, in_shardings=(psh, None, None))
        p2, o2, m = fn(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    print("TRAIN_OK", float(m["loss"]))
    """)
    assert "TRAIN_OK" in out


def test_moe_ep_matches_dense_dispatch():
    """Expert-parallel shard_map dispatch == sort-based dense dispatch
    (high capacity factor -> no drops on either path)."""
    out = run_sub("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.configs import get_config
    from repro.models.moe import moe_ffn
    from repro.models.moe_ep import moe_ffn_ep
    from repro.models.common import init_tree, ParamSpec
    from repro.sharding import DEFAULT_RULES, use_rules
    from repro.models.moe import moe_specs

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("dbrx-132b").smoke().scaled(
        n_experts=8, moe_topk=2, capacity_factor=8.0)
    specs = moe_specs(cfg, 1)
    p = init_tree(jax.random.PRNGKey(0), specs)
    p = {k: v[0] for k, v in p.items()}   # drop the layer dim
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, cfg.d_model),
                    jnp.float32)
    y_dense, aux_d = moe_ffn(cfg, p, x)
    with use_rules(mesh, DEFAULT_RULES):
        y_ep, aux_e = jax.jit(lambda pp, xx: moe_ffn_ep(cfg, pp, xx))(p, x)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_ep),
                               atol=2e-4, rtol=1e-3)
    # aux: EP uses the per-device Switch estimator (standard for EP);
    # same ballpark as the global estimate, not bit-equal
    assert abs(float(aux_d) - float(aux_e)) < 0.5
    print("MOE_EP_OK")
    """)
    assert "MOE_EP_OK" in out
