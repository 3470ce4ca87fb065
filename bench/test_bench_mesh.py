"""The mesh cell on four virtual CPU devices at a tiny size: the driver's
record, the faults its check must catch, the lower-precision control,
the all-to-all time read from a trace, and the shuffle reference tied to
the plain job.

The device count is fixed when JAX starts, so the driver runs in one
subprocess with four host devices, clean and with each planted fault,
and the tests read what it printed."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import spec
from bench.test_bench_drivers import SEED
from bench.tiny import tiny_cell

CELL = "mr-wc-2x2-crosspod"
#: each fault planted under the driver, around ``mesh_mapreduce``:
#: (keys, counts, n_unique, dropped) -> what the faulty program returns
FAULTS = {
    "count_altered": "lambda k, v, n, d: (k, v.at[0, 0].add(1), n, d)",
    "drop_reported": "lambda k, v, n, d: (k, v, n, d.at[0].add(1))",
    "reducer_left_out": "lambda k, v, n, d: (k[:3], v[:3], n[:3], d[:3])",
}
#: a fault in the input the program is handed: one record lost unseen
LOST = "record_lost"

SCRIPT = """
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
import jax
from bench.tiny import tiny_cell
from bench.window import Window
from repro.mapreduce import engine

faults = {faults}
real = engine.mesh_mapreduce


def planted(name):
    if name == {lost!r}:
        return lambda spec, t, l, *a, **k: real(
            spec, t.at[0, 0].set(-1), l.at[0, 0].set(0), *a, **k)
    if name:
        return lambda *a, **k: faults[name](*real(*a, **k))
    return real


out = {{}}
for name in [None, {lost!r}] + list(faults):
    engine.mesh_mapreduce = planted(name)
    cell = tiny_cell({cell!r})
    rec = cell.driver(cell, seed={seed}, seconds=0.01, window=Window(),
                      devices=jax.devices()[:4],
                      t_start=time.perf_counter())
    out[name or "clean"] = {{k: rec[k] for k in (
        "correct", "checks", "counters", "e2e", "attempted")}}
print("RECORDS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def records():
    code = SCRIPT.format(root=spec.ROOT, cell=CELL, seed=SEED, lost=LOST,
                         faults="{" + ", ".join(
                             f"{k!r}: {v}" for k, v in FAULTS.items())
                         + "}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         cwd=spec.ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines()
            if x.startswith("RECORDS ")][-1]
    return json.loads(line[len("RECORDS "):])


def test_mr_mesh_record(records):
    rec = records["clean"]
    c = rec["counters"]
    assert rec["correct"], rec["checks"]
    assert {x["name"] for x in rec["checks"]} == {
        "outputs_differing", "worst_unique_gap", "records_dropped"}
    assert c["jobs"] == rec["attempted"] == 2
    assert c["blocks"] == 8 * c["jobs"]
    assert c["programs_in_window"] == {"compiled": 0, "loaded": 0}
    assert c["records_dropped"] == 0
    assert c["least_bytes"] > 8 * c["valid_tokens"]
    # WordCount emits one record per valid token; of four chips, each
    # keeps about a quarter, and half go to the other pod
    records_out = c["shuffle_least_bytes"] / 8
    assert 0.6 < records_out / c["valid_tokens"] < 0.9
    assert 0.4 < c["cross_pod_least_bytes"] / 8 / c["valid_tokens"] < 0.6
    assert c["shuffle_sent_bytes"] > c["shuffle_least_bytes"]
    assert rec["e2e"]["mr_input_records_per_s"] > 0
    assert rec["e2e"]["mr_job_p50_ms"] > 0
    # the readers: the cell's five per-layer metrics, and no other
    cell = tiny_cell(CELL)
    tr = {"busy_s": 0.01, "busy_s_total": 0.04, "window_s": 0.02,
          "idle_pct": [50.0] * 4, "devices": [{"all_to_all_s": 0.0}] * 4}
    counters = dict(c, all_to_all_s=[0.001] * 4)
    got = spec.read_per_layer(cell, dict(rec, counters=counters, trace=tr,
                                         peaks={"hbm_bytes_per_s": 819e9,
                                                "ici_bits_per_s": 1600e9}))
    assert set(got) == {"mr.device_ms_per_block", "mr_job_roofline",
                        "device_idle_pct.mr", "mr.shuffle_ms_per_block",
                        "mr_shuffle_ici_roofline"}
    assert got["mr.shuffle_ms_per_block"]["value"] == pytest.approx(
        1e3 * 0.004 / c["blocks"])
    assert got["mr_shuffle_ici_roofline"]["value"] == pytest.approx(
        100 * c["shuffle_least_bytes"] / 200e9 / 0.004)
    # an untraced record, or a trace with no all-to-all, reads nothing
    untraced = spec.read_per_layer(cell, rec)
    assert not {"mr.shuffle_ms_per_block",
                "mr_shuffle_ici_roofline"} & set(untraced)


@pytest.mark.parametrize("fault", [LOST] + list(FAULTS))
def test_mr_mesh_planted_fault_reads_not_correct(records, fault):
    rec = records[fault]
    assert rec["correct"] is False
    assert any(not c["ok"] for c in rec["checks"])


def test_mr_mesh_stops_at_once_without_the_jitted_program(monkeypatch):
    """A program whose mesh path is not one jitted program cannot run the
    cell: the driver stops before it makes a block."""
    import jax
    from bench.drivers import mr_mesh
    from bench.window import Window
    from repro.mapreduce import engine
    monkeypatch.delattr(engine, "_mesh_mapreduce")
    monkeypatch.setattr(mr_mesh, "make_blocks", None)
    cell = tiny_cell(CELL)
    with pytest.raises(RuntimeError, match="no jitted mesh entry"):
        cell.driver(cell, seed=SEED, seconds=0.01, window=Window(),
                    devices=jax.devices()[:1], t_start=0.0)


def test_mr_mesh_int16_count_control_fails():
    """The reference counting in int16 in place of the program: at 1 Mi
    slots a block, the commonest word is seen past int16's range."""
    from bench.drivers import mr_mesh
    cell = tiny_cell(CELL)
    cell.config = dict(cell.config,
                       block={"slots": 1 << 20, "block_bytes": 4 << 20})
    checks = {c["name"]: c for c in mr_mesh.control(cell, SEED)}
    assert not checks["outputs_differing"]["ok"]
    assert checks["records_dropped"]["ok"]   # not by each limit


def test_all_to_all_time_is_read_from_the_window():
    """Each chip's all-to-all operations, by either of XLA's names,
    clipped to the window; other operations and host planes do not
    count."""
    from bench.drivers.mr_mesh import all_to_all
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench.window", 1000.0, 10000.0], ["bench.job", 1100.0, 500.0],
        ["all_to_all.1", 2000.0, 100.0]]}]}

    def chip(i, events):
        return {"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": [["jit_x", 0.0, 20000.0]]},
            {"name": "XLA Ops", "events": events}]}
    tr = {"planes": [host,
                     chip(0, [["all_to_all.13", 2000.0, 3000.0],
                              ["fusion.1", 5000.0, 4000.0]]),
                     chip(1, [["%all-to-all.2 = u32[4] all-to-all(x)",
                               500.0, 1000.0],
                              ["all_to_all.15", 10500.0, 1000.0]])]}
    got = all_to_all(tr)
    assert got["all_to_all_s"] == pytest.approx([3e-6, 1e-6])
    assert got["all_to_all_ops"] == ["all-to-all.2", "all_to_all.13",
                                     "all_to_all.15"]
    assert all_to_all({"planes": [chip(0, [])]}) == {}


@pytest.mark.parametrize("shuffle", [("pod", "data"), ("data",)])
def test_reducers_add_up_to_the_plain_job(shuffle):
    """Every record is owned by one reducer of its group: the reducers'
    outputs over all groups, merged, are the whole job's reduce."""
    from bench.corpus import block_key, make_blocks
    from bench.reference import shuffle as ref
    from bench.reference.mapreduce import emission, reduce_counts
    cell = tiny_cell(CELL)
    tok, _, _ = make_blocks([block_key(SEED, b) for b in range(8)],
                            {"slots": 1 << 14, "block_bytes": 1 << 16},
                            cell.config["corpus"])
    tok = np.asarray(tok)
    lay = ref.Layout((2, 2), ("pod", "data"), shuffle, 2)
    out = ref.reducer_outputs("WC", tok, lay)
    keys = np.concatenate([k for k, _ in out])
    counts = np.concatenate([c for _, c in out])
    order = np.argsort(keys, kind="stable")
    keys, counts = keys[order], counts[order]
    if shuffle == ("pod", "data"):
        uk, uc = keys, counts   # each key has one owner over the mesh
        assert len(np.unique(keys)) == len(keys)
    else:   # one owner per pod: the two pods' counts add up
        uk, first = np.unique(keys, return_index=True)
        uc = np.add.reduceat(counts, first)
    want = reduce_counts(*emission("WC", tok.reshape(-1)))
    assert np.array_equal(uk, want[0]) and np.array_equal(uc, want[1])
    least = ref.least_bytes("WC", tok, lay)
    if shuffle == ("data",):
        assert least["cross_pod"] == 0 < least["off_chip"]
    else:
        assert 0 < least["cross_pod"] < least["off_chip"]


def test_shuffle_reference_imports_nothing_of_the_program():
    code = ("import sys, json\n"
            "import numpy as np\n"
            "from bench.reference import shuffle\n"
            "t = (np.arange(64, 1088, dtype=np.int32) % 100 + 64)"
            ".reshape(4, 256)\n"
            "lay = shuffle.Layout((2, 2), ('pod', 'data'), ('pod', 'data'),"
            " 1)\n"
            "shuffle.reducer_outputs('WC', t, lay)\n"
            "shuffle.least_bytes('WC', t, lay)\n"
            "print(json.dumps(sorted(n for n in sys.modules "
            "if n == 'repro' or n.startswith('repro.'))))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []
