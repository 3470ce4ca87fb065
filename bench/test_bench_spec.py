"""BENCHMARK.json against the benchmark's contract, and every cell found
by name: its configuration, traffic mix, driver and metric readers."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(BENCH) == KEYS
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "-m", "bench.run"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time: 2 + 14 runs per cell, each
    # run_seconds + 60, plus 2 x 90 s of compile per cell and 1200 spare
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    with open(os.path.join(spec.ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_entries_have_only_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_texts(kind):
    entries = BENCH[kind]
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert spec.NAME_RE.match(e["name"]), e["name"]
        for key in ("config", "traffic"):
            if key in e:
                assert spec.NAME_RE.match(e[key])
        for key in ("why", "layer", "source"):
            if key in e:
                assert _one_line(e[key]), (e["name"], key)
        if "unit" in e:
            assert spec.UNIT_RE.match(e["unit"]) and len(e["unit"]) <= 16
            assert e["better"] in ("lower", "higher")
        for r in e.get("reduced", []):
            assert spec.NAME_RE.match(r)
        assert len(e.get("reduced", [])) <= 16


def test_metric_sources_and_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_configs_are_files_of_their_own_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["name"] in used
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            body = json.load(f)
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert body["source"] in c["source"]


def test_cells_and_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert CELLS == ["mr-puma5-1chip"]
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = spec.resolve(BENCH, cell)
    assert callable(c.driver)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        # every per-layer metric's cell reports what it moves
        assert m["moves"] in e2e
        assert callable(spec.load_reader(m["name"]))


def test_moves_name_one_end_to_end_metric_and_layers_agree():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert spec.reports(target, cell), (m["name"], cell)
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for layer in layers:
        assert layer == layer.strip()


def test_a_new_cell_and_metric_resolve_as_new_files(tmp_path):
    """A later change adds a traffic mix, a cell and a metric as new files
    and entries; the harness finds them with no other file edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in (root / "bench").rglob("*") if x.is_file())}
    (root / "bench/traffic/permu-only.json").write_text(json.dumps(
        {"why": "dummy", "jobs": ["Permu"], "blocks": 1,
         "latency_metric": "mr_job_p50_ms", "latency_percentile": 50}))
    (root / "bench/metrics/dummy.jobs.py").write_text(
        "def read(record):\n    return record['counters'].get('jobs')\n")
    bench["workloads"].append({"name": "mr-dummy", "config": "puma5-128m",
                               "traffic": "permu-only", "chips": 1,
                               "why": "dummy"})
    bench["per_layer"].append({"name": "dummy.jobs", "unit": "jobs",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["mr-dummy"]})
    cell = spec.resolve(bench, "mr-dummy", str(root / "bench"))
    assert cell.traffic["jobs"] == ["Permu"]
    assert [m["name"] for m in cell.per_layer] == ["dummy.jobs"]
    got = spec.read_per_layer(cell, {"counters": {"jobs": 3}},
                              str(root / "bench"))
    assert got == {"dummy.jobs": {"value": 3.0, "unit": "jobs"}}
    for p, body in before.items():
        assert open(p, "rb").read() == body


def test_roofline_bytes_come_from_the_reference_records():
    """``mr_job_roofline`` counts the least bytes from the reference's
    records: padding the block with empty slots changes nothing."""
    from bench import mrcheck
    from bench.reference.mapreduce import emission
    rng = np.random.default_rng(5)
    tokens = (64 + rng.zipf(1.3, 5000) % 4032).astype(np.int32)
    padded = np.concatenate([tokens, np.full(3000, -1, np.int32)])
    for job in ("WC", "SC", "Permu"):
        a = mrcheck.reference(job, tokens)
        b = mrcheck.reference(job, padded)
        keys, _ = emission(job, tokens)
        want = 8 * len(tokens) + 16 * len(keys) + 8 * len(np.unique(keys))
        for uk, _, emitted in (a, b):
            assert mrcheck.least_bytes(len(tokens), emitted,
                                       len(uk)) == want


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_without_a_tpu():
    out = _command(spec.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_the_command_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(spec.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    out = _command(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
