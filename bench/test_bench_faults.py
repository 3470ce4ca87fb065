"""The check that decides ``correct`` catches a broken timed path, and
catches the lower-precision control, at a size a CPU test runs.

Each fault is planted underneath a driver that otherwise runs as in a
benchmark run (only the chip check is skipped): an answer altered where
it is produced, half of the work left out, and a step that returns stale
state."""
import numpy as np

from bench import control
from bench.test_bench_drivers import run_driver, SEED
from bench.tiny import tiny_cell


def _failed(rec) -> bool:
    return rec["correct"] is False and any(not c["ok"]
                                           for c in rec["checks"])


def _wrap_local(monkeypatch, fn):
    from repro.mapreduce import engine
    monkeypatch.setattr(engine, "local_mapreduce",
                        fn(engine.local_mapreduce))


def test_mr_count_altered(monkeypatch):
    _wrap_local(monkeypatch, lambda f: lambda spec, t, l: (
        lambda k, v, n: (k, v.at[0].add(1), n))(*f(spec, t, l)))
    assert _failed(run_driver("mr-puma5-1chip")[1])


def test_mr_half_the_block_left_out(monkeypatch):
    def half(f):
        def g(spec, t, l):
            cut = t.shape[0] // 2
            return f(spec, t.at[cut:].set(-1), l.at[cut:].set(0))
        return g
    _wrap_local(monkeypatch, half)
    assert _failed(run_driver("mr-puma5-1chip")[1])


def test_mr_step_returns_stale_state(monkeypatch):
    def stale(f):
        first = []

        def g(spec, t, l):
            if not first:
                first.append(f(spec, t, l))
            return first[0]
        return g
    _wrap_local(monkeypatch, stale)
    assert _failed(run_driver("mr-puma5-1chip")[1])


def test_mr_int16_count_control_fails():
    from bench.corpus import block_key, make_blocks
    cell = tiny_cell("mr-puma5-1chip")
    # 4 MiB: the commonest word is seen some 46,000 times, past int16
    tok, _, _ = make_blocks([block_key(SEED, 0)],
                            {"slots": 1 << 20, "block_bytes": 4 << 20},
                            cell.config["corpus"])
    checks = control.mr_local_control(np.asarray(tok), ["WC", "Grep"])
    assert not all(c["ok"] for c in checks)
