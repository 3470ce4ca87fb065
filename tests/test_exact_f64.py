"""Integer-only IEEE float64 arithmetic (``sweep/exact_f64.py``) against
numpy's float64, bit for bit, over magnitudes the fill sees and the
edges: integers, subnormals, results that round to subnormals, and ties
broken to even."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sweep import exact_f64 as xf

N = 50_000


def _values(rng, n):
    kind = rng.integers(0, 6, n)
    return np.select(
        [kind == 0, kind == 1, kind == 2, kind == 3, kind == 4],
        [rng.uniform(0.0, 1e10, n),
         rng.integers(1, 300, n).astype(float),
         np.exp(rng.uniform(-740.0, 700.0, n)),
         rng.uniform(0.0, 1.0, n) * 1.25e8 / rng.integers(1, 50, n),
         5e-324 * rng.integers(1, 1 << 40, n)],
        rng.uniform(1.0, 2.0, n))


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(7)
    a, b = _values(rng, N), _values(rng, N)
    return a, np.where(b == 0.0, 1.0, b)


def _run(fn, *arrays):
    with jax.enable_x64(True):
        out = jax.jit(fn)(*(jnp.asarray(x) for x in arrays))
        return np.asarray(out)


def _bits(x):
    return np.asarray(x, np.float64).view(np.uint64)


def _assert_bits_equal(got_bits, want):
    want_bits = _bits(want)
    bad = np.flatnonzero(got_bits != want_bits)
    assert bad.size == 0, (f"{bad.size} lanes differ, e.g. "
                           f"{got_bits[bad[0]].view(np.float64)!r} vs "
                           f"{want[bad[0]]!r}")


@pytest.mark.parametrize("op", ["div", "mul", "sub"])
def test_binary_op_matches_numpy(operands, op):
    a, b = operands
    if op == "sub":
        a, b = np.maximum(a, b), np.minimum(a, b)
        keep = a > b
        a, b = a[keep], b[keep]
    with np.errstate(all="ignore"):
        want = {"div": a / b, "mul": a * b, "sub": a - b}[op]
    _assert_bits_equal(_run(getattr(xf, op), _bits(a), _bits(b)), want)


def test_signed_div_and_order_key(operands):
    a, b = operands
    signed = np.where(np.arange(N) % 2 == 0, -a, a)
    with np.errstate(all="ignore"):
        want = signed / b
    _assert_bits_equal(_run(xf.signed_div, _bits(signed), _bits(b)), want)
    keys = _run(xf.order_key, _bits(signed))
    assert np.all(np.diff(signed[np.argsort(keys, kind="stable")]) >= 0)
    back = _run(lambda x: xf.from_order_key(xf.order_key(x)),
                _bits(signed))
    assert np.array_equal(back, _bits(signed))


def test_int_round_trip():
    k = np.concatenate([np.arange(0, 5000), [2**31 - 1]]).astype(np.int32)
    _assert_bits_equal(_run(xf.from_int, k), k.astype(np.float64))


def test_f64_probe_exact_path_matches_numpy():
    """The chip probe's integer-only rows are exact on any backend; its
    native rows are exact where float64 is IEEE (this CPU)."""
    from benchmarks.f64_probe import probe
    out = probe()
    assert set(out["exact"]) == {"div", "mul", "mul_int", "sub"}
    assert all(r["n_diff"] == 0 for r in out["exact"].values()), out
    if jax.default_backend() == "cpu":
        assert all(r["n_diff"] == 0 for r in out["native"].values()), out


def test_ties_round_to_even_and_edges():
    # 1 + 2**-53 is a tie between 1 and its successor: even wins
    one = np.array([1.0, 1.0 + 2**-52, 3.0, 1e308, 2.0**-1022])
    tiny = np.array([2**-53, 2**-53, 2**-52 * 1.5, 1e-10, 2.0**-1074])
    with np.errstate(all="ignore"):
        _assert_bits_equal(_run(xf.sub, _bits(one), _bits(tiny)),
                           one - tiny)
        big = np.array([1e308, 3.0, 2.0**-1022, 0.0, 7.0])
        by = np.array([10.0, 3.0, 3.0, 5.0, 2.0**-1074])
        _assert_bits_equal(_run(xf.mul, _bits(big), _bits(by)), big * by)
        _assert_bits_equal(_run(xf.div, _bits(big), _bits(by)), big / by)
    inf = np.array([np.inf])
    _assert_bits_equal(_run(xf.mul, _bits(inf), _bits([2.0])), inf)
    _assert_bits_equal(_run(xf.div, _bits(inf), _bits([2.0])), inf)
