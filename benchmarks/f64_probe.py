"""Float64 exactness probe: does the default JAX device round float64
arithmetic as IEEE does?

Runs each op on 65,536 operand pairs of the magnitudes the fabric fill
sees (link capacities, member counts, shares), once with the device's
native float64 and once with the integer-only arithmetic of
``repro.sweep.exact_f64``, and counts the lanes whose bits differ from
numpy's float64 and the largest difference in ulp. On a CPU every count
is 0. A TPU emulates float64 with pairs of float32, so its native row
shows where and by how much it rounds differently; the exact row must
stay at 0 there too, or the fill kernel cannot be bit-identical.

    PYTHONPATH=src python -m benchmarks.f64_probe [--out PATH]

Prints one line per (path, op) and, last, a JSON object with all of
them; ``--out`` also writes that JSON to a file.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.sweep import exact_f64 as xf

N = 1 << 16


def operands(seed: int = 0):
    """Two float64 vectors of fill-like magnitudes and a vector of
    member counts 1..47."""
    rng = np.random.default_rng(seed)

    def vals():
        kind = rng.integers(0, 3, N)
        return np.where(
            kind == 0, rng.uniform(1e5, 1e10, N),
            np.where(kind == 1,
                     rng.integers(1, 200, N).astype(float) * 1.25e6,
                     rng.uniform(0, 1, N) * 3.7e8 / 7.0))
    a, b = vals(), vals()
    k = rng.integers(1, 48, N).astype(float)
    return a, b, k


def _compare(got: np.ndarray, want: np.ndarray) -> dict:
    g = got.view(np.int64)
    w = want.view(np.int64)
    return {"lanes": int(g.size), "n_diff": int(np.count_nonzero(g != w)),
            "max_ulp": int(np.abs(g - w).max())}


def probe(seed: int = 0) -> dict:
    a, b, k = operands(seed)
    hi, lo = np.maximum(a, b), np.minimum(a, b)
    keep = hi > lo
    hi, lo = hi[keep], lo[keep]
    native = {
        "identity": (lambda x, y, k: x, a, b, k, a),
        "add": (lambda x, y, k: x + y, a, b, k, a + b),
        "sub": (lambda x, y, k: x - y, a, b, k, a - b),
        "mul_int": (lambda x, y, k: k * y, a, b, k, k * b),
        "mul": (lambda x, y, k: x * y, a, b, k, a * b),
        "div": (lambda x, y, k: x / y, a, b, k, a / b),
        "div_int": (lambda x, y, k: x / k, a, b, k, a / k),
        "lt": (lambda x, y, k: (x < y).astype(jnp.float64), a, b, k,
               (a < b).astype(float)),
    }
    exact = {
        "div": (xf.div, a, b, a / b),
        "mul": (xf.mul, a, b, a * b),
        "mul_int": (xf.mul, k, b, k * b),
        "sub": (xf.sub, hi, lo, hi - lo),
    }
    out: dict = {"native": {}, "exact": {}}
    with jax.enable_x64(True):
        for name, (fn, x, y, kk, want) in native.items():
            got = np.asarray(jax.jit(fn)(jnp.asarray(x), jnp.asarray(y),
                                         jnp.asarray(kk)))
            out["native"][name] = _compare(got, want)
        for name, (fn, x, y, want) in exact.items():
            got = np.asarray(jax.jit(fn)(jnp.asarray(x.view(np.uint64)),
                                         jnp.asarray(y.view(np.uint64))))
            out["exact"][name] = _compare(got.view(np.float64), want)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None,
                    help="also write the JSON result to this file")
    args = ap.parse_args()
    dev = jax.devices()[0]
    result = {"platform": dev.platform, "kind": dev.device_kind,
              "n": N, **probe()}
    for path in ("native", "exact"):
        for name, row in result[path].items():
            print(f"{path:6s} {name:8s} {row['n_diff']:6d} of "
                  f"{row['lanes']} lanes "
                  f"differ, max {row['max_ulp']} ulp")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
            f.write("\n")
    print(json.dumps(result, sort_keys=True))


if __name__ == "__main__":
    main()
