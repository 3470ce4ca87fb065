"""The lower-precision control: the cell's check, with the program
replaced by its plain reference computed one precision lower, must read
not correct.

    python3 -m bench.control --workload <cell> --seeds <n> [<n> ...]

The numpy reference with counts accumulated in int16 (the engine counts
in int32) stands in for the engine's outputs, on blocks made from the
seed on the chip at the cell's own size.

Prints one JSON line per seed with the numbers compared and their
limits. Not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

import numpy as np

from bench import spec
from bench.reference.mapreduce import EMPTY


def _padded(keys: np.ndarray, counts: np.ndarray, cap: int):
    """The reference's reduction laid out as the engine's outputs."""
    k = np.full(cap, EMPTY, np.uint32)
    v = np.zeros(cap, np.int64)
    k[:len(keys)] = keys
    v[:len(counts)] = counts
    return k, v, len(keys)


def mr_local_control(tokens: np.ndarray, jobs: List[str]) -> List[dict]:
    from bench import mrcheck
    from bench.drivers.mr_local import compare
    got: Dict[tuple, tuple] = {}
    for b in range(len(tokens)):
        for name in jobs:
            uk, uc, emitted = mrcheck.reference(name, tokens[b], np.int16)
            got[(b, name)] = _padded(uk, uc, max(emitted, 1))
    return compare(got, tokens)[0]


def run_control(cell, seed: int) -> List[dict]:
    from bench.corpus import block_key, make_blocks
    tok, _, _ = make_blocks([block_key(seed, b) for b in
                             range(int(cell.traffic["blocks"]))],
                            cell.config["block"], cell.config["corpus"])
    return mr_local_control(np.asarray(tok), list(cell.traffic["jobs"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from bench import device
    from bench.checks import as_result, correct
    device.use_compile_cache()
    device.chips(cell.chips)
    for seed in args.seeds:
        checks = run_control(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct(checks),
                          "checks": as_result(checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
