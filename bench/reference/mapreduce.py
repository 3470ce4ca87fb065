"""The five PUMA jobs in plain numpy: what each map emits and the reduce.

:func:`emission` restates the engine's map functions over one block
(doc id 0): WordCount and InvertedIndex emit each valid token, SC a hash
of every valid 3-gram, Grep the position of each pattern match, Permu
three rotations of each 3-gram hash. :func:`reduce_counts` gives the
distinct keys in ascending order with the sum of their values. Every
job's values are one constant, so the sum is that constant times the
key's run length in the sorted keys.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

#: key of an empty slot in the engine's outputs
EMPTY = np.uint32(0xFFFFFFFF)
#: Grep's pattern: content id 2 (markup ids are 0..63)
GREP_PATTERN = 66
JOB_NAMES = ("WC", "SC", "II", "Grep", "Permu")


def _gram3(tokens: np.ndarray) -> np.ndarray:
    a, b, c = tokens[:-2], tokens[1:-1], tokens[2:]
    ok = (a >= 0) & (b >= 0) & (c >= 0)
    u = np.uint32
    h = (a.astype(u) * u(2654435761) ^ b.astype(u) * u(40503)
         ^ c.astype(u) * u(69427))
    return h[ok]


def emission(name: str, tokens: np.ndarray) -> Tuple[np.ndarray, int]:
    """The uint32 keys job ``name`` emits for one block and the one value
    every record carries."""
    if name in ("WC", "II"):
        # II's value is the doc id, 0 for a single block
        return tokens[tokens >= 0].astype(np.uint32), int(name == "WC")
    if name == "SC":
        return _gram3(tokens), 1
    if name == "Grep":
        return np.flatnonzero(tokens == GREP_PATTERN).astype(np.uint32), 1
    if name == "Permu":
        h = _gram3(tokens)
        return np.concatenate(
            [h ^ np.uint32((r * 0x9E3779B9) & 0xFFFFFFFF)
             for r in (0, 1, 2)]), 1
    raise ValueError(f"unknown job {name!r}")


def reduce_counts(keys: np.ndarray, value: int, count_dtype=np.int64
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Distinct keys (``EMPTY`` dropped) in ascending order, and each
    one's summed value, accumulated in ``count_dtype``."""
    k = np.sort(keys[keys != EMPTY])
    if not len(k):
        return k, np.zeros(0, count_dtype)
    first = np.flatnonzero(np.concatenate(([True], k[1:] != k[:-1])))
    runs = np.diff(np.append(first, len(k)))
    return k[first], (runs.astype(count_dtype) * count_dtype(value))
