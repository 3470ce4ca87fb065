"""What the MapReduce cells compare and count, from the numpy reference."""
from __future__ import annotations

import statistics
from typing import List, Tuple

import numpy as np

from bench.reference.mapreduce import EMPTY, emission, reduce_counts


def reduce(keys: np.ndarray, value: int, count_dtype=np.int64
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Unique keys and their counts as int64, accumulated in
    ``count_dtype``: below int32 it is the lower-precision control."""
    uk, counts = reduce_counts(keys, value, count_dtype)
    return uk, counts.astype(np.int64)


def reference(job: str, tokens: np.ndarray, count_dtype=np.int64
              ) -> Tuple[np.ndarray, np.ndarray, int]:
    """``(unique keys, counts, records emitted)`` of ``job`` on a block."""
    keys, value = emission(job, tokens)
    uk, counts = reduce(keys, value, count_dtype)
    return uk, counts, len(keys)


def least_bytes(valid_tokens: int, emitted: int, unique: int) -> int:
    """Least HBM bytes a job's data requires: each valid input token's id
    and length read once (8 B), each emitted record's key and value
    written and read once (16 B), each unique key and count written once
    (8 B). Counted from the reference's records, never from capacities
    or padding."""
    return 8 * valid_tokens + 16 * emitted + 8 * unique


def differs(keys: np.ndarray, counts: np.ndarray, n: int,
            want_keys: np.ndarray, want_counts: np.ndarray) -> bool:
    """Whether the engine's padded output differs from the reference:
    its first ``n`` slots must hold the keys and counts, the rest
    ``EMPTY``."""
    return not (n == len(want_keys)
                and np.array_equal(keys[:n], want_keys)
                and np.array_equal(counts[:n].astype(np.int64), want_counts)
                and bool(np.all(keys[n:] == EMPTY)))


def percentile(values: List[float], p: int) -> float:
    """The ``p``-th percentile (1..99), inclusive method."""
    if len(values) < 2:
        return float(values[0])
    return statistics.quantiles(values, n=100, method="inclusive")[p - 1]
