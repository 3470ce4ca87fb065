"""Plain numpy oracle of the five jobs, with no JAX involved.

:func:`emission` restates each map function of ``jobs.py`` over one
shard (doc id 0) in numpy, and :func:`reduce_counts` reduces the
records with ``np.unique``. The engine's ``local_mapreduce`` of a shard
and the merged outputs of ``mesh_mapreduce`` must equal them exactly.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.mapreduce.jobs import EMPTY, GREP_PATTERN


def _gram3(tokens: np.ndarray) -> np.ndarray:
    """Hash of every 3 consecutive tokens that are all valid."""
    a, b, c = tokens[:-2], tokens[1:-1], tokens[2:]
    ok = (a >= 0) & (b >= 0) & (c >= 0)
    u = np.uint32
    h = (a.astype(u) * u(2654435761) ^ b.astype(u) * u(40503)
         ^ c.astype(u) * u(69427))
    return h[ok]


def emission(name: str, tokens: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray]:
    """The (keys, values) records job ``name`` emits for one shard,
    as uint32 keys and int64 values."""
    if name in ("WC", "II"):
        keys = tokens[tokens >= 0].astype(np.uint32)
        # II's value is the doc id, 0 for a single shard
        return keys, np.full(keys.shape, 1 if name == "WC" else 0,
                             np.int64)
    if name == "SC":
        keys = _gram3(tokens)
    elif name == "Grep":
        keys = np.flatnonzero(tokens == GREP_PATTERN).astype(np.uint32)
    elif name == "Permu":
        h = _gram3(tokens)
        keys = np.concatenate(
            [h ^ np.uint32((r * 0x9E3779B9) & 0xFFFFFFFF)
             for r in (0, 1, 2)])
    else:
        raise ValueError(f"unknown job {name!r}")
    return keys, np.ones(keys.shape, np.int64)


def reduce_counts(keys: np.ndarray, values: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Distinct keys in ascending order and the sum of each key's
    values. A key equal to ``EMPTY`` marks an empty slot in the engine
    and is dropped here too."""
    keep = keys != EMPTY
    uniq, inv = np.unique(keys[keep], return_inverse=True)
    sums = np.bincount(inv, weights=values[keep], minlength=len(uniq))
    return uniq, sums.astype(np.int64)
