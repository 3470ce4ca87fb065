"""What each reducer of the mesh path must hold, in plain numpy.

The blocks of a job lie in device order, ``blocks_per_device`` to a
device, the devices numbered row-major over the mesh's axes. A device's
shuffle group is the devices that share its coordinates on every axis
outside ``shuffle_axes``; its index in the group is its coordinate on the
shuffle axes, row-major in their order, and ``D`` is the group's size.
Reducer ``g`` (the device of that number) owns the keys with
``key % D`` equal to its index, over the records its group's blocks
emit (Hadoop's HashPartitioner).

:func:`reducer_outputs` gives each reducer's ascending distinct keys and
their counts as int64; :func:`least_bytes` the least bytes the shuffle
must move, 8 B (a key and a value) per record whose owner is another
chip, or lies in another pod.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference.mapreduce import emission, reduce_counts

#: keys below this are counted by ``np.bincount``; wider ones are sorted
BINCOUNT_KEYS = 1 << 24
#: the mesh axis whose coordinate names a pod (a datacenter)
POD_AXIS = "pod"


class Layout:
    """Where each block lies and which reducer owns each key, on a mesh
    of ``shape`` named ``axes``."""

    def __init__(self, shape: Sequence[int], axes: Sequence[str],
                 shuffle_axes: Sequence[str], blocks_per_device: int):
        self.shape = tuple(int(s) for s in shape)
        self.axes = tuple(axes)
        self.n_devices = int(np.prod(self.shape))
        self.per_device = int(blocks_per_device)
        inner = [self.axes.index(a) for a in shuffle_axes]
        outer = [i for i in range(len(self.axes)) if i not in inner]
        self.D = int(np.prod([self.shape[i] for i in inner]))
        coords = np.array(np.unravel_index(np.arange(self.n_devices),
                                           self.shape)).T
        self.coords = coords
        #: each device's index in its shuffle group, and the group
        self.index = [int(np.ravel_multi_index(
            [c[i] for i in inner], [self.shape[i] for i in inner]))
            for c in coords]
        self.group = [tuple(int(c[i]) for i in outer) for c in coords]

    def members(self, device: int) -> List[int]:
        """The devices of ``device``'s shuffle group."""
        return [d for d in range(self.n_devices)
                if self.group[d] == self.group[device]]

    def owner(self, device: int, residue: int) -> int:
        """The device of ``device``'s group that owns ``key % D``."""
        return next(d for d in self.members(device)
                    if self.index[d] == residue)

    def blocks(self, device: int) -> range:
        return range(device * self.per_device,
                     (device + 1) * self.per_device)


def _records(job: str, tokens: np.ndarray, layout: Layout, device: int
             ) -> Tuple[np.ndarray, int]:
    """The uint32 keys the blocks of ``device`` emit, and their value."""
    out = [emission(job, tokens[b]) for b in layout.blocks(device)]
    return np.concatenate([k for k, _ in out]), out[0][1]


def _distinct(keys: np.ndarray, value: int, count_dtype
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Ascending distinct keys and their summed value in ``count_dtype``:
    a count per key id where the ids are narrow, else a sort."""
    if not len(keys) or int(keys.max()) >= BINCOUNT_KEYS:
        return reduce_counts(keys, value, count_dtype)
    runs = np.bincount(keys)
    uk = np.flatnonzero(runs)
    return uk.astype(np.uint32), (runs[uk].astype(count_dtype)
                                  * count_dtype(value))


def reducer_outputs(job: str, tokens: np.ndarray, layout: Layout,
                    count_dtype=np.int64
                    ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``(keys, counts as int64)`` of every reducer, in device order;
    counts are accumulated in ``count_dtype`` (below int32 it is the
    lower-precision control)."""
    out: List = [None] * layout.n_devices
    for first in sorted({layout.members(d)[0]
                         for d in range(layout.n_devices)}):
        members = layout.members(first)
        recs = [_records(job, tokens, layout, d) for d in members]
        keys = np.concatenate([k for k, _ in recs])
        uk, uc = _distinct(keys, recs[0][1], count_dtype)
        for d in members:
            own = uk % layout.D == layout.index[d]
            out[d] = (uk[own], uc[own].astype(np.int64))
    return out


def least_bytes(job: str, tokens: np.ndarray, layout: Layout
                ) -> Dict[str, int]:
    """Least bytes the shuffle moves, over every device's blocks:
    ``off_chip`` for records owned by another chip, ``cross_pod`` for
    those owned by a chip of another pod (8 B each). Counted from the
    reference's records, never from buffer capacities."""
    pod = layout.axes.index(POD_AXIS) if POD_AXIS in layout.axes else None
    off = cross = 0
    for d in range(layout.n_devices):
        keys, _ = _records(job, tokens, layout, d)
        per = np.bincount(keys % layout.D, minlength=layout.D)
        for r in range(layout.D):
            o = layout.owner(d, r)
            off += int(per[r]) * (o != d)
            if pod is not None:
                cross += int(per[r]) * bool(
                    layout.coords[o][pod] != layout.coords[d][pod])
    return {"off_chip": 8 * off, "cross_pod": 8 * cross}
