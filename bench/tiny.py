"""Cells cut to a size a CPU test runs in seconds: the same drivers,
traffic and checks on small blocks."""
from __future__ import annotations

from bench import spec


def tiny_cell(name: str, bench: dict = None) -> spec.Cell:
    cell = spec.resolve(bench or spec.load_benchmark(), name)
    cell.config = dict(cell.config,
                       block={"slots": 1 << 16, "block_bytes": 1 << 18})
    return cell
