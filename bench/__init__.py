"""Chip benchmark of the MapReduce engine.

Run as ``python3 -m bench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout that holds ``BENCHMARK.json``.
Everything that defines the yardstick lives in this package: cell, config
and traffic files, the data generators, the frozen plain references, the
trace reduction and one reader per per-layer metric.
"""
