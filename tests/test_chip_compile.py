"""The device paths compile for a TPU v5e at their real sizes.

Compiles against a described ``v5e:2x2`` topology, with no chip attached:
the lockstep fill kernel at its live padded shape (64 x 48 x 24, float64
bit patterns), ``local_mapreduce`` of WordCount and Permu on one 128 MiB
block, and ``mesh_mapreduce``'s jitted program on the 2x2 (pod, data)
mesh at the benchmark's two blocks per chip. Each program must fit the
chip's 16 GB of HBM. Nothing runs, so this says nothing of results or
times. The topology is described inside a fixture, never at
import: only the worker that runs this file loads the TPU compiler. A
missing or broken TPU compiler (libtpu is pinned in requirements.txt)
fails these tests; it does not skip them.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.mapreduce import JOBS
from repro.mapreduce.engine import _local_mapreduce, _mesh_mapreduce
from repro.mapreduce.jobs import BLOCK_TOKENS
from repro.sweep.vmap_fill import _jitted_fill

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)


def test_fill_kernel_compiles_at_live_shape(one_chip):
    S, C, L = 64, 48, 24
    with jax.enable_x64(True):
        def arg(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        compiled = _jitted_fill().lower(
            arg(jnp.uint64, S, L), arg(jnp.bool_, S, C, L),
            arg(jnp.int32, S, C), arg(jnp.uint64, S, C),
            arg(jnp.uint64, S, C)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("name", ["WC", "Permu"])
def test_local_mapreduce_compiles_at_block_shape(one_chip, name):
    block = jax.ShapeDtypeStruct((BLOCK_TOKENS,), jnp.int32,
                                 sharding=one_chip)
    compiled = _local_mapreduce.lower(JOBS[name], block, block).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def _sorts(hlo, stage):
    """(result types, operands) of each sort of the program under `stage`."""
    found = []
    for line in hlo.splitlines():
        if " sort(" in line and f"/{stage}/" in line:
            result, operands = re.search(r"= (.*) sort\(([^)]*)\)",
                                         line).groups()
            found.append((re.findall(r"(\w+)\[\d+\]", result),
                          operands.split(", ")))
    return found


@pytest.mark.parametrize("name", ["WC", "SC", "II", "Grep", "Permu"])
def test_local_mapreduce_names_its_stages(one_chip, name):
    """Every fusion and sort of the compiled program that JAX emitted
    carries exactly one ``mr.*`` stage, and the right one; what XLA makes
    itself (cumsum's reduce-window pieces) carries none. Nothing gathers or
    scatters: each ``mr.sort`` sorts the u32 keys with the s32 values, not
    with an iota to gather by, and ``mr.segment`` is one sort of the run
    ends' u32 keys with their s32 prefix sums."""
    block = jax.ShapeDtypeStruct((1 << 12,), jnp.int32, sharding=one_chip)
    hlo = _local_mapreduce.lower(JOBS[name], block, block).compile(
        ).as_text()
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    seen = set()
    for line in entry.splitlines()[1:]:
        m = re.search(r" (fusion|sort|gather|scatter)\(", line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        if not op or not op.group(1).startswith("jit("):
            assert m.group(1) == "fusion", line
            continue
        scopes = [p for p in op.group(1).split("/") if p.startswith("mr.")]
        assert len(scopes) == 1, line
        if m.group(1) == "sort":
            assert scopes[0] in ("mr.sort", "mr.segment"), line
        seen.add(scopes[0])
    assert seen == {"mr.map", "mr.sort", "mr.segment"}
    assert " gather(" not in hlo and " scatter(" not in hlo
    passes = 2 if JOBS[name].combine_in_map else 1
    for stage in ("mr.sort", "mr.segment"):
        sorts = _sorts(hlo, stage)
        assert len(sorts) == passes, (stage, sorts)
        for result, operands in sorts:
            assert result == ["u32", "s32"], (stage, result)
            assert len(operands) == 2, (stage, operands)
            assert not any(o.startswith("%iota") for o in operands), operands


@pytest.mark.parametrize("shuffle", [("data",), ("pod", "data")])
def test_mesh_mapreduce_compiles_on_2x2(topo, shuffle):
    """The benchmark's 1 GiB WordCount job, 2 blocks of 20 Mi slots per
    chip, fits one chip's HBM as the one jitted mesh program; every
    all-to-all carries ``mr.shuffle``, and the pack's sort and scatters
    carry ``mr.pack``."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pod", "data"),
                axis_types=(AxisType.Auto,) * 2)
    blocks = jax.ShapeDtypeStruct(
        (8, BLOCK_TOKENS), jnp.int32,
        sharding=NamedSharding(mesh, P(("pod", "data"))))
    compiled = _mesh_mapreduce.lower(
        JOBS["WC"], mesh, shuffle, ("pod", "data"), 4, blocks,
        blocks).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    hlo = compiled.as_text()
    a2a = [line for line in hlo.splitlines()
           if re.search(r" all-to-all(-start)?\(", line)]
    assert len(a2a) == 2
    assert all("/mr.shuffle/" in line for line in a2a), a2a
    pack = [re.search(r" (sort|scatter)\(", line).group(1)
            for line in hlo.splitlines()
            if re.search(r" (sort|scatter)\(", line)
            and "/mr.pack/" in line]
    assert "sort" in pack and "scatter" in pack, pack
    scatters = [line for line in hlo.splitlines() if " scatter(" in line]
    assert all("/mr.pack/" in line for line in scatters), scatters
