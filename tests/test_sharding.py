"""Logical-axis partitioning rules: divisibility fallback, conflicts,
missing mesh axes, ZeRO-1 state axes, and the hint() no-op contract."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.models.common import ParamSpec
from repro.sharding import (DEFAULT_RULES, Rules, hint, logical_to_spec,
                            mesh_axis_size, use_rules)
from repro.train.optimizer import zero1_leaf_axes


@pytest.fixture(scope="module")
def mesh():
    # 1-device mesh: divisibility is trivially satisfied; semantic checks
    # against multi-axis meshes use a fake mesh-like below.
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


class FakeMesh:
    """Mesh stand-in with arbitrary axis sizes (no devices needed)."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


def test_divisibility_fallback():
    m = FakeMesh(data=16, model=16)
    spec = logical_to_spec(m, DEFAULT_RULES, ("vocab", "embed"),
                           (49155, 2048))
    assert spec == P()  # 49155 % 16 != 0 -> replicate; embed -> None
    spec2 = logical_to_spec(m, DEFAULT_RULES, ("vocab", "embed"),
                            (49408, 2048))
    assert spec2 == P("model")


def test_axis_conflict_drops_later_dim():
    m = FakeMesh(pod=2, data=16, model=16)
    rules = DEFAULT_RULES.updated(embed="data")
    # batch takes (pod, data); embed -> data conflicts -> dropped
    spec = logical_to_spec(m, rules, ("batch", "seq", "embed"),
                           (256, 4096, 2048))
    assert spec == P(("pod", "data"))


def test_missing_mesh_axis_dropped():
    m = FakeMesh(data=16, model=16)  # no 'pod'
    spec = logical_to_spec(m, DEFAULT_RULES, ("batch", None),
                           (256, 128))
    assert spec == P("data")


def test_mesh_axis_size():
    m = FakeMesh(pod=2, data=16, model=16)
    assert mesh_axis_size(m, None) == 1
    assert mesh_axis_size(m, "data") == 16
    assert mesh_axis_size(m, ("pod", "data")) == 32
    assert mesh_axis_size(m, "absent") == 1


def test_zero1_axes_adds_fsdp_on_largest_free_dim():
    m = FakeMesh(data=16, model=16)
    spec = ParamSpec((48, 5120, 2048), jnp.bfloat16, "scaled",
                     ("layers", "embed", "qkv"))
    # qkv -> model; embed -> None by default; fsdp(data) goes on dim 1
    axes = zero1_leaf_axes(spec, m, DEFAULT_RULES)
    assert axes == ("layers", "fsdp", "qkv")


def test_zero1_axes_no_double_data():
    m = FakeMesh(data=16, model=16)
    rules = DEFAULT_RULES.updated(embed="data")
    spec = ParamSpec((48, 5120, 2048), jnp.bfloat16, "scaled",
                     ("layers", "embed", "qkv"))
    # embed already maps to data -> zero1 must not add fsdp again
    axes = zero1_leaf_axes(spec, m, rules)
    assert axes == ("layers", "embed", "qkv")


def test_hint_is_noop_outside_rules(mesh):
    x = jnp.ones((4, 4))
    y = hint(x, ("batch", "embed"))
    assert y is x


def test_hint_constrains_inside_rules(mesh):
    x = jnp.ones((4, 4))
    with use_rules(mesh, DEFAULT_RULES):
        y = jax.jit(lambda a: hint(a, ("batch", "embed")))(x)
    assert y.shape == (4, 4)


def test_rules_updated_immutably():
    r2 = DEFAULT_RULES.updated(seq="model")
    assert DEFAULT_RULES.get("seq") is None
    assert r2.get("seq") == "model"
