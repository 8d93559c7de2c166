"""Logical-axis sharding rules: divisibility, dedupe, no-mesh no-ops."""
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import sharding
from repro.launch.mesh import make_mesh


@pytest.fixture
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_noop_without_mesh():
    sharding.clear()
    x = jnp.ones((4, 4))
    assert sharding.constrain(x, "batch", None) is x
    assert sharding.spec("batch") == P()


def test_divisibility_drops_axes(mesh):
    with sharding.use_rules(mesh):
        # model axis size 1 divides everything; fake a 16-wide check via
        # explicit spec logic instead.
        s = sharding.spec("heads", shape=(8,))
        assert s == P(None) or s == P("model")  # 8 % 1 == 0 → kept


def test_spec_dedupes_axes(mesh):
    with sharding.use_rules(mesh):
        s = sharding.spec("batch", "fsdp", shape=(4, 4))
        used = [a for part in s for a in (part if isinstance(part, tuple)
                                          else [part]) if a]
        assert len(used) == len(set(used))


def test_divisibility_16way():
    mesh = make_mesh((1,), ("model",))
    rules = dict(sharding.DEFAULT_RULES)
    with sharding.use_rules(mesh, rules):
        # 7 % 1 == 0 → axis kept (size-1 mesh)
        assert sharding.spec("heads", shape=(7,)) == P("model")


def test_tuple_rule_prefix():
    # AbstractMesh suffices for spec logic (no devices needed).
    mesh = AbstractMesh((2, 2), ("data", "model"))
    rules = dict(sharding.DEFAULT_RULES)
    rules["x2"] = ("data", "model")
    with sharding.use_rules(mesh, rules):
        # dim 2: only the first axis divides → maximal prefix ("data",)
        assert sharding.spec("x2", shape=(2,)) == P(("data",))
        assert sharding.spec("x2", shape=(4,)) == P(("data", "model"))
        assert sharding.spec("x2", shape=(3,)) == P(None)
