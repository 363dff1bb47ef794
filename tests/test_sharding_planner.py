"""ShardingPlanner: divisibility fallback, rule sets, hint no-op semantics."""

import jax
import pytest
from proptest import given, settings, st
from jax.sharding import PartitionSpec

from repro.sharding.planner import ShardingPlanner, shard_hint


@pytest.fixture(scope="module")
def mesh():
    # 1x1 mesh: axis names exist, every size divides, single real device
    return jax.make_mesh((1, 1), ("data", "model"))


def planner(mesh, **kw):
    return ShardingPlanner(mesh, **kw)


def test_basic_rules_train(mesh):
    p = planner(mesh, context="train")
    assert p.spec_for((1024, 4096), ("embed", "mlp")) == PartitionSpec("data", "model")
    assert p.spec_for((64, 1024, 128), ("experts", "embed", "mlp")) == \
        PartitionSpec("model", "data", None)  # model consumed by experts first


def test_vocab_params_not_fsdp_sharded(mesh):
    p = planner(mesh, context="train")
    # embed dim of a vocab-bearing tensor stays unsharded (§Perf pair B)
    assert p.spec_for((1024, 50304), ("embed", "vocab")) == PartitionSpec(None, "model")
    assert p.spec_for((50304, 1024), ("vocab", "embed")) == PartitionSpec("model", None)
    # opt-in restores the old behavior
    p2 = planner(mesh, context="train", fsdp_vocab=True)
    assert p2.spec_for((1024, 50304), ("embed", "vocab")) == PartitionSpec("data", "model")


def test_serve_context_no_fsdp(mesh):
    p = planner(mesh, context="serve")
    assert p.spec_for((1024, 4096), ("embed", "mlp")) == PartitionSpec(None, "model")


def test_serve_weight_2d(mesh):
    p = planner(mesh, context="serve", serve_weight_2d=True)
    assert p.spec_for((1024, 4096), ("embed", "mlp")) == PartitionSpec("data", "model")


def test_divisibility_fallback():
    """Dims the axis size does not divide are replicated (e.g. hymba's 25
    heads, granite's 49155 vocab on a 16-way model axis)."""
    mesh16 = jax.make_mesh((1, 1), ("data", "model"))
    p = ShardingPlanner(mesh16)
    # fake a 16-wide model axis through the divisibility check
    p.axis_sizes = {"data": 16, "model": 16}
    assert p.spec_for((25, 64), ("heads", "head")) == PartitionSpec(None, None)
    assert p.spec_for((49155, 1536), ("vocab", "embed")) == PartitionSpec(None, None)
    assert p.spec_for((32, 64), ("heads", "head")) == PartitionSpec("model", None)


@given(
    st.integers(1, 8).map(lambda k: 2 ** k),
    st.sampled_from(["embed", "mlp", "heads", "vocab", "batch", None]),
)
@settings(max_examples=60, deadline=None)
def test_spec_rank_and_axis_use(size, logical):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    p = ShardingPlanner(mesh)
    p.axis_sizes = {"data": 4, "model": 8}
    spec = p.spec_for((size, size), (logical, logical))
    assert len(spec) == 2
    # a mesh axis is consumed at most once per tensor
    used = [a for dim in spec if dim for a in (dim if isinstance(dim, tuple) else (dim,))]
    assert len(used) == len(set(used))


def test_shard_hint_noop_outside_mesh():
    import jax.numpy as jnp

    x = jnp.ones((8, 16))
    y = shard_hint(x, ["batch", None])
    assert (y == x).all()


def test_shard_hint_skips_nondivisible_dims():
    """Under an ambient (data=2, model=4) mesh the hint pins the dims the
    axes divide and replicates the rest: 8 rows take 'data', 7 columns
    cannot take the 4-way 'model' axis, 12 columns can."""
    from jax.sharding import AbstractMesh, AxisType

    mesh = AbstractMesh((2, 4), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)

    def hinted_spec(shape):
        jaxpr = jax.make_jaxpr(lambda x: shard_hint(x, ["batch", "model"]))(
            jax.numpy.ones(shape))
        (eqn,) = jaxpr.jaxpr.eqns
        return eqn.params["sharding"].spec

    with jax.sharding.use_abstract_mesh(mesh):
        assert hinted_spec((8, 7)) == PartitionSpec("data", None)
        assert hinted_spec((8, 12)) == PartitionSpec("data", "model")
        assert hinted_spec((3, 12)) == PartitionSpec(None, "model")


def test_shard_hint_runs_under_set_mesh():
    """On a real launcher mesh, entered with ``jax.set_mesh``, the hinted
    value is unchanged."""
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh

    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        x = jnp.ones((8, 7))
        y = jax.jit(lambda a: shard_hint(a, ["batch", "model"]))(x)
        assert (y == x).all()
