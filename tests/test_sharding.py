"""Sharding rule engine + MoE dispatch tests (single-device where possible;
mesh-dependent behavior via subprocess in test_system)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.sharding import Sharder, base_rules


@pytest.fixture()
def sharder():
    mesh = make_mesh((1,), ("data",))  # single-device 'data' mesh
    rules = base_rules(False)
    return Sharder(mesh, rules)


def test_spec_basic(sharder):
    spec = sharder.spec(("embed", "heads"), (64, 32))
    # 'model' axis absent from this mesh -> dropped; embed->data kept
    assert spec == P("data")


def test_divisibility_fallback():
    mesh = make_mesh((1,), ("model",))
    s = Sharder(mesh, {"kv_heads": "model"})
    # only 1 device: axis size 1 divides everything
    assert s.spec(("kv_heads",), (4,)) == P("model")


def test_divisibility_drops_nondividing_axis():
    import os
    # simulate a 16-wide axis via rule table arithmetic (no devices needed
    # for the pure spec logic: fake axis sizes)
    mesh = make_mesh((1,), ("model",))
    s = Sharder(mesh, {"kv_heads": "model"})
    s._axis_sizes = {"model": 16}
    assert s.spec(("kv_heads",), (4,)) == P()      # 4 % 16 != 0 -> replicate
    assert s.spec(("kv_heads",), (32,)) == P("model")


def test_axis_used_once_per_spec():
    mesh = make_mesh((1,), ("data",))
    s = Sharder(mesh, {"a": "data", "b": "data"})
    s._axis_sizes = {"data": 4}
    spec = s.spec(("a", "b"), (8, 8))
    # the same mesh axis must not shard two dims
    assert spec == P("data")


def test_seq_cache_rule_switch():
    mesh = make_mesh((1,), ("model",))
    base = Sharder(mesh, base_rules(False))
    seqc = Sharder(mesh, base_rules(False, seq_sharded_cache=True))
    base._axis_sizes = {"model": 16}
    seqc._axis_sizes = {"model": 16}
    axes = ("cache_batch", "cache_seq", "act_kv_heads", None)
    assert base.spec(axes, (8, 32768, 4, 64)) == P()
    assert seqc.spec(axes, (8, 32768, 4, 64)) == P(None, "model")


def test_moe_dense_fallback_without_mesh():
    """moe_ffn must run (dense path) with no ambient sharder."""
    from repro.configs import get_config
    from repro.models.moe import moe_ffn
    from repro.models.module import Ctx

    cfg = get_config("dbrx_132b").smoke(n_experts=4, topk=2, d_model=32,
                                        expert_ff=16)
    ctx = Ctx("init", rng=jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 32), jnp.bfloat16)
    out, aux = moe_ffn(ctx, x, cfg)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    assert float(aux) > 0.0  # load-balance loss is positive


def test_moe_capacity_drops_tokens_gracefully():
    from repro.configs import get_config
    from repro.models.moe import _local_dispatch_compute, _route
    from repro.models.module import Ctx
    import dataclasses

    cfg = get_config("dbrx_132b").smoke(n_experts=4, topk=2, d_model=16,
                                        expert_ff=8)
    cfg = dataclasses.replace(cfg, capacity_factor=0.25)  # force drops
    rng = jax.random.PRNGKey(0)
    x2d = jax.random.normal(rng, (64, 16), jnp.bfloat16)
    router = jax.random.normal(rng, (16, 4), jnp.float32)
    w_in = jax.random.normal(rng, (4, 16, 8), jnp.bfloat16)
    w_g = jax.random.normal(rng, (4, 16, 8), jnp.bfloat16)
    w_out = jax.random.normal(rng, (4, 8, 16), jnp.bfloat16)
    ids, probs, aux = _route(x2d, router, cfg)
    out = _local_dispatch_compute(x2d, ids, probs, w_in, w_g, w_out, 0, cfg)
    assert out.shape == (64, 16)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    # with drops, some rows are exactly zero (token fully dropped)
    zero_rows = (np.asarray(out, np.float32) == 0).all(axis=1).sum()
    assert zero_rows > 0
