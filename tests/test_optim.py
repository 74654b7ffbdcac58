"""Optimizer + schedule + gradient compression tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.optim import (AdamWConfig, apply_updates, cosine_with_warmup,
                         init_state, quantize_int8)
from repro.optim.grad_compress import compressed_psum


def test_adamw_converges_on_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0], jnp.float32)}
    opt = init_state(params)
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0, grad_clip=1e9)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, opt, metrics = apply_updates(params, g, opt, cfg)
    assert float(loss(params)) < 1e-2
    assert int(opt.step) == 200


def test_grad_clip_controls_norm():
    params = {"w": jnp.ones((4,), jnp.float32)}
    opt = init_state(params)
    cfg = AdamWConfig(lr=0.0, grad_clip=1.0)
    g = {"w": jnp.full((4,), 100.0)}
    _, _, metrics = apply_updates(params, g, opt, cfg)
    assert float(metrics["grad_norm"]) == 200.0  # reported pre-clip


def test_master_weights_preserve_precision():
    """bf16 params + f32 master: tiny updates must not be lost."""
    params = {"w": jnp.ones((1,), jnp.bfloat16)}
    opt = init_state(params)
    cfg = AdamWConfig(lr=1e-5, weight_decay=0.0)
    g = {"w": jnp.ones((1,), jnp.bfloat16)}
    for _ in range(50):
        params, opt, _ = apply_updates(params, g, opt, cfg)
    # master moved even though each step is below bf16 resolution
    assert float(opt.master["w"][0]) < 1.0 - 1e-4


def test_schedule_shapes():
    s = cosine_with_warmup(jnp.int32(0), warmup=10, total=100)
    assert float(s) == 0.0
    s = cosine_with_warmup(jnp.int32(10), warmup=10, total=100)
    assert abs(float(s) - 1.0) < 1e-6
    s_end = cosine_with_warmup(jnp.int32(100), warmup=10, total=100,
                               min_ratio=0.1)
    assert abs(float(s_end) - 0.1) < 1e-6


def test_quantize_int8_roundtrip_error():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(1000,)) * 1e-3, jnp.float32)
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = quantize_int8(g, scale)
    deq = np.asarray(q, np.float32) * float(scale)
    cos = np.dot(deq, np.asarray(g)) / (
        np.linalg.norm(deq) * np.linalg.norm(np.asarray(g)))
    assert cos > 0.999


def test_compressed_psum_modes_single_device():
    """With a single device axis the mean must equal the input (up to
    quantization error)."""
    mesh = make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(64,)),
                          jnp.float32)}
    for mode in ("none", "bf16", "int8"):
        out = jax.shard_map(
            lambda t: compressed_psum(t, ("data",), mode=mode),
            mesh=mesh, in_specs=(jax.sharding.PartitionSpec(),),
            out_specs=jax.sharding.PartitionSpec(), check_vma=False)(g)
        a, b = np.asarray(out["w"]), np.asarray(g["w"])
        cos = np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cos > 0.99, mode
