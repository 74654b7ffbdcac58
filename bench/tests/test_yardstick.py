"""The yardstick's pieces on the CPU: operation counts against the
program's parameter shapes, the approximate-multiplier table against the
program's multiplier, weights made again leaf by leaf, traffic that does
not depend on the seed in size.

    python -m pytest bench/tests
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import flops, reference, traffic, weights  # noqa: E402


def conf(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,count", [("starcoder2_15b", 2_139_381_760),
                                        ("nemotron_4_340b", 4_633_900_032)])
def test_param_count_matches_program(name, count):
    from repro.models.registry import build_model

    sys.path.insert(0, os.path.join(ROOT, "bench"))
    from run import program_config

    model = build_model(program_config(conf(name)))
    shapes, _ = model.init(jax.random.PRNGKey(0), abstract=True)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == count == flops.param_count(conf(name)["model"])


def test_step_flops_count_every_weight_once():
    m = conf("starcoder2_15b")["model"]
    gemm_params = sum(k * n * c for _, k, n, c in flops.gemm_sites(m))
    # one decode token at position 0 reads one (query, key) pair per layer
    attn = 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    assert flops.step_flops(m, [(1, 0)]) == 2 * gemm_params + attn
    assert flops.attention_pairs([(3, 5)]) == 6 + 7 + 8


def test_pc3_tr_table_matches_program_multiplier():
    from repro.core.config import Variant
    from repro.core.floatmul import approx_mul_to_f32

    frac = np.arange(128, dtype=np.uint16)
    one = np.uint16(0x3F80)   # bf16 1.0: exponent 127, fraction 0
    x = jax.lax.bitcast_convert_type(jnp.asarray(one | frac), jnp.bfloat16)
    got = approx_mul_to_f32(x[:, None], x[None, :], Variant.PC3_TR)
    np.testing.assert_array_equal(np.asarray(got), reference.pc3_tr_table())


def test_approx_dot_matches_program_gemm():
    from repro.core import Backend, DaismConfig, Variant, daism_matmul

    ka, kw = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (16, 256), jnp.bfloat16)
    w = jax.random.normal(kw, (256, 64), jnp.bfloat16)
    got = reference.approx_dot(a, w)
    want = daism_matmul(a, w, DaismConfig(variant=Variant.PC3_TR,
                                          backend=Backend.JNP))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_weights_made_again_leaf_by_leaf():
    from repro.models.registry import build_model

    sys.path.insert(0, os.path.join(ROOT, "bench"))
    from run import program_config

    small = dict(conf("starcoder2_15b"), model=dict(
        conf("starcoder2_15b")["model"], n_layers=2, d_model=64, n_heads=4,
        kv_heads=2, head_dim=16, d_ff=128, vocab=256))
    model = build_model(program_config(small))
    shapes, _ = model.init(jax.random.PRNGKey(0), abstract=True)
    seed = 2**31 + 12345
    params = weights.make_params(shapes, seed)
    src = weights.WeightSource(weights.tree_spec(shapes), seed)
    flat = {tuple(k.key for k in kp): v for kp, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    for path, value in flat.items():
        if path[0] == "blocks":
            for layer in range(value.shape[0]):
                np.testing.assert_array_equal(src.get(path, layer),
                                              value[layer])
        else:
            np.testing.assert_array_equal(src.get(path, None), value)


@pytest.mark.parametrize("mix", ["closed4", "poisson-chat", "poisson-code"])
def test_traffic_same_sizes_for_every_seed(mix):
    with open(os.path.join(ROOT, "bench", "traffic", mix + ".json")) as f:
        wl = json.load(f)
    a = traffic.schedule(wl, 1, 40, 1000)
    b = traffic.schedule(wl, 2**33 + 7, 40, 1000)
    for key in ("max_new_tokens",):
        assert sorted(getattr(p, key) for p in a) == \
            sorted(getattr(p, key) for p in b)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert [p.prompt for p in a] != [p.prompt for p in b]
    if wl["loop"] == "open":
        assert max(p.due_s for p in a) < 40
        assert np.isclose(max(p.due_s for p in a), max(p.due_s for p in b))
    else:
        # a window reaches only the first requests: they are the same sizes
        assert [(len(p.prompt), p.max_new_tokens) for p in a] == \
            [(len(p.prompt), p.max_new_tokens) for p in b]
        first = [p.max_new_tokens for p in a[:12]]
        mid = (wl["output"]["min"] + wl["output"]["max"]) / 2
        assert abs(np.mean(first) - mid) < 1.5
