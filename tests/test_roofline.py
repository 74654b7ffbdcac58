"""Roofline methodology tests: the scan-undercount problem, the jaxpr FLOP
counter, the HLO collective parser, and the probe-correction method
validated against fully-unrolled ground truth."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.roofline import analysis as ra
from repro.roofline.flops import count_flops


def _scan_mm(unroll=1):
    def body(x, w):
        return jnp.dot(x, w), None

    return lambda x, w: lax.scan(body, x, w, unroll=unroll)[0]


def test_xla_counts_scan_body_once():
    """The motivating defect: cost_analysis under-reports scanned layers."""
    w = jnp.zeros((8, 128, 128), jnp.bfloat16)
    x = jnp.zeros((128, 128), jnp.bfloat16)
    cs = jax.jit(_scan_mm()).lower(x, w).compile().cost_analysis()
    cu = jax.jit(_scan_mm(unroll=8)).lower(x, w).compile().cost_analysis()
    assert float(cs["flops"]) < 0.2 * float(cu["flops"])


def test_jaxpr_flops_scan_equals_unrolled():
    w = jnp.zeros((8, 128, 128), jnp.bfloat16)
    x = jnp.zeros((128, 128), jnp.bfloat16)
    fs = count_flops(_scan_mm(), x, w)
    want = 8 * 2 * 128 ** 3
    assert abs(fs - want) / want < 0.01


def test_jaxpr_flops_grad_factor():
    w = jnp.zeros((8, 128, 128), jnp.bfloat16)
    x = jnp.zeros((128, 128), jnp.bfloat16)
    f = count_flops(_scan_mm(), x, w)
    g = count_flops(jax.grad(lambda x, w: (_scan_mm()(x, w) ** 2).sum(),
                             argnums=1), x, w)
    assert 2.8 < g / f < 3.3  # backward ~ 2x forward (+ fwd)


def test_jaxpr_flops_remat_recompute_counted():
    w = jnp.zeros((8, 2, 128, 128), jnp.bfloat16)
    x = jnp.zeros((128, 128), jnp.bfloat16)

    def mk(remat):
        def body(x, w):
            def f(x, w):
                return jnp.dot(jax.nn.relu(jnp.dot(x, w[0])), w[1])
            if remat:
                f = jax.checkpoint(f)
            return f(x, w), None
        return lambda x, w: (lax.scan(body, x, w)[0] ** 2).sum()

    f_plain = count_flops(jax.grad(mk(False), argnums=1), x, w)
    f_remat = count_flops(jax.grad(mk(True), argnums=1), x, w)
    assert f_remat > 1.1 * f_plain  # recompute visible


def test_collective_parser_on_hlo_text():
    hlo = """
  %ag = bf16[8,2048]{1,0} all-gather(bf16[8,128]{1,0} %x), replica_groups=[32,16]<=[512], dimensions={1}
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %y), replica_groups={{0,1,2,3}}, to_apply=%add
  %a2a = bf16[16,64]{1,0} all-to-all(bf16[16,64]{1,0} %z), replica_groups=[2,16]<=[32]
  %cp = u32[4]{0} collective-permute(u32[4]{0} %w), source_target_pairs={{0,1}}
"""
    stats = ra.collective_bytes_from_hlo(hlo, default_group=8)
    # all-gather: 8*2048*2 bytes * (15/16)
    ag = 8 * 2048 * 2 * (15 / 16)
    ar = 1024 * 4 * 2 * (3 / 4)
    a2a = 16 * 64 * 2 * (15 / 16)
    cp = 4 * 4 * 1.0
    assert abs(stats.by_op["all-gather"] - ag) < 1
    assert abs(stats.by_op["all-reduce"] - ar) < 1
    assert abs(stats.by_op["all-to-all"] - a2a) < 1
    assert abs(stats.by_op["collective-permute"] - cp) < 1
    assert stats.count == 4


def test_probe_correction_matches_full_unroll():
    """The dry-run's scan correction: bytes(corrected) must approximate the
    fully-unrolled compile's bytes within 15%."""
    L = 8

    def model(unroll):
        def body(x, w):
            h = jax.nn.relu(jnp.dot(x, w))
            return jnp.dot(h, w.T), None

        def f(x, w):
            return lax.scan(body, x, w, unroll=unroll)[0].sum()
        return f

    x = jnp.zeros((64, 256), jnp.bfloat16)
    w = jnp.zeros((L, 256, 256), jnp.bfloat16)

    def bytes_of(unroll):
        c = jax.jit(model(unroll)).lower(x, w).compile().cost_analysis()
        return float(c["bytes accessed"])

    b1, b2, bfull = bytes_of(1), bytes_of(2), bytes_of(L)
    corrected = b1 + (b2 - b1) * (L - 1) / (2 - 1)
    assert abs(corrected - bfull) / bfull < 0.15


def test_model_flops_estimate_moe_active_params():
    from repro.configs import get_config
    dense = ra.model_flops_estimate(get_config("tinyllama_1_1b"), "train",
                                    4096, 256)
    # 6 * 1.1e9 * (4096*256)
    want = 6 * 1.10e9 * 4096 * 256
    assert abs(dense - want) / want < 0.05
    moe = ra.model_flops_estimate(get_config("qwen3_moe_235b"), "train",
                                  4096, 256)
    # active ~22B of 235B
    want_moe = 6 * 22.5e9 * 4096 * 256
    assert abs(moe - want_moe) / want_moe < 0.15


def test_chip_peaks_table_is_keyed_by_device_kind():
    v5e = ra.chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        ra.chip_peaks("cpu")
