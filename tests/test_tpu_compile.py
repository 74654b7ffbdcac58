"""The Pallas kernels compile for a TPU v5e at TinyLlama-1.1B widths.

Interpret mode, which every other kernel test runs in, hides what the
Mosaic compiler refuses (unaligned slices, in-kernel gathers, VMEM
overflow). These tests compile each kernel for a *described* v5e chip —
the TPU compiler is installed, no chip is needed — and check that the
compiled program holds the custom kernel. Nothing runs, so they say
nothing about results or speed.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import Backend, DaismConfig, Variant
from repro.kernels import daism_matmul_pallas
from repro.kernels.daism_matmul import daism_matmul_kernel
from repro.kernels.flash_attention import flash_attention

D_MODEL, D_FF, VOCAB, HEAD_DIM, HEADS = 2048, 5632, 32000, 64, 32
DECODE_M, PREFILL_M = 32, 512     # one padded decode tile; 4 rows x 128
GEMM_KN = ((D_MODEL, D_FF), (D_FF, D_MODEL), (D_MODEL, VOCAB))


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]
    with _no_persistent_cache():
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("variant", [Variant.EXACT, Variant.PC3_TR],
                         ids=lambda v: v.value)
@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", GEMM_KN, ids=[f"{k}x{n}" for k, n in GEMM_KN])
def test_daism_matmul_kernel_compiles(one_chip, variant, m, k, n):
    text = _compiled_text(
        lambda a, w: daism_matmul_kernel(a, w, variant=variant,
                                         interpret=False),
        one_chip, (m, k), (k, n))
    assert "tpu_custom_call" in text
    assert "%daism_matmul" in text    # the kernel's name, as traces show it


@pytest.mark.parametrize("variant", [None, Variant.PC3_TR],
                         ids=["exact", "pc3_tr"])
def test_flash_attention_compiles(one_chip, variant):
    shape = (HEADS, 1024, HEAD_DIM)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, variant=variant,
                                        interpret=False),
        one_chip, shape, shape, shape)
    assert "tpu_custom_call" in text
    assert "%flash_attention" in text


def _compiled_pallas(sharding, m, k, n):
    cfg = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS,
                      interpret=False)
    return _compiled_text(lambda a, w: daism_matmul_pallas(a, w, cfg),
                          sharding, (m, k), (k, n))


def _kernel_rows(text):
    """Row counts of the kernel outputs in a compiled program."""
    return {int(r) for r in re.findall(r"f32\[(\d+),\d+\][^\n]*custom-call",
                                       text)}


def test_daism_matmul_pallas_compiles_padded_decode(one_chip):
    """The padding wrapper at a 4-row decode batch: M padded to the 8-row
    tile ``row_tile`` fits to it, not to block_m = 32."""
    text = _compiled_pallas(one_chip, 4, D_MODEL, D_FF)
    assert "tpu_custom_call" in text
    assert _kernel_rows(text) == {8}


# StarCoder2-15B's decode GEMMs: MLP up, MLP down, lm_head.
SC2_KN = ((6144, 24576), (24576, 6144), (6144, 49152))
# ... and its prefill GEMMs: those, the attention's q/o and k/v.
SC2_PREFILL_KN = SC2_KN + ((6144, 6144), (6144, 512))


@pytest.mark.parametrize("m,rows", [(4, 8), (12, 16)])
@pytest.mark.parametrize("k,n", SC2_KN, ids=[f"{k}x{n}" for k, n in SC2_KN])
def test_daism_matmul_pallas_fits_row_tile_at_sc2_widths(one_chip, k, n, m,
                                                         rows):
    """Row tiles of 8 and 16, under the N tile ``col_tile`` widens to
    match (2048 and 1024 where N allows), compile at StarCoder2-15B
    widths."""
    text = _compiled_pallas(one_chip, m, k, n)
    assert "tpu_custom_call" in text
    assert _kernel_rows(text) == {rows}


@pytest.mark.parametrize("k,n", SC2_PREFILL_KN,
                         ids=[f"{k}x{n}" for k, n in SC2_PREFILL_KN])
def test_daism_matmul_pallas_compiles_prefill_at_sc2_widths(one_chip, k, n):
    """A 128-row prefill GEMM (4 slots x chunk 32) runs (32, 512) tiles,
    which compile at StarCoder2-15B widths."""
    text = _compiled_pallas(one_chip, 128, k, n)
    assert "tpu_custom_call" in text
    assert _kernel_rows(text) == {128}
