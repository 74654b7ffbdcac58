"""GPipe-style pipeline parallelism over a ``stage`` mesh axis (optional
feature — DESIGN.md §5; default production meshes use data x model, but at
>100B scale a stage axis bounds per-device weight residency where FSDP
gathers become the bottleneck, e.g. nemotron train at 698 GB/device).

Mechanics: the layer stack (L, ...) is sharded onto S stages (L/S layers
each) via shard_map; activations flow stage-to-stage with
``lax.ppermute`` over M microbatches in the classic (M + S - 1)-step
schedule (bubble fraction (S-1)/(M+S-1)). Forward-differentiable: ppermute
transposes to the reverse permutation, so jax.grad works through the whole
pipeline (GPipe's recompute-per-stage corresponds to remat='full' on the
layer body).

Numerical equivalence with the sequential scan is asserted in
tests/test_pipeline.py on a 4-stage mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(layer_fn: Callable, stacked_params, x: jnp.ndarray,
                   mesh: Mesh, *, n_microbatches: int,
                   stage_axis: str = "stage") -> jnp.ndarray:
    """Run ``x`` through L stacked layers split across pipeline stages.

    layer_fn(params_slice, h) -> h applies ONE layer. stacked_params leaves
    have leading dim L with L % n_stages == 0; x: (B, ...) with
    B % n_microbatches == 0.
    """
    n_stages = mesh.shape[stage_axis]
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    assert lead % n_stages == 0, (lead, n_stages)
    assert x.shape[0] % n_microbatches == 0

    def stage_body(p_loc, x_full):
        r = lax.axis_index(stage_axis)
        s = n_stages
        m = n_microbatches
        mbs = x_full.reshape(m, x_full.shape[0] // m, *x_full.shape[1:])

        def local_layers(h):
            def body(h, p):
                return layer_fn(p, h), None
            h, _ = lax.scan(body, h, p_loc)
            return h

        perm = [(i, (i + 1) % s) for i in range(s)]
        carry = jnp.zeros_like(mbs[0])
        outputs = jnp.zeros_like(mbs)

        def step(state, t):
            carry, outputs = state
            inp = jnp.where(r == 0, mbs[jnp.clip(t, 0, m - 1)], carry)
            out = local_layers(inp)
            nxt = lax.ppermute(out, stage_axis, perm)
            idx = t - (s - 1)
            ok = (r == s - 1) & (idx >= 0) & (idx < m)
            written = outputs.at[jnp.clip(idx, 0, m - 1)].set(out)
            outputs = jnp.where(ok, written, outputs)
            return (nxt, outputs), None

        (carry, outputs), _ = lax.scan(step, (carry, outputs),
                                       jnp.arange(m + s - 1))
        # broadcast the last stage's collected outputs to every stage
        outputs = lax.psum(jnp.where(r == s - 1, outputs, 0), stage_axis)
        return outputs.reshape(x_full.shape)

    in_specs = (jax.tree.map(lambda _: P(stage_axis), stacked_params), P())
    return jax.shard_map(stage_body, mesh=mesh, in_specs=in_specs,
                     out_specs=P(), check_vma=False)(stacked_params, x)
