"""GPipe pipeline parallelism: numerical equivalence with sequential scan.

Runs in a subprocess (needs 4 fake devices for a 4-stage mesh; the main
pytest process keeps the default single-device environment)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import pipeline_apply

    mesh = make_mesh((4,), ("stage",))
    L, B, D = 8, 16, 32
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(L, D, D)) / np.sqrt(D),
                               jnp.float32),
              "b": jnp.asarray(rng.normal(size=(L, D)) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)

    def layer(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    # sequential reference
    def seq(params, x):
        def body(h, p):
            return layer(p, h), None
        h, _ = lax.scan(body, x, params)
        return h

    ref = seq(params, x)
    for m in (2, 4, 8):
        out = pipeline_apply(layer, params, x, mesh, n_microbatches=m)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    # gradients flow through the pipeline (ppermute transpose)
    g_pipe = jax.grad(lambda p: (pipeline_apply(
        layer, p, x, mesh, n_microbatches=4) ** 2).sum())(params)
    g_seq = jax.grad(lambda p: (seq(p, x) ** 2).sum())(params)
    for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    print("PIPELINE-OK")
""")


@pytest.mark.slow
def test_pipeline_matches_sequential():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert "PIPELINE-OK" in out.stdout, out.stderr[-3000:]
