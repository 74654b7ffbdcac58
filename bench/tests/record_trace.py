"""Record the small device trace that ``bench/tests/test_trace.py`` reads.

    python bench/tests/record_trace.py OUT_DIR      # on a TPU

Under the profiler it runs, each inside a ``bench.tick`` annotation and
followed by a 20 ms sleep inside ``bench.wait_arrival``: four calls of a
jitted bf16 matmul named ``step`` (512x2048 @ 2048x2048) and two calls of
the DAISM Pallas GEMM (``*=pc3_tr:pallas``, 32x1024 @ 1024x1024). It
writes the ``.xplane.pb`` under OUT_DIR and prints the planes, lines and
event names it holds.
"""
from __future__ import annotations

import glob
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", "src"))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import Backend, DaismConfig, Variant
    from repro.kernels import daism_matmul_pallas

    assert jax.devices()[0].platform == "tpu", jax.devices()
    cfg = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS)

    def step(x, w):
        return x @ w

    step = jax.jit(step)
    x = jnp.ones((512, 2048), jnp.bfloat16)
    w = jnp.ones((2048, 2048), jnp.bfloat16)
    a = jnp.ones((32, 1024), jnp.bfloat16)
    b = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x, w).block_until_ready()
    daism_matmul_pallas(a, b, cfg).block_until_ready()
    calls = [lambda: step(x, w)] * 4 + [lambda: daism_matmul_pallas(a, b, cfg)] * 2
    jax.profiler.start_trace(out_dir)
    for call in calls:
        with jax.profiler.TraceAnnotation("bench.tick"):
            call().block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait_arrival"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print("trace", path, os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            names = []
            for e in events:
                if e.name not in names:
                    names.append(e.name)
            print("  line", repr(line.name), len(events), names[:12])
            if events:
                e = events[0]
                print("    first", e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:12]) if e.stats else {})


if __name__ == "__main__":
    main(sys.argv[1])
