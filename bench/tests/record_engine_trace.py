"""Record the small engine trace that ``bench/tests/test_spans.py`` reads.

    python bench/tests/record_engine_trace.py OUT_DIR      # on a TPU

A two-layer model at toy widths (``tinyllama_1_1b`` smoke, bf16) is served
by ``ServeEngine`` under the DAISM Pallas tier (``*=pc3_tr:pallas``), warm.
Under the profiler it serves one request (a 12-token prompt, 4 tokens out):
five ticks (admission, one prefill and three decode steps), each inside a
``bench.tick`` annotation and followed by a 40 ms sleep inside
``bench.wait_arrival``. Then one call of the flash attention kernel runs
inside ``bench.tick``. So the trace holds four step programs, both Pallas
kernels under their names, and the engine's own spans nested in the
benchmark's. It writes the ``.xplane.pb`` under OUT_DIR and prints the
step programs, the kernels' op names and the engine spans it holds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "..", ".."),
                os.path.join(HERE, "..", "..", "src")]

PROMPT, NEW_TOKENS = 12, 4


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from bench import spans, trace
    from repro.configs import get_config
    from repro.kernels import flash_attention
    from repro.models.registry import build_model
    from repro.serve import EngineConfig, Request, ServeEngine

    if jax.devices()[0].platform != "tpu":
        sys.exit(f"needs a TPU; JAX sees {jax.devices()}")
    cfg = dataclasses.replace(get_config("tinyllama_1_1b").smoke(n_layers=2),
                              param_dtype="bfloat16", compute_dtype="bfloat16")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, EngineConfig(
        num_slots=4, max_seq=64, prefill_chunk=16,
        tiers=(("approx", "*=pc3_tr:pallas"),)))

    def serve(prompt, ticks_traced):
        engine.submit(Request(prompt=prompt, max_new_tokens=NEW_TOKENS,
                              policy="approx"))
        while True:
            with jax.profiler.TraceAnnotation("bench.tick"):
                more = engine.tick()
            if ticks_traced:
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(0.04)
            if not more:
                return

    attend = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    qkv = [jnp.ones((8, 512, 64), jnp.bfloat16)] * 3
    serve(list(range(1, PROMPT + 1)), False)           # compiles both steps
    attend(*qkv).block_until_ready()
    jax.profiler.start_trace(out_dir)
    serve(list(range(2, PROMPT + 2)), True)
    with jax.profiler.TraceAnnotation("bench.tick"):
        attend(*qkv).block_until_ready()
    jax.profiler.stop_trace()

    path = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print("trace", path, os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in trace.device_planes(data, [0]):
        lines = {ln.name: ln for ln in plane.lines}
        print("modules", [name for name, *_ in trace._events(
            lines[trace.MODULES_LINE])])
        print("kernels", sorted({name for name, _, _, ev in trace._events(
            lines[trace.OPS_LINE]) if trace.is_kernel(name, trace._stats(ev))}))
    counts = collections.Counter(name for name, *_ in spans.program_spans(data))
    print("engine spans", dict(counts))
    summary = spans.summarize(os.path.dirname(path), [0])
    summary.pop("engine_spans")
    print("spans", summary)


if __name__ == "__main__":
    main(sys.argv[1])
