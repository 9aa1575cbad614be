"""Record the small TPU trace that test_trace_reduce.py reads.

    python3 bench/tests/record_small_trace.py   # on a machine with a TPU

Traces a matmul, an elementwise op and the program's Pallas
paged-attention kernel three times under the harness's `bench.window` /
`bench.step` / `bench.submit` annotations, and writes the profile under
`bench/tests/data/small_trace/`; the `.xplane.pb` it holds is what
`data/small_trace.xplane.pb` was copied from.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_decode_gqa

    mm = jax.jit(lambda a: (a @ a).sum())
    ew = jax.jit(lambda a: jnp.tanh(a) * 2)
    att = jax.jit(paged_decode_gqa)
    a = jnp.ones((512, 512), jnp.bfloat16)
    q = jnp.ones((4, 2, 4, 64), jnp.bfloat16)
    pool = jnp.ones((17, 4, 2, 64), jnp.bfloat16)
    tables = jnp.asarray(np.arange(16).reshape(4, 4), jnp.int32)
    pos = jnp.asarray([3, 7, 11, 15], jnp.int32)

    def once():
        jax.block_until_ready(mm(a))
        jax.block_until_ready(ew(a))
        jax.block_until_ready(att(q, pool, pool, tables, pos))

    once()  # compile outside the trace
    jax.profiler.start_trace(os.path.join(ROOT, "bench", "tests", "data", "small_trace"))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                once()
            with jax.profiler.TraceAnnotation("bench.submit"):
                time.sleep(0.002)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main()
