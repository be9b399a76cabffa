"""The state-space dual scan's compiled kernels against its array form, on
the chip.

    python benchmark/tests/ssd_probe.py [L]

At granite-4.0-h-micro's widths (64 heads of 64 channels, state 128, chunks
of 256, one row of L = 16,384 positions by default): the output and the six
gradients of ``ops/ssd.py`` with ``impl='pallas'`` (compiled by Mosaic)
against ``impl='xla'``, as the largest difference over the largest value,
and the seconds a forward and a forward + backward take in each form (the
median of five, each ending in ``block_until_ready``).  One JSON line.  A
probe for the chip, not run by the benchmark.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> None:
    import jax
    import jax.numpy as jnp
    from deepfake_detection_tpu.ops.ssd import ssd_scan
    assert jax.default_backend() == "tpu", jax.default_backend()
    l = int(argv[0]) if argv else 16384
    h, p, n, chunk = 64, 64, 128, 256
    k = jax.random.split(jax.random.PRNGKey(30), 7)
    args = (jax.random.normal(k[0], (1, l, h, p), jnp.bfloat16),
            jnp.exp(jax.random.uniform(k[1], (1, l, h), jnp.float32,
                                       -6.9, -2.3)),
            -jax.random.uniform(k[2], (h,), jnp.float32, 1.0, 16.0),
            jax.random.normal(k[3], (1, l, n), jnp.bfloat16),
            jax.random.normal(k[4], (1, l, n), jnp.bfloat16),
            jnp.ones((h,), jnp.float32))
    w = jax.random.normal(k[6], (1, l, h, p), jnp.float32)

    def timed(f):
        jax.block_until_ready(f(*args))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    line = {"L": l, "chunk": chunk}
    outs = {}
    for impl in ("xla", "pallas"):
        fwd = jax.jit(lambda *a, impl=impl: ssd_scan(*a, chunk=chunk,
                                                     impl=impl))
        both = jax.jit(jax.value_and_grad(
            lambda *a, impl=impl: jnp.sum(ssd_scan(
                *a, chunk=chunk, impl=impl).astype(jnp.float32) * w),
            argnums=range(6)))
        line[impl + "_fwd_s"] = timed(fwd)
        line[impl + "_fwd_bwd_s"] = timed(both)
        outs[impl] = (fwd(*args),) + tuple(both(*args)[1])
    line["rel_err"] = {
        name: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32)))
                    / jnp.max(jnp.abs(b.astype(jnp.float32))))
        for name, a, b in zip(("y", "dx", "ddt", "da", "dB", "dC", "dD"),
                              outs["pallas"], outs["xla"])}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
