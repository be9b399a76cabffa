"""Flash vs XLA-dense attention microbenchmark (VERDICT r3 item 4).

Measures fwd and fwd+bwd wall time of ``ops.flash_attention`` against the
XLA dense path (``parallel.ring_attention.full_attention``) at ViT-B-like
shapes (L=196 head_dim 64) and long-sequence shapes where the O(L²) HBM
traffic of dense attention should lose to the O(L)-memory flash kernel.

Prints one JSON line per (impl, L) with ms/iter; on CPU the flash kernel
runs under the Pallas interpreter (orders of magnitude slow) so results
are only meaningful on a real TPU — the tool exists so the measurement is
one command on a chip::

    python tools/bench_attention.py [--iters 20] [--seqs 196,1024,4096]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--seqs", default="196,1024,4096")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepfake_detection_tpu.ops.flash_attention import flash_attention
    from deepfake_detection_tpu.parallel.ring_attention import full_attention

    dev = jax.devices()[0]
    dtype = getattr(jnp, args.dtype)
    rng = np.random.default_rng(0)

    def bench(fn, *xs) -> float:
        out = fn(*xs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1000

    for L in (int(s) for s in args.seqs.split(",")):
        shape = (args.batch, L, args.heads, args.head_dim)
        q, k, v = (jnp.asarray(rng.normal(size=shape), dtype)
                   for _ in range(3))
        impls = {
            "dense": jax.jit(full_attention),
            "flash": jax.jit(functools.partial(flash_attention,
                                               interpret=None)),
        }
        for name, fn in impls.items():
            # isolate each (impl, L) point: a dense-attention OOM at long L
            # must not kill the flash measurement at the same length
            try:
                fwd_ms = bench(fn, q, k, v)

                def loss(q, k, v, _fn=fn):
                    return _fn(q, k, v).astype(jnp.float32).sum()

                grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                bwd_ms = bench(grad, q, k, v)
            except Exception as e:  # noqa: BLE001 — record, continue
                # (XlaRuntimeError covers device OOM; Ctrl+C still raises)
                print(json.dumps({
                    "impl": name, "seq_len": L, "batch": args.batch,
                    "error": repr(e)[:300], "device": dev.device_kind,
                }), flush=True)
                continue
            # attention FLOPs: 2·(2·B·H·L²·D) matmuls fwd, ~2.5x more bwd
            flops_fwd = 4 * args.batch * args.heads * L * L * args.head_dim
            print(json.dumps({
                "impl": name, "seq_len": L, "batch": args.batch,
                "heads": args.heads, "head_dim": args.head_dim,
                "fwd_ms": round(fwd_ms, 3),
                "fwd_bwd_ms": round(bwd_ms, 3),
                "fwd_tflops": round(flops_fwd / fwd_ms / 1e9, 2),
                "dtype": args.dtype, "device": dev.device_kind,
            }), flush=True)


if __name__ == "__main__":
    main()
