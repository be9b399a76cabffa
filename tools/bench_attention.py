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

``--bwd`` (TPU only) times the backward of the flash kernels alone, the
split pair (dK/dV, then dQ) against the one fused kernel, at the attention
shapes of the three sequence cells (heads, kv heads, value width, length,
block, window: ``BWD_SHAPES``).  A launch's time comes from ``CHAIN``
launches in one program, each fed by the one before (the next launch's ``k``
is this one's plus 1e-30 of a ``dq`` element; one pass over ``k``, under 0.5%
of a launch), because one launch by the host's clock is the dispatch
(PERF.md section 7, PR 29 (c)).  One JSON line a shape, with whether the two
forms' gradients are the same bits on this device.  A probe, not the cell:
PRs 27, 29 and 31 all found the cell to disagree with a kernel timed alone.
``--step CELL [fused] [split]`` (TPU only) asks the cell: a sequence cell's
own train step, as the benchmark's driver builds it, ten steps a form by the
host's clock; ``split`` rebinds the op's predicate so that every layer takes
the dK/dV and dQ pair.
``--fwd [shape ...]`` (TPU only) times the forward launch alone at the same
shapes, chained the same way (the next launch's ``q`` is this one's plus
1e-30 of an ``out`` element), and prints a sha256 of one launch's ``out``
and ``lse``: run it on two commits and compare the digests for their bits.
``--sparse-bwd`` (TPU only) times the learned sparse attention's backward
launch alone (``ops/sparse_attention.py:_bwd_pallas``) at the shape of
``train_keye_dsa_32k`` (``SPARSE_SHAPE``), on a selection its own kernel
made from seeded operands, chained the same way (the next launch's ``do``
is this one's plus 1e-30 of a ``dq`` element), and prints the launch's
Mosaic kernels and a sha256 of each of its six gradients: run it on two
commits and compare the digests for their bits.
``--sparse-fwd`` (TPU only) times the three launches before it alone at
the same shape, the selection (``_select_pallas``, the next launch's
``qi`` fed), the forward (``_fwd_pallas``) and the indexer's loss
(``_kl_pallas``, both the next launch's ``q`` fed), each from a chain of
``CHAIN``, with the operands each launcher names and a sha256 of what they
make.  Both probes hand a launcher its operands by its parameters' names,
so they run with either form of the package at the same path.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# name: (rows a launch, L, q heads, k heads, v heads, d, dv, block, window)
# as models/phi4flash.py, granite4h.py and lfm2moe.py call the op in the
# cells train_phi4flash_long, train_granite4h_long, train_lfm2moe_8k (a
# microbatch of 2 rows); every scale is a power of two, folded into q
BWD_SHAPES = {
    "phi4_full": (1, 16384, 40, 20, 10, 64, 128, 1024, None),
    "phi4_window": (1, 16384, 40, 20, 10, 64, 128, 512, 512),
    "granite_full": (1, 16384, 32, 8, 8, 64, 64, 1024, None),
    "lfm2_full": (2, 8192, 32, 8, 8, 64, 64, 1024, None),
}
CHAIN = 8
# rows, L, q heads, k heads, d, indexer heads, indexer width, topk: the
# attention of models/keyevl2.py in train_keye_dsa_32k
SPARSE_SHAPE = (1, 32768, 32, 4, 128, 16, 64, 2048)


def _chain_ms(step, x, *rest) -> float:
    """Device ms of one launch of ``step(x, *rest)`` from ``CHAIN`` launches
    in one program, each fed by the one before: the next ``x`` is this one's
    plus 1e-30 of the first output's first element, the others' first
    elements are kept."""
    import statistics

    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x, *rest):
        keep = jnp.float32(0)
        for _ in range(CHAIN):
            first, *others = step(x, *rest)
            x = x + (first[0, 0, 0].astype(jnp.float32) * 1e-30
                     ).astype(x.dtype)
            for o in others:
                keep = keep + o[0, 0, 0]
        return x, keep
    jax.block_until_ready(run(x, *rest))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x, *rest))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times) / CHAIN


def _operands(fa, name):
    """(q, k, v, do) of ``BWD_SHAPES[name]`` in the kernels' padded layout,
    bf16, the power-of-two scale folded into q as the op folds it."""
    import jax
    import jax.numpy as jnp

    b, l, h, hk, hv, d, dv, block, window = BWD_SHAPES[name]
    _, _, lpq, lpk = fa._blocks(l, block, block)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def operand(key, heads, width, lp):
        x = jax.random.normal(key, (b * heads, l, width), jnp.bfloat16)
        return jnp.pad(x, ((0, 0), (0, lp - l),
                           (0, fa._round_up(width, 128) - width)))
    q = operand(keys[0], h, d, lpq) * d ** -0.5
    k, v = operand(keys[1], hk, d, lpk), operand(keys[2], hv, dv, lpk)
    return q, k, v, operand(keys[3], h, dv, lpq)


def _static(fa, name):
    """(the kernels' positional statics, their keywords) at a shape."""
    import jax.numpy as jnp

    _, l, *_, block, window = BWD_SHAPES[name]
    bq, bk, _, _ = fa._blocks(l, block, block)
    return (1.0, bq, bk, True, l, False), dict(window=window,
                                               dot_dtype=jnp.bfloat16)


def fwd_probe(names) -> None:
    import hashlib
    import importlib

    import jax
    import numpy as np

    fa = importlib.import_module("deepfake_detection_tpu.ops.flash_attention")
    assert jax.default_backend() == "tpu", jax.default_backend()

    def probe(name):
        b, l, h, hk, hv, d, dv, block, window = BWD_SHAPES[name]
        q, k, v, _ = _operands(fa, name)
        static, kw = _static(fa, name)

        def fwd(q, k, v):
            return fa._fwd(q, k, v, *static, **kw)
        out, lse = jax.jit(fwd)(q, k, v)
        visited = b * h * fa.tile_census(l, block, block, True,
                                         window)["fwd"]["visited"]
        row = {"shape": name, "rows": b, "seq_len": l, "heads": [h, hk, hv],
               "head_dims": [d, dv], "block": block, "window": window,
               "visited_tiles": visited, "chain": CHAIN,
               "fwd_ms": _chain_ms(fwd, q, k, v),
               "out_sha256": hashlib.sha256(
                   np.asarray(out).tobytes()).hexdigest(),
               "lse_sha256": hashlib.sha256(
                   np.asarray(lse).tobytes()).hexdigest(),
               "device": jax.devices()[0].device_kind}
        row["fwd_us_per_tile"] = 1e3 * row["fwd_ms"] / visited
        print(json.dumps(row), flush=True)

    for name in names:
        probe(name)


def bwd_probe(names) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("deepfake_detection_tpu.ops.flash_attention")
    assert jax.default_backend() == "tpu", jax.default_backend()

    def probe(name):
        b, l, h, hk, hv, d, dv, block, window = BWD_SHAPES[name]
        q, k, v, do = _operands(fa, name)
        static, kw = _static(fa, name)
        out, lse = jax.jit(lambda q, k, v: fa._fwd(q, k, v, *static, **kw))(
            q, k, v)
        delta = fa._delta(do, out)

        def split(k, q, v, do, lse, delta):
            dk, dv_ = fa._bwd_dkv(q, k, v, do, lse, delta, *static, **kw)
            dq = fa._bwd_dq(q, k, v, do, lse, delta, *static, **kw)
            return dq, dk, dv_

        def fused(k, q, v, do, lse, delta):
            return fa._bwd_fused(q, k, v, do, lse, delta, *static, **kw)

        rest = (q, v, do, lse, delta)
        same = [bool(np.array_equal(np.asarray(a.astype(jnp.float32)),
                                    np.asarray(b_.astype(jnp.float32))))
                for a, b_ in zip(jax.jit(split)(k, *rest),
                                 jax.jit(fused)(k, *rest))]
        visited = b * h * fa.tile_census(l, block, block, True,
                                         window)["bwd"]["visited"]
        row = {"shape": name, "rows": b, "seq_len": l, "heads": [h, hk, hv],
               "head_dims": [d, dv], "block": block, "window": window,
               "fused_bwd": fa.fused_bwd(q.shape[1], q.shape[2]),
               "visited_tiles": visited, "chain": CHAIN,
               "split_ms": _chain_ms(split, k, *rest),
               "fused_ms": _chain_ms(fused, k, *rest),
               "bit_equal": dict(zip(("dq", "dk", "dv"), same)),
               "device": jax.devices()[0].device_kind}
        row["split_us_per_tile"] = 1e3 * row["split_ms"] / visited
        row["fused_us_per_tile"] = 1e3 * row["fused_ms"] / visited
        print(json.dumps(row), flush=True)

    for name in names:
        probe(name)


def _sparse_operands(sa):
    """The seeded operands of ``SPARSE_SHAPE`` in the kernels' layout,
    the selection its own kernel made of them and the forward's outputs,
    by the launchers' parameter names, so that one probe calls either
    form of the package (:func:`_sparse_call`); the cotangents ``do``,
    ``gkl``; the launches (name: (launcher, the operand its chain feeds))."""
    import jax
    import jax.numpy as jnp

    b, l, h, hk, d, nj, e, topk = SPARSE_SHAPE
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    normal = lambda i, *s, dt=jnp.bfloat16: jax.random.normal(  # noqa: E731
        keys[i], s, jnp.float32).astype(dt)
    q, k, v = normal(0, b, h, l, d), normal(1, b, hk, l, d), \
        normal(2, b, hk, l, d)
    qi, ki, w = sa._index_layout(
        normal(3, b, l, nj, e), normal(4, b, l, e),
        normal(5, b, l, nj, dt=jnp.float32) * (nj * e) ** -0.5, q.dtype)
    named = dict(q=q, k=k, v=v, qi=qi, ki=ki, w=w, topk=topk,
                 scale=d ** -0.5, interpret=False)
    launches = {"select": (sa._select_pallas, "qi"),
                "fwd": (sa._fwd_pallas, "q"), "kl": (sa._kl_pallas, "q")}
    sel = _sparse_call(sa._select_pallas, named)
    # a row's statistics as the kernels take them, (B, 1, L)
    named.update({n: x[:, None] if x.ndim == 2 else x
                  for n, x in sel._asdict().items() if x is not None})
    named.update(zip(("o", "lse", "ilse"), _sparse_call(sa._fwd_pallas,
                                                        named)))
    named["kl"] = _sparse_call(sa._kl_pallas, named)
    return named, normal(6, b, h, l, d), normal(7, b, l, dt=jnp.float32), \
        launches


def _sparse_call(launcher, named, **over):
    """``launcher`` on the operands ``named`` gives by its parameters'
    names, ``over`` taking precedence."""
    args = dict(named, **over)
    return launcher(**{p: args[p] for p in _params(launcher)})


def _params(launcher):
    import inspect
    return list(inspect.signature(launcher).parameters)


def _sparse_chain_ms(step, x, *rest) -> float:
    """Device ms of one launch of ``step(x, *rest)`` from ``CHAIN``
    launches in one program, each fed by the one before (the next ``x`` is
    this one's plus 1e-30 of the first output's first element), from the
    median of five."""
    import statistics

    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(x, *rest):
        keep = jnp.float32(0)
        for _ in range(CHAIN):
            first, *others = jax.tree.leaves(step(x, *rest))
            x = x + (first.reshape(-1)[0].astype(jnp.float32) * 1e-30
                     ).astype(x.dtype)
            for o in others:
                keep = keep + o.reshape(-1)[0].astype(jnp.float32)
        return x, keep
    jax.block_until_ready(chain(x, *rest))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x, *rest))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times) / CHAIN


def _sparse_cells(sa, l):
    return sum(sa._last_k(i, sa.BLOCK_Q, sa.BLOCK_K) + 1
               for i in range(l // sa.BLOCK_Q))


def _digest(x) -> str:
    import hashlib

    import numpy as np
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()


def sparse_fwd_probe() -> None:
    import importlib

    import jax

    sa = importlib.import_module("deepfake_detection_tpu.ops.sparse_attention")
    assert jax.default_backend() == "tpu", jax.default_backend()
    named, _, _, launches = _sparse_operands(sa)
    b, l, h, hk, d, nj, e, topk = SPARSE_SHAPE
    row = {"shape": "keye_dsa_32k", "seq_len": l, "heads": [h, hk, nj],
           "topk": topk, "selected_pairs": int(named["counts"][0]),
           "chain": CHAIN}
    arrays = {n: x for n, x in named.items() if isinstance(x, jax.Array)}
    statics = {n: x for n, x in named.items() if n not in arrays}
    for name, (launcher, fed) in launches.items():
        operands = [n for n in _params(launcher) if n in arrays]

        def step(x, rest, launcher=launcher, fed=fed):
            return _sparse_call(launcher, dict(statics, **rest), **{fed: x})
        row[name + "_ms"] = _sparse_chain_ms(
            step, arrays[fed], {n: arrays[n] for n in operands if n != fed})
        row[name + "_operands"] = operands
    cells = _sparse_cells(sa, l)
    row["fwd_us_per_cell"] = 1e3 * row["fwd_ms"] / cells
    row["kl_us_per_cell"] = 1e3 * row["kl_ms"] / cells
    row["sha256"] = {n: _digest(named[n]) for n in
                     ("thr", "cut", "counts", "ilse", "bits", "o", "lse",
                      "kl") if n in named}
    row["device"] = jax.devices()[0].device_kind
    print(json.dumps(row), flush=True)


def sparse_bwd_probe() -> None:
    import importlib

    import jax

    sa = importlib.import_module("deepfake_detection_tpu.ops.sparse_attention")
    assert jax.default_backend() == "tpu", jax.default_backend()
    named, do, gkl, _ = _sparse_operands(sa)
    b, l, h, hk, d, nj, e, topk = SPARSE_SHAPE
    res = tuple(named[n] for n in ("q", "k", "v", "qi", "ki", "w", "thr",
                                   "cut", "o", "lse", "ilse"))
    scale = named["scale"]

    def bwd(do, res, gkl):
        return sa._bwd_pallas(res, do, gkl, scale, False)

    one = jax.jit(bwd)
    grads = one(do, res, gkl)
    bwd_ms = _sparse_chain_ms(bwd, do, res, gkl)
    text = one.lower(do, res, gkl).compile().as_text()
    print(json.dumps({
        "shape": "keye_dsa_32k", "seq_len": l, "heads": [h, hk, nj],
        "topk": topk, "selected_pairs": int(named["counts"][0]),
        "kernels": text.count("tpu_custom_call"), "chain": CHAIN,
        "bwd_ms": bwd_ms, "bwd_us_per_cell": 1e3 * bwd_ms / _sparse_cells(
            sa, l),
        "sha256": {name: _digest(g) for name, g in zip(
            ("dq", "dk", "dv", "dqi", "dki", "dw"), grads)},
        "device": jax.devices()[0].device_kind}), flush=True)


def cell_step(cell_name: str, forms) -> None:
    import gc
    import importlib
    import statistics

    import jax

    from benchmark.drivers import train_seq as D
    from benchmark.lib import manifest as M
    fa = importlib.import_module("deepfake_detection_tpu.ops.flash_attention")
    cell = M.Cell(cell_name)
    D.require_chips(cell.chips)
    D.setup_cache(cell.cache_dir)
    seed, predicate = 20261005, fa.fused_bwd
    for form in forms:
        fa.fused_bwd = predicate if form == "fused" else \
            (lambda *a, **kw: False)
        built = D.TokenBuilt(cell, os.path.join(cell.cache_dir,
                                                "attn_probe_" + form))
        dataset, variables, _ = D.make_inputs(cell, seed, built.global_batch)
        state = built.state_for(variables)
        loader, _ = built.loader_for(dataset, seed, 0)
        loader.set_epoch(0)
        rng = built.rng_for(seed)
        times = []
        for x, y in loader:
            t0 = time.perf_counter()
            state, metrics = built.train_step(state, x, y, rng)
            jax.block_until_ready(metrics["loss"])
            times.append(time.perf_counter() - t0)
        mem = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "cell": cell_name, "form": form, "first_step_s": times[0],
            "steps_ms": [round(1e3 * t, 2) for t in times[1:]],
            "median_ms": 1e3 * statistics.median(times[1:]),
            "loss": float(metrics["loss"]),
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            "attn_bwd_layers": list(
                built.model.attn_bwd_layers(built.cfg.seq_len)),
            "device": jax.devices()[0].device_kind}), flush=True)
        loader.close()
        del state, variables, built, loader
        gc.collect()
    fa.fused_bwd = predicate


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bwd", nargs="*", choices=sorted(BWD_SHAPES),
                    metavar="SHAPE", help="time the split and the fused "
                    "backward at these cells' shapes (none named: all)")
    ap.add_argument("--fwd", nargs="*", choices=sorted(BWD_SHAPES),
                    metavar="SHAPE", help="time the forward launch and hash "
                    "its out and lse at these cells' shapes (none: all)")
    ap.add_argument("--sparse-fwd", action="store_true",
                    help="time the sparse attention's selection, forward and "
                    "loss launches at train_keye_dsa_32k's shape and hash "
                    "their outputs")
    ap.add_argument("--sparse-bwd", action="store_true",
                    help="time the sparse attention's backward launch at "
                    "train_keye_dsa_32k's shape and hash its gradients")
    ap.add_argument("--step", nargs="+", metavar="CELL [FORM ...]",
                    help="a sequence cell's own train step with the fused "
                    "and/or the split backward (default: both)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--seqs", default="196,1024,4096")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    if args.fwd is not None:
        return fwd_probe(args.fwd or sorted(BWD_SHAPES))
    if args.sparse_fwd:
        return sparse_fwd_probe()
    if args.sparse_bwd:
        return sparse_bwd_probe()
    if args.bwd is not None:
        return bwd_probe(args.bwd or sorted(BWD_SHAPES))
    if args.step:
        forms = args.step[1:] or ["fused", "split"]
        assert set(forms) <= {"fused", "split"}, forms
        return cell_step(args.step[0], forms)

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
